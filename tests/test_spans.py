"""The program's spans and counters (``repro.common.spans``): nesting, self
time, per-thread stacks, counter attribution, the compile-event listener,
and the set-up and epoch paths that a program's run records."""
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import spans
from repro.common.spans import count, snapshot, span
from repro.core.dsl import GNNProgram
from repro.graph.datasets import generate_dataset
from repro.models.gnn import GNNConfig
from repro.training.optimizer import adam
from repro.training.trainer import FullBatchTrainer, MiniBatchTrainer


@pytest.fixture(autouse=True)
def fresh():
    spans.reset()
    yield
    spans.reset()


def test_nesting_self_time_and_max():
    for pause in (0.02, 0.01):
        with span("outer"):
            time.sleep(pause)
            with span("inner") as inner:
                time.sleep(0.03)
    got = snapshot()["spans"]
    assert set(got) == {"outer", "outer/inner"}
    outer, child = got["outer"], got["outer/inner"]
    assert outer["count"] == child["count"] == 2
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - child["total_s"])
    assert outer["self_s"] >= 0.03 and child["total_s"] >= 0.06
    assert child["self_s"] == pytest.approx(child["total_s"])
    assert outer["max_s"] >= 0.05 and outer["max_s"] <= outer["total_s"]
    assert inner.seconds >= 0.03


def test_decorator_opens_one_span_per_call():
    @span("work")
    def work(x):
        return x + 1

    assert work(1) == 2 and work(2) == 3
    assert snapshot()["spans"]["work"]["count"] == 2


def test_stacks_are_per_thread():
    """Two threads with spans open at once each nest only their own."""
    barrier = threading.Barrier(2, timeout=10)

    def run():
        with span("thread"):
            barrier.wait()
            with span("leaf"):
                count("hits")
            barrier.wait()

    threads = [threading.Thread(target=run) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    snap = snapshot()
    assert set(snap["spans"]) == {"thread", "thread/leaf"}
    assert snap["spans"]["thread"]["count"] == 2
    assert snap["counters"] == {"thread/leaf/hits": 2}


def test_no_update_is_lost_across_many_threads():
    """More threads than cores, switching as often as the interpreter
    allows, each adding to the same span and counter paths."""
    threads, rounds = 2 * (os.cpu_count() or 4), 300
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def run():
            for _ in range(rounds):
                with span("shared"):
                    count("n")

        pool = [threading.Thread(target=run) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    snap = snapshot()
    assert snap["spans"]["shared"]["count"] == threads * rounds
    assert snap["counters"] == {"shared/n": threads * rounds}


def test_counters_land_under_the_innermost_span():
    count("loose")
    with span("a"):
        count("n", 2)
        with span("b"):
            count("n")
            count("n", 3)
    assert snapshot()["counters"] == {"loose": 1, "a/n": 2, "a/b/n": 4}


def test_listener_counts_compile_events_only_inside_spans():
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.jit(lambda x: x * 3 + 1)(jnp.float32(2.0))
    assert snapshot()["counters"] == {}
    with span("build"):
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.jit(lambda x: x * 5 - 1)(jnp.float32(2.0))
    counters = snapshot()["counters"]
    assert counters["build/cache_hits"] == 1
    assert counters["build/backend_compiles"] >= 1


SETUP_PATHS = [
    "lower", "lower/layout", "lower/graph_op", "lower/graph_op/bsr_build",
    "lower/graph_op/transpose", "lower/graph_op/bsr_upload", "lower/decide",
    "lower/verify", "init",
    "compile_step", "compile_step/trace", "compile_step/consts",
    "compile_step/lower", "compile_step/compile",
]


@pytest.fixture(scope="module")
def dataset():
    return generate_dataset("ogbn-arxiv", scale=0.002, seed=0)


def test_program_records_setup_and_epoch_paths(dataset):
    prog = (GNNProgram.load(dataset, arch="GCN")
            .initialize_layers([16], "xavier")
            .set_optimizer("adam", 0.01, 0.9, 0.999)
            .compile(engine="xla"))
    prog.compile_step()
    losses = [prog.train_epoch()["loss"] for _ in range(2)]
    assert np.isfinite(losses).all()
    snap = snapshot()
    got = snap["spans"]
    for path in SETUP_PATHS + ["epoch", "epoch/dispatch", "epoch/loss_read"]:
        assert path in got, (path, sorted(got))
    assert got["epoch"]["count"] == got["epoch/dispatch"]["count"] == 2
    assert got["compile_step"]["total_s"] >= sum(
        got[f"compile_step/{p}"]["total_s"]
        for p in ("trace", "consts", "lower", "compile"))
    counters = snap["counters"]
    assert counters["compile_step/compiles"] == 1
    assert counters["compile_step/compile/backend_compiles"] == 1
    assert not [p for p in counters if p.startswith("epoch")], counters


def test_an_uncompiled_step_compiles_inside_its_epoch(dataset):
    prog = (GNNProgram.load(dataset, arch="GCN")
            .initialize_layers([16], "xavier")
            .compile(engine="xla"))
    prog.train_epoch()
    counters = snapshot()["counters"]
    assert counters["epoch/dispatch/compiles"] == 1
    assert counters["epoch/dispatch/compile/backend_compiles"] == 1
    assert "epoch/dispatch/trace" in snapshot()["spans"]


def test_full_batch_fit_times_its_epochs_by_span(dataset):
    prog = (GNNProgram.load(dataset, arch="GCN")
            .initialize_layers([16], "xavier")
            .compile(engine="xla"))
    trainer = FullBatchTrainer(prog.model, adam(0.01))
    res = trainer.fit(prog.params, prog.x, prog.labels, prog.train_mask, 3)
    got = snapshot()["spans"]
    assert got["epoch"]["count"] == 3
    assert sum(res.epoch_times) == pytest.approx(got["epoch"]["total_s"])


def test_sampled_epoch_records_its_batch_phases(dataset):
    cfg = GNNConfig(kind="GCN",
                    layer_dims=[dataset.features.shape[1], 16,
                                dataset.n_classes])
    tr = MiniBatchTrainer(
        cfg, dataset.graph, dataset.features, dataset.labels,
        dataset.train_mask, adam(0.01), fanouts=(5, 5), batch_size=32,
        engine="xla", seed=0)
    spans.reset()
    tr.train_epoch()
    got = snapshot()["spans"]
    n_batches = -(-int(dataset.train_mask.sum()) // 32)
    assert got["epoch"]["count"] == 1
    for phase in ("batch", "batch/sample", "batch/upload", "batch/dispatch",
                  "batch/loss_read", "batch/sample/bsr_build"):
        assert f"epoch/{phase}" in got, (phase, sorted(got))
    assert got["epoch/batch"]["count"] == n_batches
    assert got["epoch/batch/sample"]["count"] == n_batches
