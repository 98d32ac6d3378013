"""``windowed_spmm_passes``: the program's ``spmm_window`` counter, read
after the program compiles a full-batch step on the CPU."""
import json

import numpy as np
import pytest

from chipbench import harness
from chipbench.metrics import windowed_spmm_passes
from repro.common import spans
from repro.core.dsl import GNNProgram
from repro.graph.csr import CSRGraph, csr_from_edges
from repro.graph.datasets import GraphDataset


def _dataset(n=2048, f=16, seed=0):
    """A locality-free power-law graph (the benchmark generator's shape),
    on the gather side of the fill rule."""
    rng = np.random.default_rng(seed)
    deg = np.maximum((rng.pareto(1.1, n) + 1).round().astype(int), 1)
    dst = np.repeat(np.arange(n), np.minimum(deg, n // 4))
    src = rng.integers(0, n, dst.shape[0])
    g = csr_from_edges(np.concatenate([src, np.arange(n)]),
                       np.concatenate([dst, np.arange(n)]), n)
    graph = CSRGraph(indptr=g.indptr, indices=g.indices,
                     data=np.ones(g.nnz, np.float32), n_rows=n, n_cols=n)
    return GraphDataset(
        name="power-law", graph=graph,
        features=rng.standard_normal((n, f)).astype(np.float32),
        labels=rng.integers(0, 4, n).astype(np.int32), n_classes=4,
        train_mask=rng.random(n) < 0.7, spec=None)


def _compiled_step_reading(arch):
    spans.reset()
    prog = (GNNProgram.load(_dataset(), arch=arch, aggregation="gcn",
                            gat_heads=2)
            .initialize_layers([8, 8], "xavier")
            .set_optimizer("adam", 1e-3)
            .compile(engine="pallas", interpret=True))
    prog.compile_step()
    return windowed_spmm_passes.read({})


def test_reads_nothing_without_the_counter():
    spans.reset()
    assert windowed_spmm_passes.read({}) is None


@pytest.mark.parametrize("arch,passes", [("GCN", 6), ("GAT", None)])
def test_counts_the_traced_step_s_window_passes(arch, passes):
    """Three GCN layers: three forward and three backward passes, each on
    the window path off the chip (interpret mode holds the whole source in
    one window). GAT runs no SpMM: nothing to read."""
    assert _compiled_step_reading(arch) == passes


def test_is_declared_for_the_gcn_cell():
    with open(harness.ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    metric = {m["name"]: m for m in bench["per_layer"]}[
        "windowed_spmm_passes"]
    assert metric["workloads"] == ["gcn-3x256.arxiv-full"]
    assert (metric["source"], metric["layer"], metric["moves"]) == (
        "program_counter", "kernels", "epoch_s")
