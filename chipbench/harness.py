"""One benchmark run of one cell, driven by the files the cell names.

A cell ``<config>.<traffic>`` in ``BENCHMARK.json`` resolves, by name, to

- ``configs/<config>.json``: the model as it is run (arch, widths,
  optimizer), with its source;
- ``traffic/<traffic>.json``: the generator's spec, scale and topology
  seed, and the loop that drives it (``loops/<loop>.py``);
- ``cells/<cell>.json``: what the plan must bind and the limits of the
  comparison that decides ``correct``;
- ``work/<arch>.py`` and ``reference/<arch>.py``: the useful work of one
  step and the plain reference;
- ``metrics/<metric>.py``: one reader per per-layer metric.

A later cell, mix or metric is a new file; nothing here changes.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, generator, trace
from chipbench.peaks import peak_for

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench.{kind}.{name}",
                                                  path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(bench: dict, workload: str) -> dict:
    """The cell's entry in ``bench`` and the files it names."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = read_json(ROOT / configs[cell["config"]]["file"])
    return {"cell": cell, "config": cfg,
            "traffic": read_json(HERE / "traffic" / f"{cell['traffic']}.json"),
            "expect": read_json(HERE / "cells" / f"{workload}.json")}


def weights_key(seed: int):
    """A PRNG key from the whole of ``seed`` (JAX keys hold 32 bits)."""
    word = np.random.SeedSequence(seed).generate_state(1)[0]
    return jax.random.key(int(word))


def binding_mismatches(bound: dict, expect: dict) -> int:
    """How many of the expected bindings the plan did not make."""
    return sum(bound[k] != v for k, v in expect.items())


def peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def graph_stats(data, cfg: dict, dims: list[int]) -> dict:
    """What ``work/<arch>.py`` counts from: nodes, nonzeros, widths."""
    return {"n": data.n_nodes, "nnz": data.nnz,
            "x_nnz": int(np.count_nonzero(data.features)), "dims": dims,
            "heads": cfg.get("heads", 1)}


def window(session, seconds: float, trace_dir: str | None):
    """Whole steps until ``seconds`` have passed; ``(steps, s, losses)``."""
    losses = []
    ctx = (jax.profiler.trace(trace_dir) if trace_dir
           else contextlib.nullcontext())
    with ctx:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            t = time.perf_counter()
            while not losses or time.perf_counter() - t < seconds:
                losses.append(session.step())
            session.sync()
            elapsed = time.perf_counter() - t
    return len(losses), elapsed, losses


def inputs(bench: dict, workload: str, seed: int) -> dict:
    """The cell's files, its data and weights from ``seed``, and its
    reference module."""
    r = resolve(bench, workload)
    cfg = r["config"]
    r["ref"] = load_module("reference", cfg["arch"].lower())
    data = r["data"] = generator.generate(r["traffic"], seed)
    r["dims"] = [data.features.shape[1],
                 *[cfg["hidden_channels"]] * (cfg["num_layers"] - 1),
                 data.n_classes]
    log(f"{workload}: seed {seed}, {data.n_nodes} nodes, {data.nnz} "
        f"nonzeros, dims {r['dims']}")
    r["params0"] = jax.jit(lambda key: r["ref"].init(key, r["dims"], cfg))(
        weights_key(seed))
    return r


def reference(r: dict, dtype=jnp.float32, train_mask=None) -> dict:
    """The reference's three steps from the cell's weights; float32 at the
    highest matmul precision unless ``dtype`` says lower."""
    data = r["data"]
    src, dst = data.edges()
    graph = r["ref"].prepare(jnp.asarray(src), jnp.asarray(dst), data.n_nodes)
    mask = data.train_mask if train_mask is None else train_mask
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        return compare.train_reference(
            r["ref"], r["config"], r["params0"], graph,
            jnp.asarray(data.features), jnp.asarray(data.labels),
            jnp.asarray(mask), dtype=dtype)


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             traced: bool, *, t0: float, devices) -> dict:
    """One run; returns the result object that ``run.py`` prints."""
    r = inputs(bench, workload, seed)
    cfg, mix, expect = r["config"], r["traffic"], r["expect"]
    data, dims, params0 = r["data"], r["dims"], r["params0"]
    arch = cfg["arch"].lower()
    host0 = compare.leaves(params0)

    spans: dict = {}
    session = load_module("loops", mix["loop"]).Session(
        cfg, data, dims, params0, spans)
    bound = session.binding()
    log(f"bound {bound}; lower_s {spans['lower_s']:.3f} compile_s "
        f"{spans['compile_s']:.3f}")
    prog = {"losses": [session.step()]}
    prog["grad1"] = compare.leaves(session.first_gradient())
    prog["losses"] += [session.step() for _ in range(compare.STEPS - 1)]
    prog["params"] = compare.leaves(session.params())
    setup_s = time.perf_counter() - t0
    log(f"setup_s {setup_s:.3f}; checked steps' losses {prog['losses']}")

    trace_dir = None
    if traced:
        trace_dir = str(ROOT / ".traces" / workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    steps, elapsed, losses = window(session, seconds, trace_dir)
    epoch_s = elapsed / steps
    memory = peak_bytes(devices)
    log(f"window: {steps} steps in {elapsed:.4f} s, epoch_s {epoch_s:.6f}, "
        f"peak {memory} B")
    del session
    gc.collect()

    t = time.perf_counter()
    want = reference(r)
    log(f"reference {time.perf_counter() - t:.3f} s, losses {want['losses']}")
    values = compare.readings(prog, want, host0)
    gaps = compare.per_leaf(prog, want, host0)
    log("per-leaf gaps (grad, update): " + ", ".join(
        f"{g:.3g}/{u:.3g}" for g, u in zip(gaps["grad"], gaps["update"])))
    values["binding_mismatches"] = binding_mismatches(bound, expect["binding"])
    values["nonfinite_losses"] = sum(not math.isfinite(v) for v in losses)
    checks = compare.judge(values, expect["limits"])

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    result = {"correct": compare.passed(checks), "attempted": steps,
              "failed": values["nonfinite_losses"], "device": device}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    if traced:
        red = trace.load(trace_dir, len(devices))
        device["busy_s"] = red.busy_ns / 1e9
        device["window_s"] = red.window_ns / 1e9
        ctx = {"spans": spans, "epoch_s": epoch_s, "steps": steps,
               "trace": red, "peak": peak_for(devices[0].device_kind),
               "chips": len(devices),
               "work": load_module("work", arch).epoch_work(
                   graph_stats(data, cfg, dims))}
        metrics = {}
        for m in bench["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                extra = value if isinstance(value, dict) else {"value": value}
                metrics[m["name"]] = {**extra, "unit": units[m["name"]]}
        result["breakdown"] = {
            "device_ops": [[k, v / 1e9] for k, v in red.top_ops],
            "idle_gaps": [[k, v / 1e9] for k, v in red.idle_gaps]}
        log(f"pallas ops {[(k, v / 1e9) for k, v in red.pallas_by_op]}")
    else:
        metrics = {"epoch_s": epoch_s, "peak_hbm_gb": memory / 1e9,
                   "setup_s": setup_s}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in metrics.items()}
    result["metrics"] = metrics
    result["checks"] = checks
    return result

