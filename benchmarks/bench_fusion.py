"""Block-tile sweep for the fused-epilogue SpMM (DESIGN.md §8).

Two sweeps, both on the XLA inner (compiled block einsum — the CPU
wall-time stand-in; the Pallas interpreter would measure Python, not the
layout):

* ``(br, bc)`` layout grid × fused-vs-unfused epilogue: full training
  epochs (fwd + bwd + update) of a 2-layer GCN per tile shape, with the
  per-layer materialized-intermediate estimate from the plan's
  ``EpiloguePlan`` records. The fused plan runs the epilogue as the
  aggregation's consumer; the unfused plan materializes one [N, F] tensor
  per epilogue op (aggregation out, self-term combine, bias add,
  activation). Timing is *paired*: single-epoch samples alternate between
  the two variants so drifting background load cancels out of the ratio.
* ``bf`` feature-tile sweep: op-level fused epilogue timing across lane
  tiles. ``bf`` is the Pallas kernel's MXU feature tile; on the XLA inner
  it only moves the padding boundary, so this sweep isolates the padding
  cost of misaligned feature dims (``bf=None`` — the backends' default —
  picks the no-pad tile via ``kernels.ops.feature_tile``).

On this inner the expected wall-time result is *parity*: XLA fuses the
unfused variant's elementwise chain too, so the fused path's measurable
win here is the eliminated [N, F] intermediates (reported per layout);
the HBM round-trip savings are what the Pallas TPU kernel banks.

Emits ``BENCH_fusion.json`` next to the repo root so the perf trajectory
of the fused path is recorded run over run.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, time_call
from repro.core.lowering import lower
from repro.graph.datasets import generate_dataset
from repro.kernels import ops as kops
from repro.graph.csr import csr_to_bsr
from repro.models.gnn import GNNConfig, GNNModel

DATASET = "nell"          # 99%-sparse features: exercises the sparse input path
SCALE = 0.004
HIDDEN = 32
BR_BC_GRID = [(8, 32), (8, 128), (16, 64)]
BF_SWEEP = [32, 64, 128]
JSON_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_fusion.json")


def epilogue_intermediates(plan, n_nodes: int) -> tuple[int, int, int]:
    """(unfused_tensors, fused_tensors, bytes_saved) per forward pass.

    Counts the [N, d_out] float32 tensors the epilogue sequence
    materializes between the aggregation and the layer output. Unfused:
    one per op in the sequence (aggregation out + self-term combine + bias
    add + activation). Fused: exactly one (the epilogue'd output tile); the
    saved ReLU mask is common to both (it is the activation's residual).
    """
    unfused = fused = saved_bytes = 0
    for layer in plan.layers:
        e = layer.epilogue
        if e is None:
            continue
        n_ops = 1 + int(e.self_term) + int(e.bias) + int(e.activation == "relu")
        unfused += n_ops
        fused += 1
        saved_bytes += (n_ops - 1) * n_nodes * layer.d_out * 4
    return unfused, fused, saved_bytes


def _epoch_fn(model: GNNModel, x, labels, mask):
    """One jitted train epoch (fwd + bwd + SGD update) over the model."""

    @jax.jit
    def epoch(params):
        loss, grads = jax.value_and_grad(model.loss_fn)(
            params, x, labels, mask)
        return jax.tree_util.tree_map(lambda p, g: p - 0.01 * g,
                                      params, grads), loss

    return epoch


def _paired_medians(fn_a, fn_b, samples: int = 15) -> tuple[float, float]:
    """Median single-call times of two thunks, samples interleaved A/B/A/B
    so slow drift in background load hits both variants equally."""
    jax.block_until_ready(fn_a())
    jax.block_until_ready(fn_b())
    t_a, t_b = [], []
    for _ in range(samples):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a())
        t_a.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b())
        t_b.append(time.perf_counter() - t0)
    t_a.sort()
    t_b.sort()
    return t_a[len(t_a) // 2], t_b[len(t_b) // 2]


def run() -> list[str]:
    ds = generate_dataset(DATASET, scale=SCALE, seed=0)
    n = ds.graph.n_rows
    cfg = GNNConfig(kind="GCN",
                    layer_dims=[ds.features.shape[1], HIDDEN, ds.n_classes])
    x = jnp.asarray(ds.features)
    labels = jnp.asarray(ds.labels)
    mask = jnp.asarray(ds.train_mask)

    rows: list[str] = []
    record = {"dataset": DATASET, "n_nodes": int(n),
              "nnz": int(ds.graph.nnz), "grid": [], "bf_sweep": []}

    best = None
    for br, bc in BR_BC_GRID:
        epochs = {}
        for fused_flag in (True, False):
            plan = lower(cfg, ds.graph, ds.features, engine="xla",
                         fuse_epilogue=fused_flag, br=br, bc=bc)
            model = GNNModel(cfg, ds.graph, plan=plan)
            params = model.init(jax.random.PRNGKey(0))
            epoch = _epoch_fn(model, x, labels, mask)
            epochs[fused_flag] = (epoch, params)
            if fused_flag:
                uf, fu, saved = epilogue_intermediates(plan, n)
        t_fused, t_unfused = _paired_medians(
            lambda: epochs[True][0](epochs[True][1]),
            lambda: epochs[False][0](epochs[False][1]))
        times = {True: t_fused, False: t_unfused}
        speedup = times[False] / times[True]
        entry = {
            "br": br, "bc": bc,
            "fused_s": times[True], "unfused_s": times[False],
            "speedup": speedup,
            "intermediates_unfused": uf, "intermediates_fused": fu,
            "intermediate_bytes_saved": saved,
        }
        record["grid"].append(entry)
        if best is None or times[True] < best["fused_s"]:
            best = entry
        rows.append(csv_row(
            f"fusion/gcn_br{br}_bc{bc}", times[True] * 1e6,
            f"speedup_vs_unfused={speedup:.2f}x"
            f";intermediates={uf}->{fu}"
            f";bytes_saved={saved}"))

    # bf sweep: op-level fused epilogue over the best layout (the BSR pair
    # does not depend on bf — built once)
    g_w = ds.graph.sym_normalized()
    fwd = kops.BSRDevice.from_bsr(
        csr_to_bsr(g_w, br=best["br"], bc=best["bc"]))
    bwd = kops.BSRDevice.from_bsr(
        csr_to_bsr(g_w.transpose(), br=best["br"], bc=best["bc"]))
    u = jnp.asarray(np.random.default_rng(0).standard_normal(
        (n, HIDDEN)).astype(np.float32))
    bias = jnp.zeros((HIDDEN,), jnp.float32)
    for bf in BF_SWEEP:
        fused = kops.build_fused_epilogue(fwd, bwd, "xla", bf=bf)
        op = jax.jit(lambda v, _f=fused: _f(v, bias=bias, activation="relu"))
        t = time_call(lambda: op(u))
        record["bf_sweep"].append({"bf": bf, "op_s": t})
        rows.append(csv_row(f"fusion/op_bf{bf}", t * 1e6,
                            f"layout=br{best['br']}_bc{best['bc']}"
                            f";f={HIDDEN}"))

    record["best"] = best
    record["timestamp"] = time.time()
    with open(JSON_PATH, "w") as fh:
        json.dump(record, fh, indent=2)
    rows.append(csv_row(
        "fusion/best", best["fused_s"] * 1e6,
        f"br={best['br']};bc={best['bc']}"
        f";speedup_vs_unfused={best['speedup']:.2f}x"
        f";json={os.path.basename(JSON_PATH)}"))
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
