"""Smoke run of the main path on a TPU, at ogbn-arxiv's published size.

    python chip_smoke.py            # one chip: full-batch GCN, then GAT
    python chip_smoke.py --chips 4  # four chips: distributed GCN only

One chip: ``GNNProgram.load(...).initialize_layers(...).set_optimizer(...)
.compile()`` with the default engine and layout, then three
``train_epoch()`` calls, for a 3-layer GCN (hidden 256, the OGB reference
GCN for ogbn-arxiv) and DGL's 3-layer ogbn-arxiv GAT (3 heads x 250,
concatenated to 750; residual; the last layer's heads averaged), the model
of the benchmark's ``gat-3x250h3`` cell. Each phase must bind
the Pallas backend, its loss must be finite and fall over the three
epochs, and its epoch-1 logits must agree within 1e-3 (max abs error over
max abs value) with the segment-sum ``gather`` backend, both run from the
same params under ``jax.default_matmul_precision("highest")``.

Four chips: ``hierarchical_partition`` -> ``build_distributed_graph`` ->
``lower_distributed`` -> 3 epochs of ``DistributedGNNTrainer`` (GCN); its
epoch-1 global loss must agree within 1e-3 (relative) with the
single-device full-batch loss (``gather`` backend, highest precision) from
the same params.

Times printed here are smoke readings, not benchmark numbers. Each phase
also prints the program's own spans and counters over its set-up and
epochs (``repro.common.spans``; README "Tracing"). The script exits
non-zero when no TPU is present; the last line of a passing run is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import jax
import numpy as np

# The generator's labels are random, so the loss can only fall from its
# initial value toward chance (ln 40). Adam at the sources' rates overshoots
# on the first step at this size (the gather backend does the same): GCN's
# 0.01 and GAT's 0.002 (and 0.001); 0.001 and 0.0005 fall.
#: hidden widths, heads and Adam's rate of each single-chip phase (with the
#: 128-wide input and 40 classes: 3 layers)
MODELS = {"GCN": ([256, 256], 1, 0.001), "GAT": ([750, 750], 3, 0.0005)}
HIDDEN, _, LR = MODELS["GCN"]
MAX_REL_ERR = 1e-3


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def span_table() -> str:
    """The program's spans and counters since the last ``reset()``
    (``repro.common.spans``), one row per path: count, and total, self
    and longest seconds."""
    from repro.common.spans import snapshot

    snap = snapshot()
    rows = [f"{'span':<44} {'count':>6} {'total_s':>10} {'self_s':>10} "
            f"{'max_s':>10}"]
    rows += [f"{path:<44} {s['count']:>6} {s['total_s']:>10.4f} "
             f"{s['self_s']:>10.4f} {s['max_s']:>10.4f}"
             for path, s in sorted(snap["spans"].items())]
    rows += [f"{'counter':<44} {'n':>6}"]
    rows += [f"{path:<44} {n:>6g}"
             for path, n in sorted(snap["counters"].items())]
    return "\n".join(rows)


def program(ds, arch):
    from repro.core.dsl import GNNProgram

    hidden, heads, lr = MODELS[arch]
    return (GNNProgram.load(ds, arch=arch, gat_heads=heads)
            .initialize_layers(hidden, "xavier", seed=0)
            .set_optimizer("adam", lr, 0.9, 0.999))


def logits(compiled, params):
    """Forward pass under highest matmul precision, as one program."""
    from repro.common.jit import jit_hoisted

    with jax.default_matmul_precision("highest"):
        return np.asarray(jit_hoisted(compiled.model.apply)(params, compiled.x))


def single_chip_phase(ds, arch: str, agg_primitive: str) -> None:
    from repro.common import spans
    from repro.core.verify import check_plan
    from repro.kernels.ops import default_interpret

    log(f"== phase {arch} ==")
    spans.reset()
    t = time.perf_counter()
    compiled = program(ds, arch).compile(validate="off")
    t_lower = time.perf_counter() - t
    t = time.perf_counter()
    check_plan(compiled.plan, mode="fast", graph=ds.graph)
    t_verify = time.perf_counter() - t
    plan = compiled.plan
    bound = {l.agg_primitive for l in plan.layers}
    log(f"{arch}: backend={plan.backend} agg={sorted(bound)} "
        f"interpret={default_interpret()}")
    if plan.backend != "pallas" or bound != {agg_primitive}:
        raise SystemExit(f"{arch}: expected {agg_primitive}, plan bound "
                         f"{plan.backend} / {sorted(bound)}")
    if default_interpret():
        raise SystemExit("Pallas kernels would run in interpret mode")
    fwd, bwd = plan.graph_op.fwd_operand, plan.graph_op.bwd_operand
    if fwd.format == "gather":
        log(f"{arch}: CSR row-gather operands, nnz A={fwd.indices.shape[0]} "
            f"A^T={bwd.indices.shape[0]}")
    else:
        log(f"{arch}: tile=({fwd.br},{fwd.bc}) blocks A={fwd.blocks.shape[0]} "
            f"A^T={bwd.blocks.shape[0]}")
    t = time.perf_counter()
    compiled.compile_step()
    t_compile = time.perf_counter() - t
    log(f"{arch}: smoke set-up lowering_s={t_lower:.3f} "
        f"verify_s={t_verify:.3f} compile_s={t_compile:.3f}")

    params0 = compiled.params
    losses = []
    for _ in range(3):
        t = time.perf_counter()
        loss = compiled.train_epoch()["loss"]
        jax.block_until_ready(compiled.params)
        losses.append(loss)
        log(f"{arch}: smoke epoch {len(losses)} wall_s="
            f"{time.perf_counter() - t:.4f} loss={loss:.6f}")
    if not (np.isfinite(losses).all() and losses[2] < losses[0]):
        raise SystemExit(f"{arch}: loss not finite and falling: {losses}")
    dev = jax.devices()[0]
    log(f"{arch}: peak_bytes_in_use={peak_bytes(dev)}")
    log(f"{arch}: program spans and counters\n{span_table()}")

    got = logits(compiled, params0)
    del compiled
    gc.collect()
    ref = program(ds, arch).compile(engine="gather")
    want = logits(ref, params0)
    err = rel_err(got, want)
    log(f"{arch}: epoch-1 logits vs gather reference max_rel_err={err:.3e} "
        f"(bound {MAX_REL_ERR:g})")
    if not err <= MAX_REL_ERR:
        raise SystemExit(f"{arch}: logits differ from the reference by {err}")


def distributed_phase(ds, n_chips: int) -> None:
    from repro.common import spans
    from repro.core.halo import build_distributed_graph
    from repro.core.lowering import lower_distributed
    from repro.core.partitioner import hierarchical_partition
    from repro.models.gnn import GNNConfig
    from repro.training.optimizer import adam
    from repro.training.trainer import DistributedGNNTrainer

    log(f"== phase distributed GCN on {n_chips} chips ==")
    spans.reset()
    dims = [ds.features.shape[1], *HIDDEN, ds.n_classes]
    config = GNNConfig(kind="GCN", layer_dims=dims, aggregation="gcn")
    t = time.perf_counter()
    part = hierarchical_partition(ds.graph, n_chips)
    t_part = time.perf_counter() - t
    t = time.perf_counter()
    dist = build_distributed_graph(ds.graph, ds.features, ds.labels,
                                   ds.train_mask, part, aggregation="gcn")
    t_build = time.perf_counter() - t
    t = time.perf_counter()
    plan = lower_distributed(config, dist)
    t_lower = time.perf_counter() - t
    log(f"partition phase={part.phase} edge_cut={part.edge_cut} "
        f"load_imbalance={part.load_imbalance:.3f}; per rank "
        f"{dist.n_local} local + {dist.n_ghost} ghost; inner={plan.inner} "
        f"agg={plan.layers[0].agg_primitive}")
    if plan.inner != "pallas":
        raise SystemExit(f"distributed inner backend is {plan.inner}")
    t = time.perf_counter()
    trainer = DistributedGNNTrainer(dist, config, adam(LR), plan=plan)
    t_trainer = time.perf_counter() - t
    leaf = jax.tree_util.tree_leaves(trainer._data)[0]
    placed = sorted(s.device.id for s in leaf.addressable_shards)
    log(f"shards of the rank-stacked data on devices {placed}")
    if len(set(placed)) != n_chips:
        raise SystemExit(f"data is not spread over {n_chips} devices")
    log(f"smoke set-up partition_s={t_part:.3f} build_s={t_build:.3f} "
        f"lowering_s={t_lower:.3f} trainer_s={t_trainer:.3f}")

    params0 = trainer.params
    losses = []
    for _ in range(3):
        t = time.perf_counter()
        losses.append(trainer.train_epoch())
        jax.block_until_ready(trainer.params)
        log(f"distributed: smoke epoch {len(losses)} wall_s="
            f"{time.perf_counter() - t:.4f} global_loss={losses[-1]:.6f}")
    if not (np.isfinite(losses).all() and losses[2] < losses[0]):
        raise SystemExit(f"distributed loss not finite and falling: {losses}")
    for d in jax.devices()[:n_chips]:
        log(f"device {d.id}: peak_bytes_in_use={peak_bytes(d)}")
    log(f"distributed: program spans and counters\n{span_table()}")

    del trainer
    gc.collect()
    from repro.common.jit import jit_hoisted

    single = program(ds, "GCN").compile(engine="gather")
    with jax.default_matmul_precision("highest"):
        loss1 = float(jit_hoisted(single.model.loss_fn)(
            params0, single.x, single.labels, single.train_mask))
    gap = abs(losses[0] - loss1) / abs(loss1)
    log(f"epoch-1 global loss {losses[0]:.6f} vs single-device "
        f"{loss1:.6f}: rel_gap={gap:.3e} (bound {MAX_REL_ERR:g})")
    if not gap <= MAX_REL_ERR:
        raise SystemExit(f"distributed loss differs from single-device: {gap}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    if jax.default_backend() != "tpu":
        raise SystemExit(f"no TPU: JAX backend is {jax.default_backend()!r}")
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))
    from repro.common.jit import configure_compile_cache
    from repro.graph.datasets import generate_dataset

    log(f"compile cache: {configure_compile_cache()}")
    devices = jax.devices()
    dev = devices[0]
    log(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)}")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices")

    t = time.perf_counter()
    ds = generate_dataset("ogbn-arxiv", scale=1.0, seed=0)
    log(f"ogbn-arxiv: nodes={ds.graph.n_rows} nnz={ds.graph.nnz} "
        f"features={ds.features.shape[1]} classes={ds.n_classes} "
        f"generate_s={time.perf_counter() - t:.3f}")

    if args.chips == 4:
        distributed_phase(ds, 4)
    else:
        single_chip_phase(ds, "GCN", "pallas.spmm_fused_epilogue")
        gc.collect()
        single_chip_phase(ds, "GAT", "pallas.spmm_attention")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
