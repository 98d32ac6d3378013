"""Paper Fig 6/7 analog: distributed (MPI-backend analog) per-epoch time.

A CPU-only harness: its epoch times are XLA-CPU times on 8 virtual host
devices, not device measurements (the on-chip benchmark replaces it).

Two sweeps, both in a subprocess with 8 host devices so the parent keeps 1:

1. Arch x regime epoch times under the plan-driven distributed trainer
   (corafull analog = 95%-sparse features -> Alg-1 sparse input path,
   flickr analog = dense path), plus a rank sweep on GCN.
2. Bulk-vs-overlap pairing (DESIGN.md §11): every dataset x rank-count
   config is trained twice from the same DistributedGraph — once with the
   bulk primitives (``overlap=False``, full P-1 ring) and once with the
   split-phase primitives (interior SpMM overlapped with the exchange,
   live-shift-only ring) — and the paired epoch times land in
   ``BENCH_distributed.json`` at the repo root together with the
   interior/boundary block breakdown per config.

The ``ring`` dataset is a locality round: clusters arranged in a ring with
directed cross edges to the next cluster only, placed ring-order on ranks
(an explicit ``PartitionResult``, the placement a locality-aware
partitioner converges to) — the regime where all but one ring shift is
dead and live-shift skipping pays. corafull/flickr under the hierarchical
partitioner keep every shift live and measure the split overhead honestly.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import csv_row

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CODE = textwrap.dedent("""
    import json, time
    import jax, numpy as np
    from repro.graph.datasets import generate_dataset
    from repro.graph.csr import csr_from_edges
    from repro.core.partitioner import PartitionResult, hierarchical_partition
    from repro.core.halo import build_distributed_graph
    from repro.core.lowering import effective_aggregation, lower_distributed
    from repro.models.gnn import GNNConfig
    from repro.training.trainer import DistributedGNNTrainer
    from repro.training.optimizer import adam

    ARCHS = [("GCN", "gcn"), ("SAGE", "mean"), ("GIN", "sum"), ("GAT", "sum")]
    REGIMES = {"sparse": "corafull", "dense": "flickr"}  # 95% vs 45% zeros

    class _DS:
        pass

    RING_CLUSTERS, RING_PER = 8, 96

    def ring_dataset(clusters=RING_CLUSTERS, per=RING_PER, f=96, c=8,
                     seed=0):
        '''Ring of clusters: directed cross edges to the NEXT cluster only.
        Placed ring-order on ranks, every rank's ghosts live one ring
        distance away and all other shifts are dead.'''
        rng = np.random.default_rng(seed)
        n = clusters * per
        src, dst = [], []
        for k in range(clusters):
            base = k * per
            src.append(rng.integers(base, base + per, per * 6))
            dst.append(rng.integers(base, base + per, per * 6))
            nxt = ((k + 1) % clusters) * per
            src.append(rng.integers(base, base + per, per * 2))
            dst.append(rng.integers(nxt, nxt + per, per * 2))
        src = np.concatenate(src).astype(np.int64)
        dst = np.concatenate(dst).astype(np.int64)
        ds = _DS()
        ds.graph = csr_from_edges(src=src, dst=dst, n_rows=n)
        ds.features = rng.standard_normal((n, f)).astype(np.float32)
        ds.labels = rng.integers(0, c, n).astype(np.int32)
        ds.train_mask = rng.random(n) < 0.5
        ds.n_classes = c
        return ds

    def ring_placement(ranks):
        '''Clusters -> ranks in ring order: cross traffic stays at ring
        distance 1 for any rank count dividing the cluster count.'''
        assign = (np.repeat(np.arange(RING_CLUSTERS), RING_PER)
                  % ranks).astype(np.int32)
        return PartitionResult(assign, ranks, "metis_kway", 0, 1.0, 1.0)

    def make_trainer(ds, part, kind, agg, overlap):
        cfg = GNNConfig(kind=kind,
                        layer_dims=[ds.features.shape[1], 16, ds.n_classes],
                        aggregation=agg)
        dist = build_distributed_graph(
            ds.graph, ds.features, ds.labels, ds.train_mask, part,
            br=8, bc=32, aggregation=effective_aggregation(cfg))
        plan = lower_distributed(cfg, dist, inner="xla", overlap=overlap)
        return dist, plan, DistributedGNNTrainer(dist, cfg, adam(0.01),
                                                 interpret=True, plan=plan)

    def time_epochs(tr, n=4):
        tr.train_epoch()  # compile
        t0 = time.perf_counter()
        for _ in range(n):
            tr.train_epoch()
        return (time.perf_counter() - t0) / n

    out = {"archs": {}, "ranks": {}, "overlap": {}}
    datasets = {r: generate_dataset(name, scale=0.004, seed=0)
                for r, name in REGIMES.items()}
    # -- arch x regime sweep at 8 ranks (overlapped default path) ------------
    parts8 = {r: hierarchical_partition(ds.graph, 8)
              for r, ds in datasets.items()}
    for kind, agg in ARCHS:
        for regime, ds in datasets.items():
            part = parts8[regime]
            _, plan, tr = make_trainer(ds, part, kind, agg, True)
            out["archs"][f"{kind}/{regime}"] = {
                "epoch_s": time_epochs(tr, 2),
                "input_path": plan.layers[0].feature_path,
                "agg_primitive": plan.layers[0].agg_primitive,
                "input_sparsity": round(plan.feature_sparsity, 4),
                "edge_cut": int(part.edge_cut),
                "load_imb": round(float(part.load_imbalance), 4),
                "phase": part.phase,
                "ranks": 8,
            }
    # -- rank sweep on GCN/sparse (the paper's scaling axis) -----------------
    for ranks in (2, 4, 8):
        part = hierarchical_partition(datasets["sparse"].graph, ranks)
        _, plan, tr = make_trainer(datasets["sparse"], part, "GCN", "gcn",
                                   True)
        out["ranks"][str(ranks)] = {
            "epoch_s": time_epochs(tr, 2),
            "phase": part.phase, "edge_cut": int(part.edge_cut),
            "load_imb": round(float(part.load_imbalance), 4),
        }
    # -- bulk vs overlap pairing (DESIGN.md §11) -----------------------------
    over_sets = {"corafull": datasets["sparse"], "flickr": datasets["dense"],
                 "ring": ring_dataset()}
    for dsname, ds in over_sets.items():
        for ranks in (2, 4, 8):
            part = (ring_placement(ranks) if dsname == "ring"
                    else hierarchical_partition(ds.graph, ranks))
            dist, plan, tr_ov = make_trainer(ds, part, "GCN", "gcn", True)
            _, _, tr_bulk = make_trainer(ds, part, "GCN", "gcn", False)
            bulk_s = time_epochs(tr_bulk)
            over_s = time_epochs(tr_ov)
            ov = plan.overlap
            out["overlap"][f"{dsname}/ranks={ranks}"] = {
                "dataset": dsname, "ranks": ranks,
                "bulk_epoch_s": bulk_s, "overlap_epoch_s": over_s,
                "speedup": bulk_s / over_s,
                "interior_blocks": ov.interior_blocks,
                "boundary_blocks": ov.boundary_blocks,
                "live_shifts": list(ov.live_shifts),
                "total_shifts": ov.total_shifts,
            }
    print("RESULT:" + json.dumps(out))
""")


def run() -> list[str]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    res = subprocess.run([sys.executable, "-c", _CODE], env=env,
                         capture_output=True, text=True, timeout=3600)
    rows = []
    if res.returncode != 0:
        rows.append(csv_row("distributed/error", 0.0,
                            res.stderr.strip().splitlines()[-1][:100]
                            if res.stderr else "unknown"))
        return rows
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT:")][-1]
    data = json.loads(line[len("RESULT:"):])
    with open(os.path.join(REPO, "BENCH_distributed.json"), "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    for key, d in sorted(data["archs"].items()):
        rows.append(csv_row(
            f"distributed/{key}", d["epoch_s"] * 1e6,
            f"input={d['input_path']};s={d['input_sparsity']}"
            f";agg={d['agg_primitive'].split('.')[-1]}",
        ))
    for ranks, d in sorted(data["ranks"].items()):
        rows.append(csv_row(
            f"distributed/scaling/ranks={ranks}", d["epoch_s"] * 1e6,
            f"phase={d['phase']};edge_cut={d['edge_cut']}"
            f";load_imb={d['load_imb']:.3f}",
        ))
    for key, d in sorted(data["overlap"].items()):
        rows.append(csv_row(
            f"distributed/overlap/{key}", d["overlap_epoch_s"] * 1e6,
            f"bulk={d['bulk_epoch_s'] * 1e6:.0f}us"
            f";speedup={d['speedup']:.2f}x"
            f";live={len(d['live_shifts'])}/{d['total_shifts']}"
            f";int_b={d['interior_blocks']};bnd_b={d['boundary_blocks']}",
        ))
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
