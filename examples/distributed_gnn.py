"""Distributed GNN training — the paper's MPI backend, end to end.

A CPU-only harness: it re-executes itself with 8 virtual host devices
(``--xla_force_host_platform_device_count``), partitions a synthetic graph
with the hierarchical partitioner (Alg 4), builds per-rank local|ghost
views, and trains with halo exchange + pipelined per-layer gradient psum.
The four-chip run of the same path is ``python chip_smoke.py --chips 4``.

Run:  PYTHONPATH=src python examples/distributed_gnn.py
"""
import os
import subprocess
import sys


def main():
    if os.environ.get("_DIST_CHILD") != "1":
        env = dict(os.environ)
        env["_DIST_CHILD"] = "1"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"
        raise SystemExit(subprocess.run([sys.executable, __file__],
                                        env=env).returncode)

    import jax

    from repro.common.jit import configure_compile_cache
    from repro.core.halo import build_distributed_graph
    from repro.core.lowering import lower_distributed
    from repro.core.partitioner import hierarchical_partition
    from repro.graph.datasets import generate_dataset
    from repro.models.gnn import GNNConfig
    from repro.training.optimizer import adam
    from repro.training.trainer import DistributedGNNTrainer

    configure_compile_cache()
    print(f"devices: {len(jax.devices())}")
    # corafull analog: 95%-sparse bag-of-words features, so the per-rank
    # Alg-1 decision binds the distributed sparse input path
    ds = generate_dataset("corafull", scale=0.005, seed=0)
    config = GNNConfig(kind="SAGE",
                       layer_dims=[ds.features.shape[1], 16, ds.n_classes],
                       aggregation="mean")

    part = hierarchical_partition(ds.graph, 8)
    print(f"partitioner: phase={part.phase} edge_cut={part.edge_cut} "
          f"load_imbalance={part.load_imbalance:.3f}")

    dist = build_distributed_graph(ds.graph, ds.features, ds.labels,
                                   ds.train_mask, part, br=8, bc=32,
                                   aggregation=config.aggregation)
    print(f"per-rank: {dist.n_local} local + {dist.n_ghost} ghost slots, "
          f"halo≤{dist.max_send} nodes/round")

    plan = lower_distributed(config, dist)
    print(plan.describe())

    trainer = DistributedGNNTrainer(dist, config, adam(0.01), plan=plan)
    for epoch in range(5):
        loss = trainer.train_epoch()
        print(f"epoch {epoch + 1}  global loss {loss:.4f}")


if __name__ == "__main__":
    main()
