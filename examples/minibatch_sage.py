"""Mini-batch GraphSAGE — neighbour-sampled training through the plan pipeline.

Trains a 2-layer GraphSAGE model on a synthetic Flickr analog with
fanout-(10, 10) neighbour sampling: each step touches only the sampled
L-hop frontier of its seed batch, so peak memory scales with batch size
and fanouts instead of graph size (DESIGN.md §7). The lowering pass runs
the Algorithm-1 sparsity engine on a template batch's gathered frontier
features and binds the per-batch sparse input path when it wins; held-out
accuracy comes from the dataset's val/test splits.

Run:  PYTHONPATH=src python examples/minibatch_sage.py
"""
from repro.common.jit import configure_compile_cache
from repro.graph.datasets import generate_dataset
from repro.models.gnn import GNNConfig
from repro.training.optimizer import adam
from repro.training.trainer import MiniBatchTrainer


def main():
    configure_compile_cache()
    ds = generate_dataset("flickr", scale=0.02, seed=0)
    print(f"graph: {ds.graph.n_rows} nodes, {ds.graph.nnz} edges, "
          f"feature sparsity {ds.feature_sparsity:.2%}, "
          f"train/val/test = {int(ds.train_mask.sum())}/"
          f"{int(ds.val_mask.sum())}/{int(ds.test_mask.sum())}")

    config = GNNConfig(kind="SAGE",
                       layer_dims=[ds.features.shape[1], 32, ds.n_classes],
                       aggregation="mean")
    trainer = MiniBatchTrainer(
        config, ds.graph, ds.features, ds.labels, ds.train_mask, adam(0.01),
        fanouts=(10, 10), batch_size=128, n_buckets=2, engine="xla", seed=0,
    )
    print("synthesized plan:")
    print(trainer.plan.describe())

    for epoch in range(10):
        loss = trainer.train_epoch()
        if (epoch + 1) % 2 == 0:
            print(f"epoch {epoch + 1:3d}  loss {loss:.4f}  "
                  f"val acc {trainer.evaluate(ds.val_mask):.3f}")
    print(f"test accuracy: {trainer.evaluate(ds.test_mask):.3f}")
    print(f"step retraces: {trainer.n_traces} "
          f"(bounded by {trainer.plan.n_buckets} buckets)")


if __name__ == "__main__":
    main()
