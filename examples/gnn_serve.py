"""Online GNN serving — train a model, then serve it (DESIGN.md §12).

Trains a small GraphSAGE model with neighbour sampling, then stands up
the ``GNNServingEngine`` on top of the trained plan: seed-node queries
are coalesced into waves, padded into the sampler's shape buckets (so
the serve path never retraces after one warmup per bucket), executed
through the compiled infer path, and answered with logits in user
node-id space. The multi-level embedding cache short-circuits repeated
queries and serves historical layer-1 embeddings via ``embed``.

Run:  PYTHONPATH=src python examples/gnn_serve.py
"""
import numpy as np

from repro.common.jit import configure_compile_cache
from repro.graph.datasets import generate_dataset
from repro.models.gnn import GNNConfig
from repro.serving.gnn_engine import GNNRequest, GNNServingEngine
from repro.training.optimizer import adam
from repro.training.trainer import MiniBatchTrainer


def main():
    configure_compile_cache()
    ds = generate_dataset("flickr", scale=0.01, seed=0)
    config = GNNConfig(kind="SAGE",
                       layer_dims=[ds.features.shape[1], 32, ds.n_classes],
                       aggregation="mean")
    trainer = MiniBatchTrainer(
        config, ds.graph, ds.features, ds.labels, ds.train_mask, adam(0.01),
        fanouts=(10, 10), batch_size=64, n_buckets=2, engine="xla", seed=0)
    for epoch in range(4):
        loss = trainer.train_epoch()
        print(f"train epoch {epoch}: loss {loss:.4f}")

    engine = GNNServingEngine(trainer, wave_size=4, use_cache=True,
                              cache_hidden=True, seed=0)
    traces = engine.warmup()
    print(f"warmup: {traces} traces for "
          f"{len(engine.sampler.buckets)} buckets")

    # a burst of overlapping queries: the wave computes each node once
    rng = np.random.default_rng(3)
    for rid in range(8):
        ids = rng.choice(ds.graph.n_rows, size=4, replace=False)
        if rid % 2 == 1:  # every other request repeats the previous one
            ids[:2] = prev[:2]
        engine.submit(GNNRequest(rid=rid, node_ids=ids))
        prev = ids
    for req in engine.run():
        pred = np.argmax(req.logits, axis=-1)
        print(f"request {req.rid}: nodes {req.node_ids.tolist()} "
              f"-> classes {pred.tolist()} "
              f"({req.latency_s * 1e3:.2f}ms)")

    # repeated queries now hit the logits cache bitwise-identically
    ids = np.asarray([1, 5, 9])
    first = engine.serve(ids)
    again = engine.serve(ids)
    assert np.array_equal(first, again)
    emb = engine.embed(ids, level=1)
    print(f"historical layer-1 embeddings: {emb.shape}")
    print(f"stats: {engine.stats()}")


if __name__ == "__main__":
    main()
