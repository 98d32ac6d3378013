"""Quickstart — the paper's Listing 1, in this framework.

Train a 3-layer GCN (hidden 32, the paper's §V-B protocol) on a synthetic
Corafull analog. The sparsity engine inspects X once (95% sparse here) and
binds the sparse input path; aggregation runs through the fused BSR
operator.

Run:  PYTHONPATH=src python examples/quickstart.py

For graphs that do not fit in device memory, the neighbour-sampled
mini-batch path (DESIGN.md §7) decouples footprint from graph size — see
examples/minibatch_sage.py.

For runs that must survive bad gradients, dying ranks, and overloaded
serving, the resilient runtime (DESIGN.md §13) wraps every trainer in
guarded steps with skip → LR-backoff → rollback, deterministic fault
injection, and elastic recovery — see runtime/resilience.py.
"""
from repro.common.jit import configure_compile_cache
from repro.core.dsl import GNNProgram
from repro.graph.datasets import generate_dataset

def main():
    configure_compile_cache()
    dataset = generate_dataset("corafull", scale=0.02, seed=0)
    print(f"graph: {dataset.graph.n_rows} nodes, {dataset.graph.nnz} edges, "
          f"feature sparsity {dataset.feature_sparsity:.2%}")

    # Listing 1: gnn.load / initializeLayers / optimizer / per-epoch loop
    gnn = GNNProgram.load(dataset, arch="GCN", aggregation="gcn")
    gnn.initialize_layers([dataset.features.shape[1], 32, dataset.n_classes],
                          "xavier", seed=0)
    gnn.set_optimizer("adam", 0.01, 0.9, 0.999)
    prog = gnn.compile(engine="xla")  # synthesis: lowering -> ExecutionPlans
    print("synthesized plan:")
    print(prog.describe_plan())

    for epoch in range(30):
        metrics = prog.train_epoch()
        if (epoch + 1) % 5 == 0:
            print(f"epoch {metrics['epoch']:3d}  loss {metrics['loss']:.4f}")
    print(f"train accuracy: {prog.accuracy():.3f}")


if __name__ == "__main__":
    main()
