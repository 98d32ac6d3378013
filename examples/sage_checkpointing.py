"""GraphSAGE with max aggregation + fault-tolerant training.

Demonstrates: SAGE/max (the paper's Listing 1 example), the fused Adam
kernel, periodic checkpointing, and a simulated failure + restart that
resumes from the last checkpoint.

Run:  PYTHONPATH=src python examples/sage_checkpointing.py
"""
import tempfile

import jax

from repro.common.jit import configure_compile_cache
from repro.graph.datasets import generate_dataset
from repro.models.gnn import GNNConfig, GNNModel
from repro.runtime.checkpoint import latest_step
from repro.training.optimizer import adam
from repro.training.trainer import FullBatchTrainer


def main():
    configure_compile_cache()
    ds = generate_dataset("flickr", scale=0.01, seed=0)
    cfg = GNNConfig(kind="SAGE", aggregation="max",
                    layer_dims=[ds.features.shape[1], 32, ds.n_classes])
    model = GNNModel(cfg, ds.graph, engine="xla")
    params = model.init(jax.random.PRNGKey(0))

    with tempfile.TemporaryDirectory() as ckpt:
        trainer = FullBatchTrainer(model, adam(0.01, fused=True),
                                   ckpt_dir=ckpt, ckpt_every=20)
        r1 = trainer.fit(params, ds.features, ds.labels, ds.train_mask,
                         epochs=60)
        print(f"phase 1: {len(r1.losses)} epochs, "
              f"loss {r1.losses[0]:.3f} -> {r1.losses[-1]:.3f}")
        print(f"latest checkpoint: step {latest_step(ckpt)}")

        # --- simulated crash: a NEW trainer resumes from the checkpoint ---
        trainer2 = FullBatchTrainer(model, adam(0.01, fused=True),
                                    ckpt_dir=ckpt, ckpt_every=20)
        r2 = trainer2.fit(params, ds.features, ds.labels, ds.train_mask,
                          epochs=100)
        print(f"restart: resumed from epoch {r2.restored_from}, "
              f"ran {len(r2.losses)} more epochs, "
              f"final loss {r2.losses[-1]:.3f}")
        assert r2.restored_from == 60


if __name__ == "__main__":
    main()
