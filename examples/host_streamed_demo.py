"""Host-streamed shard training — graphs bigger than device memory.

Builds a synthetic graph whose stacked BSR operands exceed a configured
device-memory budget, keeps the per-shard operands host-resident, and
trains a 2-layer GCN with ``streamed_spmm``: a prefetcher streams block
strips to the device one step ahead (DESIGN.md §11), so at most two strips
of each operand are device-resident at any point — forward and backward.

Run:  PYTHONPATH=src python examples/host_streamed_demo.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.common.jit import configure_compile_cache
from repro.core.pipeline import arch_layer_fns, pipelined_value_and_grad
from repro.graph.datasets import generate_dataset
from repro.models.gnn import GNNConfig, LayerOps, init_params
from repro.runtime.streaming import build_streamed_operand
from repro.training.optimizer import adam

# the scale-out premise: operands must NOT fit this device budget
DEVICE_BUDGET_BYTES = 96 * 1024


def main():
    configure_compile_cache()
    ds = generate_dataset("corafull", scale=0.02, seed=0)
    config = GNNConfig(kind="GCN",
                       layer_dims=[ds.features.shape[1], 32, ds.n_classes],
                       aggregation="gcn")

    op = build_streamed_operand(ds.graph, aggregation="gcn", k_shards=4,
                                budget_bytes=DEVICE_BUDGET_BYTES)
    total, resident = op.total_nbytes(), op.device_nbytes()
    assert total > DEVICE_BUDGET_BYTES, (
        f"demo premise broken: operands ({total}B) fit the budget")
    assert resident <= DEVICE_BUDGET_BYTES, (
        f"streamed residency ({resident}B) breaks the budget")
    print(f"graph: {ds.graph.n_rows} nodes, {ds.graph.indices.shape[0]} edges"
          f" in {len(op.shard_offsets) - 1} host shards")
    print(f"operands: {total / 1024:.0f} KiB host-resident total, budget "
          f"{DEVICE_BUDGET_BYTES / 1024:.0f} KiB, peak device residency "
          f"{resident / 1024:.0f} KiB "
          f"({op.fwd.n_strips}+{op.bwd.n_strips} strips, 2 live each)")

    # train entirely in streamed (shard-contiguous) node order
    x = jnp.asarray(ds.features[op.order])
    labels = jnp.asarray(ds.labels[op.order])
    mask = jnp.asarray(ds.train_mask[op.order])

    layer_ops = [LayerOps(aggregate=op.aggregate)
                 for _ in range(config.n_layers)]
    layer_fns = arch_layer_fns(config, layer_ops)
    opt = adam(0.01)
    params = init_params(config, jax.random.PRNGKey(0))
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        loss, grads = pipelined_value_and_grad(
            layer_fns, params, x, labels, mask)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    for epoch in range(5):
        params, opt_state, loss = step(params, opt_state)
        print(f"epoch {epoch + 1}  loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
