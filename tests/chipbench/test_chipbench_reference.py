"""Each plain reference (``chipbench/reference``) against the program on
the CPU at a small size: logits and the loss's gradients, from the same
weights, for GCN on dense and on sparse features."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, harness

SPARSE_X = {"n_features": 8_415, "feature_sparsity": 0.95}
CASES = [("gcn-3x256.arxiv-full", 0.01, {}, "xla.feature_matmul_dense"),
         ("gcn-3x256.arxiv-full", 0.01, SPARSE_X,
          "xla.feature_matmul_sparse")]


@pytest.mark.parametrize("workload,scale,spec,layer0", CASES,
                         ids=["gcn", "gcn-sparse-x"])
def test_reference_matches_program(workload, scale, spec, layer0, shrink):
    shrink(scale, hidden=64, spec=spec)
    with open(harness.ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    r = harness.inputs(bench, workload, seed=3)
    session = harness.load_module("loops", "full_batch").Session(
        r["config"], r["data"], r["dims"], r["params0"], {})
    program = session.program
    assert program.plan.layers[0].primitive == layer0

    data, ref = r["data"], r["ref"]
    src, dst = data.edges()
    graph = ref.prepare(jnp.asarray(src), jnp.asarray(dst), data.n_nodes)
    x, labels = jnp.asarray(data.features), jnp.asarray(data.labels)
    mask = jnp.asarray(data.train_mask)

    def ref_loss(p):
        return compare.masked_nll(ref.logits(p, graph, x, r["config"]),
                                  labels, mask)

    p0 = r["params0"]
    np.testing.assert_allclose(
        jax.jit(program.model.apply)(p0, program.x),
        jax.jit(lambda p: ref.logits(p, graph, x, r["config"]))(p0),
        rtol=1e-4, atol=1e-5)
    want = jax.jit(jax.grad(ref_loss))(p0)
    got = jax.jit(jax.grad(program.model.loss_fn))(
        p0, program.x, program.labels, program.train_mask)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-6 * float(jnp.abs(w).max()))
