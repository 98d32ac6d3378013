"""The GAT cell's plain reference (``chipbench/reference/gat.py``) against
the program on the CPU at a small size, its bfloat16 control against the
cell's limits, and its work count (``chipbench/work/gat.py``) by hand."""
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, harness
from chipbench.work import gat

CELL = "gat-3x250h3.arxiv-full"
HIDDEN = 48  # 3 heads of 16


def _bench():
    with open(harness.ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_gat_reference_matches_program(shrink):
    """Logits, the loss's gradients and three Adam steps of the program
    against the reference, from the same weights, on the XLA backend (the
    row-gather kernels are held to the XLA path in
    ``tests/test_csr_gather_attention.py``)."""
    shrink(0.01, hidden=HIDDEN)
    r = harness.inputs(_bench(), CELL, seed=2**31 + 17)
    session = harness.load_module("loops", "full_batch").Session(
        r["config"], r["data"], r["dims"], r["params0"], {})
    program = session.program
    assert session.binding()["agg"] == ["xla.spmm_attention"]

    data, ref = r["data"], r["ref"]
    src, dst = data.edges()
    graph = ref.prepare(jnp.asarray(src), jnp.asarray(dst), data.n_nodes)
    x, labels = jnp.asarray(data.features), jnp.asarray(data.labels)
    mask = jnp.asarray(data.train_mask)

    def ref_loss(p):
        return compare.masked_nll(ref.logits(p, graph, x, r["config"]),
                                  labels, mask)

    p0 = r["params0"]
    with jax.default_matmul_precision("highest"):
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
                                   atol=1e-5 * float(jnp.abs(w).max()))

    host0 = compare.leaves(p0)
    prog = {"losses": [session.step()]}
    prog["grad1"] = compare.leaves(session.first_gradient())
    prog["losses"] += [session.step() for _ in range(compare.STEPS - 1)]
    prog["params"] = compare.leaves(session.params())
    values = compare.readings(prog, harness.reference(r), host0)
    limits = {k: v for k, v in r["expect"]["limits"].items() if k in values}
    assert compare.passed(compare.judge(values, limits)), values


def test_gat_bfloat16_control_is_not_correct(shrink):
    shrink(0.02, hidden=HIDDEN)
    r = harness.inputs(_bench(), CELL, seed=2**31 + 9)
    want = harness.reference(r)
    got = harness.reference(r, dtype=jnp.bfloat16)
    values = compare.readings(got, want, compare.leaves(r["params0"]))
    limits = {k: v for k, v in r["expect"]["limits"].items() if k in values}
    assert not compare.passed(compare.judge(values, limits)), values


def test_gat_hand_count():
    # 4 nodes, 6 nonzeros, 12 feature nonzeros; widths 3 -> 4 -> 2 with 2
    # heads: layer 0 concatenates 2 x 2, the last averages 2 heads of 2,
    # so both attend over W = 4 lanes with K = 2
    w = gat.epoch_work({"n": 4, "nnz": 6, "x_nnz": 12, "dims": [3, 4, 2],
                        "heads": 2})
    # per layer: fwd 2·6·4 + 5·6·2, bwd_row 2·6·4 + 8·6·2,
    # bwd_col 4·6·4 + 8·6·2
    attn = 108 + 144 + 192
    # layer 0: Z, R and dW, dW_res over x_nnz (4 · 2·12·4), scores and
    # their gradients (6 · 2·4·4); layer 1 over n·d_in = 16, plus dH
    layer0 = 4 * 2 * 12 * 4 + 6 * 2 * 4 * 4 + attn
    layer1 = 4 * 2 * 16 * 4 + 2 * 2 * 16 * 4 + 6 * 2 * 4 * 4 + attn
    assert w["flops"] == layer0 + layer1 == 2424
    assert w["sparse_flops"] == 2 * attn
    # bytes: CSR 6·4 + 5·4; fwd reads Z, t, s and writes out, m, l
    # (4 nodes · (4 + 2·2) · 4, twice); bwd_row reads 2·4 + 5·2 words a
    # node and writes 2; bwd_col reads the same and writes 4 + 2
    csr = 24 + 20
    per_layer = {"fwd": csr + 2 * 4 * 8 * 4,
                 "bwd_row": csr + 4 * 18 * 4 + 4 * 2 * 4,
                 "bwd_col": csr + 4 * 18 * 4 + 4 * 6 * 4}
    assert w["attention"] == {
        "fwd": (216, 2 * per_layer["fwd"]),
        "bwd_row": (288, 2 * per_layer["bwd_row"]),
        "bwd_col": (384, 2 * per_layer["bwd_col"])}
    assert w["sparse_bytes"] == 2 * sum(per_layer.values())
