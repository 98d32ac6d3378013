"""CSR row-gather attention: the three kernels against the BSR reference
and a dense edge softmax at the highest precision, in TPU interpret mode
(which models the kernels' DMAs and semaphores); the custom VJP against
autodiff of the dense softmax; the fill rule under attention; and GAT and
GT plans on the gather kernels against the XLA backend's BSR plan.

Small tiles (8 rows) and chunks (8 nonzeros) stand in for the defaults,
256 and 1024, so that a tiny graph still crosses tile and chunk
boundaries: a ragged last tile, empty rows, an empty tile and a hub row
whose nonzeros span several chunks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.common import spans
from repro.core.lowering import lower
from repro.graph.csr import csr_from_edges, csr_to_bsr
from repro.kernels import ops as kops
from repro.kernels.csr_gather_attention import (
    csr_gather_attention_bwd_col,
    csr_gather_attention_bwd_row,
    csr_gather_attention_fwd,
)
from repro.kernels.ref import bsr_attention_bwd_ref, bsr_attention_ref
from repro.models.gnn import GNNConfig, GNNModel

pytestmark = pytest.mark.kernels

TPU_INTERPRET = pltpu.InterpretParams()
N, HUB, HEADS = 37, 7, 3  # 37 rows: a ragged last tile of 8


def _mask(rng, n=N):
    a = rng.random((n, n)) < 0.04
    a[3] = False      # an empty row
    a[16:24] = False  # an empty tile
    a[HUB] = True     # a hub row: 37 nonzeros, 5 chunks of 8
    return a


def _csr(a):
    """(indptr, indices, rows) of a boolean matrix's nonzero pattern."""
    rows, cols = np.nonzero(a)
    indptr = np.concatenate([[0], np.cumsum(a.sum(1))])
    return tuple(jnp.asarray(v, jnp.int32) for v in (indptr, cols, rows))


def _dense_attention(a, z, asrc, adst):
    """``[n, K·D]``: per head, a softmax over each row's nonzeros of
    ``leaky_relu(adst_i + asrc_j)`` weighting ``z_j``; empty rows 0."""
    n, w = z.shape
    z3 = z.reshape(n, HEADS, w // HEADS)
    pre = adst[:, None, :] + asrc[None, :, :]
    e = jnp.where(a[:, :, None], jnp.where(pre >= 0, pre, 0.2 * pre),
                  -jnp.inf)
    m = jnp.max(e, axis=1, keepdims=True)
    p = jnp.where(a[:, :, None], jnp.exp(e - jnp.where(
        jnp.isfinite(m), m, 0.0)), 0.0)
    att = p / jnp.maximum(p.sum(1, keepdims=True), 1e-20)
    return jnp.einsum("ijh,jhd->ihd", att, z3).reshape(n, w)


def _inputs(rng, w):
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return f(N, w), f(N, HEADS), f(N, HEADS), f(N, w)


@pytest.mark.parametrize("width", [120, 750])
def test_kernels_match_bsr_reference_and_dense_softmax(rng, width):
    a = _mask(rng)
    z, asrc, adst, dy = _inputs(rng, width)
    kw = dict(heads=HEADS, interpret=TPU_INTERPRET, tm=8, k=8)
    out, m, l = csr_gather_attention_fwd(*_csr(a), z, asrc, adst,
                                         n_rows=N, **kw)
    with jax.default_matmul_precision("highest"):
        want = _dense_attention(a, z, asrc, adst)
    np.testing.assert_allclose(out, want, atol=2e-6, rtol=1e-5)

    # the BSR family's oracle on the same pattern, padded to its tile
    bsr = csr_to_bsr(csr_from_edges(np.nonzero(a)[1], np.nonzero(a)[0], N),
                     br=8, bc=8)
    pad = lambda v: jnp.pad(v, [(0, bsr.padded_rows - N)]
                            + [(0, 0)] * (v.ndim - 1))
    z3 = z.reshape(N, HEADS, -1)
    ref_args = (jnp.asarray(bsr.block_rows), jnp.asarray(bsr.block_cols),
                jnp.asarray(bsr.blocks))
    out_b, m_b, l_b = bsr_attention_ref(*ref_args, pad(z3), pad(asrc),
                                        pad(adst), bsr.padded_rows)
    np.testing.assert_allclose(out, out_b[:N].reshape(N, -1), atol=2e-6)
    np.testing.assert_allclose(m, m_b[:N], atol=1e-6)
    np.testing.assert_allclose(l, l_b[:N], rtol=1e-5)

    r = jnp.einsum("nhd,nhd->nh", dy.reshape(N, HEADS, -1),
                   out.reshape(N, HEADS, -1))
    dc = csr_gather_attention_bwd_row(*_csr(a), z, asrc, adst, dy, r, m, l,
                                      n_rows=N, **kw)
    dzv, dd = csr_gather_attention_bwd_col(*_csr(a.T), asrc, adst, z, dy, r,
                                           m, l, n_rows=N, **kw)
    dzv_b, dd_b, dc_b = bsr_attention_bwd_ref(
        *ref_args, pad(z3), pad(asrc), pad(adst), pad(m), pad(l),
        pad(dy.reshape(N, HEADS, -1)), pad(r), bsr.padded_rows)
    np.testing.assert_allclose(dzv, dzv_b[:N].reshape(N, -1), atol=1e-5)
    np.testing.assert_allclose(dd, dd_b[:N], atol=5e-5, rtol=1e-5)
    np.testing.assert_allclose(dc, dc_b[:N], atol=5e-5, rtol=1e-5)
    assert not np.asarray(dc[3]).any()  # the empty row has no score


def test_vjp_matches_autodiff_of_dense_softmax(rng):
    """``csr_mha_pair``'s recompute VJP against autodiff of the dense
    softmax, through z (value and score paths), a_src and a_dst."""
    a = _mask(rng)
    z, _, _, dy = _inputs(rng, 3 * 40)
    a_src, a_dst = (jnp.asarray(rng.standard_normal((HEADS, 40)) * 0.3,
                                jnp.float32) for _ in range(2))
    fwd = (*_csr(a), jnp.ones(int(a.sum()), jnp.float32))
    bwd = (*_csr(a.T), jnp.ones(int(a.sum()), jnp.float32))

    def gather(z, a_src, a_dst):
        z3 = z.reshape(N, HEADS, -1)
        return kops.csr_mha_pair(fwd, bwd, z3, a_src, a_dst, (N, N),
                                 True).reshape(N, -1)

    def dense(z, a_src, a_dst):
        z3 = z.reshape(N, HEADS, -1)
        return _dense_attention(a, z, jnp.einsum("nhd,hd->nh", z3, a_src),
                                jnp.einsum("nhd,hd->nh", z3, a_dst))

    with jax.default_matmul_precision("highest"):
        got, pull = jax.vjp(gather, z, a_src, a_dst)
        want, pull_ref = jax.vjp(dense, z, a_src, a_dst)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)
        for g, w in zip(pull(dy), pull_ref(dy)):
            np.testing.assert_allclose(g, w, atol=2e-5 * float(
                jnp.abs(w).max()), rtol=1e-4)


def _power_law(rng, n=2048):
    """Locality-free power-law in-degrees: almost every nonzero owns a
    block."""
    deg = np.maximum((rng.pareto(1.1, n) + 1).round().astype(int), 1)
    dst = np.repeat(np.arange(n), np.minimum(deg, n // 4))
    src = rng.integers(0, n, dst.shape[0])
    return csr_from_edges(np.concatenate([src, np.arange(n)]),
                          np.concatenate([dst, np.arange(n)]), n)


def _banded(n=512, width=24):
    src = np.concatenate([np.maximum(np.arange(n) - d, 0)
                          for d in range(width)])
    return csr_from_edges(src, np.tile(np.arange(n), width), n)


@pytest.mark.parametrize("case", ["power-law", "banded"])
def test_fill_rule_under_attention(rng, case):
    """Attention operands follow the SpMM's fill rule: scattered nonzeros
    take the gather format with no block built, block-dense ones BSR; the
    counters and the plan note say which."""
    graph = _power_law(rng) if case == "power-law" else _banded()
    want = "gather" if case == "power-law" else "bsr"
    spans.reset()
    cfg = GNNConfig(kind="GAT", layer_dims=[16, 12, 4], gat_heads=3)
    x = rng.standard_normal((graph.n_rows, 16)).astype(np.float32)
    plan = lower(cfg, graph, x, engine="pallas", interpret=True)
    snap = spans.snapshot()
    assert {layer.agg_primitive for layer in plan.layers} == {
        "pallas.spmm_attention"}
    assert {layer.operand for layer in plan.layers} == {want}
    assert all(layer.attention.operand == want
               and f"fused-{want}" in layer.describe()
               and f"{want} operand: " in layer.note
               for layer in plan.layers)
    assert [layer.attention.head_dim for layer in plan.layers] == [4, 4]
    counters = snap["counters"]
    assert (counters["lower/decide/attention_gather"],
            counters["lower/decide/attention_bsr"]) == (
        (2, 0) if want == "gather" else (0, 2))
    built = [p for p in snap["spans"]
             if p.rsplit("/", 1)[-1] in ("bsr_build", "bsr_upload")]
    assert bool(built) == (want == "bsr")


def _sparse(rng, n=48):
    """One to three uniform sources a node, and a self loop: at this size
    fewer than 12 nonzeros per (8, 16) block."""
    dst = np.repeat(np.arange(n), rng.integers(1, 4, n))
    src = rng.integers(0, n, dst.shape[0])
    return csr_from_edges(np.concatenate([src, np.arange(n)]),
                          np.concatenate([dst, np.arange(n)]), n)


@pytest.mark.parametrize("kind", ["GAT", "GT"])
def test_gather_plan_trains_like_xla(rng, kind):
    """Loss and every gradient of the full model on the gather attention
    kernels against the XLA backend's BSR plan, from the same weights."""
    graph = _sparse(rng)
    cfg = GNNConfig(kind=kind, layer_dims=[16, 12, 4], gat_heads=3)
    x = jnp.asarray(rng.standard_normal((48, 16)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 4, 48), jnp.int32)
    mask = jnp.asarray(rng.random(48) < 0.7)
    out, operand = {}, {}
    params = None
    for engine in ("pallas", "xla"):
        plan = lower(cfg, graph, np.asarray(x), engine=engine,
                     interpret=True)
        operand[engine] = plan.layers[0].operand
        model = GNNModel(cfg, graph, plan=plan)
        params = params or model.init(jax.random.PRNGKey(0))
        with jax.default_matmul_precision("highest"):
            out[engine] = jax.value_and_grad(model.loss_fn)(params, x,
                                                            labels, mask)
    assert operand == {"pallas": "gather", "xla": "bsr"}
    assert out["pallas"][0] == pytest.approx(float(out["xla"][0]), abs=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(out["pallas"][1]),
                    jax.tree_util.tree_leaves(out["xla"][1])):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-4)
