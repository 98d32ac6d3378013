"""CSR row-gather SpMM: the three kernels and their custom VJP against a
dense float32 product at the highest precision, in TPU interpret mode
(which models the kernels' DMAs and semaphores), and the fill rule that
picks the operand format in the Pallas backend and the lowering.

Small tiles (8 rows) and chunks (8 or 16 nonzeros) stand in for the
defaults, 256 and 1024, so that tiny graphs still cross tile and chunk
boundaries: a row count that is no multiple of the tile, empty rows and an
empty tile, and one hub row whose nonzeros span many chunks.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.backends import get_backend
from repro.common import spans
from repro.core.lowering import lower
from repro.core.verify import verify_plan
from repro.graph.csr import csr_from_dense, csr_from_edges
from repro.kernels import ops as kops
from repro.kernels.csr_gather_spmm import (
    csr_gather_spmm,
    csr_gather_spmm_fused_epilogue,
    csr_gather_spmm_masked,
)
from repro.models.gnn import GNNConfig, GNNModel

pytestmark = pytest.mark.kernels

TPU_INTERPRET = pltpu.InterpretParams()
N_ROWS, N_COLS, HUB = 37, 60, 7  # 37 rows: a ragged last tile of 8


def _dense(rng, n=N_ROWS, m=N_COLS, density=0.12):
    a = (rng.random((n, m)) < density) * rng.standard_normal((n, m))
    a[3] = 0.0            # an empty row
    a[16:24] = 0.0        # an empty tile
    a[HUB] = rng.standard_normal(m)  # a hub row: 60 nonzeros, 8 chunks
    return a.astype(np.float32)


def _csr(a):
    """(indptr, indices, rows, values) of a dense matrix."""
    rows, cols = np.nonzero(a)
    indptr = np.concatenate([[0], np.cumsum((a != 0).sum(1))])
    return (jnp.asarray(indptr, jnp.int32), jnp.asarray(cols, jnp.int32),
            jnp.asarray(rows, jnp.int32),
            jnp.asarray(a[rows, cols], jnp.float32))


def _hi(a, b):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jnp.asarray(a) @ jnp.asarray(b))


ENTRY_POINTS = ["plain", "epilogue-relu", "epilogue-none", "masked"]


@pytest.mark.parametrize("entry", ENTRY_POINTS)
@pytest.mark.parametrize("f", [40, 128, 256])
def test_entry_point_matches_dense(rng, f, entry):
    a = _dense(rng)
    x = rng.standard_normal((N_COLS, f)).astype(np.float32)
    kw = dict(n_rows=N_ROWS, interpret=TPU_INTERPRET, tm=8, k=8)
    if entry == "plain":
        got = csr_gather_spmm(*_csr(a), jnp.asarray(x), **kw)
        np.testing.assert_allclose(np.asarray(got), _hi(a, x), atol=1e-5,
                                   rtol=1e-5)
    elif entry == "masked":
        mask = (rng.random((N_COLS, f)) < 0.5).astype(np.float32)
        got = csr_gather_spmm_masked(*_csr(a), jnp.asarray(x),
                                     jnp.asarray(mask), **kw)
        np.testing.assert_allclose(np.asarray(got), _hi(a, x * mask),
                                   atol=1e-5, rtol=1e-5)
    else:
        relu = entry == "epilogue-relu"
        s = rng.standard_normal((N_ROWS, f)).astype(np.float32)
        b = rng.standard_normal((1, f)).astype(np.float32)
        out = csr_gather_spmm_fused_epilogue(
            *_csr(a), jnp.asarray(x), jnp.asarray(s), jnp.asarray(b),
            jnp.float32(0.5), activation="relu" if relu else "none", **kw)
        pre = _hi(a, x) + 0.5 * s + b
        y = out[0] if relu else out
        np.testing.assert_allclose(np.asarray(y),
                                   np.maximum(pre, 0) if relu else pre,
                                   atol=1e-5, rtol=1e-5)
        if relu:
            np.testing.assert_array_equal(np.asarray(out[1]),
                                          (pre > 0).astype(np.float32))


@pytest.mark.parametrize("k", [8, 16, 1024])
def test_chunking_does_not_change_the_sum(rng, k):
    """The hub row crosses chunk windows at every chunk size; the result
    is the same product."""
    a = _dense(rng)
    x = rng.standard_normal((N_COLS, 40)).astype(np.float32)
    got = csr_gather_spmm(*_csr(a), jnp.asarray(x), n_rows=N_ROWS,
                          interpret=TPU_INTERPRET, tm=8, k=k)
    np.testing.assert_allclose(np.asarray(got), _hi(a, x), atol=1e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("activation", ["relu", "none"])
def test_fused_pair_gradients_match_dense(rng, activation):
    """custom_vjp of the fused-epilogue pair: d/du, d/dself, d/dbias and
    d/dalpha against jax.grad of the dense expression."""
    a = _dense(rng)
    f = 40
    fwd = kops.CSRDevice(*_csr(a), n_rows=N_ROWS, n_cols=N_COLS)
    bwd = kops.CSRDevice(*_csr(np.ascontiguousarray(a.T)), n_rows=N_COLS,
                         n_cols=N_ROWS)
    fused = kops.build_gather_fused_epilogue(fwd, bwd,
                                             interpret=TPU_INTERPRET)
    u = jnp.asarray(rng.standard_normal((N_COLS, f)), jnp.float32)
    s = jnp.asarray(rng.standard_normal((N_ROWS, f)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((f,)), jnp.float32)
    alpha = jnp.float32(0.7)
    cot = jnp.asarray(rng.standard_normal((N_ROWS, f)), jnp.float32)
    act = jax.nn.relu if activation == "relu" else (lambda v: v)
    dense = jnp.asarray(a)

    def ref(u, s, b, alpha):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(act(dense @ u + alpha * s + b) * cot)

    def got(u, s, b, alpha):
        return jnp.sum(fused(u, s, b, alpha, activation) * cot)

    want = jax.grad(ref, argnums=(0, 1, 2, 3))(u, s, b, alpha)
    have = jax.grad(got, argnums=(0, 1, 2, 3))(u, s, b, alpha)
    for w, h in zip(want, have):
        np.testing.assert_allclose(np.asarray(h), np.asarray(w), atol=1e-4,
                                   rtol=1e-4)


# ---------------------------------------------------------------------------
# The fill rule: gather for locality-free graphs, BSR where blocks fill
# ---------------------------------------------------------------------------

def _power_law(rng, n=2048):
    """Locality-free power-law in-degrees (the benchmark generator's
    shape): uniform sources, so almost every nonzero owns a block."""
    deg = np.maximum((rng.pareto(1.1, n) + 1).round().astype(int), 1)
    dst = np.repeat(np.arange(n), np.minimum(deg, n // 4))
    src = rng.integers(0, n, dst.shape[0])
    return csr_from_edges(np.concatenate([src, np.arange(n)]),
                          np.concatenate([dst, np.arange(n)]), n)


def _banded(n=512, width=24):
    """Every node reads its ``width`` nearest predecessors: dense blocks."""
    src = np.concatenate([np.maximum(np.arange(n) - d, 0)
                          for d in range(width)])
    dst = np.tile(np.arange(n), width)
    return csr_from_edges(src, dst, n)


def _lower(graph, kind="GCN", **kw):
    spans.reset()
    cfg = GNNConfig(kind=kind, layer_dims=[16, 8, 4], gat_heads=2)
    x = np.random.default_rng(1).standard_normal(
        (graph.n_rows, 16)).astype(np.float32)
    return lower(cfg, graph, x, engine="pallas", interpret=True, **kw)


def _operand_counts():
    c = spans.snapshot()["counters"]
    return (c.get("lower/decide/operand_gather"),
            c.get("lower/decide/operand_bsr"))


def _bsr_builds():
    return [p for p in spans.snapshot()["spans"]
            if p.rsplit("/", 1)[-1] in ("bsr_build", "bsr_upload")]


@pytest.mark.parametrize("case", ["power-law", "banded", "attention"])
def test_fill_rule_picks_operand_format(rng, case):
    graph = _banded() if case == "banded" else _power_law(rng)
    plan = _lower(graph, kind="GAT" if case == "attention" else "GCN")
    gop = plan.graph_op
    want = "bsr" if case == "banded" else "gather"
    assert {layer.operand for layer in plan.layers} == {want}
    assert gop.fwd_operand.format == gop.bwd_operand.format == want
    assert all(f"{want} operand" in layer.note for layer in plan.layers)
    # the engagement counter reads what was chosen, for A and Aᵀ
    assert _operand_counts() == ((2, 0) if want == "gather" else (0, 2))
    if want == "gather":
        assert isinstance(gop.fwd_operand, kops.CSRDevice)
        assert not _bsr_builds()  # no block of A or Aᵀ is built
        assert gop.fwd_bytes == gop.fwd_operand.nbytes + gop.bwd_operand.nbytes
    else:
        assert _bsr_builds()


def test_layer0_sparse_feature_operand_follows_the_rule(rng):
    """The sparse-feature operand goes through the same builder: scattered
    nonzeros take the gather format, and X @ W matches the dense product."""
    x = rng.standard_normal((200, 300)).astype(np.float32)
    x[rng.random(x.shape) < 0.99] = 0.0
    backend = get_backend("pallas")
    assert backend.build_spmm_operand(csr_from_dense(x), br=8, bc=128,
                                      fmt="auto").format == "gather"
    xw = backend.feature_matmul_sparse(x, br=8, bc=128, interpret=True)
    w = jnp.asarray(rng.standard_normal((300, 24)), jnp.float32)
    np.testing.assert_allclose(np.asarray(xw(w)), _hi(x, np.asarray(w)),
                               atol=1e-4, rtol=1e-4)


def test_gather_plan_trains_like_xla(rng):
    """One step of the full model on the gather operands (loss and every
    gradient) against the XLA backend's BSR plan."""
    graph = _power_law(rng, n=300)
    cfg = GNNConfig(kind="GCN", layer_dims=[16, 8, 4])
    x = jnp.asarray(rng.standard_normal((300, 16)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 4, 300), jnp.int32)
    mask = jnp.asarray(rng.random(300) < 0.7)
    out, operand = {}, {}
    for engine in ("pallas", "xla"):
        plan = lower(cfg, graph, np.asarray(x), engine=engine,
                     interpret=True)
        operand[engine] = plan.layers[0].operand
        model = GNNModel(cfg, graph, plan=plan)
        params = model.init(jax.random.PRNGKey(0))
        out[engine] = jax.value_and_grad(model.loss_fn)(params, x, labels,
                                                        mask)
    assert operand == {"pallas": "gather", "xla": "bsr"}
    assert out["pallas"][0] == pytest.approx(float(out["xla"][0]), abs=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(out["pallas"][1]),
                    jax.tree_util.tree_leaves(out["xla"][1])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4,
                                   rtol=1e-4)


def _corrupt(field, fn):
    def apply(dev):
        host = dev.host_view()
        return dataclasses.replace(
            dev, **{field: jnp.asarray(fn(host[field]))})
    return apply


def _swap_first_two(a):
    a = a.copy()
    a[[0, 1]] = a[[1, 0]]
    return a


CORRUPTIONS = {
    "csr.indptr": _corrupt("indptr", lambda p: np.where(
        np.arange(p.shape[0]) == 5, p[-1], p).astype(np.int32)),
    "csr.indices_in_range": _corrupt("indices", lambda i: np.where(
        np.arange(i.shape[0]) == 3, 10**6, i).astype(np.int32)),
    "csr.indices_sorted": _corrupt("indices", _swap_first_two),
    "csr.row_ids": _corrupt("rows", lambda r: r[::-1].copy()),
    "csr.finite": _corrupt("values", lambda v: np.where(
        np.arange(v.shape[0]) == 0, np.nan, v).astype(np.float32)),
    "layout.operand_rows": _corrupt("values", lambda v: v * 2.0),
}


@pytest.mark.parametrize("invariant", list(CORRUPTIONS))
def test_verify_flags_corrupted_csr_operand(rng, invariant):
    graph = _power_law(rng, n=300)
    plan = _lower(graph, validate="off")
    assert verify_plan(plan, mode="fast", graph=graph) == []
    gop = dataclasses.replace(
        plan.graph_op,
        fwd_operand=CORRUPTIONS[invariant](plan.graph_op.fwd_operand))
    bad = dataclasses.replace(plan, graph_op=gop)
    hits = [v for v in verify_plan(bad, mode="fast", graph=graph)
            if v.invariant == invariant]
    assert hits and all(v.operand == "graph_op.fwd" for v in hits)
