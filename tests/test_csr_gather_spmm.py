"""CSR row-gather SpMM: the three entry points (rows read from VMEM
windows of the source, one window or several) and their custom VJP
against a dense float32 product at the highest precision, in TPU
interpret mode (which models the kernels' DMAs and semaphores); the
tile-column operand, the windows the chip's VMEM holds, the verifier's
order rules, and the fill rule that picks the operand format in the
Pallas backend and the lowering.

Small tiles (8 rows), chunks (8 or 16 nonzeros) and windows (24 to 64
source rows) stand in for the defaults, so that tiny graphs still cross
tile, chunk and window boundaries: a row count that is no multiple of the
tile, empty rows and an empty tile, one hub row whose nonzeros span many
chunks, and a ragged last window.
"""
import dataclasses
import hashlib

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
from repro.kernels.csr_gather_attention import (
    csr_gather_attention_bwd_col,
    csr_gather_attention_bwd_row,
    csr_gather_attention_fwd,
)
from repro.kernels.csr_gather_spmm import (
    WINDOW_TILE,
    _tile_bytes,
    csr_gather_spmm,
    csr_gather_spmm_fused_epilogue,
    csr_gather_spmm_masked,
    window_rows,
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


def _csr(a, tile=None):
    """(indptr, indices, rows, values) of a dense matrix, in row order or,
    given ``tile``, in tile-column order."""
    rows, cols = np.nonzero(a)
    if tile:
        order = np.lexsort((rows, cols, rows // tile))
        rows, cols = rows[order], cols[order]
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


# a chip whose VMEM holds windows of 8 rows: eight windows of A's 60 source
# rows (the last ragged), five of Aᵀ's 37
_SMALL_VMEM = 8 << 10


@pytest.mark.parametrize("path", ["rows", "window", "small-vmem"])
@pytest.mark.parametrize("activation", ["relu", "none"])
def test_fused_pair_gradients_match_dense(rng, activation, path,
                                          monkeypatch):
    """custom_vjp of the fused-epilogue pair: d/du, d/dself, d/dbias and
    d/dalpha against jax.grad of the dense expression, over row-ordered
    operands and tile-column ones, in one window or many."""
    a = _dense(rng)
    f = 40
    tile = None if path == "rows" else 8
    if path == "small-vmem":
        monkeypatch.setattr(kops, "chip_vmem", lambda: _SMALL_VMEM)
    fwd, bwd = _device(a, tile), _device(np.ascontiguousarray(a.T), tile)
    spans.reset()
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
    # one forward and one backward pass, each counted once
    assert spans.snapshot()["counters"].get("spmm_window") == 2


# ---------------------------------------------------------------------------
# The window path: source rows read from a VMEM window of x
# ---------------------------------------------------------------------------

# 60 source rows in windows of 64, 32 and 24 rows: one window, two, and
# three with a ragged last (24 + 24 + 12)
WINDOWS = [pytest.param(64, id="S1"), pytest.param(32, id="S2"),
           pytest.param(24, id="S3-ragged")]


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("entry", ["plain", "epilogue-relu", "masked"])
@pytest.mark.parametrize("f", [40, 256])
def test_window_entry_point_matches_dense(rng, f, entry, window):
    """Each entry point on the window path against the dense product: an
    empty row, an empty tile, (tile, window) pairs with no nonzero, and
    the hub row's nonzeros spread over every window."""
    a = _dense(rng)
    x = rng.standard_normal((N_COLS, f)).astype(np.float32)
    kw = dict(n_rows=N_ROWS, window=window, interpret=TPU_INTERPRET, tm=8,
              k=8)
    op = _csr(a, tile=8)
    if entry == "plain":
        got = csr_gather_spmm(*op, jnp.asarray(x), **kw)
        np.testing.assert_allclose(np.asarray(got), _hi(a, x), atol=1e-5,
                                   rtol=1e-5)
    elif entry == "masked":
        mask = (rng.random((N_COLS, f)) < 0.5).astype(np.float32)
        got = csr_gather_spmm_masked(*op, jnp.asarray(x), jnp.asarray(mask),
                                     **kw)
        np.testing.assert_allclose(np.asarray(got), _hi(a, x * mask),
                                   atol=1e-5, rtol=1e-5)
    else:
        s = rng.standard_normal((N_ROWS, f)).astype(np.float32)
        b = rng.standard_normal((1, f)).astype(np.float32)
        y, m = csr_gather_spmm_fused_epilogue(
            *op, jnp.asarray(x), jnp.asarray(s), jnp.asarray(b),
            jnp.float32(0.5), activation="relu", **kw)
        pre = _hi(a, x) + 0.5 * s + b
        np.testing.assert_allclose(np.asarray(y), np.maximum(pre, 0),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(m),
                                      (pre > 0).astype(np.float32))


@pytest.mark.parametrize("activation", ["relu", "none"])
def test_window_epilogue_runs_once(activation):
    """Row 0 reads -1 from the first window and +3 from the third: the
    self term and bias are added once, and ReLU and its mask see the whole
    sum, not the first window's -1."""
    a = np.zeros((8, 60), np.float32)
    a[0, 5], a[0, 50] = -1.0, 3.0
    x = jnp.ones((60, 128), jnp.float32)
    s = jnp.full((8, 128), 0.5, jnp.float32)
    b = jnp.full((1, 128), 0.5, jnp.float32)
    out = csr_gather_spmm_fused_epilogue(
        *_csr(a, tile=8), x, s, b, jnp.float32(-0.5), activation=activation,
        n_rows=8, window=24, interpret=TPU_INTERPRET, tm=8, k=8)
    y = out[0] if activation == "relu" else out
    want = np.full((8, 128), 0.25, np.float32)
    want[0] = 2.25
    np.testing.assert_array_equal(np.asarray(y), want)
    if activation == "relu":
        np.testing.assert_array_equal(np.asarray(out[1]), np.ones((8, 128)))


def test_tile_column_operand_holds_the_csr(rng):
    """``CSRDevice.from_csr`` reorders the CSR's nonzeros, the same triples
    at the same size, by (tile, column, row); without a tile it keeps row
    order."""
    graph = _power_law(rng, n=3000)
    dev = kops.CSRDevice.from_csr(graph, column_tile=WINDOW_TILE)
    plain = kops.CSRDevice.from_csr(graph)
    h, p = dev.host_view(), plain.host_view()
    assert dev.column_tile == WINDOW_TILE and plain.column_tile is None
    assert dev.nbytes == plain.nbytes
    np.testing.assert_array_equal(h["indptr"], graph.indptr)
    np.testing.assert_array_equal(p["indices"], graph.indices)
    np.testing.assert_array_equal(
        p["rows"], np.repeat(np.arange(graph.n_rows), np.diff(graph.indptr)))
    key = np.lexsort((h["rows"], h["indices"], h["rows"] // WINDOW_TILE))
    np.testing.assert_array_equal(key, np.arange(graph.nnz))
    triples = lambda v: sorted(zip(v["rows"].tolist(), v["indices"].tolist(),
                                   v["values"].tolist()))
    assert triples(h) == triples(p)


# ogbn-arxiv's published node count, and the VMEM ``pltpu.get_tpu_info``
# reports for "TPU v5 lite"
ARXIV_NODES = 169_343
V5E_VMEM = 128 << 20


@pytest.mark.parametrize("f,n_windows", [(256, 2), (128, 1), (40, 1)])
def test_windows_at_arxiv(f, n_windows):
    """At arxiv's size a v5e holds half the source at 256 lanes and all of
    it at 128 or fewer, beside the tiles, inside its VMEM."""
    c = window_rows(f, WINDOW_TILE, V5E_VMEM)
    assert c % 8 == 0 and -(-ARXIV_NODES // c) == n_windows
    f_pad = -(-f // 128) * 128
    assert c * f_pad * 4 + _tile_bytes(WINDOW_TILE, f_pad) <= V5E_VMEM


def _device(a, tile):
    return kops.CSRDevice(*_csr(a, tile), n_rows=a.shape[0],
                          n_cols=a.shape[1], column_tile=tile)


def test_row_ordered_operand_takes_one_window_only(rng, monkeypatch):
    """A row-ordered operand runs the SpMM where one window holds its
    source, and refuses where it would need more; in tile-column order the
    same product runs over eight windows."""
    a = _dense(rng)
    x = jnp.asarray(rng.standard_normal((N_COLS, 40)), jnp.float32)
    rows, tiled = _device(a, None), _device(a, 8)
    np.testing.assert_allclose(np.asarray(rows.matmul(x, TPU_INTERPRET)),
                               _hi(a, x), atol=1e-5, rtol=1e-5)
    monkeypatch.setattr(kops, "chip_vmem", lambda: _SMALL_VMEM)
    with pytest.raises(ValueError, match="row-ordered"):
        rows.matmul(x, TPU_INTERPRET)
    np.testing.assert_allclose(np.asarray(tiled.matmul(x, TPU_INTERPRET)),
                               _hi(a, x), atol=1e-5, rtol=1e-5)


def test_attention_refuses_a_tile_column_operand(rng):
    """The gather-attention kernels run row by row: a tile-column operand
    would mix rows, so ``build_gather_mha`` refuses it."""
    a = _dense(rng)
    rows, tiled = _device(a, None), _device(a, 8)
    kops.build_gather_mha(rows, rows)
    with pytest.raises(ValueError, match="row-ordered"):
        kops.build_gather_mha(tiled, rows)


def _attention_jaxpr_digests():
    n, nnz, heads, w = 40, 200, 3, 750
    idx = jax.ShapeDtypeStruct((nnz,), jnp.int32)
    ptr = jax.ShapeDtypeStruct((n + 1,), jnp.int32)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    kw = dict(heads=heads, n_rows=n)
    z, st = f32(n, w), f32(n, heads)
    calls = {
        "fwd": (lambda *a: csr_gather_attention_fwd(*a, **kw),
                (ptr, idx, idx, z, st, st)),
        "bwd_row": (lambda *a: csr_gather_attention_bwd_row(*a, **kw),
                    (ptr, idx, idx, z, st, st, z, st, st, st)),
        "bwd_col": (lambda *a: csr_gather_attention_bwd_col(*a, **kw),
                    (ptr, idx, idx, st, st, z, z, st, st, st)),
    }
    return {name: hashlib.sha256(
        str(jax.make_jaxpr(fn)(*args)).encode()).hexdigest()
        for name, (fn, args) in calls.items()}


def test_attention_kernels_are_unchanged():
    """The gather-attention kernels keep one row copy per nonzero over a
    row-ordered operand: their jaxprs are pinned to the ones they had
    before the SpMM took the window path."""
    assert _attention_jaxpr_digests() == {
        "fwd": "555348b654c9e9ae7af985893f164318"
               "658b2acf55db2f47eea2f1cf8638bd7e",
        "bwd_row": "0f83a4dc1d1be2cf93f181dd270dcd50"
                   "e4383420b138a6658589f93083486981",
        "bwd_col": "2520ec111665b2885d930c103d943883"
                   "df4f03aa2a52464bf46e24862932fdac",
    }


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
        # the SpMM's operand sorts each tile by column; attention's, rows
        tile = None if case == "attention" else WINDOW_TILE
        assert gop.fwd_operand.column_tile == gop.bwd_operand.column_tile \
            == tile
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
    """The first nonzero traded with the first of another column: out of
    order in row order and in tile-column order alike."""
    a = a.copy()
    j = int(np.flatnonzero(a != a[0])[0])
    a[[0, j]] = a[[j, 0]]
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


def _shuffle_stream(dev):
    """The nonzeros reordered at random: the triples still match the CSR,
    their order does not."""
    host = dev.host_view()
    perm = np.random.default_rng(3).permutation(host["indices"].shape[0])
    return dataclasses.replace(dev, **{
        k: jnp.asarray(host[k][perm]) for k in ("indices", "rows", "values")})


def test_verify_accepts_tile_column_order_and_flags_a_shuffled_stream(rng):
    graph = _power_law(rng, n=5000)  # three tiles of 2048 rows
    plan = _lower(graph, validate="off")
    assert plan.graph_op.fwd_operand.column_tile == WINDOW_TILE
    assert verify_plan(plan, mode="fast", graph=graph) == []
    gop = dataclasses.replace(
        plan.graph_op, bwd_operand=_shuffle_stream(plan.graph_op.bwd_operand))
    bad = dataclasses.replace(plan, graph_op=gop)
    flagged = {v.invariant for v in verify_plan(bad, mode="fast",
                                                graph=graph)
               if v.operand == "graph_op.bwd"}
    assert {"csr.indices_sorted", "csr.row_ids"} <= flagged
