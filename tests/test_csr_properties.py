"""Property-based invariants of the CSR/BSR containers (`graph/csr.py`):
transpose round-trip, BSR/dense agreement, dedupe idempotence,
normalisation row-sums, and the int32 index-dtype contract."""
try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # seeded-random fallback loop (no collection error)
    from _hypothesis_fallback import hypothesis, st
import numpy as np
import pytest

from repro.graph.csr import CSRGraph, csr_from_edges, csr_from_dense, csr_to_bsr

pytestmark = pytest.mark.sampling

given, settings = hypothesis.given, hypothesis.settings


def _random_graph(n, e, seed, with_weights=False):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    data = rng.standard_normal(e).astype(np.float32) if with_weights else None
    return csr_from_edges(src, dst, n, data=data)


def _assert_index_dtypes(g: CSRGraph):
    """The satellite contract: int32 indices at construction, always."""
    assert g.indptr.dtype == np.int32, g.indptr.dtype
    assert g.indices.dtype == np.int32, g.indices.dtype


@given(n=st.integers(2, 60), e=st.integers(1, 300), seed=st.integers(0, 999))
@settings(max_examples=25, deadline=None)
def test_transpose_roundtrip(n, e, seed):
    g = _random_graph(n, e, seed)
    t = g.transpose()
    tt = t.transpose()
    _assert_index_dtypes(g)
    _assert_index_dtypes(t)
    _assert_index_dtypes(tt)
    np.testing.assert_array_equal(tt.indptr, g.indptr)
    np.testing.assert_array_equal(tt.indices, g.indices)
    np.testing.assert_allclose(tt.data, g.data)
    np.testing.assert_allclose(t.to_dense(), g.to_dense().T)


@given(n=st.integers(2, 40), e=st.integers(1, 200), seed=st.integers(0, 999),
       br=st.sampled_from([2, 4, 8]), bc=st.sampled_from([4, 8, 16]))
@settings(max_examples=25, deadline=None)
def test_bsr_dense_equals_csr_dense(n, e, seed, br, bc):
    g = _random_graph(n, e, seed, with_weights=True)
    bsr = csr_to_bsr(g, br=br, bc=bc)
    np.testing.assert_allclose(bsr.to_dense(), g.to_dense(), rtol=1e-6)


@given(n=st.integers(2, 50), e=st.integers(1, 250), seed=st.integers(0, 999))
@settings(max_examples=25, deadline=None)
def test_csr_from_edges_dedupe_idempotent(n, e, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    g1 = csr_from_edges(src, dst, n)  # dedupe=True collapses duplicates
    # rebuilding from the already-deduped edge list is a fixed point
    s2, d2 = g1.edge_list()
    g2 = csr_from_edges(s2, d2, n, data=g1.data)
    _assert_index_dtypes(g1)
    _assert_index_dtypes(g2)
    np.testing.assert_array_equal(g2.indptr, g1.indptr)
    np.testing.assert_array_equal(g2.indices, g1.indices)
    np.testing.assert_allclose(g2.data, g1.data)
    # duplicates collapsed: at most one entry per (row, col)
    keys = np.asarray(d2, np.int64) * n + np.asarray(s2, np.int64)
    assert len(np.unique(keys)) == g1.nnz


@given(n=st.integers(2, 50), e=st.integers(1, 250), seed=st.integers(0, 999))
@settings(max_examples=25, deadline=None)
def test_row_normalized_row_sums(n, e, seed):
    g = _random_graph(n, e, seed)  # unit weights
    rn = g.row_normalized()
    _assert_index_dtypes(rn)
    sums = rn.to_dense().sum(axis=1)
    deg = g.degrees()
    np.testing.assert_allclose(sums[deg > 0], 1.0, rtol=1e-5)
    np.testing.assert_allclose(sums[deg == 0], 0.0)


@given(n=st.integers(2, 40), e=st.integers(1, 200), seed=st.integers(0, 999))
@settings(max_examples=25, deadline=None)
def test_sym_normalized_matches_dense_formula(n, e, seed):
    g = _random_graph(n, e, seed)
    sym = g.sym_normalized()
    _assert_index_dtypes(sym)
    a = g.to_dense()
    d_in = np.maximum(a.sum(axis=1), 1.0)   # unit weights: row sums = in-deg
    d_out = np.maximum(a.sum(axis=0), 1.0)
    expect = a / np.sqrt(d_in)[:, None] / np.sqrt(d_out)[None, :]
    np.testing.assert_allclose(sym.to_dense(), expect, rtol=1e-5, atol=1e-7)


def _row_scaled_loop(g: CSRGraph, scale) -> np.ndarray:
    """The per-row loop the normalisations were written as: the reference
    their vectorised form must match bit for bit."""
    data = g.data.copy()
    for row in range(g.n_rows):
        s, e = g.indptr[row], g.indptr[row + 1]
        data[s:e] *= scale(row, g.indices[s:e])
    return data


@pytest.mark.parametrize("kind", ["row", "sym"])
def test_normalisations_match_the_row_loop(kind):
    g = _random_graph(60, 400, 7, with_weights=True)  # some empty rows
    deg_in = np.maximum(g.degrees(), 1)
    if kind == "row":
        got, inv = g.row_normalized(), 1.0 / deg_in.astype(np.float32)
        want = _row_scaled_loop(g, lambda row, cols: inv[row])
    else:
        deg_out = np.maximum(np.bincount(g.indices, minlength=g.n_cols), 1)
        d_in = 1.0 / np.sqrt(deg_in).astype(np.float32)
        d_out = 1.0 / np.sqrt(deg_out).astype(np.float32)
        got = g.sym_normalized()
        want = _row_scaled_loop(g, lambda row, cols: d_in[row] * d_out[cols])
    assert got.data.dtype == want.dtype
    np.testing.assert_array_equal(got.data, want)


def test_csr_from_dense_dtypes(rng):
    x = rng.standard_normal((13, 17)).astype(np.float32)
    x[rng.random(x.shape) < 0.8] = 0.0
    g = csr_from_dense(x)
    _assert_index_dtypes(g)
    np.testing.assert_allclose(g.to_dense(), x)


def test_int32_overflow_guard():
    """The contract is enforced, not silently wrapped."""
    with pytest.raises(OverflowError):
        CSRGraph(indptr=np.array([0]), n_rows=0, n_cols=0,
                 indices=_FakeHuge(), data=np.zeros(0, np.float32))


class _FakeHuge:
    """Stand-in with a too-large first dim (allocating 2^31 ints is not
    something a unit test should do)."""
    shape = (np.iinfo(np.int32).max + 1,)
