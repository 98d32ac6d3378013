"""BSR SpMM Pallas kernel vs pure-jnp oracle: shape/dtype sweeps +
hypothesis property tests (interpret mode on CPU)."""
try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # seeded-random fallback loop (no collection error)
    from _hypothesis_fallback import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph.csr import csr_from_edges, csr_to_bsr, csr_from_dense
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.bsr_spmm import bsr_spmm_fused_epilogue, bsr_spmm_masked

pytestmark = pytest.mark.kernels


def _random_graph(rng, n, n_edges, n_cols=None):
    src = rng.integers(0, n_cols or n, n_edges)
    dst = rng.integers(0, n, n_edges)
    return csr_from_edges(src, dst, n, n_cols=n_cols)


@pytest.mark.parametrize("n,edges,f", [(17, 60, 32), (64, 400, 64),
                                       (130, 900, 96), (33, 0, 32)])
@pytest.mark.parametrize("br,bc", [(8, 16), (8, 128), (16, 32)])
def test_bsr_spmm_matches_dense(rng, n, edges, f, br, bc):
    g = _random_graph(rng, n, edges)
    dense = g.to_dense()
    x = rng.standard_normal((n, f)).astype(np.float32)
    dev = kops.BSRDevice.from_bsr(csr_to_bsr(g, br=br, bc=bc))
    y = dev.matmul(jnp.asarray(x), bf=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y), dense @ x, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bsr_spmm_dtypes(rng, dtype):
    g = _random_graph(rng, 40, 200)
    dense = g.to_dense()
    x = rng.standard_normal((40, 64)).astype(np.float32)
    dev = kops.BSRDevice.from_bsr(csr_to_bsr(g, br=8, bc=16))
    y = dev.matmul(jnp.asarray(x).astype(dtype), bf=32, interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(
        np.asarray(y, dtype=np.float32), dense @ x, atol=tol, rtol=tol
    )


def test_bsr_spmm_rectangular(rng):
    """Non-square operand (the sparse-feature-matmul use case)."""
    g = _random_graph(rng, 50, 300, n_cols=70)
    dense = g.to_dense()
    w = rng.standard_normal((70, 48)).astype(np.float32)
    dev = kops.BSRDevice.from_bsr(csr_to_bsr(g, br=8, bc=16))
    y = dev.matmul(jnp.asarray(w), bf=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y), dense @ w, atol=1e-4, rtol=1e-4)


def test_bsr_ref_oracle_agrees(rng):
    g = _random_graph(rng, 37, 180)
    bsr = csr_to_bsr(g, br=8, bc=16)
    x = rng.standard_normal((bsr.padded_cols, 32)).astype(np.float32)
    y_ref = kref.bsr_spmm_ref(
        jnp.asarray(bsr.block_rows), jnp.asarray(bsr.block_cols),
        jnp.asarray(bsr.blocks), jnp.asarray(x), bsr.padded_rows,
    )
    dense = np.zeros((bsr.padded_rows, bsr.padded_cols), np.float32)
    d = bsr.to_dense()
    dense[: d.shape[0], : d.shape[1]] = d
    np.testing.assert_allclose(np.asarray(y_ref), dense @ x, atol=1e-4)


@hypothesis.given(
    n=st.integers(4, 48),
    f=st.sampled_from([16, 32, 48]),
    density=st.floats(0.01, 0.5),
    seed=st.integers(0, 2**31 - 1),
)
@hypothesis.settings(max_examples=20, deadline=None)
def test_bsr_spmm_property(n, f, density, seed):
    """Property: kernel == dense matmul for arbitrary sparsity patterns."""
    r = np.random.default_rng(seed)
    mat = r.standard_normal((n, n)).astype(np.float32)
    mat[r.random((n, n)) > density] = 0.0
    csr = csr_from_dense(mat)
    x = r.standard_normal((n, f)).astype(np.float32)
    dev = kops.BSRDevice.from_bsr(csr_to_bsr(csr, br=8, bc=16))
    y = dev.matmul(jnp.asarray(x), bf=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y), mat @ x, atol=1e-3, rtol=1e-3)


def test_last_in_row_is_dual_of_first(rng):
    """Every block-row has exactly one first and one last block; within the
    row-sorted flat layout last is first shifted by one block-row."""
    g = _random_graph(rng, 57, 300)
    bsr = csr_to_bsr(g, br=8, bc=16)
    n_block_rows = bsr.padded_rows // bsr.br
    assert bsr.first_in_row.sum() == n_block_rows  # incl. empty-row zero blocks
    assert bsr.last_in_row.sum() == n_block_rows
    np.testing.assert_array_equal(bsr.last_in_row[:-1], bsr.first_in_row[1:])
    assert bsr.last_in_row[-1] == 1 and bsr.first_in_row[0] == 1
    # per block-row: the last flag sits on the row's final flat block
    for r in np.unique(bsr.block_rows):
        idx = np.flatnonzero(bsr.block_rows == r)
        np.testing.assert_array_equal(
            bsr.last_in_row[idx], (idx == idx[-1]).astype(np.int32))


@pytest.mark.parametrize("has_self,has_bias,activation", [
    (True, True, "relu"),
    (True, False, "none"),
    (False, True, "relu"),
    (False, False, "none"),
    (False, True, "none"),
])
def test_fused_epilogue_kernel_matches_oracle(rng, has_self, has_bias,
                                              activation):
    """act(A @ X + alpha*self + bias) fused at last_in_row == composed ops,
    and the saved mask is the pre-activation sign."""
    n, f, br, bc, bf = 45, 32, 8, 16, 16
    g = _random_graph(rng, n, 260)
    bsr = csr_to_bsr(g, br=br, bc=bc)
    dense = np.zeros((bsr.padded_rows, bsr.padded_cols), np.float32)
    d = bsr.to_dense()
    dense[: d.shape[0], : d.shape[1]] = d
    x = rng.standard_normal((bsr.padded_cols, f)).astype(np.float32)
    self_t = (rng.standard_normal((bsr.padded_rows, f)).astype(np.float32)
              if has_self else None)
    bias = (rng.standard_normal((1, f)).astype(np.float32)
            if has_bias else None)
    alpha = jnp.float32(0.7) if has_self else None

    out = bsr_spmm_fused_epilogue(
        jnp.asarray(bsr.block_rows), jnp.asarray(bsr.block_cols),
        jnp.asarray(bsr.first_in_row), jnp.asarray(bsr.last_in_row),
        jnp.asarray(bsr.blocks), jnp.asarray(x),
        None if self_t is None else jnp.asarray(self_t),
        None if bias is None else jnp.asarray(bias), alpha,
        n_rows_padded=bsr.padded_rows, bf=bf, activation=activation,
        interpret=True)

    z = dense @ x
    if has_self:
        z = z + 0.7 * self_t
    if has_bias:
        z = z + bias
    if activation == "relu":
        y, mask = out
        np.testing.assert_allclose(np.asarray(y), np.maximum(z, 0.0),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(mask), (z > 0).astype(np.float32))
    else:
        np.testing.assert_allclose(np.asarray(out), z, atol=1e-4, rtol=1e-4)


def test_fused_epilogue_kernel_agrees_with_xla_ref(rng):
    """Pallas-interpret fused kernel == the lax-composed XLA inner."""
    n, f = 40, 48
    g = _random_graph(rng, n, 220)
    bsr = csr_to_bsr(g, br=8, bc=16)
    x = rng.standard_normal((bsr.padded_cols, f)).astype(np.float32)
    s = rng.standard_normal((bsr.padded_rows, f)).astype(np.float32)
    b = rng.standard_normal((1, f)).astype(np.float32)
    args = (jnp.asarray(bsr.block_rows), jnp.asarray(bsr.block_cols))
    y_p, m_p = bsr_spmm_fused_epilogue(
        *args, jnp.asarray(bsr.first_in_row), jnp.asarray(bsr.last_in_row),
        jnp.asarray(bsr.blocks), jnp.asarray(x), jnp.asarray(s),
        jnp.asarray(b), jnp.float32(1.3), n_rows_padded=bsr.padded_rows,
        bf=16, activation="relu", interpret=True)
    y_r, m_r = kref.bsr_spmm_fused_ref(
        *args, jnp.asarray(bsr.blocks), jnp.asarray(x), bsr.padded_rows,
        jnp.asarray(s), jnp.asarray(b), jnp.float32(1.3), "relu")
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(m_p), np.asarray(m_r))


def test_masked_spmm_kernel_matches_oracle(rng):
    """A @ (mask ⊙ X) with the mask applied on tile load == masked matmul."""
    n, f = 50, 32
    g = _random_graph(rng, n, 240)
    bsr = csr_to_bsr(g, br=8, bc=16)
    dense = np.zeros((bsr.padded_rows, bsr.padded_cols), np.float32)
    d = bsr.to_dense()
    dense[: d.shape[0], : d.shape[1]] = d
    x = rng.standard_normal((bsr.padded_cols, f)).astype(np.float32)
    mask = (rng.random((bsr.padded_cols, f)) < 0.5).astype(np.float32)
    y = bsr_spmm_masked(
        jnp.asarray(bsr.block_rows), jnp.asarray(bsr.block_cols),
        jnp.asarray(bsr.first_in_row), jnp.asarray(bsr.blocks),
        jnp.asarray(x), jnp.asarray(mask),
        n_rows_padded=bsr.padded_rows, bf=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y), dense @ (mask * x),
                               atol=1e-4, rtol=1e-4)


def test_aligned_matmul_adds_no_copies(rng):
    """Satellite: tile-aligned operands take the pad/slice-free path — the
    jaxpr of the aligned call contains no pad equation."""
    n, f, bc = 128, 128, 16  # n % bc == 0, f % bf == 0
    g = _random_graph(rng, n, 500)
    dev = kops.BSRDevice.from_bsr(csr_to_bsr(g, br=8, bc=bc))
    assert dev.n_cols_padded == n
    x = jnp.asarray(rng.standard_normal((n, f)).astype(np.float32))
    jaxpr_aligned = jax.make_jaxpr(
        lambda v: dev.matmul_ref(v))(x)
    assert "pad" not in str(jaxpr_aligned), "aligned path must not pad"
    # misaligned still pads (and still agrees with the dense oracle)
    x_odd = jnp.asarray(rng.standard_normal((n, 20)).astype(np.float32))
    jaxpr_odd = jax.make_jaxpr(
        lambda v: dev.matmul(v, bf=16, interpret=True))(x_odd)
    assert "pad" in str(jaxpr_odd)
    np.testing.assert_allclose(
        np.asarray(dev.matmul(x, bf=16, interpret=True)),
        g.to_dense() @ np.asarray(x), atol=1e-4, rtol=1e-4)


def test_transpose_pair_is_adjoint(rng):
    """<A x, y> == <x, Aᵀ y> through the BSR pair."""
    g = _random_graph(rng, 30, 150)
    fwd, bwd = kops.build_bsr_pair(g, br=8, bc=16)
    x = jnp.asarray(rng.standard_normal((30, 16)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((30, 16)).astype(np.float32))
    ax = fwd.matmul(x, bf=16, interpret=True)
    aty = bwd.matmul(y, bf=16, interpret=True)
    np.testing.assert_allclose(
        float(jnp.vdot(ax, y)), float(jnp.vdot(x, aty)), rtol=1e-4
    )


def _hub_bsr(rng, n=64, bc=8):
    """BSR whose block-row 0 is a hub: it touches every block-column, so it
    straddles several small windows of the block stream."""
    src = np.concatenate([rng.integers(0, n, 200), np.arange(n)])
    dst = np.concatenate([rng.integers(0, n, 200), np.zeros(n, np.int64)])
    return csr_to_bsr(csr_from_edges(src, dst, n), br=8, bc=bc)


@pytest.mark.parametrize("window", [1, 3, 5])
def test_windowed_stream_matches_whole(rng, window):
    """Windows smaller than the hub row: every block-row that straddles a
    window boundary resumes from the carry, the fused epilogue fires once
    at the true last_in_row, and all three kernels match the dense oracle."""
    bsr = _hub_bsr(rng)
    assert np.bincount(bsr.block_rows)[0] > window  # hub spans windows
    dense = np.zeros((bsr.padded_rows, bsr.padded_cols), np.float32)
    d = bsr.to_dense()
    dense[: d.shape[0], : d.shape[1]] = d
    f = 16
    x = rng.standard_normal((bsr.padded_cols, f)).astype(np.float32)
    s = rng.standard_normal((bsr.padded_rows, f)).astype(np.float32)
    b = rng.standard_normal((1, f)).astype(np.float32)
    m = (rng.random((bsr.padded_cols, f)) < 0.5).astype(np.float32)
    idx = (jnp.asarray(bsr.block_rows), jnp.asarray(bsr.block_cols),
           jnp.asarray(bsr.first_in_row))
    kw = dict(n_rows_padded=bsr.padded_rows, bf=f, interpret=True,
              window=window)
    y = kops.bsr_spmm(*idx, jnp.asarray(bsr.blocks), jnp.asarray(x), **kw)
    np.testing.assert_allclose(np.asarray(y), dense @ x, atol=1e-4, rtol=1e-4)
    y, mask = bsr_spmm_fused_epilogue(
        *idx, jnp.asarray(bsr.last_in_row), jnp.asarray(bsr.blocks),
        jnp.asarray(x), jnp.asarray(s), jnp.asarray(b), jnp.float32(0.7),
        activation="relu", **kw)
    z = dense @ x + 0.7 * s + b
    np.testing.assert_allclose(np.asarray(y), np.maximum(z, 0.0),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(mask), (z > 0).astype(np.float32))
    y = bsr_spmm_masked(*idx, jnp.asarray(bsr.blocks), jnp.asarray(x),
                        jnp.asarray(m), **kw)
    np.testing.assert_allclose(np.asarray(y), dense @ (m * x),
                               atol=1e-4, rtol=1e-4)


def test_block_windows_fit_smem_budget():
    """Windows tile the stream exactly, in order, each within the budget."""
    from repro.kernels.bsr_spmm import SMEM_INDEX_WORDS, block_windows

    for n_blocks, streams in ((1_081_262, 3), (917_090, 4), (5, 3)):
        wins = block_windows(n_blocks, streams)
        assert wins[0][0] == 0 and wins[-1][1] == n_blocks
        assert all(a[1] == b[0] for a, b in zip(wins, wins[1:]))
        assert all((e - s) * streams <= SMEM_INDEX_WORDS for s, e in wins)
