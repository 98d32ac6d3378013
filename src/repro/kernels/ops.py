"""Jit'd wrappers around the Pallas kernels.

Builders accept host-side numpy structures (CSRGraph / dense feature
matrices), run the one-time layout conversions (CSR→BSR, padding), and
return device-callable closures. ``interpret`` defaults to True off-TPU so
the same code path validates on CPU (per the Pallas guidance for this
environment) and compiles natively on TPU.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from repro.common.spans import count, span
from repro.graph.csr import BSRMatrix, CSRGraph, csr_from_dense, csr_to_bsr
from repro.kernels.bsr_spmm import (
    bsr_spmm,
    bsr_spmm_fused_epilogue,
    bsr_spmm_masked,
)
from repro.kernels.bsr_attention import (
    bsr_attention_bwd_col,
    bsr_attention_bwd_row,
    bsr_attention_fwd,
)
from repro.kernels.csr_gather_attention import (
    csr_gather_attention_bwd_col,
    csr_gather_attention_bwd_row,
    csr_gather_attention_fwd,
)
from repro.kernels.csr_gather_spmm import (
    WINDOW_TILE,
    csr_gather_spmm,
    csr_gather_spmm_fused_epilogue,
    csr_gather_spmm_masked,
    window_rows,
)
from repro.kernels.fused_adam import fused_adam  # re-export


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def chip_vmem() -> int | None:
    """VMEM bytes of the TPU this process runs on, as the chip reports
    them; ``None`` off the chip."""
    if jax.default_backend() != "tpu":
        return None
    return pltpu.get_tpu_info().vmem_capacity_bytes


def feature_tile(f: int) -> tuple[int, int]:
    """(bf, f_pad): the lane-tile size and padded feature dim for a SpMM.

    Full 128-lane tiles when the feature dim divides evenly; one un-padded
    tile of the dim itself when f < 128; otherwise 128-lane tiles with the
    dim padded up to the next multiple (e.g. f=200 -> bf=128, f_pad=256).
    The same policy the distributed backend applies to its local SpMMs,
    now shared with the fused-epilogue closures so narrow feature dims
    never pay a 128-pad.
    """
    bf = min(128, f) if f % 128 != 0 else 128
    f_pad = -(-f // bf) * bf
    return bf, f_pad


@dataclasses.dataclass
class BSRDevice:
    """Device-resident flattened BSR + padding metadata."""

    block_rows: jax.Array
    block_cols: jax.Array
    first_in_row: jax.Array
    blocks: jax.Array
    n_rows: int
    n_cols: int
    n_rows_padded: int
    n_cols_padded: int
    br: int
    bc: int
    last_in_row: jax.Array | None = None  # dual of first_in_row (fused epilogue)

    format: ClassVar[str] = "bsr"

    @classmethod
    @span("bsr_upload")
    def from_bsr(cls, bsr: BSRMatrix) -> "BSRDevice":
        return cls(
            block_rows=jnp.asarray(bsr.block_rows),
            block_cols=jnp.asarray(bsr.block_cols),
            first_in_row=jnp.asarray(bsr.first_in_row),
            blocks=jnp.asarray(bsr.blocks),
            n_rows=bsr.n_rows,
            n_cols=bsr.n_cols,
            n_rows_padded=bsr.padded_rows,
            n_cols_padded=bsr.padded_cols,
            br=bsr.br,
            bc=bsr.bc,
            last_in_row=jnp.asarray(bsr.last_in_row),
        )

    def host_view(self) -> dict:
        """One-shot host copy of the index/flag/value arrays (a single
        ``device_get`` round-trip) — what the plan-contract verifier
        (``core.verify``) inspects instead of pulling fields one by one."""
        arrays = {"rows": self.block_rows, "cols": self.block_cols,
                  "first": self.first_in_row, "blocks": self.blocks}
        if self.last_in_row is not None:
            arrays["last"] = self.last_in_row
        host = jax.device_get(arrays)
        return {k: np.asarray(v) for k, v in host.items()}

    def matmul(self, x: jax.Array, bf: int = 128, interpret: bool | None = None) -> jax.Array:
        """Y = A @ X, unpadded in/out: x is [n_cols, F'], returns [n_rows, F'].

        Pad/slice are no-ops when the operand is already aligned
        (``x.shape[0] == n_cols_padded`` and ``F % bf == 0``) — the common
        tile-aligned case adds zero copies.
        """
        interpret = default_interpret() if interpret is None else interpret
        f = x.shape[-1]
        f_pad = -(-f // bf) * bf
        x_p = x
        if x.shape[0] != self.n_cols_padded or f_pad != f:
            x_p = jnp.pad(x, ((0, self.n_cols_padded - x.shape[0]),
                              (0, f_pad - f)))
        y = bsr_spmm(
            self.block_rows, self.block_cols, self.first_in_row, self.blocks,
            x_p, n_rows_padded=self.n_rows_padded, bf=bf, interpret=interpret,
        )
        if self.n_rows != self.n_rows_padded or f != f_pad:
            y = y[: self.n_rows, :f]
        return y

    def matmul_ref(self, x: jax.Array) -> jax.Array:
        """Same BSR layout lowered as XLA block-gather + einsum — the
        compiled-path stand-in for CPU wall-time benchmarks (the Pallas
        interpreter would measure Python, not the layout)."""
        from repro.kernels.ref import bsr_spmm_ref

        f = x.shape[-1]
        x_p = x
        if x.shape[0] != self.n_cols_padded:
            x_p = jnp.pad(x, ((0, self.n_cols_padded - x.shape[0]), (0, 0)))
        y = bsr_spmm_ref(self.block_rows, self.block_cols, self.blocks,
                         x_p, self.n_rows_padded)
        if self.n_rows != self.n_rows_padded:
            y = y[: self.n_rows]
        return y


@dataclasses.dataclass
class CSRDevice:
    """Device-resident CSR: the row-gather kernels' operand
    (``kernels/csr_gather_spmm.py``, ``kernels/csr_gather_attention.py``),
    for graphs whose nonzeros do not fill BSR blocks. O(nnz) bytes: no
    blocks, no padding.

    ``column_tile=None`` (the default) keeps plain row order, in which each
    row's nonzeros are contiguous and sorted by column: the attention
    kernels' order (their online softmax runs row by row). Given a tile,
    the nonzeros are grouped by destination tile of ``column_tile`` rows
    and sorted by column inside each tile (stable, so rows ascend within a
    column): the SpMM's order, in which every (tile, source window) pair
    owns one contiguous run. ``indptr`` holds the per-row counts either
    way, so ``indptr`` at a multiple of the tile bounds a tile's run."""

    indptr: jax.Array   # [n_rows + 1] int32
    indices: jax.Array  # [nnz] int32, the column of each nonzero
    rows: jax.Array     # [nnz] int32, the row of each nonzero
    values: jax.Array   # [nnz] float32
    n_rows: int
    n_cols: int
    column_tile: int | None = None

    format: ClassVar[str] = "gather"

    @classmethod
    @span("csr_build")
    def from_csr(cls, csr: CSRGraph,
                 column_tile: int | None = None) -> "CSRDevice":
        rows = np.repeat(np.arange(csr.n_rows, dtype=np.int32),
                         np.diff(csr.indptr))
        indices, values = csr.indices, csr.data
        if column_tile:
            order = np.argsort(
                (rows // column_tile).astype(np.int64) * max(csr.n_cols, 1)
                + indices, kind="stable")
            rows, indices, values = rows[order], indices[order], values[order]
        return cls(indptr=jnp.asarray(csr.indptr, jnp.int32),
                   indices=jnp.asarray(indices, jnp.int32),
                   rows=jnp.asarray(rows),
                   values=jnp.asarray(values, jnp.float32),
                   n_rows=csr.n_rows, n_cols=csr.n_cols,
                   column_tile=column_tile)

    @property
    def arrays(self) -> tuple:
        """(indptr, indices, rows, values): the kernels' leading arguments."""
        return self.indptr, self.indices, self.rows, self.values

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self.arrays)

    def host_view(self) -> dict:
        """One-shot host copy of the four arrays (for ``core.verify``)."""
        host = jax.device_get(dict(zip(("indptr", "indices", "rows",
                                        "values"), self.arrays)))
        return {k: np.asarray(v) for k, v in host.items()}

    def matmul(self, x: jax.Array, interpret: bool | None = None) -> jax.Array:
        """Y = A @ X on unpadded x [n_cols, F] -> [n_rows, F]."""
        return gather_pass(csr_gather_spmm, self.arrays, self.column_tile,
                           x, n_rows=self.n_rows, interpret=interpret)


def gather_pass(entry, arrays, column_tile: int | None, x, *args,
                n_rows: int, interpret: bool | None = None, **kw):
    """One SpMM pass over a CSR operand through ``entry`` (one of the
    three ``csr_gather_spmm*``, kernels ``csr_window_spmm*``), at the
    operand's tiles and the windows the chip's VMEM holds
    (``window_rows``; off the chip one window holds the whole source).
    More than one window needs the operand's tile-column order: a
    row-ordered one raises where its source does not fit one window. Each
    pass adds 1 to the counter ``spmm_window`` under the open span, while
    tracing."""
    interpret = default_interpret() if interpret is None else interpret
    tm = column_tile or WINDOW_TILE
    vmem = chip_vmem()
    window = None if vmem is None else window_rows(x.shape[1], tm, vmem)
    if column_tile is None and window is not None and window < x.shape[0]:
        raise ValueError(
            f"a row-ordered CSR operand reads {x.shape[0]} source rows, more "
            f"than one VMEM window of {window}: build it with a column_tile")
    count("spmm_window")
    return entry(*arrays, x, *args, n_rows=n_rows, window=window,
                 interpret=interpret, tm=tm, **kw)


def build_bsr_pair(graph: CSRGraph, br: int = 8,
                   bc: int | None = None) -> tuple[BSRDevice, BSRDevice]:
    """(A_bsr, Aᵀ_bsr) — the forward/backward duo, materialised once at load
    exactly as the paper materialises CSR (fwd) + CSC (bwd) in §IV-B.b.
    ``bc=None`` = the adaptive fallback width (``graph.csr.adaptive_bc``)."""
    fwd = BSRDevice.from_bsr(csr_to_bsr(graph, br=br, bc=bc))
    bwd = BSRDevice.from_bsr(csr_to_bsr(graph.transpose(), br=br, bc=bc))
    return fwd, bwd


def build_sparse_feature_matmul(x_np: np.ndarray, br: int = 8,
                                bc: int | None = None,
                                engine: "str | None" = None):
    """Sparsity-engine sparse path for X @ W: X (sparse features) in the
    selected backend's layout (legacy flat-args form; the lowering pass uses
    ``backend.feature_matmul_sparse`` directly, which also carries the
    pre-transposed backward operand).

    Returns ``(fn, args)`` where ``fn(*args, w)`` computes X @ W via the
    backend's spmm primitive. The O(nnz) conversion happens here, once
    (Alg 1 Phase 1 'DenseToCSR' analog). ``engine=None`` keeps the Pallas
    kernel (this helper's historical behaviour); pass a registry name to
    route elsewhere.
    """
    from repro.backends import get_backend  # local: backends imports this module

    backend = get_backend(engine or "pallas")
    bsr = backend.build_spmm_operand(csr_from_dense(np.asarray(x_np)), br=br, bc=bc)
    if not isinstance(bsr, BSRDevice):  # edge-list backends: closure form only
        return (lambda w, *, _b=backend, _op=bsr: _b.spmm(_op, w)), ()

    def fn(block_rows, block_cols, first, blocks, w, *, _meta=bsr):
        dev = dataclasses.replace(
            _meta, block_rows=block_rows, block_cols=block_cols,
            first_in_row=first, blocks=blocks,
        )
        return backend.spmm(dev, w)

    args = (bsr.block_rows, bsr.block_cols, bsr.first_in_row, bsr.blocks)
    return fn, args


# convenience jit'd dense path used by the engine and benchmarks
@jax.jit
def dense_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    return x @ w


def build_csr_matmul_xla(x_np: np.ndarray):
    """CSR-style X@W whose work is ∝ nnz — the CPU wall-time analog of the
    paper's per-row FMA kernel (Alg 2): gather W rows per nonzero, scale,
    segment-sum into output rows. Used for γ calibration and the crossover
    benchmark; the BSR Pallas kernel is the TPU-target lowering."""
    csr = csr_from_dense(np.asarray(x_np))
    src, dst = csr.edge_list()  # src = column (into W), dst = output row
    cols = jnp.asarray(src)
    rows = jnp.asarray(dst)
    vals = jnp.asarray(csr.data)
    n_rows = csr.n_rows

    @jax.jit
    def fn(w):
        msgs = w[cols] * vals[:, None]
        return jax.ops.segment_sum(msgs, rows, num_segments=n_rows)

    return fn


# ---------------------------------------------------------------------------
# Functional fwd/bwd BSR pair — usable inside shard_map (no closures over
# device arrays; the per-rank BSR arrays arrive as sharded arguments).
# ---------------------------------------------------------------------------

def _dispatch_spmm(arrays, x, n_rows_padded, bf, interpret, inner):
    rows, cols, first, blocks = arrays
    if inner == "pallas":
        interpret = default_interpret() if interpret is None else interpret
        return bsr_spmm(rows, cols, first, blocks, x,
                        n_rows_padded=n_rows_padded, bf=bf, interpret=interpret)
    from repro.kernels.ref import bsr_spmm_ref

    return bsr_spmm_ref(rows, cols, blocks, x, n_rows_padded)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def bsr_spmm_pair(fwd_arrays, bwd_arrays, x, n_rows_padded, bf, interpret,
                  inner="pallas"):
    """Y = A @ X where (fwd_arrays, bwd_arrays) are the BSR of A and Aᵀ.

    Differentiable in ``x`` only (the graph is data, not a parameter); the
    VJP multiplies by the pre-built transposed operand — conflict-free, no
    autodiff through the sparse layout. ``inner`` picks the executor:
    ``"pallas"`` runs the fused kernel, ``"xla"`` the compiled block-gather
    + einsum — the same split as the backend registry, so the distributed
    composition can ride either. ``x`` must already be padded:
    [n_cols_padded, F], F % bf == 0, and — for the VJP shapes to line up —
    both paddings must share a common multiple (pad the logical dims to
    lcm(br, bc) up front; see pad_graph_dims).
    """
    return _dispatch_spmm(fwd_arrays, x, n_rows_padded, bf, interpret, inner)


def _pair_fwd(fwd_arrays, bwd_arrays, x, n_rows_padded, bf, interpret, inner):
    y = bsr_spmm_pair(fwd_arrays, bwd_arrays, x, n_rows_padded, bf, interpret,
                      inner)
    return y, (fwd_arrays, bwd_arrays, x.shape[0])


def _zero_cotangents(tree):
    """Zero cotangents: float0 for integer leaves (index arrays)."""
    def z(a):
        if jnp.issubdtype(jnp.result_type(a), jnp.floating):
            return jnp.zeros_like(a)
        return np.zeros(np.shape(a), dtype=jax.dtypes.float0)
    return jax.tree_util.tree_map(z, tree)


def _pair_bwd(n_rows_padded, bf, interpret, inner, res, dy):
    fwd_arrays, bwd_arrays, n_cols_padded = res
    dx = _dispatch_spmm(bwd_arrays, dy.astype(jnp.float32), n_cols_padded,
                        bf, interpret, inner)
    return _zero_cotangents(fwd_arrays), _zero_cotangents(bwd_arrays), dx


bsr_spmm_pair.defvjp(_pair_fwd, _pair_bwd)


# ---------------------------------------------------------------------------
# Fused-epilogue pair: forward epilogue in VMEM at last_in_row, backward
# applying the saved activation mask inside the transposed SpMM.
# ---------------------------------------------------------------------------

def _dispatch_fused(fwd_arrays, x, self_term, bias, alpha, n_rows_padded,
                    bf, interpret, inner, activation):
    """(y, mask|None) on the selected inner executor. ``fwd_arrays`` is the
    5-tuple (rows, cols, first, last, blocks)."""
    rows, cols, first, last, blocks = fwd_arrays
    if inner == "pallas":
        interpret = default_interpret() if interpret is None else interpret
        out = bsr_spmm_fused_epilogue(
            rows, cols, first, last, blocks, x, self_term, bias, alpha,
            n_rows_padded=n_rows_padded, bf=bf, activation=activation,
            interpret=interpret)
        return out if activation == "relu" else (out, None)
    from repro.kernels.ref import bsr_spmm_fused_ref

    return bsr_spmm_fused_ref(rows, cols, blocks, x, n_rows_padded,
                              self_term, bias, alpha, activation)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def bsr_spmm_fused_pair(fwd_arrays, bwd_arrays, x, self_term, bias, alpha,
                        geom, bf, interpret, inner="pallas",
                        activation="none"):
    """Y = act(A @ X + alpha * self_term + bias) over a pre-built BSR pair.

    The fused-epilogue sibling of ``bsr_spmm_pair``: ``fwd_arrays`` is the
    5-tuple BSR of A (rows, cols, first, last, blocks), ``bwd_arrays`` the
    4-tuple BSR of Aᵀ. Differentiable in ``x``, ``self_term``, ``bias`` and
    ``alpha`` (pass ``None`` to drop an epilogue operand — the spec is
    static by presence). The VJP reuses the saved activation mask *inside*
    the transposed SpMM (``bsr_spmm_masked`` on the Pallas inner), so the
    masked cotangent mask ⊙ dY is never materialized; dbias/dself/dalpha are
    lane/row reductions of the same masked stream.

    ``geom = (n_rows_padded, n_cols_padded, n_back_padded)`` carries the
    static pair geometry: A's padded rows/cols and Aᵀ's padded rows. Unlike
    ``bsr_spmm_pair`` the two paddings need not share a common multiple —
    the VJP re-tiles the cotangent between them (statically, zero rows only).
    Operands are padded: x [n_cols_padded, F], self_term [n_rows_padded, F],
    bias [1, F], F % bf == 0.
    """
    n_rows_padded, _, _ = geom
    y, _ = _dispatch_fused(fwd_arrays, x, self_term, bias, alpha,
                           n_rows_padded, bf, interpret, inner, activation)
    return y


def _fused_pair_fwd(fwd_arrays, bwd_arrays, x, self_term, bias, alpha,
                    geom, bf, interpret, inner, activation):
    n_rows_padded, _, _ = geom
    y, mask = _dispatch_fused(fwd_arrays, x, self_term, bias, alpha,
                              n_rows_padded, bf, interpret, inner, activation)
    res = (fwd_arrays, bwd_arrays, mask, self_term, bias, alpha)
    return y, res


def _fused_pair_bwd(geom, bf, interpret, inner, activation, res, dy):
    fwd_arrays, bwd_arrays, mask, self_term, bias, alpha = res
    n_rows_padded, n_cols_padded, n_back_padded = geom
    dy = dy.astype(jnp.float32)
    bc_t = bwd_arrays[-1].shape[-1]  # Aᵀ block-column size
    t_in = -(-n_rows_padded // bc_t) * bc_t  # dY rows re-tiled for Aᵀ
    dz = dy * mask if activation == "relu" else dy
    if activation == "relu" and inner == "pallas":
        # the fused backward: mask applied to the dY tile on load
        rows, cols, first, blocks = bwd_arrays
        interp = default_interpret() if interpret is None else interpret
        dy_t = jnp.pad(dy, ((0, t_in - n_rows_padded), (0, 0)))
        m_t = jnp.pad(mask, ((0, t_in - n_rows_padded), (0, 0)))
        dx = bsr_spmm_masked(rows, cols, first, blocks, dy_t, m_t,
                             n_rows_padded=n_back_padded, bf=bf,
                             interpret=interp)
    else:
        dz_t = jnp.pad(dz, ((0, t_in - n_rows_padded), (0, 0)))
        dx = _dispatch_spmm(bwd_arrays, dz_t, n_back_padded, bf, interpret,
                            inner)
    # re-tile Aᵀ's output rows back to x's padding (extra rows are zeros:
    # they index past A's logical columns)
    if n_back_padded > n_cols_padded:
        dx = dx[:n_cols_padded]
    elif n_back_padded < n_cols_padded:
        dx = jnp.pad(dx, ((0, n_cols_padded - n_back_padded), (0, 0)))
    return (_zero_cotangents(fwd_arrays), _zero_cotangents(bwd_arrays),
            dx, *_epilogue_cotangents(dz, self_term, bias, alpha))


def _epilogue_cotangents(dz, self_term, bias, alpha):
    """(dself, dbias, dalpha) of ``alpha·self_term + bias`` given the
    (masked) cotangent dz: a scale and two reductions of the same stream."""
    dself = dalpha = None
    if self_term is not None:
        a = jnp.asarray(alpha, jnp.float32)
        dself = a * dz
        dalpha = jnp.vdot(dz, self_term.astype(jnp.float32)).astype(
            jnp.result_type(alpha))
    dbias = None if bias is None else dz.sum(axis=0, keepdims=True)
    return dself, dbias, dalpha


bsr_spmm_fused_pair.defvjp(_fused_pair_fwd, _fused_pair_bwd)


# ---------------------------------------------------------------------------
# Fused-epilogue pair over CSR operands: the row-gather kernels
# ---------------------------------------------------------------------------

def _gather_fused(fwd_arrays, x, self_term, bias, alpha, geom, interpret,
                  activation):
    """(y, mask|None) of the fused-epilogue pass over A."""
    out = gather_pass(csr_gather_spmm_fused_epilogue, fwd_arrays, geom[2],
                      x, self_term, bias, alpha, n_rows=geom[0],
                      interpret=interpret, activation=activation)
    return out if activation == "relu" else (out, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def csr_spmm_fused_pair(fwd_arrays, bwd_arrays, x, self_term, bias, alpha,
                        geom, interpret=None, activation="none"):
    """Y = act(A @ X + alpha * self_term + bias) over a CSR pair.

    The row-gather sibling of ``bsr_spmm_fused_pair``: ``fwd_arrays`` and
    ``bwd_arrays`` are the (indptr, indices, rows, values) of A and Aᵀ,
    ``geom`` is A's ``(n_rows, n_cols)`` and the ``column_tile`` of A and
    of Aᵀ. Operands are unpadded: x [n_cols, F], self_term [n_rows, F],
    bias [1, F]. The VJP sums rows of the masked cotangent mask ⊙ dY along
    Aᵀ (``csr_gather_spmm_masked``). Each pass runs through
    ``gather_pass``.
    """
    y, _ = _gather_fused(fwd_arrays, x, self_term, bias, alpha, geom,
                         interpret, activation)
    return y


def _csr_fused_fwd(fwd_arrays, bwd_arrays, x, self_term, bias, alpha, geom,
                   interpret, activation):
    y, mask = _gather_fused(fwd_arrays, x, self_term, bias, alpha, geom,
                            interpret, activation)
    return y, (fwd_arrays, bwd_arrays, mask, self_term, bias, alpha)


def _csr_fused_bwd(geom, interpret, activation, res, dy):
    fwd_arrays, bwd_arrays, mask, self_term, bias, alpha = res
    dy = dy.astype(jnp.float32)
    if activation == "relu":
        dz = dy * mask
        dx = gather_pass(csr_gather_spmm_masked, bwd_arrays, geom[3], dy,
                         mask, n_rows=geom[1], interpret=interpret)
    else:
        dz = dy
        dx = gather_pass(csr_gather_spmm, bwd_arrays, geom[3], dy,
                         n_rows=geom[1], interpret=interpret)
    return (_zero_cotangents(fwd_arrays), _zero_cotangents(bwd_arrays),
            dx, *_epilogue_cotangents(dz, self_term, bias, alpha))


csr_spmm_fused_pair.defvjp(_csr_fused_fwd, _csr_fused_bwd)


def build_gather_fused_epilogue(fwd: "CSRDevice", bwd: "CSRDevice",
                                interpret: bool | None = None):
    """Differentiable fused-epilogue closure over a (A, Aᵀ) CSRDevice pair,
    with ``build_fused_epilogue``'s calling convention. Rows are gathered
    whole, so there is no lane tile to choose."""
    geom = (fwd.n_rows, fwd.n_cols, fwd.column_tile, bwd.column_tile)

    def fused(u, self_term=None, bias=None, alpha=None, activation="none"):
        s = a = None
        if self_term is not None:
            s = self_term.astype(jnp.float32)
            a = jnp.float32(1.0) if alpha is None else alpha
        b = None if bias is None else bias.reshape(1, -1).astype(jnp.float32)
        y = csr_spmm_fused_pair(fwd.arrays, bwd.arrays,
                                u.astype(jnp.float32), s, b, a, geom,
                                interpret, activation)
        return y.astype(u.dtype)

    return fused


def build_fused_epilogue(fwd: "BSRDevice", bwd: "BSRDevice", inner: str,
                         interpret: bool | None = None,
                         bf: int | None = None):
    """Differentiable fused-epilogue closure over a (A, Aᵀ) BSRDevice pair —
    the op behind the registry's ``spmm_fused_epilogue`` on the Pallas and
    XLA backends. Handles padding at the boundary (no-op when aligned, like
    ``BSRDevice.matmul``) so the custom VJP sees only tile-aligned operands.
    ``bf=None`` picks the lane tile per call via ``feature_tile`` (one
    un-padded tile for narrow feature dims — the epilogue must not pay a
    128-pad the unfused path doesn't); pass an explicit ``bf`` to sweep the
    tile, as ``benchmarks/bench_fusion.py`` does.

    Returns ``fused(u, self_term=None, bias=None, alpha=None,
    activation="none")`` computing ``act(A @ u + alpha * self_term + bias)``
    on unpadded [n_cols, F] -> [n_rows, F].
    """
    if fwd.last_in_row is None:
        raise ValueError("fwd operand lacks last_in_row (rebuild via from_bsr)")
    fwd_arrays = (fwd.block_rows, fwd.block_cols, fwd.first_in_row,
                  fwd.last_in_row, fwd.blocks)
    bwd_arrays = (bwd.block_rows, bwd.block_cols, bwd.first_in_row, bwd.blocks)
    n_rows, n_rows_padded = fwd.n_rows, fwd.n_rows_padded
    n_cols_padded = fwd.n_cols_padded
    geom = (n_rows_padded, n_cols_padded, bwd.n_rows_padded)

    def fused(u, self_term=None, bias=None, alpha=None, activation="none"):
        f = u.shape[-1]
        if bf is not None:
            bf_eff, f_pad = bf, -(-f // bf) * bf
        elif inner == "pallas":
            bf_eff, f_pad = feature_tile(f)
        else:
            # compiled inners take any feature width — never pad lanes (the
            # unfused block einsum doesn't, and the epilogue must not cost
            # a wider SpMM than the ops it replaces)
            bf_eff, f_pad = f, f
        u_p = u
        if u.shape[0] != n_cols_padded or f_pad != f:
            u_p = jnp.pad(u, ((0, n_cols_padded - u.shape[0]), (0, f_pad - f)))
        s_p = a = None
        if self_term is not None:
            s_p = self_term.astype(jnp.float32)
            if self_term.shape[0] != n_rows_padded or f_pad != f:
                s_p = jnp.pad(s_p, ((0, n_rows_padded - self_term.shape[0]),
                                    (0, f_pad - f)))
            a = jnp.float32(1.0) if alpha is None else alpha
        b_p = None
        if bias is not None:
            b_p = jnp.pad(bias.reshape(1, -1).astype(jnp.float32),
                          ((0, 0), (0, f_pad - f)))
        y = bsr_spmm_fused_pair(fwd_arrays, bwd_arrays,
                                u_p.astype(jnp.float32), s_p, b_p, a,
                                geom, bf_eff, interpret, inner, activation)
        if n_rows != n_rows_padded or f != f_pad:
            y = y[:n_rows, :f]
        return y.astype(u.dtype)

    return fused


# ---------------------------------------------------------------------------
# Fused sparse multi-head attention pair (DESIGN.md §10): edge softmax +
# aggregation in one pass, recompute VJP from saved (m, l) row statistics.
# ---------------------------------------------------------------------------

def _fit_rows(x, n):
    """Pad or slice the leading axis to length n (static shapes only)."""
    if x.shape[0] == n:
        return x
    if x.shape[0] > n:
        return x[:n]
    return jnp.pad(x, [(0, n - x.shape[0])] + [(0, 0)] * (x.ndim - 1))


def _attn_head_pad(dh: int, bf: int) -> int:
    """Per-head lane padding from the layout tile. A cached bf narrower than
    the head dim tiles it (pad up to a multiple); a wider bf would be pure
    padding, so the head dim rides as one un-padded tile."""
    if bf and bf < dh:
        return -(-dh // bf) * bf
    return dh


def _dispatch_attn_fwd(fwd_arrays, z, a_src, a_dst, geom, bf, interpret,
                       inner):
    """Shared forward: returns (out [n_dst,H,Dh], m, l [n_dst,H], asrc, adst).

    ``z`` is the *unpadded* [n_src, H, Dh] source stack; destinations are the
    leading ``n_dst`` rows of the same ordering (full-batch: n_dst == n_src;
    distributed: the local rows of the [local | ghost] buffer; mini-batch:
    the bipartite dst frontier prefix)."""
    n_dst, n_src, nr_pad, nc_pad, _, _ = geom
    rows, cols, first, last, blocks = fwd_arrays
    h, dh = z.shape[1], z.shape[2]
    z32 = z.astype(jnp.float32)
    asrc = jnp.einsum("nhd,hd->nh", z32, a_src.astype(jnp.float32))
    adst = jnp.einsum("nhd,hd->nh", z32, a_dst.astype(jnp.float32))
    if inner == "pallas":
        interp = default_interpret() if interpret is None else interpret
        dh_p = _attn_head_pad(dh, bf)
        zp = z32 if dh_p == dh else jnp.pad(
            z32, ((0, 0), (0, 0), (0, dh_p - dh)))
        out2, m, l = bsr_attention_fwd(
            rows, cols, first, last, blocks,
            _fit_rows(adst[:n_dst], nr_pad), _fit_rows(asrc, nc_pad),
            _fit_rows(zp, nc_pad).reshape(nc_pad, h * dh_p),
            n_rows_padded=nr_pad, heads=h, dh=dh_p, interpret=interp)
        out = out2.reshape(nr_pad, h, dh_p)[:n_dst, :, :dh]
    else:
        from repro.kernels.ref import bsr_attention_ref

        out_p, m, l = bsr_attention_ref(
            rows, cols, blocks, _fit_rows(z32, nc_pad),
            _fit_rows(asrc, nc_pad), _fit_rows(adst[:n_dst], nr_pad), nr_pad)
        out = out_p[:n_dst]
    return out, m[:n_dst], l[:n_dst], asrc, adst


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def sparse_mha_pair(fwd_arrays, bwd_arrays, z, a_src, a_dst, geom, bf=0,
                    interpret=None, inner="pallas"):
    """Fused sparse multi-head attention over a pre-built BSR pair.

    ``out_i = Σ_j softmax_j(leaky_relu(a_dst·z_i + a_src·z_j)) z_j`` over the
    nonzero pattern of A. ``fwd_arrays`` is the 5-tuple BSR of A (rows, cols,
    first, last, blocks), ``bwd_arrays`` the 4-tuple BSR of Aᵀ (the backward
    col pass accumulates source-side cotangents along it). Differentiable in
    ``z [n_src, H, Dh]``, ``a_src [H, Dh]``, ``a_dst [H, Dh]``; returns
    ``[n_dst, H, Dh]``.

    The VJP *recomputes* the attention weights from the saved per-row
    ``(max, denominator)`` stats instead of storing the [E, H] weight
    tensor — O(N·H) residual memory instead of O(E·H).

    ``geom = (n_dst, n_src, n_rows_padded, n_cols_padded, nT_rows_padded,
    nT_cols_padded)`` carries the static pair geometry; ``bf`` is the cached
    layout lane tile (0 = one un-padded head tile).
    """
    out, _, _, _, _ = _dispatch_attn_fwd(fwd_arrays, z, a_src, a_dst, geom,
                                         bf, interpret, inner)
    return out


def _mha_fwd(fwd_arrays, bwd_arrays, z, a_src, a_dst, geom, bf, interpret,
             inner):
    out, m, l, asrc, adst = _dispatch_attn_fwd(
        fwd_arrays, z, a_src, a_dst, geom, bf, interpret, inner)
    res = (fwd_arrays, bwd_arrays, z, a_src, a_dst, out, m, l, asrc, adst)
    return out, res


def _mha_bwd(geom, bf, interpret, inner, res, dy):
    fwd_arrays, bwd_arrays, z, a_src, a_dst, out, m, l, asrc, adst = res
    n_dst, n_src, nr_pad, nc_pad, nt_r, nt_c = geom
    h, dh = z.shape[1], z.shape[2]
    dy = dy.astype(jnp.float32)
    z32 = z.astype(jnp.float32)
    r = jnp.einsum("nhd,nhd->nh", dy, out.astype(jnp.float32))
    rows, cols, first, last, blocks = fwd_arrays
    if inner == "pallas":
        interp = default_interpret() if interpret is None else interpret
        dh_p = _attn_head_pad(dh, bf)
        zp, dyp = z32, dy
        if dh_p != dh:
            zp = jnp.pad(z32, ((0, 0), (0, 0), (0, dh_p - dh)))
            dyp = jnp.pad(dy, ((0, 0), (0, 0), (0, dh_p - dh)))
        dc = bsr_attention_bwd_row(
            rows, cols, first, blocks,
            _fit_rows(adst[:n_dst], nr_pad), _fit_rows(asrc, nc_pad),
            _fit_rows(zp, nc_pad).reshape(nc_pad, h * dh_p),
            _fit_rows(dyp, nr_pad).reshape(nr_pad, h * dh_p),
            _fit_rows(r, nr_pad), _fit_rows(m, nr_pad), _fit_rows(l, nr_pad),
            n_rows_padded=nr_pad, heads=h, dh=dh_p, interpret=interp)[:n_dst]
        rows_t, cols_t, first_t, blocks_t = bwd_arrays
        dzv2, dd = bsr_attention_bwd_col(
            rows_t, cols_t, first_t, blocks_t,
            _fit_rows(asrc, nt_r), _fit_rows(adst[:n_dst], nt_c),
            _fit_rows(zp, nt_r).reshape(nt_r, h * dh_p),
            _fit_rows(dyp, nt_c).reshape(nt_c, h * dh_p),
            _fit_rows(r, nt_c), _fit_rows(m, nt_c), _fit_rows(l, nt_c),
            n_rows_padded=nt_r, heads=h, dh=dh_p, interpret=interp)
        dzv = dzv2.reshape(nt_r, h, dh_p)[:n_src, :, :dh]
        dd = dd[:n_src]
    else:
        from repro.kernels.ref import bsr_attention_bwd_ref

        dzv_p, dd_p, dc_p = bsr_attention_bwd_ref(
            rows, cols, blocks, _fit_rows(z32, nc_pad),
            _fit_rows(asrc, nc_pad), _fit_rows(adst[:n_dst], nr_pad),
            _fit_rows(m, nr_pad), _fit_rows(l, nr_pad),
            _fit_rows(dy, nr_pad), _fit_rows(r, nr_pad), nr_pad)
        dzv, dd, dc = dzv_p[:n_src], dd_p[:n_src], dc_p[:n_dst]
    a_src32 = a_src.astype(jnp.float32)
    a_dst32 = a_dst.astype(jnp.float32)
    # dz = value-path + score-path: dd (source side) rides a_src; dc
    # (destination side) rides a_dst on the leading n_dst rows.
    dz = (dzv + dd[..., None] * a_src32[None]
          + _fit_rows(dc, n_src)[..., None] * a_dst32[None])
    da_src = jnp.einsum("nh,nhd->hd", dd, z32)
    da_dst = jnp.einsum("nh,nhd->hd", dc, z32[:n_dst])
    return (_zero_cotangents(fwd_arrays), _zero_cotangents(bwd_arrays),
            dz.astype(z.dtype), da_src.astype(a_src.dtype),
            da_dst.astype(a_dst.dtype))


sparse_mha_pair.defvjp(_mha_fwd, _mha_bwd)


# ---------------------------------------------------------------------------
# Fused sparse multi-head attention over CSR operands: the row-gather
# kernels, with the same recompute VJP from the saved (m, l) statistics.
# ---------------------------------------------------------------------------

_FULL = jax.lax.Precision.HIGHEST


def _csr_attn_fwd(fwd_arrays, z, a_src, a_dst, geom, interpret):
    """(out [n_dst,H,Dh], m, l [n_dst,H], asrc, adst) of the gather kernel."""
    n_dst, _ = geom
    n, h, dh = z.shape
    z32 = z.astype(jnp.float32)
    # the scores' projections in full float32: softmax logits are where a
    # one-pass bf16 product would show (O(N·H·Dh) work)
    asrc = jnp.einsum("nhd,hd->nh", z32, a_src.astype(jnp.float32),
                      precision=_FULL)
    adst = jnp.einsum("nhd,hd->nh", z32[:n_dst], a_dst.astype(jnp.float32),
                      precision=_FULL)
    indptr, indices, rows, _ = fwd_arrays
    out, m, l = csr_gather_attention_fwd(
        indptr, indices, rows, z32.reshape(n, h * dh), asrc, adst, heads=h,
        n_rows=n_dst, interpret=interpret)
    return out.reshape(n_dst, h, dh), m, l, asrc, adst


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def csr_mha_pair(fwd_arrays, bwd_arrays, z, a_src, a_dst, geom,
                 interpret=None):
    """Fused sparse multi-head attention over a CSR pair.

    The row-gather sibling of ``sparse_mha_pair``: ``fwd_arrays`` and
    ``bwd_arrays`` are the (indptr, indices, rows, values) of A and Aᵀ (the
    values are ignored: the nonzero pattern is the mask), ``geom`` is A's
    ``(n_rows, n_cols)``. Differentiable in ``z [n_src, H, Dh]``, ``a_src``
    and ``a_dst [H, Dh]``; returns ``[n_dst, H, Dh]``. The VJP recomputes the
    weights from the saved ``(m, l)`` (O(N·H) residuals): a row pass over A
    for the destination-side score gradient, a column pass over Aᵀ for the
    value path and the source-side score gradient.
    """
    interpret = default_interpret() if interpret is None else interpret
    return _csr_attn_fwd(fwd_arrays, z, a_src, a_dst, geom, interpret)[0]


def _csr_mha_fwd(fwd_arrays, bwd_arrays, z, a_src, a_dst, geom, interpret):
    interpret = default_interpret() if interpret is None else interpret
    out, m, l, asrc, adst = _csr_attn_fwd(fwd_arrays, z, a_src, a_dst, geom,
                                          interpret)
    return out, (fwd_arrays, bwd_arrays, z, a_src, a_dst, out, m, l, asrc,
                 adst)


def _csr_mha_bwd(geom, interpret, res, dy):
    fwd_arrays, bwd_arrays, z, a_src, a_dst, out, m, l, asrc, adst = res
    interpret = default_interpret() if interpret is None else interpret
    n_dst, n_src = geom
    h, dh = z.shape[1], z.shape[2]
    z32 = z.astype(jnp.float32)
    dy = dy.astype(jnp.float32)
    r = jnp.einsum("nhd,nhd->nh", dy, out, precision=_FULL)
    z2, dy2 = z32.reshape(n_src, h * dh), dy.reshape(n_dst, h * dh)
    kw = dict(heads=h, interpret=interpret)
    dc = csr_gather_attention_bwd_row(
        *fwd_arrays[:3], z2, asrc, adst, dy2, r, m, l, n_rows=n_dst, **kw)
    dzv, dd = csr_gather_attention_bwd_col(
        *bwd_arrays[:3], asrc, adst, z2, dy2, r, m, l, n_rows=n_src, **kw)
    a_src32 = a_src.astype(jnp.float32)
    a_dst32 = a_dst.astype(jnp.float32)
    dz = (dzv.reshape(n_src, h, dh) + dd[..., None] * a_src32[None]
          + _fit_rows(dc, n_src)[..., None] * a_dst32[None])
    da_src = jnp.einsum("nh,nhd->hd", dd, z32, precision=_FULL)
    da_dst = jnp.einsum("nh,nhd->hd", dc, z32[:n_dst], precision=_FULL)
    return (_zero_cotangents(fwd_arrays), _zero_cotangents(bwd_arrays),
            dz.astype(z.dtype), da_src.astype(a_src.dtype),
            da_dst.astype(a_dst.dtype))


csr_mha_pair.defvjp(_csr_mha_fwd, _csr_mha_bwd)


def build_gather_mha(fwd: "CSRDevice", bwd: "CSRDevice",
                     interpret: bool | None = None):
    """Differentiable fused-attention closure over a (A, Aᵀ) CSRDevice pair,
    with ``build_sparse_mha``'s calling convention: ``mha(z, a_src, a_dst)``
    on ``z [n_src, H, Dh]`` -> ``[n_dst, H, Dh]``. The kernels run row by
    row: both operands must be in row order (``column_tile`` None)."""
    if fwd.column_tile is not None or bwd.column_tile is not None:
        raise ValueError("the gather-attention kernels need row-ordered "
                         "CSR operands (column_tile=None)")
    geom = (fwd.n_rows, fwd.n_cols)

    def mha(z, a_src, a_dst):
        return csr_mha_pair(fwd.arrays, bwd.arrays, z, a_src, a_dst, geom,
                            interpret)

    return mha


def derive_last_in_row(block_rows: jax.Array) -> jax.Array:
    """last_in_row markers from a sorted block-row stream — for operand dicts
    that carry only (rows, cols, first, blocks), e.g. the sampled-batch and
    distributed 4-tuples. Trailing padding blocks (zero blocks appended to
    the final block-row) are fully masked, so finalizing at the stream tail
    is equivalent to finalizing at the last real block."""
    tail = jnp.ones((1,), jnp.int32)
    if block_rows.shape[0] == 1:
        return tail
    return jnp.concatenate(
        [(block_rows[1:] != block_rows[:-1]).astype(jnp.int32), tail])


def build_sparse_mha(fwd: "BSRDevice", bwd: "BSRDevice", inner: str,
                     interpret: bool | None = None, bf: int | None = None):
    """Differentiable fused-attention closure over a (A, Aᵀ) BSRDevice pair —
    the op behind the registry's ``sparse_mha``/``spmm_attention`` on the
    Pallas and XLA backends.

    Returns ``mha(z, a_src, a_dst)`` on unpadded ``z [n_src, H, Dh]`` →
    ``[n_dst, H, Dh]``.
    """
    if fwd.last_in_row is None:
        raise ValueError("fwd operand lacks last_in_row (rebuild via from_bsr)")
    fwd_arrays = (fwd.block_rows, fwd.block_cols, fwd.first_in_row,
                  fwd.last_in_row, fwd.blocks)
    bwd_arrays = (bwd.block_rows, bwd.block_cols, bwd.first_in_row, bwd.blocks)
    geom = (fwd.n_rows, fwd.n_cols, fwd.n_rows_padded, fwd.n_cols_padded,
            bwd.n_rows_padded, bwd.n_cols_padded)
    bf_eff = 0 if bf is None else bf

    def mha(z, a_src, a_dst):
        return sparse_mha_pair(fwd_arrays, bwd_arrays, z, a_src, a_dst,
                               geom, bf_eff, interpret, inner)

    return mha


def pad_graph_dims(graph: CSRGraph, multiple: int = 128) -> CSRGraph:
    """Bump logical dims to a multiple so BSR paddings of A and Aᵀ agree."""
    ceil = lambda v: -(-v // multiple) * multiple
    n_r, n_c = ceil(graph.n_rows), ceil(graph.n_cols)
    indptr = np.concatenate([
        graph.indptr, np.full(n_r - graph.n_rows, graph.indptr[-1], graph.indptr.dtype)
    ])
    return CSRGraph(indptr=indptr, indices=graph.indices, data=graph.data,
                    n_rows=n_r, n_cols=n_c,
                    validate=False)  # structure unchanged, already validated
