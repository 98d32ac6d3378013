"""Fused BSR flash-attention kernels (DESIGN.md §10).

Edge-softmax attention (GAT / sparse multi-head attention) over the BSR
layout from §4: scores ``leaky_relu(a_dst·z_i + a_src·z_j)`` are computed
per block, normalised with an *online* segment softmax per block-row
(running max + rescale recurrence, same shape as
``kernels/flash_attention.py``), and the weighted aggregate accumulates in
a single VMEM pass.  Per-edge scores and softmax weights never touch HBM —
only the per-row ``(max, denominator)`` statistics are written out, which
is exactly what the recompute-VJP backward needs.

The block stream contract matches ``bsr_spmm``: blocks sorted by
(block-row, block-col), ``first_in_row``/``last_in_row`` marking the
segment boundaries, empty block-rows carrying one explicit zero block.
The nonzero pattern of each block is the adjacency mask; block *values*
are ignored beyond zero/nonzero (edge weights do not participate in
attention). The stream runs in SMEM-sized windows exactly as in
``bsr_spmm`` (``windowed_call``): a row straddling a window boundary
resumes its (out, max, denominator) tiles from the carry.

Each grid step handles every head of its block (a static loop), so
every block is legal for any head count and head width: features stay
node-major ``[N, H*Dh]`` in full-width ``(br|bc, H*Dh)`` blocks, and a
head's lanes are selected by masking (the MXU runs full-width products
and the kernel keeps head ``h``'s lanes); statistics indexed by a tile's
rows stay ``[N, H]`` in ``(br, H)`` blocks (a head's column is picked out
with a lane mask); statistics indexed by a tile's columns ride as
``[H, N/bc, 1, bc]`` rows. Nothing is laid out with a narrow minor
dimension the chip's tiled HBM layout would pad to 128 lanes (an
``[.., N, 1]`` column pads 128×, a head-major ``[H, N, 64]`` 2×), and the
kernels never transpose a vector.

Three kernels live here:
  * ``bsr_attention_fwd``      — forward over A, emits (out, m, l)
  * ``bsr_attention_bwd_row``  — backward row pass over A, emits dc
  * ``bsr_attention_bwd_col``  — backward col pass over Aᵀ, emits (dzv, dd)

The ``custom_vjp`` wrapper (``sparse_mha_pair``) and the lax-composed
references live in ``kernels/ops.py`` / ``kernels/ref.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bsr_spmm import mm, mm_nt, open_row, windowed_call

NEG_INF = -1e30
LEAKY_SLOPE = 0.2


def _scores(col, row):
    """Raw block of attention logits: leaky_relu(col_i + row_j).

    col: (br, 1) projections of the tile's row nodes.
    row: (1, bc) projections of the tile's column nodes.
    Returns (br, bc) pre-activation and activated scores.
    """
    pre = col + row
    s = jnp.where(pre >= 0, pre, LEAKY_SLOPE * pre)
    return pre, s


def _lane(tile, h):
    """Column ``h`` of a (n, H) statistics tile, as (n, 1)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.sum(jnp.where(lane == h, tile, 0.0), axis=1, keepdims=True)


def _put_lane(tile, h, col):
    """``tile`` with column ``h`` replaced by the (n, 1) ``col``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.where(lane == h, col, tile)


def _head(shape, h, dh):
    """Lane mask of head ``h`` over an (n, H*Dh) feature tile."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= h * dh) & (lane < (h + 1) * dh)


def _row(s, bc):
    """[N, H] -> [H, N/bc, 1, bc]: statistics indexed by a tile's columns."""
    n, h = s.shape
    return s.T.reshape(h, n // bc, 1, bc)


# -- block specs (grid = (1, window blocks)) ----------------------------------

def _block_spec(br, bc):
    return pl.BlockSpec((1, br, bc), lambda i, b, off, *_: (off[0] + b, 0, 0))


def _at_row(i, b, off, rows, *_):
    return rows[b]


def _at_col(i, b, off, rows, cols, *_):
    return cols[b]


def _at_carry(i, b, off, rows, *_):
    return rows[0]


def _tile(n, width, at=_at_row):
    """(n, width) tile of an [N, width] operand (features or statistics)
    at block ``at``."""
    return pl.BlockSpec((n, width), lambda *g: (at(*g), 0))


def _row_tile(heads, bc):
    """(H, 1, bc) rows of an [H, N/bc, 1, bc] operand at the block's column."""
    return pl.BlockSpec((heads, None, 1, bc),
                        lambda *g: (0, _at_col(*g), 0, 0))


# ---------------------------------------------------------------------------
# Forward: online segment softmax + aggregation
# ---------------------------------------------------------------------------

def _make_fwd_kernel(heads: int, dh: int):
    def make(resume: bool):
        def kernel(off_ref, rows_ref, cols_ref, flag_ref, last_ref,
                   blocks_ref, adst_ref, asrc_ref, z_ref, *rest):
            carries = rest[:3] if resume else ()
            o_ref, m_ref, l_ref = rest[-3:]
            b = pl.program_id(1)
            # The output tiles stay VMEM-resident across the consecutive
            # grid steps of one block-row (same index), so they double as
            # the running state of the flash recurrence — no scratch needed.
            open_row(flag_ref[b], (o_ref, m_ref, l_ref),
                     (0.0, NEG_INF, 0.0), carries)

            mask = blocks_ref[0] != 0.0
            adst, m_all, l_all, z = (adst_ref[...], m_ref[...], l_ref[...],
                                     z_ref[...])
            scale = jnp.zeros(o_ref.shape, jnp.float32)
            upd = jnp.zeros(o_ref.shape, jnp.float32)
            for h in range(heads):
                _, s = _scores(_lane(adst, h), asrc_ref[h])
                s = jnp.where(mask, s, NEG_INF)
                m_prev = _lane(m_all, h)
                m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # exp(NEG_INF - NEG_INF) = 1 on fully-masked rows: re-mask p.
                p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
                sel = _head(o_ref.shape, h, dh)
                scale = jnp.where(sel, alpha, scale)
                upd = jnp.where(sel, mm(p, z), upd)
                m_all = _put_lane(m_all, h, m_new)
                l_all = _put_lane(l_all, h, _lane(l_all, h) * alpha
                                  + p.sum(axis=-1, keepdims=True))
            o_ref[...] = o_ref[...] * scale + upd
            m_ref[...] = m_all
            l_ref[...] = l_all

            @pl.when(last_ref[b] == 1)
            def _finalize():
                l_fin = l_ref[...]
                denom = jnp.ones(o_ref.shape, jnp.float32)
                for h in range(heads):
                    denom = jnp.where(_head(o_ref.shape, h, dh),
                                      _lane(l_fin, h), denom)
                o_ref[...] = o_ref[...] / jnp.maximum(denom, 1e-20)
                # Empty rows carry m = NEG_INF; clamp so the saved stats stay
                # finite (the backward recompute exponentiates against them).
                m_ref[...] = jnp.where(l_fin > 0.0, m_ref[...], 0.0)

        return kernel

    return make


@functools.partial(
    jax.jit,
    static_argnames=("n_rows_padded", "heads", "dh", "interpret", "window"))
def bsr_attention_fwd(block_rows, block_cols, first_in_row, last_in_row,
                      blocks, adst, asrc, z, *, n_rows_padded, heads, dh,
                      interpret=False, window: Optional[int] = None):
    """Fused edge-softmax aggregation over a BSR adjacency.

    blocks: [n_blocks, br, bc] — nonzero pattern = adjacency mask.
    adst:   [n_rows_padded, heads] destination projections a_dst·z_i.
    asrc:   [n_cols_padded, heads] source projections a_src·z_j.
    z:      [n_cols_padded, heads * dh] head-major source features.

    Returns (out [n_rows_padded, heads*dh], m [n_rows_padded, heads],
    l [n_rows_padded, heads]) where out is already normalised and (m, l)
    are the per-row softmax statistics for the recompute backward.
    """
    _, br, bc = blocks.shape
    w = heads * dh
    stat = jax.ShapeDtypeStruct((n_rows_padded, heads), jnp.float32)
    return tuple(windowed_call(
        _make_fwd_kernel(heads, dh), name="bsr_attention_fwd",
        lead=1, block_rows=block_rows,
        block_cols=block_cols, first_in_row=first_in_row,
        extra_streams=(last_in_row,),
        in_specs=[_block_spec(br, bc), _tile(br, heads),
                  _row_tile(heads, bc), _tile(bc, w, _at_col)],
        inputs=[blocks, adst, _row(asrc, bc), z],
        out_specs=[_tile(br, w), _tile(br, heads), _tile(br, heads)],
        out_shape=[jax.ShapeDtypeStruct((n_rows_padded, w), jnp.float32),
                   stat, stat],
        carry_specs=[_tile(br, w, _at_carry), _tile(br, heads, _at_carry),
                     _tile(br, heads, _at_carry)],
        interpret=interpret, window=window))


# ---------------------------------------------------------------------------
# Backward, row pass over A: dc_i = Σ_j dpre_ij
# ---------------------------------------------------------------------------

def _make_bwd_row_kernel(heads: int, dh: int):
    def make(resume: bool):
        def kernel(off_ref, rows_ref, cols_ref, flag_ref,
                   blocks_ref, adst_ref, asrc_ref, z_ref, dy_ref, r_ref,
                   m_ref, l_ref, *rest):
            carries = rest[:1] if resume else ()
            dc_ref = rest[-1]
            b = pl.program_id(1)
            open_row(flag_ref[b], (dc_ref,), (0.0,), carries)

            mask = blocks_ref[0] != 0.0
            adst, r, m, l = adst_ref[...], r_ref[...], m_ref[...], l_ref[...]
            z, dy = z_ref[...], dy_ref[...]
            dc = jnp.zeros(dc_ref.shape, jnp.float32)
            for h in range(heads):
                pre, s = _scores(_lane(adst, h), asrc_ref[h])
                # Recompute softmax weights from the saved (m, l) stats.
                att = (jnp.exp(s - _lane(m, h))
                       / jnp.maximum(_lane(l, h), 1e-20))
                att = jnp.where(mask, att, 0.0)
                datt = mm_nt(jnp.where(_head(dy.shape, h, dh), dy, 0.0), z)
                ds = att * (datt - _lane(r, h))
                dpre = ds * jnp.where(pre >= 0, 1.0, LEAKY_SLOPE)
                dc = _put_lane(dc, h, dpre.sum(axis=-1, keepdims=True))
            dc_ref[...] += dc

        return kernel

    return make


@functools.partial(
    jax.jit,
    static_argnames=("n_rows_padded", "heads", "dh", "interpret", "window"))
def bsr_attention_bwd_row(block_rows, block_cols, first_in_row,
                          blocks, adst, asrc, z, dy, r, m, l, *,
                          n_rows_padded, heads, dh, interpret=False,
                          window: Optional[int] = None):
    """Row pass of the recompute backward: dc [n_rows_padded, heads]."""
    _, br, bc = blocks.shape
    w = heads * dh
    stat = _tile(br, heads)
    (dc,) = windowed_call(
        _make_bwd_row_kernel(heads, dh), name="bsr_attention_bwd_row",
        lead=1, block_rows=block_rows,
        block_cols=block_cols, first_in_row=first_in_row,
        in_specs=[_block_spec(br, bc), stat, _row_tile(heads, bc),
                  _tile(bc, w, _at_col), _tile(br, w), stat, stat, stat],
        inputs=[blocks, adst, _row(asrc, bc), z, dy, r, m, l],
        out_specs=[stat],
        out_shape=[jax.ShapeDtypeStruct((n_rows_padded, heads),
                                        jnp.float32)],
        carry_specs=[_tile(br, heads, _at_carry)],
        interpret=interpret, window=window)
    return dc


# ---------------------------------------------------------------------------
# Backward, col pass over Aᵀ: dzv_j = Σ_i att_ij dy_i, dd_j = Σ_i dpre_ij
# ---------------------------------------------------------------------------

def _make_bwd_col_kernel(heads: int, dh: int):
    def make(resume: bool):
        def kernel(off_ref, rows_ref, cols_ref, flag_ref,
                   blocks_ref, asrc_ref, adst_ref, z_ref, dy_ref, r_ref,
                   m_ref, l_ref, *rest):
            # Tile rows are *sources* j, tile cols are *destinations* i; the
            # destination-side stats arrive as (1, bc) rows per head.
            carries = rest[:2] if resume else ()
            dzv_ref, dd_ref = rest[-2:]
            b = pl.program_id(1)
            open_row(flag_ref[b], (dzv_ref, dd_ref), (0.0, 0.0), carries)

            mask = blocks_ref[0] != 0.0
            asrc, z, dy = asrc_ref[...], z_ref[...], dy_ref[...]
            dzv = jnp.zeros(dzv_ref.shape, jnp.float32)
            dd = jnp.zeros(dd_ref.shape, jnp.float32)
            for h in range(heads):
                pre, s = _scores(_lane(asrc, h), adst_ref[h])
                att = jnp.exp(s - m_ref[h]) / jnp.maximum(l_ref[h], 1e-20)
                att = jnp.where(mask, att, 0.0)
                sel = _head(z.shape, h, dh)
                datt = mm_nt(jnp.where(sel, z, 0.0), dy)
                ds = att * (datt - r_ref[h])
                dpre = ds * jnp.where(pre >= 0, 1.0, LEAKY_SLOPE)
                dzv = jnp.where(sel, mm(att, dy), dzv)
                dd = _put_lane(dd, h, dpre.sum(axis=-1, keepdims=True))
            dzv_ref[...] += dzv
            dd_ref[...] += dd

        return kernel

    return make


@functools.partial(
    jax.jit,
    static_argnames=("n_rows_padded", "heads", "dh", "interpret", "window"))
def bsr_attention_bwd_col(block_rows, block_cols, first_in_row,
                          blocks, asrc, adst, z, dy, r, m, l, *,
                          n_rows_padded, heads, dh, interpret=False,
                          window: Optional[int] = None):
    """Col pass of the recompute backward over Aᵀ.

    Operands indexed by block_rows live on the *source* side (asrc, z);
    operands indexed by block_cols live on the *destination* side
    (adst, dy, r, m, l).  Returns (dzv [n_rows_padded, heads*dh],
    dd [n_rows_padded, heads]) on the source side.
    """
    _, br, bc = blocks.shape
    w = heads * dh
    dst = _row_tile(heads, bc)
    return tuple(windowed_call(
        _make_bwd_col_kernel(heads, dh), name="bsr_attention_bwd_col",
        lead=1, block_rows=block_rows,
        block_cols=block_cols, first_in_row=first_in_row,
        in_specs=[_block_spec(br, bc), _tile(br, heads), dst,
                  _tile(br, w), _tile(bc, w, _at_col), dst, dst, dst],
        inputs=[blocks, asrc, _row(adst, bc), z, dy, _row(r, bc),
                _row(m, bc), _row(l, bc)],
        out_specs=[_tile(br, w), _tile(br, heads)],
        out_shape=[jax.ShapeDtypeStruct((n_rows_padded, w), jnp.float32),
                   jax.ShapeDtypeStruct((n_rows_padded, heads),
                                        jnp.float32)],
        carry_specs=[_tile(br, w, _at_carry), _tile(br, heads, _at_carry)],
        interpret=interpret, window=window))
