"""CSR row-gather attention kernels — edge-softmax attention at one row copy
per nonzero (DESIGN.md §10, §15).

The row-gather sibling of ``kernels/bsr_attention.py`` for graphs whose
nonzeros do not fill blocks, built on the machinery of
``kernels/csr_gather_spmm.py`` (``gather_loop``): a grid over destination
tiles of ``TILE_ROWS`` rows, each owning its rows whole, with its output
accumulated in VMEM; column indices and row ids brought into SMEM a
``CHUNK`` at a time by double-buffered DMA; one async copy per nonzero of
the one row it reads. Per head ``h`` (``K`` heads of ``D`` lanes, features
node-major ``[n, K·D]``)::

    e_ij = leaky_relu(s_i + t_j)      s = a_dst·z_i, t = a_src·z_j
    out_i = Σ_j softmax_j(e_ij) z_j

* **Packed rows.** A copy carries everything its edge needs. The forward
  and the backward row pass gather ``[z_j | t_j]``; the backward column
  pass (over Aᵀ) gathers ``[dY_i | lse_i - s_i | lse_i - 0.2·s_i | -r_i]``,
  so that ``exp(leaky_relu(s_i + t_j) - lse_i)`` is the larger exponent of
  its two branches and ``dY_i·z_j - r_i`` one lane sum (the tile's ``z``
  row holds 1 at the ``-r`` lanes). The per-head scalars ride in the spare
  lanes after the features (750 + 3 ≤ 768), all inside the row's last
  128-lane group (``packing``), at the lanes where the tile-side per-row
  statistics (``[n, 128]``) keep them too, so scores and softmax
  statistics are one-vreg vector ops.
* **Online softmax** per destination row and head, as in the BSR kernel:
  the running ``(m, l)`` and the output row are rescaled as each nonzero
  arrives; a head's scale reaches its lanes by a lane mask and a lane
  reduction, so head boundaries need no alignment (and no lane rotation,
  which measured slow on a v5e). Accumulation is float32 on the VPU.
* **Recompute VJP** (``kernels/ops.py:csr_mha_pair``): the weights are
  recomputed from the saved row statistics, ``lse = m + log l``; no
  ``[E, K]`` or ``[E, K·D]`` tensor exists in either direction.

Three kernels, one ``pallas_call`` each, named as the BSR family is:

* ``csr_gather_attention_fwd``     — over A, emits ``(out, m, l)``
* ``csr_gather_attention_bwd_row`` — over A, emits ``dc = Σ_j dpre_ij``
* ``csr_gather_attention_bwd_col`` — over Aᵀ, emits ``dzv = Σ_i att_ij dY_i``
  and ``dd = Σ_i dpre_ij``
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bsr_attention import LEAKY_SLOPE, NEG_INF
from repro.kernels.csr_gather_spmm import (
    CHUNK,
    LANES,
    TILE_ROWS,
    UNROLL,
    _pad_to,
    gather_loop,
)


def packing(width: int, n_stats: int) -> tuple[int, int]:
    """``(wp, off)``: the width of a packed row of ``width`` feature lanes
    and ``n_stats`` scalars, a whole number of 128-lane groups, and the
    lane within its last group where the first scalar sits. The scalars
    follow the features, or start the last group where the features end
    before it."""
    if n_stats > LANES:
        raise ValueError(f"{n_stats} per-head scalars exceed one lane group")
    wp = -(-(width + n_stats) // LANES) * LANES
    return wp, max(width, wp - LANES) - (wp - LANES)


def _lanes(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _leaky(x):
    return jnp.where(x >= 0, x, LEAKY_SLOPE * x)


def _stat_lanes(rows: int, off: int, heads: int):
    lane = _lanes((rows, LANES))
    return (lane >= off) & (lane < off + heads)


def _pick(v, lane: int):
    """``[n, 1]``: lane ``lane`` of ``v [n, 128]``."""
    return jnp.sum(jnp.where(_lanes(v.shape) == lane, v, 0.0), axis=1,
                   keepdims=True)


def _spread(v, off: int, heads: int, dh: int, wp: int):
    """``[n, wp]``: each lane of head ``h`` (``[h·dh, (h+1)·dh)``) takes lane
    ``off + h`` of ``v [n, 128]``; lanes of no head take 0."""
    lane = _lanes((v.shape[0], wp))
    out = jnp.zeros((v.shape[0], wp), jnp.float32)
    for h in range(heads):
        head = (lane >= h * dh) & (lane < (h + 1) * dh)
        out = jnp.where(head, _pick(v, off + h), out)
    return out


def _head_sums(prod, off: int, heads: int, dh: int):
    """``[n, 128]``: lane ``off + h`` holds the sum of ``prod [n, wp]`` over
    head ``h``'s lanes; other lanes 0."""
    lane = _lanes(prod.shape)
    stat = _lanes((prod.shape[0], LANES))
    out = jnp.zeros((prod.shape[0], LANES), jnp.float32)
    for h in range(heads):
        head = (lane >= h * dh) & (lane < (h + 1) * dh)
        total = jnp.sum(jnp.where(head, prod, 0.0), axis=1, keepdims=True)
        out = jnp.where(stat == off + h, total, out)
    return out


def _pack(feat, stats, wp: int, off: int):
    """``[n, 1, wp]`` rows: ``feat [n, W]``, then the ``[n, S]`` scalars from
    lane ``wp - 128 + off``, zeros elsewhere — the gather's HBM view."""
    n, w = feat.shape
    at = wp - LANES + off
    parts = [feat.astype(jnp.float32),
             jnp.zeros((n, at - w), jnp.float32),
             *[s.astype(jnp.float32) for s in stats]]
    used = at + sum(s.shape[1] for s in stats)
    parts.append(jnp.zeros((n, wp - used), jnp.float32))
    return jnp.concatenate(parts, axis=1).reshape(n, 1, wp)


def _stat_tile(stat, off: int):
    """``[n, 128]`` with ``stat [n, K]`` at lanes ``[off, off + K)``."""
    n, k = stat.shape
    return jnp.pad(stat.astype(jnp.float32), ((0, 0), (off, LANES - off - k)))


def _lse(m, l):
    return jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)), 0.0)


def _call(kernel, *, name, indptr, indices, rows, gathered, tiles, outs,
          n_rows, tm, k, interpret):
    """One ``pallas_call`` of a gather-attention kernel: nonzero streams
    and the packed rows ``gathered [n, 1, wp]`` in HBM, ``tiles`` (row-side
    inputs, ``[n_rows, width]``) and ``outs`` (their widths) in VMEM tiles
    of ``tm`` rows."""
    wp = gathered.shape[-1]
    n_tiles = max(-(-n_rows // tm), 1)
    nnz = indices.shape[0]
    tile_ptr = indptr.astype(jnp.int32)[
        jnp.minimum(jnp.arange(n_tiles + 1) * tm, n_rows)]
    k_len = max(-(-nnz // k), 1) * k
    streams = [_pad_to(a.astype(jnp.int32), k_len) for a in (indices, rows)]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    tile = lambda width: pl.BlockSpec((tm, width), lambda i, *_: (i, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[hbm] * 3 + [tile(a.shape[1]) for a in tiles],
            out_specs=[tile(width) for width in outs],
            scratch_shapes=[
                pltpu.SMEM((2 * k,), jnp.int32),
                pltpu.SMEM((2 * k,), jnp.int32),
                pltpu.VMEM((2, k, 1, wp), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((n_rows, width), jnp.float32)
                   for width in outs],
        interpret=interpret,
        name=name,
    )(tile_ptr, *streams, gathered, *tiles)


def _unroll(interpret) -> int:
    """Nonzeros per loop iteration: ``UNROLL`` for the chip and for TPU
    interpret mode; one where the kernel is discharged into XLA
    (``interpret=True``), whose tracing an unrolled body only slows."""
    return 1 if interpret is True else UNROLL


def _loop(t, tile_ptr, k, indices, rows, gathered, cols_s, rows_s, gbuf,
          idx_sem, row_sem, tm, unroll, body, init):
    """``gather_loop`` over the index and row-id streams; ``body(at, row)``
    per nonzero with ``at`` its destination row's slice of the tile."""
    gather_loop(t=t, tile_ptr=tile_ptr, k=k,
                streams=((indices, cols_s), (rows, rows_s)), x=gathered,
                gbuf=gbuf, idx_sem=idx_sem, row_sem=row_sem,
                body=lambda j, row: body(pl.ds(rows_s[j] - t * tm, 1), row),
                init=init, unroll=unroll)


# ---------------------------------------------------------------------------
# Forward: online segment softmax + aggregation over A
# ---------------------------------------------------------------------------

def _make_fwd_kernel(*, tm, k, heads, dh, wp, off, unroll):
    def kernel(tile_ptr, indices, rows, zt, s_ref, o_ref, m_ref, l_ref,
               cols_s, rows_s, gbuf, idx_sem, row_sem):
        t = pl.program_id(0)

        def init():
            o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)
            m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

        def one(at, row):
            g = row()
            e = jnp.where(_stat_lanes(1, off, heads),
                          _leaky(s_ref[at, :] + g[:, wp - LANES:]), NEG_INF)
            m_old = m_ref[at, :]
            m_new = jnp.maximum(m_old, e)
            alpha = jnp.exp(m_old - m_new)
            p = jnp.exp(e - m_new)
            l_ref[at, :] = l_ref[at, :] * alpha + p
            m_ref[at, :] = m_new
            o_ref[at, :] = (o_ref[at, :] * _spread(alpha, off, heads, dh, wp)
                            + _spread(p, off, heads, dh, wp) * g)

        _loop(t, tile_ptr, k, indices, rows, zt, cols_s, rows_s, gbuf,
              idx_sem, row_sem, tm, unroll, one, init)

        l = jnp.where(_stat_lanes(tm, off, heads), l_ref[...], 0.0)
        denom = _spread(l, off, heads, dh, wp)
        o_ref[...] = o_ref[...] / jnp.maximum(denom, 1e-20)
        # empty rows keep m = NEG_INF: clamp so the saved stats stay finite
        m_ref[...] = jnp.where(l > 0.0, m_ref[...], 0.0)
        l_ref[...] = l

    return kernel


_STATIC = ("heads", "n_rows", "interpret", "tm", "k")


@functools.partial(jax.jit, static_argnames=_STATIC)
def csr_gather_attention_fwd(indptr, indices, rows, z, asrc, adst, *,
                             heads: int, n_rows: int, interpret=False,
                             tm: int = TILE_ROWS, k: int = CHUNK):
    """Edge-softmax aggregation over A in CSR (rows = destinations).

    z [n_cols, K·D] source features (head-major lanes), asrc [n_cols, K]
    ``a_src·z_j``, adst [n_rows, K] ``a_dst·z_i``. Returns
    ``(out [n_rows, K·D], m [n_rows, K], l [n_rows, K])``: ``out`` already
    normalised, ``(m, l)`` the rows' softmax max and denominator."""
    w = z.shape[1]
    wp, off = packing(w, heads)
    out, m, l = _call(
        _make_fwd_kernel(tm=tm, k=k, heads=heads, dh=w // heads, wp=wp,
                         off=off, unroll=_unroll(interpret)),
        name="csr_gather_attention_fwd", indptr=indptr, indices=indices,
        rows=rows, gathered=_pack(z, [asrc], wp, off),
        tiles=[_stat_tile(adst, off)], outs=[wp, LANES, LANES],
        n_rows=n_rows, tm=tm, k=k, interpret=interpret)
    stats = slice(off, off + heads)
    return out[:, :w], m[:, stats], l[:, stats]


# ---------------------------------------------------------------------------
# Backward, row pass over A: dc_i = Σ_j dpre_ij
# ---------------------------------------------------------------------------

def _make_bwd_row_kernel(*, tm, k, heads, dh, wp, off, unroll):
    def kernel(tile_ptr, indices, rows, zt, dy_ref, st_ref, dc_ref,
               cols_s, rows_s, gbuf, idx_sem, row_sem):
        t = pl.program_id(0)

        def init():
            dc_ref[...] = jnp.zeros(dc_ref.shape, jnp.float32)

        def one(at, row):
            g = row()
            st = st_ref[at, :]  # s | lse | r, one lane group each
            pre = st[:, :LANES] + g[:, wp - LANES:]
            att = jnp.where(_stat_lanes(1, off, heads),
                            jnp.exp(_leaky(pre) - st[:, LANES:2 * LANES]),
                            0.0)
            datt = _head_sums(dy_ref[at, :] * g, off, heads, dh)
            dc_ref[at, :] += (att * (datt - st[:, 2 * LANES:])
                              * jnp.where(pre >= 0, 1.0, LEAKY_SLOPE))

        _loop(t, tile_ptr, k, indices, rows, zt, cols_s, rows_s, gbuf,
              idx_sem, row_sem, tm, unroll, one, init)

    return kernel


@functools.partial(jax.jit, static_argnames=_STATIC)
def csr_gather_attention_bwd_row(indptr, indices, rows, z, asrc, adst, dy, r,
                                 m, l, *, heads: int, n_rows: int,
                                 interpret=False, tm: int = TILE_ROWS,
                                 k: int = CHUNK):
    """Row pass of the recompute backward over A: ``dc [n_rows, K]``, the
    score gradient summed over each destination's edges. dy [n_rows, K·D];
    r, m, l [n_rows, K] (``r_i = dY_i·out_i`` per head)."""
    w = z.shape[1]
    wp, off = packing(w, heads)
    st = jnp.concatenate([_stat_tile(a, off)
                          for a in (adst, _lse(m, l), r)], axis=1)
    (dc,) = _call(
        _make_bwd_row_kernel(tm=tm, k=k, heads=heads, dh=w // heads, wp=wp,
                             off=off, unroll=_unroll(interpret)),
        name="csr_gather_attention_bwd_row", indptr=indptr, indices=indices,
        rows=rows, gathered=_pack(z, [asrc], wp, off),
        tiles=[_pad_to(dy.astype(jnp.float32), wp, axis=1), st],
        outs=[LANES], n_rows=n_rows, tm=tm, k=k, interpret=interpret)
    return dc[:, off:off + heads]


# ---------------------------------------------------------------------------
# Backward, column pass over Aᵀ: dzv_j = Σ_i att_ij dY_i, dd_j = Σ_i dpre_ij
# ---------------------------------------------------------------------------

def _make_bwd_col_kernel(*, tm, k, heads, dh, wp, off, unroll):
    r_lane = wp - LANES + off + 2 * heads  # head 0's -r_i lane

    def kernel(tile_ptr, indices, rows, dpk, z_ref, t_ref, dzv_ref, dd_ref,
               cols_s, rows_s, gbuf, idx_sem, row_sem):
        t = pl.program_id(0)

        def init():
            dzv_ref[...] = jnp.zeros(dzv_ref.shape, jnp.float32)
            dd_ref[...] = jnp.zeros(dd_ref.shape, jnp.float32)

        def one(at, row):
            # tile rows are sources j; the gathered row is destination i's:
            # dY_i, then A_i = lse_i - s_i, B_i = lse_i - 0.2·s_i and -r_i
            g = row()
            # the two branches of leaky_relu(s_i + t_j) - lse_i, side by side
            x = t_ref[at, :] - g[:, wp - LANES:]
            # dY_i·z_j - r_i per head: the tile's z row has 1 at the -r lanes
            prod = g * z_ref[at, :]
            lane, wide = _lanes((1, LANES)), _lanes((1, wp))
            dd = jnp.zeros((1, LANES), jnp.float32)
            spread = jnp.zeros((1, wp), jnp.float32)
            for h in range(heads):
                xa, xb = _pick(x, off + h), _pick(x, off + heads + h)
                att = jnp.exp(jnp.maximum(xa, xb))
                head = (wide >= h * dh) & (wide < (h + 1) * dh)
                datt = jnp.sum(jnp.where(head | (wide == r_lane + h), prod,
                                         0.0), axis=1, keepdims=True)
                dpre = att * datt * jnp.where(xa >= xb, 1.0, LEAKY_SLOPE)
                dd = jnp.where(lane == off + h, dpre, dd)
                spread = jnp.where(head, att, spread)
            dd_ref[at, :] += dd
            dzv_ref[at, :] += spread * g

        _loop(t, tile_ptr, k, indices, rows, dpk, cols_s, rows_s, gbuf,
              idx_sem, row_sem, tm, unroll, one, init)

    return kernel


@functools.partial(jax.jit, static_argnames=_STATIC)
def csr_gather_attention_bwd_col(indptr, indices, rows, asrc, adst, z, dy, r,
                                 m, l, *, heads: int, n_rows: int,
                                 interpret=False, tm: int = TILE_ROWS,
                                 k: int = CHUNK):
    """Column pass of the recompute backward over Aᵀ (rows = sources):
    ``(dzv [n_rows, K·D], dd [n_rows, K])``. Source side: asrc [n_rows, K],
    z [n_rows, K·D]; destination side (gathered): adst, r, m, l [n_dst, K],
    dy [n_dst, K·D]."""
    n, w = z.shape
    wp, off = packing(w, 3 * heads)
    lse = _lse(m, l)
    zeros, ones = (jnp.full((n, heads), v, jnp.float32) for v in (0.0, 1.0))
    dzv, dd = _call(
        _make_bwd_col_kernel(tm=tm, k=k, heads=heads, dh=w // heads, wp=wp,
                             off=off, unroll=_unroll(interpret)),
        name="csr_gather_attention_bwd_col", indptr=indptr, indices=indices,
        rows=rows,
        gathered=_pack(dy, [lse - adst, lse - LEAKY_SLOPE * adst, -r], wp,
                       off),
        tiles=[_pack(z, [zeros, zeros, ones], wp, off).reshape(n, wp),
               _stat_tile(jnp.concatenate([asrc, LEAKY_SLOPE * asrc], 1),
                          off)],
        outs=[wp, LANES], n_rows=n_rows, tm=tm, k=k, interpret=interpret)
    return dzv[:, :w], dd[:, off:off + heads]
