"""CSR row-gather SpMM Pallas kernels — work and traffic ∝ nnz × F.

The O(nnz) sibling of ``kernels/bsr_spmm.py`` for graphs whose nonzeros do
not cluster into blocks (DESIGN.md §15). Where nearly every nonzero owns
its own (8, 128) block, the BSR grid pays a fixed step and DMAs a
mostly-zero block plus a whole feature tile per nonzero; here each nonzero
costs one DMA of the one source row it reads.

Operand: CSR in HBM — ``indptr`` [n_rows + 1] int32, ``indices`` [nnz]
int32 (sorted within each row), ``values`` [nnz] float32 — plus ``rows``
[nnz] int32, the row of each nonzero (``indptr`` expanded), so that the
kernel reads a nonzero's row as it reads its column.

* **Grid** over destination row tiles of ``TILE_ROWS`` rows. A tile owns
  its rows whole, so its output tile is accumulated in VMEM and written
  once: no carry between grid steps, no ``first_in_row`` flags. The tiles'
  first nonzeros (``indptr[::TILE_ROWS]``) are scalar-prefetched.
* **Indices, rows and values** reach SMEM a chunk of ``CHUNK`` nonzeros at
  a time, by DMA, double-buffered. Mosaic slices a 1-D HBM array only at
  its (1024,) tile, so chunks are the aligned ``[q·CHUNK, (q+1)·CHUNK)``
  windows of the nonzero stream, and a tile reads the part of each window
  that falls inside its rows.
* **Gather.** ``x`` stays in HBM, viewed as ``[n_cols, 1, F]`` (Mosaic
  slices a 2-D f32 array only at whole (8, 128) tiles; a unit middle axis
  makes one row a whole tile). Each nonzero starts one async copy of its
  source row, full width, into a VMEM gather buffer. The rows of chunk
  ``c + 1`` are issued before chunk ``c`` is waited for and summed, so one
  chunk of copies is always in flight. A DMA semaphore counts bytes: the
  ``m`` copies of a chunk are retired by one wait per set bit of ``m``,
  each sized ``2**b`` rows.
* **Accumulation** in f32 on the VPU: ``y[row] += v·x[col]`` per nonzero
  into the VMEM output tile. The issue and accumulate loops run
  ``UNROLL`` nonzeros per iteration. (Measured on a v5e: walking
  ``indptr`` to sum each row in registers took 39–41 ns per nonzero, the
  per-nonzero row ids 24–25; a one-hot (rows × chunk) product on the MXU
  was faster but lost accuracy.)
* **Epilogue** (``alpha·self + bias``, ReLU, saved mask) at the end of the
  tile, in VMEM, as in the BSR kernel. The masked backward gathers rows of
  ``mask ⊙ dY``, formed once in XLA (the fused VJP forms it for ``dbias``
  anyway): one row copy per nonzero, not two.

Feature widths are padded to whole 128-lane rows for the copies (a copy of
a narrower row is refused); every product is float32 end to end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: nonzeros per index DMA — the (1024,) tile of a 1-D HBM array
CHUNK = 1024
#: destination rows per grid step (256, 512 and 1024 measure alike)
TILE_ROWS = 256
#: nonzeros per iteration of the issue and accumulate loops (on a v5e,
#: 16 takes 24 ns per nonzero, 8 takes 25, 1 took 36)
UNROLL = 16
LANES = 128


def gather_loop(*, t, tile_ptr, k: int, streams, x, gbuf, idx_sem, row_sem,
                body, init=None, unroll: int = UNROLL):
    """Drive destination tile ``t``'s nonzeros through the double-buffered
    gather: the machinery every row-gather kernel shares.

    ``streams`` pairs each 1-D HBM nonzero stream with its SMEM double
    buffer ``[2·k]``; the first pair must be the column indices (the rows of
    ``x [n, 1, F]`` to gather). ``init()`` runs once the tile's bounds are
    known; ``body(j, row)`` then runs per nonzero once its row has landed,
    with ``j`` its position in the SMEM buffers and ``row()`` reading the
    landed row ``[1, F]`` from the gather buffer. The issue and body loops
    run ``unroll`` nonzeros per iteration.
    """
    s, e = tile_ptr[t], tile_ptr[t + 1]
    q0 = s // k
    nch = jnp.where(e > s, (e + k - 1) // k - q0, 0)
    if init is not None:
        init()
    cols_s = streams[0][1]

    def bounds(c):
        """(window start, first, end) of chunk ``c``'s nonzeros."""
        q = q0 + c
        return q * k, jnp.maximum(s, q * k), jnp.minimum(e, q * k + k)

    def idx_copies(c, slot):
        src = pl.ds(pl.multiple_of((q0 + c) * k, k), k)
        dst = pl.ds(slot * k, k)
        return [pltpu.make_async_copy(a.at[src], b.at[dst], idx_sem.at[slot])
                for a, b in streams]

    def each(lo, hi, fn):
        """``fn(p)`` for p in [lo, hi), ``unroll`` per iteration."""
        n_groups = (hi - lo) // unroll

        def group(g, carry):
            for u in range(unroll):
                fn(lo + g * unroll + u)
            return carry

        def tail(p, carry):
            fn(p)
            return carry

        jax.lax.fori_loop(0, n_groups, group, 0)
        jax.lax.fori_loop(lo + n_groups * unroll, hi, tail, 0)

    def issue_rows(c, slot):
        base, lo, hi = bounds(c)

        def one(p):
            col = cols_s[slot * k + p - base]
            pltpu.make_async_copy(x.at[col], gbuf.at[slot, p - base],
                                  row_sem.at[slot]).start()

        each(lo, hi, one)

    def wait_rows(c, slot):
        _, lo, hi = bounds(c)
        m = hi - lo
        for b in reversed(range(k.bit_length())):
            @pl.when(((m >> b) & 1) == 1)
            def _():
                buf = gbuf.at[slot, pl.ds(0, 1 << b)]
                pltpu.make_async_copy(buf, buf, row_sem.at[slot]).wait()

    def accumulate(c, slot):
        base, lo, hi = bounds(c)
        each(lo, hi, lambda p: body(slot * k + p - base,
                                    lambda: gbuf[slot, p - base]))

    @pl.when(nch > 0)
    def _():
        for cp in idx_copies(0, 0):
            cp.start()

        @pl.when(nch > 1)
        def _():
            for cp in idx_copies(1, 1):
                cp.start()

        for cp in idx_copies(0, 0):
            cp.wait()
        issue_rows(0, 0)

        def step(c, carry):
            slot = c % 2

            @pl.when(c + 1 < nch)
            def _():
                for cp in idx_copies(c + 1, 1 - slot):
                    cp.wait()
                issue_rows(c + 1, 1 - slot)

            wait_rows(c, slot)
            accumulate(c, slot)

            @pl.when(c + 2 < nch)
            def _():
                for cp in idx_copies(c + 2, slot):
                    cp.start()

            return carry

        jax.lax.fori_loop(0, nch, step, 0)


def _make_kernel(*, tm: int, k: int, has_self: bool, has_bias: bool,
                 relu: bool):
    """Kernel specialised to its (static) epilogue spec.

    Ref layout: scalar prefetch (tile_ptr[, alpha]), HBM inputs (indices,
    rows, values, x), VMEM inputs ([self][, bias]), outputs (y[, mask]),
    scratch (columns, rows, values, gather buffer, semaphores).
    """

    def kernel(*refs):
        it = iter(refs)
        tile_ptr = next(it)
        alpha = next(it) if has_self else None
        indices, rows, values, x = (next(it) for _ in range(4))
        self_ref = next(it) if has_self else None
        bias_ref = next(it) if has_bias else None
        y_ref = next(it)
        mask_ref = next(it) if relu else None
        cols_s, rows_s, vals_s, gbuf, idx_sem, row_sem = (
            next(it) for _ in range(6))

        t = pl.program_id(0)

        def init():
            y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)

        def one(j, row):
            r = rows_s[j] - t * tm
            y_ref[pl.ds(r, 1), :] += vals_s[j] * row()

        gather_loop(t=t, tile_ptr=tile_ptr, k=k,
                    streams=((indices, cols_s), (rows, rows_s),
                             (values, vals_s)),
                    x=x, gbuf=gbuf, idx_sem=idx_sem, row_sem=row_sem,
                    body=one, init=init)

        if has_self or has_bias or relu:
            acc = y_ref[...]
            if has_self:
                acc = acc + alpha[0] * self_ref[...]
            if has_bias:
                acc = acc + bias_ref[...]
            if relu:
                mask_ref[...] = (acc > 0.0).astype(jnp.float32)
                acc = jnp.maximum(acc, 0.0)
            y_ref[...] = acc

    return kernel


def _pad_to(a, n, axis=0):
    """``a`` zero-padded to length ``n`` along ``axis``."""
    extra = n - a.shape[axis]
    if extra == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, extra)
    return jnp.pad(a, widths)


def _spmm(indptr, indices, rows, values, x, *, name, n_rows, interpret, tm,
          k, self_term=None, bias=None, alpha=None, relu=False):
    """The one gather implementation behind the three entry points:
    ``act(A @ x + alpha·self_term + bias)`` on unpadded ``x [n_cols, F]``,
    returning ``[n_rows, F]`` (and the ReLU mask)."""
    n_cols, f = x.shape
    f_pad = -(-f // LANES) * LANES
    n_tiles = max(-(-n_rows // tm), 1)  # the last tile may be ragged
    nnz = indices.shape[0]
    tile_ptr = indptr.astype(jnp.int32)[
        jnp.minimum(jnp.arange(n_tiles + 1) * tm, n_rows)]
    # the last chunk's window stays inside the arrays
    k_len = max(-(-nnz // k), 1) * k
    streams = [_pad_to(a, k_len) for a in (indices.astype(jnp.int32),
                                           rows.astype(jnp.int32),
                                           values.astype(jnp.float32))]
    x3 = _pad_to(x.astype(jnp.float32), f_pad, axis=1).reshape(n_cols, 1,
                                                                  f_pad)

    sp = [tile_ptr]
    if self_term is not None:
        sp.append(jnp.asarray(alpha, jnp.float32).reshape(1))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    tile = pl.BlockSpec((tm, f_pad), lambda i, *_: (i, 0))
    in_specs, inputs = [hbm] * 4, [*streams, x3]
    if self_term is not None:
        in_specs.append(tile)
        inputs.append(_pad_to(self_term.astype(jnp.float32), f_pad, axis=1))
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, f_pad), lambda i, *_: (0, 0)))
        inputs.append(_pad_to(bias.reshape(1, f).astype(jnp.float32), f_pad,
                              axis=1))
    n_out = 1 + relu
    outs = pl.pallas_call(
        _make_kernel(tm=tm, k=k, has_self=self_term is not None,
                     has_bias=bias is not None, relu=relu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sp),
            grid=(n_tiles,),
            in_specs=in_specs,
            out_specs=[tile] * n_out,
            scratch_shapes=[
                pltpu.SMEM((2 * k,), jnp.int32),
                pltpu.SMEM((2 * k,), jnp.int32),
                pltpu.SMEM((2 * k,), jnp.float32),
                pltpu.VMEM((2, k, 1, f_pad), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((n_rows, f_pad), jnp.float32)] * n_out,
        interpret=interpret,
        name=name,
    )(*sp, *inputs)
    if f_pad != f:
        outs = [o[:, :f] for o in outs]
    return tuple(outs) if relu else outs[0]


_STATIC = ("n_rows", "interpret", "tm", "k")


@functools.partial(jax.jit, static_argnames=_STATIC)
def csr_gather_spmm(indptr, indices, rows, values, x, *, n_rows: int,
                    interpret=False, tm: int = TILE_ROWS, k: int = CHUNK):
    """Y = A @ X with A in CSR; x [n_cols, F] -> float32 [n_rows, F]."""
    return _spmm(indptr, indices, rows, values, x, name="csr_gather_spmm",
                 n_rows=n_rows, interpret=interpret, tm=tm, k=k)


@functools.partial(jax.jit, static_argnames=_STATIC + ("activation",))
def csr_gather_spmm_fused_epilogue(indptr, indices, rows, values, x,
                                   self_term=None, bias=None, alpha=None, *,
                                   n_rows: int, activation: str = "none",
                                   interpret=False, tm: int = TILE_ROWS,
                                   k: int = CHUNK):
    """Y = act(A @ X + alpha * self_term + bias), epilogue fused in VMEM.

    Returns ``(y, mask)`` when ``activation == "relu"`` (mask is the saved
    0/1 pre-activation sign, float32), else ``y`` alone; self_term
    [n_rows, F], bias [1, F] or [F], alpha a scalar (required with
    self_term)."""
    if activation not in ("none", "relu"):
        raise ValueError(f"unsupported fused activation {activation!r}")
    if self_term is not None and alpha is None:
        raise ValueError("self_term requires alpha (use 1.0 for plain add)")
    return _spmm(indptr, indices, rows, values, x,
                 name="csr_gather_spmm_fused_epilogue", n_rows=n_rows,
                 interpret=interpret, tm=tm, k=k, self_term=self_term,
                 bias=bias, alpha=alpha, relu=activation == "relu")


@functools.partial(jax.jit, static_argnames=_STATIC)
def csr_gather_spmm_masked(indptr, indices, rows, values, x, mask, *,
                           n_rows: int, interpret=False, tm: int = TILE_ROWS,
                           k: int = CHUNK):
    """Y = A @ (mask ⊙ X) — the fused-epilogue VJP (A the transposed
    operand, X the incoming cotangent, mask the saved ReLU mask)."""
    if mask.shape != x.shape:
        raise ValueError(f"mask shape {mask.shape} != x shape {x.shape}")
    return _spmm(indptr, indices, rows, values, x * mask,
                 name="csr_gather_spmm_masked", n_rows=n_rows,
                 interpret=interpret, tm=tm, k=k)
