"""CSR row-gather SpMM Pallas kernels — work and traffic ∝ nnz × F.

The O(nnz) sibling of ``kernels/bsr_spmm.py`` for graphs whose nonzeros do
not cluster into blocks (DESIGN.md §15). Where nearly every nonzero owns
its own (8, 128) block, the BSR grid pays a fixed step and DMAs a
mostly-zero block plus a whole feature tile per nonzero; here each nonzero
costs one read of its source row from VMEM.

Operand: CSR in HBM — ``indptr`` [n_rows + 1] int32 (per-row counts),
``indices`` [nnz] int32, ``values`` [nnz] float32 — plus ``rows`` [nnz]
int32, the row of each nonzero, so that the kernel reads a nonzero's row
as it reads its column. The nonzeros are grouped by destination tile, so
``indptr`` at the tile boundaries bounds each tile's run; the SpMM's
operand sorts each tile's run by column (``ops.CSRDevice``), so that the
nonzeros of any (tile, source window) pair are contiguous. Indices, row
ids and values reach SMEM a chunk of ``CHUNK`` nonzeros at a time, by
double-buffered DMA. Mosaic slices a 1-D HBM array only at its (1024,)
tile, so chunks are the aligned ``[q·CHUNK, (q+1)·CHUNK)`` windows of the
nonzero stream, and a tile reads the part of each window that falls inside
its run. Loops run ``UNROLL`` nonzeros per iteration.

**Windows.** The source rows are split into windows of ``C`` rows, ``C``
the most that fits ``VMEM_SHARE`` of the VMEM the chip reports beside the
tile buffers (``window_rows``); off the chip one window holds the whole
source. One ``pallas_call`` per window (kernels ``csr_window_spmm*``) runs
a grid over destination tiles of ``WINDOW_TILE`` rows: its window of ``x``
is one VMEM block, fetched once (single-buffered), and each nonzero of the
(tile, window) run — bounded by a binary search per tile
(``window_bounds``) — reads its source row from the window with a dynamic
one-row load and adds ``v·x[col]`` into its destination row in f32 on the
VPU. Each call after the first adds into the previous call's output,
read back as an input; the first call starts from zero, and only the last
adds ``alpha·self + bias`` and applies ReLU and the saved mask. (Aliasing
that input to the output held one pass's accumulator in one buffer, but
the compiled GCN step on a v5e then held 173 MB more temporaries, PERF.md
§6.) ``x`` is read from HBM once per pass. (Measured on a v5e: one async row copy
from HBM per nonzero took 24 ns, walking ``indptr`` to sum each row in
registers 39–41; a one-hot (rows × chunk) product on the MXU was faster
but lost accuracy.)

The masked backward sums rows of ``mask ⊙ dY``, formed once in XLA (the
fused VJP forms it for ``dbias`` anyway). Feature widths are padded to
whole 128-lane rows; every product is float32 end to end.

``gather_loop`` — one async copy per nonzero of its full source row from
HBM — is the attention kernels' machinery (``csr_gather_attention.py``):
their online softmax runs row by row, over a row-ordered operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: nonzeros per index DMA — the (1024,) tile of a 1-D HBM array
CHUNK = 1024
#: destination rows per grid step of the attention kernels' row gather
#: (256, 512 and 1024 measure alike)
TILE_ROWS = 256
#: nonzeros per loop iteration (on a v5e, 16 takes 24 ns per row copy, 8
#: takes 25, 1 took 36)
UNROLL = 16
LANES = 128

#: destination rows per tile of the SpMM, and of the tiles inside which its
#: operand sorts the nonzeros by column (``kernels/ops.py`` ``CSRDevice``);
#: on a v5e at arxiv's size a pass took 10.86 / 10.44 / 10.17 ms at 512 /
#: 1024 / 2048 rows and 256 lanes (PERF.md §6)
WINDOW_TILE = 2048
#: share of the chip's VMEM that a window and its tiles may fill; the rest
#: is Mosaic's own, and ``VMEM_HEADROOM`` rides on the call's limit
VMEM_SHARE = 7 / 8
VMEM_HEADROOM = 4 << 20


def each(lo, hi, fn, unroll: int = UNROLL):
    """``fn(p)`` for p in [lo, hi), ``unroll`` per loop iteration."""
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


def _chunks(s, e, k: int):
    """``(q0, nch)``: the first aligned ``k``-chunk of the nonzeros
    ``[s, e)`` and how many chunks they touch."""
    q0 = s // k
    return q0, jnp.where(e > s, (e + k - 1) // k - q0, 0)


def _chunk_bounds(s, e, q, k: int):
    """(chunk start, first, end) of chunk ``q``'s share of ``[s, e)``."""
    return q * k, jnp.maximum(s, q * k), jnp.minimum(e, q * k + k)


def _chunk_copies(streams, idx_sem, q, slot, k: int):
    """The DMAs of chunk ``q`` of each 1-D HBM stream into slot ``slot`` of
    its SMEM double buffer ``[2·k]``."""
    src = pl.ds(pl.multiple_of(q * k, k), k)
    dst = pl.ds(slot * k, k)
    return [pltpu.make_async_copy(a.at[src], b.at[dst], idx_sem.at[slot])
            for a, b in streams]


def stream_loop(*, s, e, k: int, streams, idx_sem, body,
                unroll: int = UNROLL):
    """Run ``body(j)`` for the nonzeros ``[s, e)`` of 1-D HBM streams, with
    ``j`` their position in the SMEM double buffers ``[2·k]`` that
    ``streams`` pairs them with; the chunks arrive by double-buffered
    DMA."""
    q0, nch = _chunks(s, e, k)

    @pl.when(nch > 0)
    def _():
        for cp in _chunk_copies(streams, idx_sem, q0, 0, k):
            cp.start()

        def step(c, carry):
            slot = c % 2

            @pl.when(c + 1 < nch)
            def _():
                for cp in _chunk_copies(streams, idx_sem, q0 + c + 1,
                                        1 - slot, k):
                    cp.start()

            for cp in _chunk_copies(streams, idx_sem, q0 + c, slot, k):
                cp.wait()
            base, lo, hi = _chunk_bounds(s, e, q0 + c, k)
            each(lo, hi, lambda p: body(slot * k + p - base), unroll)
            return carry

        jax.lax.fori_loop(0, nch, step, 0)


def gather_loop(*, t, tile_ptr, k: int, streams, x, gbuf, idx_sem, row_sem,
                body, init=None, unroll: int = UNROLL):
    """Drive destination tile ``t``'s nonzeros through the double-buffered
    row gather of the attention kernels: one async copy per nonzero of its
    source row from ``x`` in HBM.

    ``streams`` pairs each 1-D HBM nonzero stream with its SMEM double
    buffer ``[2·k]``; the first pair must be the column indices (the rows of
    ``x [n, 1, F]`` to gather; Mosaic slices a 2-D f32 array only at whole
    (8, 128) tiles, and a unit middle axis makes one row a whole tile).
    ``init()`` runs once the tile's bounds are known; ``body(j, row)`` then
    runs per nonzero once its row has landed, with ``j`` its position in
    the SMEM buffers and ``row()`` reading the landed row ``[1, F]`` from
    the gather buffer. The rows of chunk ``c + 1`` are issued before chunk
    ``c`` is waited for, and a DMA semaphore counts bytes, so the ``m``
    copies of a chunk are retired by one wait per set bit of ``m``. The
    issue and body loops run ``unroll`` nonzeros per iteration.
    """
    s, e = tile_ptr[t], tile_ptr[t + 1]
    q0, nch = _chunks(s, e, k)
    if init is not None:
        init()
    cols_s = streams[0][1]

    def bounds(c):
        return _chunk_bounds(s, e, q0 + c, k)

    def idx_copies(c, slot):
        return _chunk_copies(streams, idx_sem, q0 + c, slot, k)

    def issue_rows(c, slot):
        base, lo, hi = bounds(c)

        def one(p):
            col = cols_s[slot * k + p - base]
            pltpu.make_async_copy(x.at[col], gbuf.at[slot, p - base],
                                  row_sem.at[slot]).start()

        each(lo, hi, one, unroll)

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
                                    lambda: gbuf[slot, p - base]), unroll)

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


def _epilogue(y_ref, alpha, self_ref, bias_ref, mask_ref):
    """``alpha·self + bias``, then ReLU and its saved 0/1 mask, on the
    finished output tile; a ``None`` ref skips its term."""
    acc = y_ref[...]
    if self_ref is not None:
        acc = acc + alpha[0] * self_ref[...]
    if bias_ref is not None:
        acc = acc + bias_ref[...]
    if mask_ref is not None:
        mask_ref[...] = (acc > 0.0).astype(jnp.float32)
        acc = jnp.maximum(acc, 0.0)
    y_ref[...] = acc


def _make_window_kernel(*, tm: int, k: int, base: int, first: bool,
                        last: bool, has_self: bool, has_bias: bool,
                        relu: bool):
    """Kernel of the source window ``[base, base + C)``,
    specialised to its place among the windows and its epilogue.

    Ref layout: scalar prefetch (lo, hi[, alpha]), HBM inputs (indices,
    rows, values), VMEM inputs (x window[, accumulator][, self][, bias]),
    outputs (y[, mask]), scratch (columns, rows, values, semaphores).
    """
    has_self, has_bias, relu = has_self and last, has_bias and last, (
        relu and last)

    def kernel(*refs):
        it = iter(refs)
        lo, hi = next(it), next(it)
        alpha = next(it) if has_self else None
        indices, rows, values, xw = (next(it) for _ in range(4))
        acc_ref = None if first else next(it)
        self_ref = next(it) if has_self else None
        bias_ref = next(it) if has_bias else None
        y_ref = next(it)
        mask_ref = next(it) if relu else None
        cols_s, rows_s, vals_s, idx_sem = (next(it) for _ in range(4))

        t = pl.program_id(0)
        y_ref[...] = (jnp.zeros(y_ref.shape, jnp.float32) if first
                      else acc_ref[...])

        def one(j):
            r = rows_s[j] - t * tm
            y_ref[pl.ds(r, 1), :] += (vals_s[j]
                                      * xw[pl.ds(cols_s[j] - base, 1), :])

        stream_loop(s=lo[t], e=hi[t], k=k,
                    streams=((indices, cols_s), (rows, rows_s),
                             (values, vals_s)),
                    idx_sem=idx_sem, body=one)

        if has_self or has_bias or relu:
            _epilogue(y_ref, alpha, self_ref, bias_ref, mask_ref)

    return kernel


def _pad_to(a, n, axis=0):
    """``a`` zero-padded to length ``n`` along ``axis``."""
    extra = n - a.shape[axis]
    if extra == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, extra)
    return jnp.pad(a, widths)


def _lanes(f: int) -> int:
    return -(-f // LANES) * LANES


def _tile_bytes(tm: int, f_pad: int) -> int:
    """VMEM of a window call's tiles: accumulator in, y, self and mask,
    two buffers each."""
    return 8 * tm * f_pad * 4


def window_rows(f: int, tm: int, vmem_bytes: int) -> int:
    """Rows of the largest ``[C, f_pad]`` float32 window that fits, beside
    the tiles, in ``VMEM_SHARE`` of ``vmem_bytes``: a multiple of 8."""
    f_pad = _lanes(f)
    free = int(vmem_bytes * VMEM_SHARE) - _tile_bytes(tm, f_pad)
    return max(free // (f_pad * 4) // 8 * 8, 8)


def window_bounds(indices, tile_ptr, c: int, n_win: int):
    """``[n_win + 1, T]``: row ``s`` holds each tile's first nonzero whose
    column is at least ``s·c`` (the tile's end in the last row). A tile's
    columns ascend, so each bound is a binary search inside the tile."""
    starts, ends = tile_ptr[:-1], tile_ptr[1:]
    if n_win == 1:
        return jnp.stack([starts, ends])
    shape = (n_win - 1, starts.shape[0])
    target = jnp.arange(1, n_win, dtype=jnp.int32)[:, None] * c
    last = max(indices.shape[0] - 1, 0)

    def halve(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) // 2
        right = (mid < hi) & (indices[jnp.minimum(mid, last)] < target)
        return jnp.where(right, mid + 1, lo), jnp.where(right, hi, mid)

    lo, _ = jax.lax.fori_loop(
        0, max(indices.shape[0], 1).bit_length(), halve,
        (jnp.broadcast_to(starts, shape), jnp.broadcast_to(ends, shape)))
    return jnp.concatenate([starts[None], lo, ends[None]])


def _epilogue_inputs(ops, tile):
    """(BlockSpecs, arrays) of the epilogue's self term and bias, where
    given."""
    specs, arrays = [], []
    if "self" in ops:
        specs.append(tile)
        arrays.append(ops["self"])
    if "bias" in ops:
        specs.append(pl.BlockSpec(ops["bias"].shape, lambda i, *_: (0, 0)))
        arrays.append(ops["bias"])
    return specs, arrays


def _index_scratch(k: int) -> list:
    """SMEM double buffers of columns, rows and values."""
    return [pltpu.SMEM((2 * k,), dtype) for dtype in
            (jnp.int32, jnp.int32, jnp.float32)]


def _window_spmm(ops, *, name, n_rows, n_tiles, interpret, tm, k, relu,
                 window):
    """One ``pallas_call`` per source window of ``window`` rows, each over
    all destination tiles, each adding into the previous one's output; the
    epilogue runs in the last."""
    x = ops["x"]
    n_cols, f_pad = x.shape
    c = min(window or n_cols, max(n_cols, 1))
    n_win = max(-(-n_cols // c), 1)
    if n_win > 1 and c % 8:
        raise ValueError(f"window of {c} rows: windows split x at whole "
                         "(8, 128) tiles")
    bounds = window_bounds(ops["streams"][0], ops["tile_ptr"], c, n_win)
    tile = pl.BlockSpec((tm, f_pad), lambda i, *_: (i, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    vmem_limit = c * f_pad * 4 + _tile_bytes(tm, f_pad) + VMEM_HEADROOM
    outs = []
    for s in range(n_win):
        first, last = s == 0, s == n_win - 1
        sp = [bounds[s], bounds[s + 1], *(ops["alpha"] if last else [])]
        in_specs = [hbm] * 3 + [pl.BlockSpec(
            (c, f_pad), lambda i, *_, s=s: (s, 0),
            pipeline_mode=pl.Buffered(1))]
        inputs = [*ops["streams"], x]
        if not first:  # the accumulator so far, read tile by tile
            in_specs.append(tile)
            inputs.append(outs[0])
        if last:
            specs, arrays = _epilogue_inputs(ops, tile)
            in_specs += specs
            inputs += arrays
        n_out = 1 + (relu and last)
        outs = pl.pallas_call(
            _make_window_kernel(tm=tm, k=k, base=s * c, first=first,
                                last=last, has_self="self" in ops,
                                has_bias="bias" in ops, relu=relu),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(sp),
                grid=(n_tiles,),
                in_specs=in_specs,
                out_specs=[tile] * n_out,
                scratch_shapes=[*_index_scratch(k),
                                pltpu.SemaphoreType.DMA((2,))],
            ),
            out_shape=[jax.ShapeDtypeStruct((n_rows, f_pad),
                                            jnp.float32)] * n_out,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=vmem_limit),
            interpret=interpret,
            name=name,
        )(*sp, *inputs)
    return outs


def _spmm(indptr, indices, rows, values, x, *, name, n_rows, window,
          interpret, tm, k, self_term=None, bias=None, alpha=None,
          relu=False):
    """The implementation behind the three entry points:
    ``act(A @ x + alpha·self_term + bias)`` on unpadded ``x [n_cols, F]``,
    returning ``[n_rows, F]`` (and the ReLU mask), under the kernel name
    ``csr_window_<name>``. ``window`` is the rows of ``x`` per VMEM window
    (``None``: one window holds all of ``x``); more than one window needs
    each tile's nonzeros in column order."""
    f = x.shape[1]
    f_pad = _lanes(f)
    n_tiles = max(-(-n_rows // tm), 1)  # the last tile may be ragged
    nnz = indices.shape[0]
    # the last chunk's window stays inside the arrays
    k_len = max(-(-nnz // k), 1) * k
    ops = {
        "tile_ptr": indptr.astype(jnp.int32)[
            jnp.minimum(jnp.arange(n_tiles + 1) * tm, n_rows)],
        "streams": [_pad_to(a, k_len) for a in (
            indices.astype(jnp.int32), rows.astype(jnp.int32),
            values.astype(jnp.float32))],
        "x": _pad_to(x.astype(jnp.float32), f_pad, axis=1),
        "alpha": ([] if self_term is None
                  else [jnp.asarray(alpha, jnp.float32).reshape(1)]),
    }
    if self_term is not None:
        ops["self"] = _pad_to(self_term.astype(jnp.float32), f_pad, axis=1)
    if bias is not None:
        ops["bias"] = _pad_to(bias.reshape(1, f).astype(jnp.float32), f_pad,
                              axis=1)
    outs = _window_spmm(ops, name=f"csr_window_{name}", n_rows=n_rows,
                        n_tiles=n_tiles, interpret=interpret, tm=tm, k=k,
                        relu=relu, window=window)
    if f_pad != f:
        outs = [o[:, :f] for o in outs]
    return tuple(outs) if relu else outs[0]


_STATIC = ("n_rows", "window", "interpret", "tm", "k")


@functools.partial(jax.jit, static_argnames=_STATIC)
def csr_gather_spmm(indptr, indices, rows, values, x, *, n_rows: int,
                    window: int | None = None, interpret=False,
                    tm: int = WINDOW_TILE, k: int = CHUNK):
    """Y = A @ X with A in CSR; x [n_cols, F] -> float32 [n_rows, F].
    ``window`` rows of ``x`` per VMEM window (``None``: all of them); more
    than one window needs an operand in tile-column order at tiles of
    ``tm`` rows."""
    return _spmm(indptr, indices, rows, values, x, name="spmm",
                 n_rows=n_rows, window=window, interpret=interpret, tm=tm,
                 k=k)


@functools.partial(jax.jit, static_argnames=_STATIC + ("activation",))
def csr_gather_spmm_fused_epilogue(indptr, indices, rows, values, x,
                                   self_term=None, bias=None, alpha=None, *,
                                   n_rows: int, activation: str = "none",
                                   window: int | None = None,
                                   interpret=False, tm: int = WINDOW_TILE,
                                   k: int = CHUNK):
    """Y = act(A @ X + alpha * self_term + bias), epilogue fused in VMEM in
    the last window's call.

    Returns ``(y, mask)`` when ``activation == "relu"`` (mask is the saved
    0/1 pre-activation sign, float32), else ``y`` alone; self_term
    [n_rows, F], bias [1, F] or [F], alpha a scalar (required with
    self_term)."""
    if activation not in ("none", "relu"):
        raise ValueError(f"unsupported fused activation {activation!r}")
    if self_term is not None and alpha is None:
        raise ValueError("self_term requires alpha (use 1.0 for plain add)")
    return _spmm(indptr, indices, rows, values, x,
                 name="spmm_fused_epilogue", n_rows=n_rows, window=window,
                 interpret=interpret, tm=tm, k=k, self_term=self_term,
                 bias=bias, alpha=alpha, relu=activation == "relu")


@functools.partial(jax.jit, static_argnames=_STATIC)
def csr_gather_spmm_masked(indptr, indices, rows, values, x, mask, *,
                           n_rows: int, window: int | None = None,
                           interpret=False, tm: int = WINDOW_TILE,
                           k: int = CHUNK):
    """Y = A @ (mask ⊙ X) — the fused-epilogue VJP (A the transposed
    operand, X the incoming cotangent, mask the saved ReLU mask)."""
    if mask.shape != x.shape:
        raise ValueError(f"mask shape {mask.shape} != x shape {x.shape}")
    return _spmm(indptr, indices, rows, values, x * mask,
                 name="spmm_masked", n_rows=n_rows, window=window,
                 interpret=interpret, tm=tm, k=k)
