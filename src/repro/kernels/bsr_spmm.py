"""Block-sparse-row SpMM Pallas kernel — the TPU-native form of the paper's
cache-tiled CPU SpMM (Alg 2) and block-per-row CUDA SpMM (Alg 3).

Adaptation summary (DESIGN.md §2):

* Paper Alg 2 streams 128-byte feature tiles through ZMM registers with a
  lookahead-D software prefetch. On TPU the analogous structure is: feature
  tiles of 128 lanes held in VMEM, with the *scalar-prefetched* block-column
  index array driving the BlockSpec ``index_map`` — the Pallas pipeline
  issues the DMA for grid step i+1 while step i computes, which is exactly
  the paper's latency-hiding prefetch re-expressed for a DMA machine.
* Paper Alg 3 maps one node to one thread block so accumulation is
  atomic-free. On TPU the grid is *sequential*: all blocks of a block-row
  are visited consecutively (blocks are sorted by row), so the output tile
  stays resident in VMEM and is accumulated without atomics; ``first_in_row``
  tells the kernel when to zero the accumulator.
* Irregular per-edge gathers become dense (BR, BC) @ (BC, BF) sub-matmuls on
  the MXU. CSR->BSR conversion is a one-time O(nnz) cost amortised over
  epochs — the same argument the paper makes for its CSR/CSC materialisation.

Grid layout: ``(num_feature_tiles, n_blocks)`` — blocks innermost so the
output tile for a block-row is revisited on consecutive steps.

Block-stream windows: scalar-prefetched index arrays live whole in SMEM
(1 MiB on v5e — three int32 streams of 90k blocks already overflow it, and
ogbn-arxiv at its published size has ~1M blocks). Every kernel here
therefore runs one ``pallas_call`` per *window* of the block stream
(``block_windows``), in stream order. The output of one window is the
aliased input of the next, so tiles a window never visits keep their
values. A block-row that straddles a window boundary (hub rows can span
many windows) is *resumed*: the window's first flag is rewritten to
``RESUME`` and the kernel reloads that row's partial tiles from the carry
instead of zeroing them. Epilogues still fire once, at the true
``last_in_row``.

Fused-epilogue family (DESIGN.md §8): ``bsr_spmm_fused_epilogue`` extends
the kernel with an epilogue applied when the *last* block of each block-row
completes (``last_in_row``, the dual of ``first_in_row``):

    acc = A @ X                     (the block-row accumulation above)
    acc += alpha * self_term        (optional; alpha is an SMEM scalar)
    acc += bias                     (optional; one (1, BF) lane tile)
    y, mask = relu(acc), acc > 0    (optional; mask saved for the VJP)

The epilogue runs while the output tile is still resident in VMEM — the
separate XLA ops for bias add / self-term combine / activation (and their
three materialized [N, F] round-trips through HBM) disappear. The matching
backward, ``bsr_spmm_masked``, is the transposed SpMM with the activation
mask applied to the dY tile *on load*: dX = Aᵀ @ (mask ⊙ dY) without ever
materializing the masked cotangent.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# int32 words of per-block index streams one pallas_call may scalar-prefetch
# (half of v5e's SMEM, leaving room for Mosaic's own scalars)
SMEM_INDEX_WORDS = 1 << 17
# window flag: the row continues from the previous window's carried tiles
RESUME = 2


def block_windows(n_blocks: int, n_streams: int,
                  window: Optional[int] = None) -> list[tuple[int, int]]:
    """Static ``[start, stop)`` windows of the block stream whose
    ``n_streams`` int32 index arrays fit ``SMEM_INDEX_WORDS``
    (``window`` overrides the size, e.g. to exercise resumes in tests)."""
    size = window or SMEM_INDEX_WORDS // n_streams
    return [(s, min(s + size, n_blocks)) for s in range(0, n_blocks, size)]


def mm(a, b):
    """f32-accumulated MXU product. Its precision is the ambient
    ``jax.default_matmul_precision`` when the kernel is traced ("highest"
    gives Mosaic's fp32 contraction, the default one bf16 pass)."""
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def mm_nt(a, b):
    """``a @ b.T`` on the MXU without materialising the transpose."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def open_row(flag, outs: Sequence, inits: Sequence[float],
             carries: Sequence) -> None:
    """At a block-row's first block in this window: fill the resident
    output tiles with ``inits`` (flag 1) or reload them from the previous
    window's carried tiles (flag ``RESUME``)."""

    @pl.when(flag == 1)
    def _init():
        for o, v in zip(outs, inits):
            o[...] = jnp.full(o.shape, v, o.dtype)

    if carries:
        @pl.when(flag == RESUME)
        def _resume():
            for o, c in zip(outs, carries):
                o[...] = c[...]


def windowed_call(make_kernel: Callable[[bool], Callable], *, name: str,
                  lead: int, block_rows, block_cols, first_in_row,
                  extra_streams=(), scalars=(), in_specs, inputs, out_specs,
                  out_shape, carry_specs, interpret, window=None) -> list:
    """Run one BSR kernel over the block stream, one window per call.

    ``name`` is every call's ``pallas_call`` name: the Mosaic custom
    call's ``kernel_name``, by which traces and compiled HLO find it.

    Scalar-prefetch layout seen by ``make_kernel(resume)``'s kernel and by
    every index map (after the grid indices ``(lead_i, b)``): ``off`` (the
    window's first block, so ``blocks`` index maps read ``off[0] + b``),
    ``rows``, ``cols``, ``flags``, then ``extra_streams`` and ``scalars``.
    After the inputs come, when ``resume``, one carry ref per output (block
    spec ``carry_specs[i]``, indexed at the window's first block-row), then
    the outputs. Returns the list of outputs.
    """
    n_blocks = first_in_row.shape[0]
    outs: list = []
    for start, stop in block_windows(n_blocks, 3 + len(extra_streams),
                                     window):
        first = first_in_row[start:stop]
        sp = [jnp.full((1,), start, jnp.int32), block_rows[start:stop],
              block_cols[start:stop], first.at[0].set(RESUME - first[0]),
              *(s[start:stop] for s in extra_streams), *scalars]
        n_in = len(sp) + len(inputs)
        outs = pl.pallas_call(
            make_kernel(bool(outs)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(sp),
                grid=(lead, stop - start),
                in_specs=[*in_specs, *carry_specs[:len(outs)]],
                out_specs=out_specs,
            ),
            out_shape=out_shape,
            input_output_aliases={n_in + i: i for i in range(len(outs))},
            interpret=interpret,
            name=name,
        )(*sp, *inputs, *outs)
    return outs


def _row_map(j, b, off, rows, *_):
    return (rows[b], j)


def _col_map(j, b, off, rows, cols, *_):
    return (cols[b], j)


def _carry_map(j, b, off, rows, *_):
    return (rows[0], j)


def _block_map(j, b, off, *_):
    return (off[0] + b, 0, 0)


def _make_spmm_kernel(*, masked: bool, epilogue: bool, has_self: bool,
                      has_bias: bool, relu: bool):
    """SpMM kernel specialised to its (static) operand spec.

    Ref layout: scalar prefetch (off, rows, cols, flags[, last][, alpha]),
    inputs (blocks, x[, mask][, self][, bias]), carries (y[, relu mask])
    when resuming, outputs (y[, relu mask]).
    """

    def make(resume: bool):
        def kernel(*refs):
            it = iter(refs)
            _off, _rows, _cols, flags = (next(it) for _ in range(4))
            last = next(it) if epilogue else None
            alpha = next(it) if has_self else None
            blocks, x = next(it), next(it)
            m_in = next(it) if masked else None
            self_ref = next(it) if has_self else None
            bias_ref = next(it) if has_bias else None
            carries = [next(it) for _ in range(1 + relu)] if resume else []
            y_ref = next(it)
            mask_ref = next(it) if relu else None

            b = pl.program_id(1)
            open_row(flags[b], (y_ref,), (0.0,), carries[:1])
            x_blk = x[...]
            if masked:
                # the fusion: dY tile masked in VMEM as it streams in — the
                # [N, F] masked cotangent is never materialized in HBM
                x_blk = x_blk * m_in[...]
            y_ref[...] += mm(blocks[0], x_blk)

            if epilogue:
                @pl.when(last[b] == 1)
                def _epilogue():
                    acc = y_ref[...]
                    if has_self:
                        acc = acc + alpha[0] * self_ref[...].astype(jnp.float32)
                    if has_bias:
                        acc = acc + bias_ref[...].astype(jnp.float32)
                    if relu:
                        mask_ref[...] = (acc > 0.0).astype(jnp.float32)
                        acc = jnp.maximum(acc, 0.0)
                    y_ref[...] = acc

        return kernel

    return make


def _spmm(block_rows, block_cols, first_in_row, blocks, x, *, name,
          n_rows_padded, bf, interpret, window, last_in_row=None, mask=None,
          self_term=None, bias=None, alpha=None, relu=False):
    """The one BSR SpMM implementation behind all three entry points."""
    _, br, bc = blocks.shape
    n_cols_padded, f = x.shape
    if f % bf != 0:
        raise ValueError(f"feature dim {f} must be a multiple of tile {bf}")
    if n_cols_padded % bc != 0:
        raise ValueError("x rows must be padded to the block-column size")

    in_specs = [pl.BlockSpec((1, br, bc), _block_map),
                pl.BlockSpec((bc, bf), _col_map)]
    inputs = [blocks, x]
    if mask is not None:
        in_specs.append(pl.BlockSpec((bc, bf), _col_map))
        inputs.append(mask)
    if self_term is not None:
        in_specs.append(pl.BlockSpec((br, bf), _row_map))
        inputs.append(self_term)
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bf), lambda j, *_: (0, j)))
        inputs.append(bias)
    n_out = 1 + relu
    outs = windowed_call(
        _make_spmm_kernel(masked=mask is not None,
                          epilogue=last_in_row is not None,
                          has_self=self_term is not None,
                          has_bias=bias is not None, relu=relu),
        name=name, lead=f // bf, block_rows=block_rows, block_cols=block_cols,
        first_in_row=first_in_row,
        extra_streams=() if last_in_row is None else (last_in_row,),
        scalars=(() if self_term is None
                 else (jnp.asarray(alpha, jnp.float32).reshape(1),)),
        in_specs=in_specs, inputs=inputs,
        out_specs=[pl.BlockSpec((br, bf), _row_map)] * n_out,
        out_shape=[jax.ShapeDtypeStruct((n_rows_padded, f), jnp.float32)] * n_out,
        carry_specs=[pl.BlockSpec((br, bf), _carry_map)] * n_out,
        interpret=interpret, window=window)
    return tuple(outs) if relu else outs[0]


@functools.partial(
    jax.jit, static_argnames=("n_rows_padded", "bf", "interpret", "window")
)
def bsr_spmm(
    block_rows: jax.Array,  # [n_blocks] int32 (sorted)
    block_cols: jax.Array,  # [n_blocks] int32
    first_in_row: jax.Array,  # [n_blocks] int32 0/1
    blocks: jax.Array,  # [n_blocks, BR, BC]
    x: jax.Array,  # [n_cols_padded, F] (F % bf == 0)
    *,
    n_rows_padded: int,
    bf: int = 128,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Y = A @ X with A in flattened BSR. Output is float32 [n_rows_padded, F]."""
    return _spmm(block_rows, block_cols, first_in_row, blocks, x,
                 name="bsr_spmm", n_rows_padded=n_rows_padded, bf=bf,
                 interpret=interpret, window=window)


# ---------------------------------------------------------------------------
# Fused-epilogue forward: epilogue applied at ``last_in_row`` in VMEM
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("n_rows_padded", "bf", "activation", "interpret",
                     "window"),
)
def bsr_spmm_fused_epilogue(
    block_rows: jax.Array,  # [n_blocks] int32 (sorted)
    block_cols: jax.Array,  # [n_blocks] int32
    first_in_row: jax.Array,  # [n_blocks] int32 0/1
    last_in_row: jax.Array,  # [n_blocks] int32 0/1 (dual of first_in_row)
    blocks: jax.Array,  # [n_blocks, BR, BC]
    x: jax.Array,  # [n_cols_padded, F] (F % bf == 0)
    self_term: "jax.Array | None" = None,  # [n_rows_padded, F]
    bias: "jax.Array | None" = None,  # [1, F]
    alpha: "jax.Array | None" = None,  # scalar; required with self_term
    *,
    n_rows_padded: int,
    bf: int = 128,
    activation: str = "none",
    interpret: bool = False,
    window: Optional[int] = None,
):
    """Y = act(A @ X + alpha * self_term + bias), epilogue fused in VMEM.

    Returns ``(y, mask)`` when ``activation == "relu"`` (mask is the saved
    0/1 pre-activation sign, float32), else ``y`` alone. All optional
    operands are static by presence — jit specialises per epilogue spec.
    """
    if activation not in ("none", "relu"):
        raise ValueError(f"unsupported fused activation {activation!r}")
    if self_term is not None and alpha is None:
        raise ValueError("self_term requires alpha (use 1.0 for plain add)")
    f = x.shape[1]
    if self_term is not None and self_term.shape != (n_rows_padded, f):
        raise ValueError(
            f"self_term must be [{n_rows_padded}, {f}], got {self_term.shape}")
    if bias is not None and bias.shape != (1, f):
        raise ValueError(f"bias must be [1, {f}], got {bias.shape}")
    return _spmm(block_rows, block_cols, first_in_row, blocks, x,
                 name="bsr_spmm_fused_epilogue", n_rows_padded=n_rows_padded,
                 bf=bf, interpret=interpret, window=window,
                 last_in_row=last_in_row,
                 self_term=self_term, bias=bias, alpha=alpha,
                 relu=activation == "relu")


# ---------------------------------------------------------------------------
# Fused backward: transposed SpMM with the activation mask applied on load
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("n_rows_padded", "bf", "interpret", "window")
)
def bsr_spmm_masked(
    block_rows: jax.Array,  # [n_blocks] int32 (sorted)
    block_cols: jax.Array,  # [n_blocks] int32
    first_in_row: jax.Array,  # [n_blocks] int32 0/1
    blocks: jax.Array,  # [n_blocks, BR, BC]
    x: jax.Array,  # [n_cols_padded, F] — the incoming cotangent dY
    mask: jax.Array,  # [n_cols_padded, F] — saved activation mask
    *,
    n_rows_padded: int,
    bf: int = 128,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Y = A @ (mask ⊙ X) with A in flattened BSR — the fused-epilogue VJP
    (A is the pre-built transposed operand, X the incoming cotangent)."""
    if mask.shape != x.shape:
        raise ValueError(f"mask shape {mask.shape} != x shape {x.shape}")
    return _spmm(block_rows, block_cols, first_in_row, blocks, x,
                 name="bsr_spmm_masked", n_rows_padded=n_rows_padded, bf=bf,
                 interpret=interpret, window=window, mask=mask)
