"""Layout stage: node-order selection and the BSR tile of the operands.

Morphling attributes most of its speedups to memory-efficient,
architecture-aware layouts (§ abstract, § layouts). A ``LayoutPlan``
records the node order a plan's sparse operands were built in and the
``(br, bc)`` tile they were counted at:

* the **node order** — ``none`` / ``degree`` / ``rcm``
  (``graph/csr.py:reorder_graph``); ``auto`` picks by BSR block count at
  the default tile and keeps the identity unless the best order cuts the
  count by at least 10% (``_select_order``);
* the **tile** — the caller's ``(br, bc)``, with an adaptive ``bc``
  (``graph/csr.py:adaptive_bc``) when none is given;
* the **operand format** — ``operand_format``: CSR row gather below
  ``GATHER_FILL`` nonzeros per block at the tile, BSR above.

The lowering pass threads the plan through every plan consumer; the
permutation contract (features in as ``X[perm]``, outputs back as
``Y[inv_perm]``) is upheld by the trainers, never by the user
(DESIGN.md §9).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.graph.csr import (
    CSRGraph,
    REORDER_MODES,
    adaptive_bc,
    bsr_block_count,
    reorder_graph,
)

#: fill (nonzeros per block at the planned tile) below which a Pallas
#: aggregation operand stays CSR and runs the row-gather SpMM
#: (``kernels/csr_gather_spmm.py``). Measured on a TPU v5e: a BSR grid step
#: costs ~0.3 µs per block per 128-lane tile whatever its fill, a row
#: gather ~25 ns per nonzero at 40 to 256 lanes (PERF.md §5); 0.3 µs /
#: 25 ns = 12 at one lane tile. Wider rows only move the crossover up (BSR
#: pays per lane tile, the gather per row), so one tile is the
#: conservative ratio. The SpMM now reads a row from a VMEM window for
#: ~6.8 ns instead (``kernels/csr_gather_spmm.py``, PERF.md §6), which
#: would put its crossover near 0.3 µs / 6.8 ns ≈ 44; no graph here runs the
#: BSR side to calibrate that (PERF.md §7), and attention still pays a copy.
GATHER_FILL = 12.0


@dataclasses.dataclass
class LayoutPlan:
    """One graph's chosen layout: node order + BSR tile, plan-visible.

    ``perm[new] = old`` / ``inv_perm[old] = new`` (``None`` for the
    identity order). ``source`` records provenance: ``default`` (identity
    order, adaptive tile), ``explicit`` (identity order, the caller's
    tile), ``requested`` (a named order), ``auto`` (the order
    ``_select_order`` picked), ``sampled`` (the sampler's tile, no block
    count), ``distributed`` (within-rank order baked into the data
    distribution, no trainer-boundary permutation).
    """

    order: str                        # "none" | "degree" | "rcm"
    br: int
    bc: int
    perm: Optional[np.ndarray] = dataclasses.field(default=None, repr=False)
    inv_perm: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)
    source: str = "default"
    n_blocks: int = 0                 # BSR(A) block count at this layout
    padding_waste: float = 0.0        # BSRMatrix.padding_waste() at it
    # the renumbered graph (P·A·Pᵀ) the plan was computed from — kept so
    # the lowering pass does not rebuild it; always consistent with perm
    reordered_graph: Optional[CSRGraph] = dataclasses.field(
        default=None, repr=False)

    @property
    def permutes(self) -> bool:
        return self.order != "none" and self.perm is not None

    def describe(self) -> str:
        line = f"{self.order} {self.br}x{self.bc}"
        if self.n_blocks:
            line += f" blocks={self.n_blocks} waste={self.padding_waste:.1%}"
        return f"{line} [{self.source}]"


def operand_format(nnz: int, n_blocks: int) -> str:
    """``"gather"`` (CSR row gather) or ``"bsr"`` for a Pallas operand with
    ``nnz`` nonzeros in ``n_blocks`` blocks at its tile (``GATHER_FILL``)."""
    return "gather" if nnz < GATHER_FILL * max(n_blocks, 1) else "bsr"


def default_layout(graph: CSRGraph, br: Optional[int] = None,
                   bc: Optional[int] = None) -> LayoutPlan:
    """Identity order at the given or adaptive tile, with its block count."""
    br = 8 if br is None else int(br)
    bc = adaptive_bc(graph.n_cols) if bc is None else int(bc)
    nb = bsr_block_count(graph, br, bc)
    return LayoutPlan(order="none", br=br, bc=bc,
                      n_blocks=nb, padding_waste=_waste(graph, br, bc, nb))


def _waste(graph: CSRGraph, br: int, bc: int, n_blocks: int) -> float:
    """Cheap padding-waste estimate without materialising blocks: assumes
    every last-row/last-col overhang block is occupied proportionally."""
    bsr_rows = -(-graph.n_rows // br) * br
    bsr_cols = max(-(-graph.n_cols // bc), 1) * bc
    row_over, col_over = bsr_rows - graph.n_rows, bsr_cols - graph.n_cols
    # upper bound: one block-row's worth of row overhang, one block-col's
    # of col overhang, over the stored total
    n_bcols = bsr_cols // bc
    n_brows = bsr_rows // br
    est = (min(n_blocks, n_bcols) * row_over * bc
           + min(n_blocks, n_brows) * col_over * br)
    return min(est / max(n_blocks * br * bc, 1), 1.0)


def _select_order(graph: CSRGraph, mode: str = "auto", br: int = 8,
                  bc: Optional[int] = None, min_gain: float = 0.1,
                  ) -> tuple[str, CSRGraph, Optional[np.ndarray],
                             Optional[np.ndarray]]:
    """Resolve the reorder mode and return ``(mode, reordered graph, perm,
    inv_perm)`` — the reordered candidates are built once here and the
    winner's graph is reused by the lowering pass.

    ``auto`` picks by BSR block count at a reference tile. A permutation
    is not free — the trainer boundary pays two gathers per forward (and
    their scatters per backward) — so ``auto`` only permutes when the
    best mode shrinks the block count by at least ``min_gain``
    (relative). Ties and marginal wins keep ``none``.
    """
    if mode != "auto":
        if mode not in ("none",) + REORDER_MODES:
            raise ValueError(f"unknown reorder mode {mode!r}")
        if mode == "none":
            return "none", graph, None, None
        g_r, perm, inv = reorder_graph(graph, mode)
        return mode, g_r, perm, inv
    if graph.n_rows != graph.n_cols:
        return "none", graph, None, None
    bc = adaptive_bc(graph.n_cols) if bc is None else bc
    base = bsr_block_count(graph, br, bc)
    best = ("none", graph, None, None)
    best_count = base
    for m in REORDER_MODES:
        g_r, perm, inv = reorder_graph(graph, m)
        count = bsr_block_count(g_r, br, bc)
        if count < best_count:
            best, best_count = (m, g_r, perm, inv), count
    if best_count > base * (1.0 - min_gain):
        return "none", graph, None, None
    return best


def choose_order(graph: CSRGraph, mode: str = "auto", br: int = 8,
                 bc: Optional[int] = None, min_gain: float = 0.1) -> str:
    """The mode-only view of ``_select_order`` (validates explicit
    modes; ``auto`` applies the min-gain rule)."""
    return _select_order(graph, mode, br, bc, min_gain)[0]
