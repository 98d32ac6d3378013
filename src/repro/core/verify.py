"""Plan contract verifier — static analysis over lowered plans (DESIGN.md §14).

Nine PRs of lowering machinery accumulated implicit cross-layer contracts:
the BSR ``first_in_row``/``last_in_row`` duals every fused kernel's
accumulator protocol assumes, the PR-5 permutation boundary
(``perm[new] = old``, operands built on the permuted graph), the PR-7
interior/boundary split rules, the PR-8 bucket caps and relabel tables,
and the binding legality rules (epilogue/attention plans only on archs
that support them). A violated contract used to surface as silently wrong
gradients — scatter-add oracles shrug at malformed streams; the Pallas
kernels do not.

This module checks the whole catalog *at lowering time* and emits
structured :class:`PlanViolation` diagnostics instead of downstream NaNs.
It is invoked from ``lower`` / ``lower_distributed`` / ``lower_sampled``
(and therefore ``GNNProgram.compile``) through a
``validate="full" | "fast" | "off"`` knob:

* ``"fast"`` (the default) — metadata and index-structure checks only:
  O(n_blocks) over the index arrays, O(n) over permutations. No block
  *values* are read, so nothing large crosses the device boundary and
  lowering wall-time grows by well under 5 %. A CSR (row-gather) operand
  is O(nnz) and is read whole: structure, finite values, per-row mass.
* ``"full"`` — everything in fast, plus value-level checks: zeroed
  padding, finite blocks, per-block-row mass agreement between operand
  and exec graph, interior+boundary reconstruction of the bulk operand,
  and a template-batch pass over the sampler (relabel bijectivity,
  frontier chaining, masked padding).
* ``"off"`` — no verification (microbenchmarks of raw lowering cost).

``verify_plan`` returns the violation list; ``check_plan`` raises
:class:`PlanVerificationError` carrying it. Plans are dispatched by shape,
not by class import, so this module stays import-light (``lowering``
imports it, not the reverse).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.common.spans import span

VALIDATE_MODES = ("off", "fast", "full")

#: the invariant catalog — every class a check can emit, with the contract
#: it guards. Tests count mutation coverage against these names.
INVARIANT_CATALOG = {
    # BSR structure (all operand forms: BSRDevice, stacked dicts, padded
    # sampled dicts)
    "bsr.index_dtype": "block indices and first/last flags are int32",
    "bsr.rows_in_range": "block-row ids within [0, padded_rows/br)",
    "bsr.cols_in_range": "block-col ids within [0, padded_cols/bc)",
    "bsr.rows_sorted": "block-row ids non-decreasing along the stream",
    "bsr.cols_sorted": "block-cols strictly increasing within a block-row",
    "bsr.first_in_row": "first_in_row=1 exactly at block-row transitions",
    "bsr.last_in_row": "last_in_row=1 exactly before block-row transitions",
    "bsr.row_coverage": "every block-row covered (explicit zero blocks)",
    "bsr.padding_zero": "row/col overhang regions of edge blocks are zero",
    "bsr.finite": "block values are finite (no NaN/Inf in operands)",
    # CSR structure (the row-gather operand, kernels.ops.CSRDevice)
    "csr.indptr": "indptr int32 of n_rows + 1 entries, non-decreasing "
                  "from 0 to nnz",
    "csr.indices_in_range": "column indices int32 within [0, n_cols)",
    "csr.indices_sorted": "column indices non-decreasing within each row "
                          "(row order) or each destination tile (tile-"
                          "column order)",
    "csr.row_ids": "row ids agree with indptr: in row order its expansion; "
                   "in tile-column order inside their tile, per-row counts "
                   "equal to indptr's, ascending within a column",
    "csr.finite": "values are finite (no NaN/Inf in operands)",
    # PR-5 permutation contract
    "perm.bijection": "perm and inv_perm are permutations of [0, n)",
    "perm.inverse": "perm[inv_perm] == identity (mutually inverse)",
    "layout.tile_match": "operands built at the layout's (br, bc) tile",
    "layout.graph_match": "operand row space matches the exec graph",
    "layout.operand_rows": "per-row (BSR: per-block-row) operand mass "
                           "matches the aggregation-weighted exec graph",
    # PR-7 split-phase rules
    "split.interior_no_ghost": "interior operand never reads a ghost column",
    "split.reconstruction": "interior + boundary blocks reconstruct the "
                            "bulk operand exactly",
    "split.live_shifts": "live-shift set matches the halo schedule",
    "halo.schedule_paired": "every live send slot has a matching recv slot "
                            "on the destination rank",
    "halo.slot_unique": "each ghost slot is written by exactly one sender",
    # PR-8 sampled contracts
    "sampled.caps_shape": "bucket cap tuples sized to the layer count",
    "sampled.caps_monotone": "bucket caps non-decreasing across buckets",
    "sampled.caps_aligned": "node caps aligned to lcm(br, bc)",
    "sampled.relabel_bijective": "relabel tables are bijections (unique "
                                 "ids, dst prefix contract)",
    "sampled.frontier_chain": "layer l's dst frontier is layer l+1's src",
    "sampled.padding_masked": "padded rows masked and padding edges zero",
    # binding legality
    "binding.epilogue_arch": "epilogue plans only on non-attention, "
                             "non-max archs",
    "binding.attention_arch": "attention plans only on GAT/GT, with "
                              "consistent head geometry",
    "binding.dim_chain": "layer i's d_out feeds layer i+1's d_in",
    "binding.operand_dtype": "operand blocks / features are float32",
    "binding.primitive": "bound primitives name the plan's backend",
}


@dataclasses.dataclass(frozen=True)
class PlanViolation:
    """One violated contract: which layer, which operand, which invariant."""

    layer: int        # -1 = plan-level (layout, operands shared by layers)
    operand: str      # e.g. "graph_op.fwd", "fwd_interior[rank 2]"
    invariant: str    # a key of INVARIANT_CATALOG
    detail: str

    def __str__(self) -> str:
        where = "plan" if self.layer < 0 else f"layer {self.layer}"
        return f"[{self.invariant}] {where} / {self.operand}: {self.detail}"


class PlanVerificationError(ValueError):
    """Raised by ``check_plan`` when a lowered plan violates its contracts."""

    def __init__(self, violations: list[PlanViolation], kind: str = "plan"):
        self.violations = list(violations)
        lines = "\n  ".join(str(v) for v in self.violations)
        super().__init__(
            f"{kind} failed contract verification "
            f"({len(self.violations)} violation(s)):\n  {lines}")


def _np(a) -> np.ndarray:
    """Host view of a numpy or device array (no-op for numpy)."""
    if isinstance(a, np.ndarray):
        return a
    import jax

    return np.asarray(jax.device_get(a))


class _Ctx:
    def __init__(self, mode: str):
        self.mode = mode
        self.violations: list[PlanViolation] = []

    @property
    def full(self) -> bool:
        return self.mode == "full"

    def flag(self, layer: int, operand: str, invariant: str, detail: str):
        assert invariant in INVARIANT_CATALOG, invariant
        self.violations.append(
            PlanViolation(layer=int(layer), operand=operand,
                          invariant=invariant, detail=detail))


# ---------------------------------------------------------------------------
# BSR structure checks
# ---------------------------------------------------------------------------

def _check_bsr_stream(
    v: _Ctx,
    operand: str,
    rows: np.ndarray,
    cols: np.ndarray,
    first: Optional[np.ndarray],
    last: Optional[np.ndarray],
    blocks,                      # array or None (fast mode skips values)
    nrb: int,
    ncb: int,
    *,
    layer: int = -1,
    strict_sorted: bool = True,
    padded: bool = False,
    n_rows: int = 0,
    n_cols: int = 0,
    br: int = 0,
    bc: int = 0,
) -> None:
    """Verify one flattened BSR block stream.

    ``strict_sorted=False`` / ``padded=True`` relax the within-row column
    order for streams carrying trailing padding blocks (stacked per-rank
    operands and ``_pad_bsr`` outputs pad with ``col=0, first=0`` blocks
    appended after the real stream), where only the padding signature is
    exempt from the ordering contract.
    """
    rows = _np(rows)
    cols = _np(cols)
    n = rows.shape[0]
    for name, arr in (("rows", rows), ("cols", cols)):
        if arr.dtype != np.int32:
            v.flag(layer, operand, "bsr.index_dtype",
                   f"{name} dtype {arr.dtype}, expected int32")
    if n == 0:
        if nrb > 0:
            v.flag(layer, operand, "bsr.row_coverage",
                   f"empty stream but {nrb} block-rows need coverage")
        return

    r64 = rows.astype(np.int64)
    c64 = cols.astype(np.int64)
    if r64.min() < 0 or r64.max() >= nrb:
        v.flag(layer, operand, "bsr.rows_in_range",
               f"block-rows span [{r64.min()}, {r64.max()}], "
               f"valid range [0, {nrb})")
    if c64.min() < 0 or c64.max() >= ncb:
        v.flag(layer, operand, "bsr.cols_in_range",
               f"block-cols span [{c64.min()}, {c64.max()}], "
               f"valid range [0, {ncb})")
    if not (r64[1:] >= r64[:-1]).all():
        bad = int(np.flatnonzero(r64[1:] < r64[:-1])[0]) + 1
        v.flag(layer, operand, "bsr.rows_sorted",
               f"block-row decreases at flat block {bad}")

    same_row = r64[1:] == r64[:-1]
    nonincreasing = same_row & (c64[1:] <= c64[:-1])
    if nonincreasing.any():
        idx = np.flatnonzero(nonincreasing) + 1
        if padded:
            # padding signature: appended zero blocks carry col=0, first=0
            f = _np(first).astype(np.int64) if first is not None else None
            sig = (c64[idx] == 0)
            if f is not None:
                sig &= f[idx] == 0
            idx = idx[~sig]
        if idx.size and strict_sorted:
            v.flag(layer, operand, "bsr.cols_sorted",
                   f"block-cols not strictly increasing within block-row "
                   f"{int(r64[idx[0]])} at flat block {int(idx[0])}")

    if first is not None:
        f = _np(first)
        if f.dtype != np.int32:
            v.flag(layer, operand, "bsr.index_dtype",
                   f"first_in_row dtype {f.dtype}, expected int32")
        f64 = f.astype(np.int64)
        want = np.ones(n, dtype=np.int64)
        want[1:] = (~same_row).astype(np.int64)
        if not np.array_equal(f64, want):
            bad = int(np.flatnonzero(f64 != want)[0])
            v.flag(layer, operand, "bsr.first_in_row",
                   f"first_in_row[{bad}]={int(f64[bad])} but block-row "
                   f"transition says {int(want[bad])} "
                   f"(block-row {int(r64[bad])})")
    if last is not None:
        l = _np(last)
        l64 = l.astype(np.int64)
        want = np.ones(n, dtype=np.int64)
        want[:-1] = (~same_row).astype(np.int64)
        if not np.array_equal(l64, want):
            bad = int(np.flatnonzero(l64 != want)[0])
            v.flag(layer, operand, "bsr.last_in_row",
                   f"last_in_row[{bad}]={int(l64[bad])} but block-row "
                   f"transition says {int(want[bad])} "
                   f"(block-row {int(r64[bad])})")

    covered = np.unique(r64[(r64 >= 0) & (r64 < nrb)])
    if covered.shape[0] != nrb:
        missing = np.setdiff1d(np.arange(nrb), covered)
        v.flag(layer, operand, "bsr.row_coverage",
               f"{missing.shape[0]} uncovered block-row(s), first: "
               f"{int(missing[0])} — empty rows need explicit zero blocks")

    if blocks is None or not v.full:
        return
    b = _np(blocks)
    if b.dtype != np.float32:
        v.flag(layer, operand, "binding.operand_dtype",
               f"blocks dtype {b.dtype}, expected float32")
    if not np.isfinite(b).all():
        v.flag(layer, operand, "bsr.finite",
               f"{int((~np.isfinite(b)).sum())} non-finite block value(s)")
    # zeroed padding: overhang rows/cols of blocks in the last block-row /
    # block-col must be zero (the DMA ships them; the kernels trust them)
    if n_rows and br:
        row_over = nrb * br - n_rows
        if row_over > 0:
            sel = r64 == nrb - 1
            tail = b[sel][:, br - row_over:, :]
            if tail.size and float(np.abs(tail).max()) != 0.0:
                v.flag(layer, operand, "bsr.padding_zero",
                       f"nonzero value in the {row_over}-row overhang of "
                       f"the last block-row")
    if n_cols and bc:
        col_over = ncb * bc - n_cols
        if col_over > 0:
            sel = c64 == ncb - 1
            tail = b[sel][:, :, bc - col_over:]
            if tail.size and float(np.abs(tail).max()) != 0.0:
                v.flag(layer, operand, "bsr.padding_zero",
                       f"nonzero value in the {col_over}-col overhang of "
                       f"the last block-col")


def _stacked_fast_clean(d: dict, nrb: int, ncb: int) -> bool:
    """One vectorised screening pass over a stacked per-rank BSR dict
    ``{"rows": [P, n], "cols": [P, n], "first": [P, n]}``.

    Returns True when every fast-mode invariant holds for every rank —
    the hot path for ``validate="fast"``, where the per-rank loop in
    ``_check_bsr_stream`` costs more than the checks themselves. Any
    failure returns False and the caller re-runs the per-rank checker
    for exact (rank, block) diagnostics; the screening itself never
    flags.
    """
    rows = np.asarray(d["rows"])
    cols = np.asarray(d["cols"])
    first = np.asarray(d["first"]) if d.get("first") is not None else None
    if rows.dtype != np.int32 or cols.dtype != np.int32:
        return False
    if rows.ndim != 2 or rows.shape[1] == 0:
        return False
    r = rows.astype(np.int64, copy=False)
    c = cols.astype(np.int64, copy=False)
    if r.min() < 0 or r.max() >= nrb or c.min() < 0 or c.max() >= ncb:
        return False
    same_row = r[:, 1:] == r[:, :-1]
    if not (r[:, 1:] >= r[:, :-1]).all():
        return False
    noninc = same_row & (c[:, 1:] <= c[:, :-1])
    if noninc.any():
        pad_sig = c[:, 1:] == 0  # appended padding blocks: col=0, first=0
        if first is not None:
            pad_sig &= first[:, 1:] == 0
        if (noninc & ~pad_sig).any():
            return False
    if first is not None:
        if first.dtype != np.int32:
            return False
        want = np.ones(rows.shape, dtype=bool)
        want[:, 1:] = ~same_row
        if not np.array_equal(first.astype(bool), want):
            return False
    # coverage: every (rank, block-row) pair must appear at least once
    P = rows.shape[0]
    counts = np.bincount(
        (r + np.arange(P, dtype=np.int64)[:, None] * nrb).ravel(),
        minlength=P * nrb)
    return bool((counts > 0).all())


def _check_bsr_device(v: _Ctx, operand: str, dev, *, layer: int = -1,
                      want_br: int = 0, want_bc: int = 0) -> None:
    """Checks for a ``kernels.ops.BSRDevice`` (or ``BSRMatrix``-shaped)
    operand: the strict single-matrix contract (no padding blocks)."""
    br, bc = int(dev.br), int(dev.bc)
    if want_br and (br != want_br or bc != want_bc):
        v.flag(layer, operand, "layout.tile_match",
               f"operand tile ({br}, {bc}) != layout tile "
               f"({want_br}, {want_bc})")
    nrb = -(-int(dev.n_rows) // br)
    ncb = max(-(-int(dev.n_cols) // bc), 1)
    if v.full and hasattr(dev, "host_view"):  # one device_get round-trip
        h = dev.host_view()
        rows, cols = h["rows"], h["cols"]
        first, last = h.get("first"), h.get("last")
        blocks = h["blocks"]
    else:  # fast mode: indices only — the block values never leave device
        rows = getattr(dev, "block_rows")
        cols = getattr(dev, "block_cols")
        first = getattr(dev, "first_in_row", None)
        last = getattr(dev, "last_in_row", None)
        blocks = dev.blocks if v.full else None
    _check_bsr_stream(
        v, operand, rows, cols, first, last, blocks, nrb, ncb, layer=layer,
        strict_sorted=True, padded=False, n_rows=int(dev.n_rows),
        n_cols=int(dev.n_cols), br=br, bc=bc)


# ---------------------------------------------------------------------------
# PR-5: permutation / layout contract
# ---------------------------------------------------------------------------

def _check_layout(v: _Ctx, lp, n_exec_rows: Optional[int]) -> None:
    if lp is None:
        return
    perm = lp.perm
    inv = lp.inv_perm
    if perm is None and inv is None:
        return
    if perm is None or inv is None:
        v.flag(-1, "layout", "perm.bijection",
               "perm/inv_perm must be set together "
               f"(perm={'set' if perm is not None else 'None'}, "
               f"inv_perm={'set' if inv is not None else 'None'})")
        return
    perm = _np(perm).astype(np.int64)
    inv = _np(inv).astype(np.int64)
    n = perm.shape[0]
    ident = np.arange(n, dtype=np.int64)
    for name, p in (("perm", perm), ("inv_perm", inv)):
        if p.shape[0] != n or not np.array_equal(np.sort(p), ident):
            v.flag(-1, "layout", "perm.bijection",
                   f"{name} is not a permutation of [0, {n})")
            return
    if not np.array_equal(perm[inv], ident):
        bad = int(np.flatnonzero(perm[inv] != ident)[0])
        v.flag(-1, "layout", "perm.inverse",
               f"perm[inv_perm] != identity (first mismatch at node {bad})")
    if n_exec_rows is not None and n != n_exec_rows:
        v.flag(-1, "layout", "layout.graph_match",
               f"permutation over {n} nodes but exec graph has "
               f"{n_exec_rows} rows")


def _row_sums(indptr: np.ndarray, values: np.ndarray) -> np.ndarray:
    rows = np.repeat(np.arange(indptr.shape[0] - 1), np.diff(indptr))
    return np.bincount(rows, weights=values.astype(np.float64),
                       minlength=indptr.shape[0] - 1)


def _weighted_row_sums(graph, aggregation, transposed: bool):
    """Per-row mass of the aggregation-weighted exec graph (of its
    transpose for the backward operand); None where operands keep raw
    weights (max: attention masks) or the weighting does not apply."""
    from repro.core.aggregate import _weighted_graph

    if aggregation == "max":
        return None
    try:
        weighted = _weighted_graph(graph, aggregation)
    except (ValueError, AssertionError):
        return None
    if transposed:  # rows of Aᵀ are the columns of A
        return np.bincount(weighted.indices,
                           weights=weighted.data.astype(np.float64),
                           minlength=weighted.n_cols)
    return _row_sums(weighted.indptr, weighted.data)


def _check_operand_rows(v: _Ctx, operand: str, dev, graph, aggregation,
                        transposed: bool) -> None:
    """Full mode: per-block-row mass of the operand must equal the
    aggregation-weighted exec graph's — catches operands built on the
    wrong (un-permuted, mis-weighted) graph even when totals agree."""
    row_sums = _weighted_row_sums(graph, aggregation, transposed)
    if row_sums is None:
        return
    br = int(dev.br)
    nrb = -(-row_sums.shape[0] // br)
    want = np.zeros(nrb, dtype=np.float64)
    np.add.at(want, np.arange(row_sums.shape[0]) // br, row_sums)
    got = np.zeros(nrb, dtype=np.float64)
    rows = _np(dev.block_rows).astype(np.int64)
    blocks = _np(dev.blocks).astype(np.float64)
    sel = (rows >= 0) & (rows < nrb)
    np.add.at(got, rows[sel], blocks[sel].sum(axis=(1, 2)))
    if not np.allclose(got, want, rtol=1e-4, atol=1e-5):
        bad = int(np.argmax(np.abs(got - want)))
        v.flag(-1, operand, "layout.operand_rows",
               f"block-row {bad} mass {got[bad]:.6g} != weighted graph's "
               f"{want[bad]:.6g} — operand not built on the exec graph?")


def _check_csr_device(v: _Ctx, operand: str, dev, graph, aggregation,
                      transposed: bool) -> None:
    """Checks for a ``kernels.ops.CSRDevice`` (the row-gather operand). It
    is O(nnz) and the kernel trusts every word of it, so fast mode reads
    it whole: pointers, column range and order, finite values, and — given
    the exec graph — per-row mass against the weighted graph. The order is
    the one the operand declares: rows (``column_tile`` None) or columns
    within each destination tile of ``column_tile`` rows."""
    h = dev.host_view()
    indptr, indices, values = h["indptr"], h["indices"], h["values"]
    rows = h["rows"]
    n_rows, n_cols, nnz = int(dev.n_rows), int(dev.n_cols), indices.shape[0]
    if values.dtype != np.float32:
        v.flag(-1, operand, "binding.operand_dtype",
               f"values dtype {values.dtype}, expected float32")
    if indices.dtype != np.int32:
        v.flag(-1, operand, "csr.indices_in_range",
               f"indices dtype {indices.dtype}, expected int32")
    elif nnz and (int(indices.min()) < 0 or int(indices.max()) >= n_cols):
        v.flag(-1, operand, "csr.indices_in_range",
               f"column indices span [{int(indices.min())}, "
               f"{int(indices.max())}], valid range [0, {n_cols})")
    if not np.isfinite(values).all():
        v.flag(-1, operand, "csr.finite",
               f"{int((~np.isfinite(values)).sum())} non-finite value(s)")
    p = indptr.astype(np.int64)
    if (indptr.dtype != np.int32 or p.shape[0] != n_rows + 1
            or p[0] != 0 or p[-1] != nnz or (p[1:] < p[:-1]).any()):
        bad = np.flatnonzero(p[1:] < p[:-1])
        where = f", decreases at row {int(bad[0])}" if bad.size else ""
        v.flag(-1, operand, "csr.indptr",
               f"indptr {indptr.dtype}[{p.shape[0]}] spans "
               f"[{int(p[0])}, {int(p[-1])}] for {n_rows} rows and "
               f"{nnz} nonzeros{where}")
        return  # rows are undefined: order and mass cannot be read
    tile = dev.column_tile or 1  # row order: every row its own group
    starts = p[np.minimum(np.arange(-(-n_rows // tile) + 1) * tile, n_rows)]
    group = np.repeat(np.arange(starts.shape[0] - 1), np.diff(starts))
    rows_ok = (rows.dtype == np.int32 and rows.shape[0] == nnz
               and (nnz == 0 or 0 <= int(rows.min()) <= int(rows.max())
                    < n_rows))
    if rows_ok and dev.column_tile:
        same_col = indices[1:] == indices[:-1]
        rows_ok = (np.array_equal(rows // tile, group)
                   and np.array_equal(np.bincount(rows, minlength=n_rows),
                                      np.diff(p))
                   and not (same_col & (rows[1:] < rows[:-1])
                            & (group[1:] == group[:-1])).any())
    elif rows_ok:
        rows_ok = np.array_equal(rows, group)
    if not rows_ok:
        v.flag(-1, operand, "csr.row_ids",
               f"row ids {rows.dtype}[{rows.shape[0]}] disagree with indptr")
    group_start = np.zeros(nnz, dtype=bool)
    group_start[starts[:-1][starts[:-1] < nnz]] = True
    down = np.flatnonzero((indices[1:] < indices[:-1])
                          & ~group_start[1:]) + 1
    if down.size:
        where = (f"tile {int(group[down[0]])}" if dev.column_tile
                 else f"row {int(group[down[0]])}")
        v.flag(-1, operand, "csr.indices_sorted",
               f"column {int(indices[down[0]])} after "
               f"{int(indices[down[0] - 1])} in {where}")
    want = (None if graph is None
            else _weighted_row_sums(graph, aggregation, transposed))
    if want is None or want.shape[0] != n_rows or not rows_ok:
        return
    got = np.bincount(rows, weights=values.astype(np.float64),
                      minlength=n_rows)
    if not np.allclose(got, want, rtol=1e-4, atol=1e-5):
        bad = int(np.argmax(np.abs(got - want)))
        v.flag(-1, operand, "layout.operand_rows",
               f"row {bad} mass {got[bad]:.6g} != weighted graph's "
               f"{want[bad]:.6g} — operand not built on the exec graph?")


# ---------------------------------------------------------------------------
# binding legality (shared by all three plan families)
# ---------------------------------------------------------------------------

_ATTENTION_ARCHS = ("GAT", "GT")


def _check_bindings(v: _Ctx, plan, allowed_prefixes: tuple[str, ...]) -> None:
    from repro.core.lowering import attention_head_dim  # lowering imports us

    layers = plan.layers
    for i, layer in enumerate(layers):
        if i + 1 < len(layers) and layer.d_out != layers[i + 1].d_in:
            v.flag(i, "layers", "binding.dim_chain",
                   f"layer {i} d_out={layer.d_out} but layer {i + 1} "
                   f"d_in={layers[i + 1].d_in}")
        is_attn = layer.op_kind in _ATTENTION_ARCHS
        if layer.epilogue is not None and (
                is_attn or plan.aggregation == "max"):
            v.flag(i, "epilogue", "binding.epilogue_arch",
                   f"epilogue plan bound on arch={layer.op_kind} "
                   f"aggregation={plan.aggregation} (no fused epilogue "
                   f"exists for attention archs or max)")
        if layer.attention is not None and not is_attn:
            v.flag(i, "attention", "binding.attention_arch",
                   f"attention plan bound on non-attention arch "
                   f"{layer.op_kind}")
        if layer.attention is not None and is_attn:
            a = layer.attention
            want = attention_head_dim(layer.op_kind, max(a.heads, 1),
                                      layer.d_out, i == len(layers) - 1)
            if a.heads < 1 or a.head_dim != want:
                v.flag(i, "attention", "binding.attention_arch",
                       f"attention geometry {a.heads}h x {a.head_dim} "
                       f"inconsistent with d_out={layer.d_out}")
        for prim in (layer.primitive, layer.agg_primitive):
            prefix = prim.split(".", 1)[0]
            if prefix not in allowed_prefixes:
                v.flag(i, "primitive", "binding.primitive",
                       f"primitive {prim!r} names backend {prefix!r}, "
                       f"expected one of {allowed_prefixes}")


# ---------------------------------------------------------------------------
# plan families
# ---------------------------------------------------------------------------

def _verify_model_plan(v: _Ctx, plan, graph) -> None:
    _check_bindings(v, plan, (plan.backend, "gather"))
    lp = plan.layout
    gop = plan.graph_op
    n_exec = getattr(gop, "n_nodes", None) if gop is not None else None
    _check_layout(v, lp, n_exec)
    if graph is not None and n_exec is not None and graph.n_rows != n_exec:
        v.flag(-1, "graph_op", "layout.graph_match",
               f"exec graph has {graph.n_rows} rows but operands were "
               f"built for {n_exec}")
    if gop is None:
        return
    for name, dev, transposed in (("graph_op.fwd", gop.fwd_operand, False),
                                  ("graph_op.bwd", gop.bwd_operand, True)):
        if getattr(dev, "format", None) == "gather":
            _check_csr_device(v, name, dev, graph, plan.aggregation,
                              transposed)
            continue
        if dev is None or not hasattr(dev, "block_rows"):
            continue
        _check_bsr_device(
            v, name, dev,
            want_br=lp.br if lp is not None else 0,
            want_bc=lp.bc if lp is not None else 0)
        if v.full and graph is not None:
            _check_operand_rows(v, name, dev, graph, plan.aggregation,
                                transposed)


def _live_shift_set(send_idx: np.ndarray) -> tuple:
    P = send_idx.shape[0]
    return tuple(int(s) for s in range(1, P)
                 if bool((send_idx[:, s - 1] >= 0).any()))


def _verify_distributed_plan(v: _Ctx, plan, dist) -> None:
    _check_bindings(v, plan, ("distributed", "gather"))
    _check_layout(v, plan.layout, None)
    if dist is None:
        return

    P = dist.n_ranks
    br, bc = dist.br, dist.bc
    n_local, n_ghost = dist.n_local, dist.n_ghost
    lp = plan.layout
    if lp is not None and (lp.br != br or lp.bc != bc):
        v.flag(-1, "layout", "layout.tile_match",
               f"plan layout tile ({lp.br}, {lp.bc}) != DistributedGraph "
               f"tile ({br}, {bc})")

    def stacked(name, d, nrb, ncb):
        if d is None:
            return
        # fast mode: one vectorised pass over all ranks; drop to the
        # per-rank checker only to name the failing (rank, block)
        if not v.full and _stacked_fast_clean(d, nrb, ncb):
            return
        for p in range(P):
            _check_bsr_stream(
                v, f"{name}[rank {p}]", d["rows"][p], d["cols"][p],
                d.get("first", [None] * P)[p], None,
                d["blocks"][p] if v.full else None,
                nrb, ncb, strict_sorted=True, padded=True,
                n_rows=nrb * br, n_cols=ncb * bc, br=br, bc=bc)

    nrb_l = n_local // br
    ncb_l = n_local // bc
    ncb_lg = (n_local + n_ghost) // bc
    nrb_lg = (n_local + n_ghost) // br
    stacked("fwd", dist.fwd, nrb_l, ncb_lg)
    stacked("bwd", dist.bwd, nrb_lg, ncb_l)
    if plan.feat_fwd is not None:
        f_pad = plan.feat_f_pad
        stacked("feat_fwd", plan.feat_fwd, nrb_l, max(f_pad // bc, 1))
        stacked("feat_bwd", plan.feat_bwd, max(f_pad // br, 1), ncb_l)

    # -- split-phase rules (PR-7) -------------------------------------------
    if dist.fwd_interior is not None:
        cols_i = np.asarray(dist.fwd_interior["cols"], dtype=np.int64)
        if cols_i.size and int(cols_i.max()) >= ncb_l:
            v.flag(-1, "fwd_interior", "split.interior_no_ghost",
                   f"interior block-col {int(cols_i.max())} reaches into "
                   f"the ghost region (local block-cols end at {ncb_l})")
        stacked("fwd_interior", dist.fwd_interior, nrb_l, ncb_l)
        stacked("bwd_interior", dist.bwd_interior, nrb_l, ncb_l)
        stacked("fwd_boundary", dist.fwd_boundary, nrb_l, ncb_lg)
        stacked("bwd_boundary", dist.bwd_boundary, nrb_lg, ncb_l)
        if v.full:
            _check_split_reconstruction(v, dist, nrb_l, ncb_lg)

    # -- halo schedule ------------------------------------------------------
    send_idx = np.asarray(dist.send_idx)
    recv_slot = np.asarray(dist.recv_slot)
    for s in range(1, P):
        for o in range(P):
            r = (o + s) % P
            ms = send_idx[o, s - 1] >= 0
            mr = recv_slot[r, s - 1] >= 0
            if not np.array_equal(ms, mr):
                v.flag(-1, f"halo[shift {s}]", "halo.schedule_paired",
                       f"rank {o} sends {int(ms.sum())} rows at shift {s} "
                       f"but rank {r} receives {int(mr.sum())}")
    for p in range(P):
        slots = recv_slot[p][recv_slot[p] >= 0]
        if slots.size != np.unique(slots).size:
            v.flag(-1, f"halo[rank {p}]", "halo.slot_unique",
                   f"rank {p} has ghost slots written by multiple senders")
        if slots.size and int(slots.max()) >= n_ghost:
            v.flag(-1, f"halo[rank {p}]", "halo.schedule_paired",
                   f"recv slot {int(slots.max())} outside ghost region "
                   f"[0, {n_ghost})")

    live = _live_shift_set(send_idx)
    if dist.live_shifts is not None and tuple(dist.live_shifts) != live:
        v.flag(-1, "live_shifts", "split.live_shifts",
               f"DistributedGraph.live_shifts={tuple(dist.live_shifts)} "
               f"but the halo schedule says {live}")
    if plan.overlap is not None and tuple(plan.overlap.live_shifts) != live:
        v.flag(-1, "overlap", "split.live_shifts",
               f"OverlapPlan.live_shifts={tuple(plan.overlap.live_shifts)} "
               f"but the halo schedule says {live}")


def _accumulate_blocks(d, p, ncb, nrb, br, bc) -> np.ndarray:
    acc = np.zeros((nrb * ncb, br, bc), dtype=np.float64)
    rows = np.asarray(d["rows"][p], dtype=np.int64)
    cols = np.asarray(d["cols"][p], dtype=np.int64)
    blocks = np.asarray(d["blocks"][p], dtype=np.float64)
    sel = (rows >= 0) & (rows < nrb) & (cols >= 0) & (cols < ncb)
    np.add.at(acc, rows[sel] * ncb + cols[sel], blocks[sel])
    return acc


def _check_split_reconstruction(v: _Ctx, dist, nrb, ncb) -> None:
    """interior + boundary must re-add to the bulk forward operand, block
    by block — the y_int + y_bnd == y_bulk stitching contract."""
    br, bc = dist.br, dist.bc
    ncb_l = dist.n_local // bc
    for p in range(dist.n_ranks):
        bulk = _accumulate_blocks(dist.fwd, p, ncb, nrb, br, bc)
        got = _accumulate_blocks(dist.fwd_boundary, p, ncb, nrb, br, bc)
        interior = _accumulate_blocks(dist.fwd_interior, p, ncb_l, nrb,
                                      br, bc)
        got.reshape(nrb, ncb, br, bc)[:, :ncb_l] += interior.reshape(
            nrb, ncb_l, br, bc)
        if not np.allclose(got, bulk, rtol=1e-5, atol=1e-6):
            bad = int(np.argmax(np.abs(got - bulk).sum(axis=(1, 2))))
            v.flag(-1, f"split[rank {p}]", "split.reconstruction",
                   f"interior + boundary != bulk at block "
                   f"(row {bad // ncb}, col {bad % ncb})")
            return


def _verify_sampled_plan(v: _Ctx, plan) -> None:
    _check_bindings(v, plan, (plan.backend, "gather"))
    sampler = plan.sampler
    _check_layout(v, plan.layout,
                  sampler.graph.n_rows if sampler is not None else None)
    if sampler is None:
        return
    L = sampler.n_layers
    br, bc = sampler.br, sampler.bc
    align = int(np.lcm(br, bc))
    prev = None
    for k, b in enumerate(sampler.buckets):
        name = f"bucket[{k}]"
        if (len(b.node_caps) != L + 1 or len(b.nnz_caps) != L
                or len(b.fwd_block_caps) != L or len(b.bwd_block_caps) != L):
            v.flag(-1, name, "sampled.caps_shape",
                   f"cap tuples sized for {len(b.node_caps) - 1} layers, "
                   f"plan has {L}")
            continue
        for l, cap in enumerate(b.node_caps):
            if cap <= 0 or cap % align != 0:
                v.flag(-1, name, "sampled.caps_aligned",
                       f"node_caps[{l}]={cap} not a positive multiple of "
                       f"lcm(br={br}, bc={bc})={align}")
        for l in range(L):
            if b.fwd_block_caps[l] < b.node_caps[l + 1] // br:
                v.flag(-1, name, "sampled.caps_aligned",
                       f"fwd_block_caps[{l}]={b.fwd_block_caps[l]} below "
                       f"the row-coverage floor "
                       f"{b.node_caps[l + 1] // br}")
        if prev is not None:
            if b.seed_cap < prev.seed_cap:
                v.flag(-1, name, "sampled.caps_monotone",
                       f"seed_cap {b.seed_cap} < previous bucket's "
                       f"{prev.seed_cap}")
            for l in range(min(len(b.node_caps), len(prev.node_caps))):
                if b.node_caps[l] < prev.node_caps[l]:
                    v.flag(-1, name, "sampled.caps_monotone",
                           f"node_caps[{l}]={b.node_caps[l]} < previous "
                           f"bucket's {prev.node_caps[l]}")
                    break
        prev = b

    if v.full:
        _verify_template_batch(v, plan)


def _verify_template_batch(v: _Ctx, plan) -> None:
    """Full mode: draw one deterministic batch and check the runtime-side
    sampled contracts (relabel bijectivity, frontier chaining, masked
    padding, per-block BSR structure). Uses a private RNG so the
    sampler's training stream is untouched."""
    sampler = plan.sampler
    g = sampler.graph
    rng = np.random.default_rng(0xC0FFEE)
    n_seeds = min(plan.batch_size, g.n_rows)
    seeds = rng.choice(g.n_rows, size=n_seeds, replace=False)
    try:
        batch = sampler.sample_batch(seeds, rng=rng)
    except (AssertionError, ValueError) as e:
        v.flag(-1, "sampler", "sampled.caps_monotone",
               f"template batch violates bucket caps: {e}")
        return

    bucket = batch.bucket
    L = sampler.n_layers
    for l, blk in enumerate(batch.blocks):
        name = f"block[{l}]"
        dst = np.asarray(blk.dst_nodes)
        src = np.asarray(blk.src_nodes)
        if np.unique(dst).shape[0] != dst.shape[0]:
            v.flag(l, name, "sampled.relabel_bijective",
                   "duplicate ids in the dst frontier")
        if np.unique(src).shape[0] != src.shape[0]:
            v.flag(l, name, "sampled.relabel_bijective",
                   "duplicate ids in the src frontier")
        if not np.array_equal(src[: dst.shape[0]], dst):
            v.flag(l, name, "sampled.relabel_bijective",
                   "src frontier prefix != dst frontier (relabel table "
                   "broke the prefix contract)")
        if l + 1 < L:
            nxt = np.asarray(batch.blocks[l + 1].src_nodes)
            if not np.array_equal(dst, nxt):
                v.flag(l, name, "sampled.frontier_chain",
                       f"block {l} dst frontier != block {l + 1} src "
                       f"frontier")
        n_e = blk.n_edges
        w_pad = np.asarray(blk.edge_w[n_e:])
        if w_pad.size and float(np.abs(w_pad).max()) != 0.0:
            v.flag(l, name, "sampled.padding_masked",
                   "padding edges carry nonzero weight")
        dst_cap = bucket.node_caps[l + 1]
        src_cap = bucket.node_caps[l]
        d_pad = np.asarray(blk.edge_dst[n_e:])
        if d_pad.size and not (d_pad == dst_cap - 1).all():
            v.flag(l, name, "sampled.padding_masked",
                   "padding edges do not target the reserved dump row")
        for bname, d, nrb, ncb, nr, nc in (
                ("fwd_bsr", blk.fwd_bsr, dst_cap // sampler.br,
                 src_cap // sampler.bc, dst_cap, src_cap),
                ("bwd_bsr", blk.bwd_bsr, src_cap // sampler.br,
                 dst_cap // sampler.bc, src_cap, dst_cap)):
            if d is None:
                continue
            _check_bsr_stream(
                v, f"{name}.{bname}", d["rows"], d["cols"], d["first"],
                None, d["blocks"], nrb, ncb, layer=l, strict_sorted=True,
                padded=True, n_rows=nr, n_cols=nc, br=sampler.br,
                bc=sampler.bc)

    counts = [batch.blocks[0].n_src] + [b.n_dst for b in batch.blocks]
    for l, m in enumerate(batch.valid):
        m = np.asarray(m)
        want = np.zeros(m.shape[0], dtype=bool)
        want[: counts[l]] = True
        if not np.array_equal(m, want):
            v.flag(-1, f"valid[{l}]", "sampled.padding_masked",
                   f"validity mask is not the {counts[l]}-row prefix")
    if batch.x is not None:
        x = np.asarray(batch.x)
        pad_rows = x[counts[0]:]
        if pad_rows.size and float(np.abs(pad_rows).max()) != 0.0:
            v.flag(-1, "x", "sampled.padding_masked",
                   "padded feature rows are not zero")
        if x.dtype != np.float32:
            v.flag(-1, "x", "binding.operand_dtype",
                   f"gathered features dtype {x.dtype}, expected float32")


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _resolve_mode(mode: str) -> str:
    if mode not in VALIDATE_MODES:
        raise ValueError(
            f"validate={mode!r}: expected one of {VALIDATE_MODES}")
    return mode


def verify_plan(plan, *, mode: str = "fast", graph=None,
                dist=None) -> list[PlanViolation]:
    """Run the invariant catalog over a lowered plan; return violations.

    ``graph`` is the *exec* graph a ``ModelPlan``'s operands were built
    from (post-reorder); ``dist`` is the ``DistributedGraph`` behind a
    ``DistributedModelPlan`` (the plan itself does not carry the stacked
    operands). Dispatch is structural: any object with ``graph_op`` /
    ``n_ranks`` / ``sampler`` is treated as the corresponding family.
    """
    mode = _resolve_mode(mode)
    v = _Ctx(mode)
    if mode == "off":
        return []
    if hasattr(plan, "sampler"):
        _verify_sampled_plan(v, plan)
    elif hasattr(plan, "n_ranks"):
        _verify_distributed_plan(v, plan, dist)
    elif hasattr(plan, "graph_op"):
        _verify_model_plan(v, plan, graph)
    else:
        raise TypeError(f"not a lowered plan: {type(plan).__name__}")
    return v.violations


@span("verify")
def check_plan(plan, *, mode: str = "fast", graph=None, dist=None) -> None:
    """``verify_plan`` that raises :class:`PlanVerificationError`."""
    if _resolve_mode(mode) == "off":
        return
    violations = verify_plan(plan, mode=mode, graph=graph, dist=dist)
    if violations:
        raise PlanVerificationError(violations, kind=type(plan).__name__)
