"""Layout-optimization stage: reorder selection + BSR tile autotuning.

Morphling attributes most of its speedups to memory-efficient,
architecture-aware layouts (§ abstract, § layouts); FeatGraph shows the
schedule must be tuned per (graph, feature dim). Before this stage every
plan ran hardcoded tiles (``csr_to_bsr(br=8, bc=128)``) on whatever node
ordering the dataset shipped with — block density, padding waste and
per-block-row work were accidents of the input.

``plan_layout`` runs at lowering time and decides, per
``(graph fingerprint, feature dim, backend, fused?)``:

* the **node order** — ``none`` / ``degree`` / ``rcm``
  (``graph/csr.py:reorder_graph``), chosen by BSR block count at a
  reference tile;
* the **tile** ``(br, bc, bf)`` — measured over a small candidate grid
  with paired-interleaved timing when the backend compiles
  (XLA anywhere, Pallas on a real TPU), or scored by a block-count /
  padding cost model when timing would measure the Pallas Python
  interpreter instead of the layout (the ``calibrate_gamma`` analogy:
  an offline microbenchmark on the *current* backend);
* and caches the winner to disk, so the measurement runs once per
  fingerprint — a cache hit never re-measures.

The result is a ``LayoutPlan`` the lowering pass threads through every
plan consumer; the permutation contract (features in as ``X[perm]``,
outputs back as ``Y[inv_perm]``) is upheld by the trainers, never by the
user (DESIGN.md §9).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Optional, Sequence

import numpy as np

from repro.graph.csr import (
    CSRGraph,
    REORDER_MODES,
    adaptive_bc,
    bsr_block_count,
    csr_to_bsr,
    reorder_graph,
)

#: default (br, bc) candidate grid; bf candidates derive from the feature dim
TILE_CANDIDATES = ((8, 16), (8, 32), (8, 64), (8, 128), (16, 32), (16, 64))

#: modelled fixed cost per block (grid-step overhead: index prefetch, DMA
#: issue) in MAC-equivalents — keeps the cost model from picking tiny tiles
#: whose per-block overhead would dominate
BLOCK_OVERHEAD = 4096.0

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

#: timed candidates since import — the cache-determinism proof observable
#: (a cache hit leaves this untouched)
_MEASURE_CALLS = 0


def measure_calls() -> int:
    return _MEASURE_CALLS


@dataclasses.dataclass
class LayoutPlan:
    """One graph's chosen layout: node order + BSR tile, plan-visible.

    ``perm[new] = old`` / ``inv_perm[old] = new`` (``None`` for the
    identity order); ``bf == 0`` means the per-call ``feature_tile``
    policy rather than a pinned lane tile. ``source`` records provenance:
    ``default`` (no tuning ran), ``cost-model``, ``measured``, ``cache``
    (a previous measurement, loaded), ``distributed`` (within-rank order
    baked into the data distribution, no trainer-boundary permutation).
    """

    order: str                        # "none" | "degree" | "rcm"
    br: int
    bc: int
    bf: int = 0
    perm: Optional[np.ndarray] = dataclasses.field(default=None, repr=False)
    inv_perm: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)
    source: str = "default"
    fingerprint: str = ""
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
        bf = self.bf if self.bf else "auto"
        line = f"{self.order} {self.br}x{self.bc} bf={bf}"
        if self.n_blocks:
            line += f" blocks={self.n_blocks} waste={self.padding_waste:.1%}"
        return f"{line} [{self.source}]"


def operand_format(nnz: int, n_blocks: int) -> str:
    """``"gather"`` (CSR row gather) or ``"bsr"`` for a Pallas operand with
    ``nnz`` nonzeros in ``n_blocks`` blocks at its tile (``GATHER_FILL``)."""
    return "gather" if nnz < GATHER_FILL * max(n_blocks, 1) else "bsr"


def default_layout(graph: CSRGraph, br: Optional[int] = None,
                   bc: Optional[int] = None) -> LayoutPlan:
    """The un-autotuned fallback: identity order, given or adaptive tile."""
    br = 8 if br is None else int(br)
    bc = adaptive_bc(graph.n_cols) if bc is None else int(bc)
    nb = bsr_block_count(graph, br, bc)
    return LayoutPlan(order="none", br=br, bc=bc, bf=0,
                      n_blocks=nb, padding_waste=_waste(graph, br, bc, nb))


def graph_fingerprint(graph: CSRGraph, f_dim: int, backend: str, fused: bool,
                      order: str = "auto",
                      tiles: Optional[Sequence[tuple[int, int]]] = None,
                      n_heads: int = 0, attention: bool = False,
                      ) -> str:
    """Cache key: exact graph structure + every tuning condition.

    Hashes indptr/indices (O(nnz), the same order as one CSR pass), so two
    graphs collide only if they are structurally identical — the condition
    under which a cached tile transfers exactly. The order request and any
    custom candidate grid are part of the key: a run with a restricted
    grid must never shadow the default-grid winner. Attention plans
    (``attention=True`` + the head count) key separately from SpMM plans:
    the same graph tuned for a GAT must not shadow (or be shadowed by) its
    GCN tile — the attention kernel's lane dim is the per-head dim, not the
    full feature width.
    """
    h = hashlib.sha256()
    h.update(np.asarray(
        [graph.n_rows, graph.n_cols, graph.nnz, int(f_dim)],
        dtype=np.int64).tobytes())
    h.update(backend.encode())
    h.update(b"fused" if fused else b"unfused")
    h.update(f"attn={int(bool(attention))}x{int(n_heads)}".encode())
    h.update(f"order={order}".encode())
    h.update(repr("default" if tiles is None
                  else tuple(map(tuple, tiles))).encode())
    h.update(np.ascontiguousarray(graph.indptr).tobytes())
    h.update(np.ascontiguousarray(graph.indices).tobytes())
    return h.hexdigest()[:20]


def default_cache_path() -> str:
    return os.environ.get(
        "MORPHLING_LAYOUT_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "morphling-repro",
                     "layout_cache.json"))


def _load_cache(path: str) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return {}


def _store_entry(path: str, key: str, entry: dict) -> None:
    # re-read immediately before the atomic replace so concurrent tuners
    # merge rather than clobber; the remaining load→replace window can
    # still lose one entry under a true race, which only costs that
    # graph a re-measure on its next cold run
    cache = _load_cache(path)
    cache[key] = entry
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(cache, fh, indent=1, sort_keys=True)
    os.replace(tmp, path)


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


def _timing_available(backend: str) -> bool:
    """Wall-time only means something when the candidate compiles: XLA's
    block einsum anywhere, the Pallas kernel on a real TPU. Interpret-mode
    Pallas would time the Python interpreter, not the layout."""
    if backend == "xla":
        return True
    if backend == "pallas":
        import jax

        return jax.default_backend() == "tpu"
    return False


def _select_order(graph: CSRGraph, mode: str = "auto", br: int = 8,
                  bc: Optional[int] = None, min_gain: float = 0.1,
                  ) -> tuple[str, CSRGraph, Optional[np.ndarray],
                             Optional[np.ndarray]]:
    """Resolve the reorder mode and return ``(mode, reordered graph, perm,
    inv_perm)`` — the reordered candidates are built once here and the
    winner's graph is reused by the tuner and the lowering pass.

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


def _bf_candidates(f_dim: int) -> tuple[int, ...]:
    """Lane-tile candidates. 0 = the per-call ``feature_tile`` policy (no
    pinned tile, never lane-pads on compiled inners) — always a candidate,
    so pinning a ``bf`` can only win, never regress the default.

    A pinned bf is only a *distinct* program when it changes the padded
    width, i.e. for wide non-multiple dims (f > 128, f % 128 != 0) where
    full 128-lane tiles pad the dim the per-call policy leaves unpadded
    on compiled inners; elsewhere the grid stays 1-wide on this axis
    (no duplicate-program timing).
    """
    cands = {0}
    if f_dim > 128 and f_dim % 128 != 0:
        cands.add(128)
    return tuple(sorted(cands))


def _f_pad_for(f_dim: int, bf: int) -> int:
    from repro.kernels.ops import feature_tile

    if bf == 0:
        return feature_tile(f_dim)[1]
    return -(-f_dim // bf) * bf


def _candidate_grid(graph: CSRGraph, f_dim: int,
                    tiles: Optional[Sequence[tuple[int, int]]],
                    lane_matters: bool = True) -> list:
    """(br, bc, bf) candidates. ``lane_matters=False`` collapses the bf
    axis to the per-call policy (0): the unfused compiled SpMM
    (``matmul_ref``) ignores bf entirely, so sweeping it would time
    byte-identical programs and persist a noise-picked winner."""
    tiles = TILE_CANDIDATES if tiles is None else tuple(tiles)
    bfs = _bf_candidates(f_dim) if lane_matters else (0,)
    grid = []
    for br, bc in tiles:
        if bc > 2 * graph.n_cols and bc > 16:
            continue  # a lane tile twice the matrix is pure padding
        for bf in bfs:
            grid.append((int(br), int(bc), int(bf)))
    return grid or [(8, adaptive_bc(graph.n_cols), 0)]


def _model_scores(graph: CSRGraph, f_dim: int, grid: list) -> list[float]:
    """Block-density / padding cost model (timing-free fallback): modelled
    MAC volume over stored blocks — padded feature lanes included — plus a
    fixed per-block overhead. Linear in exactly the quantities the kernel's
    grid executes: one (br, bc)·(bc, bf) MAC per block per lane tile."""
    scores = []
    for br, bc, bf in grid:
        nb = bsr_block_count(graph, br, bc)
        scores.append(
            nb * (2.0 * br * bc * _f_pad_for(f_dim, bf) + BLOCK_OVERHEAD))
    return scores


def _time_scores(graph: CSRGraph, f_dim: int, backend: str, fused: bool,
                 grid: list, seed: int, interpret: Optional[bool],
                 repeats: int = 7) -> list[float]:
    """Median wall time per candidate, samples interleaved round-robin so
    background-load drift hits every candidate equally (the paired-timing
    discipline of ``bench_fusion``)."""
    global _MEASURE_CALLS
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    rng = np.random.default_rng(seed)
    u = jnp.asarray(
        rng.standard_normal((graph.n_cols, f_dim)).astype(np.float32))
    bias = jnp.zeros((f_dim,), jnp.float32)
    inner = "pallas" if backend == "pallas" else "xla"
    # candidate-independent O(nnz) work hoisted out of the loop; the
    # backward operand only exists on the fused path (its closure carries
    # the VJP pair — the timed region itself is forward-only)
    graph_t = graph.transpose() if fused else None
    thunks = []
    for br, bc, bf in grid:
        fwd = kops.BSRDevice.from_bsr(csr_to_bsr(graph, br=br, bc=bc))
        if fused:
            bwd = kops.BSRDevice.from_bsr(csr_to_bsr(graph_t, br=br, bc=bc))
            fn = kops.build_fused_epilogue(
                fwd, bwd, inner, interpret=interpret, bf=bf or None)
            op = jax.jit(
                lambda v, _fn=fn: _fn(v, bias=bias, activation="relu"))
        elif inner == "pallas":
            from repro.kernels.ops import feature_tile

            op = jax.jit(lambda v, _o=fwd,
                         _bf=bf or feature_tile(f_dim)[0]: _o.matmul(
                             v, _bf, interpret))
        else:
            op = jax.jit(lambda v, _o=fwd: _o.matmul_ref(v))
        thunks.append(op)
    for op in thunks:  # compile outside the timed region
        jax.block_until_ready(op(u))
    samples: list[list[float]] = [[] for _ in thunks]
    for _ in range(repeats):
        for i, op in enumerate(thunks):
            t0 = time.perf_counter()
            jax.block_until_ready(op(u))
            samples[i].append(time.perf_counter() - t0)
    _MEASURE_CALLS += len(grid)
    return [sorted(s)[len(s) // 2] for s in samples]


def plan_layout(
    graph: CSRGraph,
    f_dim: int,
    *,
    backend: str = "xla",
    fused: bool = True,
    order: str = "auto",
    tiles: Optional[Sequence[tuple[int, int]]] = None,
    cache_path: Optional[str] = None,
    measure: Optional[bool] = None,
    interpret: Optional[bool] = None,
    seed: int = 0,
    n_heads: int = 0,
    attention: bool = False,
) -> LayoutPlan:
    """Resolve the full layout for one graph: order + autotuned tile.

    ``f_dim`` is the width the SpMM operand runs at — for GNN aggregation
    that is the model's hidden width (post-transform tensors), which is
    what ``lower`` passes; attention plans pass the per-head width and set
    ``attention=True`` + ``n_heads`` so their cache entries key separately
    from SpMM plans on the same graph. ``measure=None`` auto-detects
    (``_timing_available``); ``False`` forces the cost model, ``True``
    forces timing. The disk cache under ``cache_path`` (default
    ``default_cache_path()``) is keyed by ``graph_fingerprint`` — a hit
    recomputes the permutation (cheap, deterministic) and skips all
    measurement.
    """
    cache_path = default_cache_path() if cache_path is None else cache_path
    key = graph_fingerprint(graph, f_dim, backend, fused, order, tiles,
                            n_heads=n_heads, attention=attention)
    if measure is None:
        measure = _timing_available(backend)
    cached = _load_cache(cache_path).get(key)
    if cached is not None and measure and cached.get("source") == "cost-model":
        # a compiled backend is available now but the entry was modelled
        # (e.g. tuned on a dev box, now on real hardware): upgrade it
        cached = None
    if cached is not None:
        mode = cached["order"]
        g_r = perm = inv = None
        if mode != "none":
            g_r, perm, inv = reorder_graph(graph, mode)
        return LayoutPlan(
            order=mode, br=int(cached["br"]), bc=int(cached["bc"]),
            bf=int(cached.get("bf", 0)), perm=perm, inv_perm=inv,
            source="cache", fingerprint=key,
            n_blocks=int(cached.get("n_blocks", 0)),
            padding_waste=float(cached.get("padding_waste", 0.0)),
            reordered_graph=g_r)

    mode, g_r, perm, inv = _select_order(graph, order)
    lane_matters = fused or backend == "pallas"
    grid = _candidate_grid(g_r, f_dim, tiles, lane_matters)
    if measure:
        scores = _time_scores(g_r, f_dim, backend, fused, grid, seed,
                              interpret)
        source = "measured"
    else:
        scores = _model_scores(g_r, f_dim, grid)
        source = "cost-model"
    br, bc, bf = grid[int(np.argmin(scores))]
    bsr = csr_to_bsr(g_r, br=br, bc=bc)
    plan = LayoutPlan(
        order=mode, br=br, bc=bc, bf=bf, perm=perm, inv_perm=inv,
        source=source, fingerprint=key, n_blocks=bsr.n_blocks,
        padding_waste=bsr.padding_waste(),
        reordered_graph=g_r if mode != "none" else None)
    _store_entry(cache_path, key, {
        "order": mode, "br": br, "bc": bc, "bf": bf, "source": source,
        "n_blocks": plan.n_blocks, "padding_waste": plan.padding_waste,
        "backend": backend, "f_dim": int(f_dim), "fused": bool(fused),
        "attention": bool(attention), "n_heads": int(n_heads),
        "scores": {f"{g[0]}x{g[1]}x{g[2]}": float(s)
                   for g, s in zip(grid, scores)},
    })
    return plan


def cached_layout(graph: CSRGraph, f_dim: int, *, backend: str = "xla",
                  fused: bool = True, n_heads: int = 0,
                  attention: bool = False,
                  cache_path: Optional[str] = None) -> Optional[LayoutPlan]:
    """Pure cache lookup — ``None`` on a miss, never measures. What
    ``bench_fusion`` consults so fused-vs-unfused is compared at the
    autotuned layout when one exists."""
    cache_path = default_cache_path() if cache_path is None else cache_path
    key = graph_fingerprint(graph, f_dim, backend, fused,
                            n_heads=n_heads, attention=attention)
    if key not in _load_cache(cache_path):
        return None
    # measure=False: honour the entry as-is, never trigger the
    # upgrade-on-measure path — this helper must stay lookup-only
    return plan_layout(graph, f_dim, backend=backend, fused=fused,
                       n_heads=n_heads, attention=attention,
                       cache_path=cache_path, measure=False)
