"""Fused neighbour aggregation — the paper's central memory/throughput result.

Two execution paths, matching the paper's evaluation:

* ``gather_scatter_aggregate`` — the PyG/DGL baseline (§II, Eq. 12): gather
  per-edge source features, scale, segment-sum. Materialises the O(|E|·F)
  edge-message tensor the paper identifies as the dominant memory term.
* ``make_fused_aggregate`` — Morphling's fused path (Eq. 13): messages are
  accumulated directly into destination rows by the Pallas SpMM kernels
  (BSR, or CSR row gather where the nonzeros do not fill blocks); peak
  memory is O(|V|·F). The custom VJP backward multiplies by the
  pre-transposed graph (the paper's CSC view, §IV-B.b) so gradients are
  conflict-free by construction.

Aggregator weighting (paper §III-A): ``sum`` = raw A (GIN), ``mean`` = D⁻¹A
(SAGE-mean), ``gcn`` = D^{-1/2}AD^{-1/2} (GCN). ``max`` is not a matmul and
uses the segment path on all backends (documented fall-back, DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.backends import Backend, select_backend
from repro.common.spans import span
from repro.graph.csr import CSRGraph

Aggregation = Literal["sum", "mean", "gcn", "max"]


def _weighted_graph(graph: CSRGraph, aggregation: Aggregation) -> CSRGraph:
    if aggregation in ("sum", "max"):
        return graph
    if aggregation == "mean":
        return graph.row_normalized()
    if aggregation == "gcn":
        return graph.sym_normalized()
    raise ValueError(f"unknown aggregation {aggregation!r}")


# ---------------------------------------------------------------------------
# Baseline: gather-scatter (PyG/DGL execution model)
# ---------------------------------------------------------------------------

def gather_scatter_aggregate(
    src: jax.Array,  # [E] int32
    dst: jax.Array,  # [E] int32
    weights: jax.Array,  # [E] float
    x: jax.Array,  # [N, F]
    n_nodes: int,
    aggregation: Aggregation = "sum",
) -> jax.Array:
    """The O(|E|·F) baseline: materialise per-edge messages, then scatter."""
    messages = x[src]  # <-- the [|E|, F] tensor Morphling eliminates
    if aggregation == "max":
        return jax.ops.segment_max(
            messages, dst, num_segments=n_nodes, indices_are_sorted=False
        )
    messages = messages * weights[:, None]
    return jax.ops.segment_sum(
        messages, dst, num_segments=n_nodes, indices_are_sorted=False
    )


# ---------------------------------------------------------------------------
# Fused: Pallas SpMM (BSR or CSR row gather) with pre-transposed backward
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FusedGraphOp:
    """A graph bound to its fused aggregation operator (per aggregation)."""

    aggregate: Callable[[jax.Array], jax.Array]
    n_nodes: int
    aggregation: Aggregation
    fwd_bytes: int  # sparse-operand footprint, for the memory benchmark
    # baseline (gather-scatter) inputs for comparisons
    src: jax.Array
    dst: jax.Array
    weights: jax.Array
    backend: str = "xla"  # registry name of the backend serving `aggregate`
    # fused-epilogue operator (u, self_term, bias, alpha, activation) ->
    # act(A·u + alpha·self_term + bias); None when the aggregation is not a
    # matmul (max) — the registry's ``spmm_fused_epilogue`` over the pair
    aggregate_epilogue: "Callable | None" = dataclasses.field(
        default=None, repr=False)
    # fused attention operator (z [N, H*Dh], a_src, a_dst, heads) ->
    # [N, H, Dh] — the registry's ``spmm_attention`` over the pair; None
    # when not requested or when the backend has no fused attention
    aggregate_attention: "Callable | None" = dataclasses.field(
        default=None, repr=False)
    # the (A, Aᵀ) operand pair behind `aggregate` — kept for the contract
    # verifier (core/verify.py); None on the segment (max) path where no
    # matmul operand exists unless attention asked for the pair
    fwd_operand: object = dataclasses.field(default=None, repr=False)
    bwd_operand: object = dataclasses.field(default=None, repr=False)

    def baseline(self, x: jax.Array) -> jax.Array:
        return gather_scatter_aggregate(
            self.src, self.dst, self.weights, x, self.n_nodes, self.aggregation
        )


def _operand_pair(backend: Backend, weighted: CSRGraph, br: int,
                  bc: int | None, fmt: str, attention: bool):
    """(A, Aᵀ) on ``backend``, both in the format A takes; ``attention``
    builds them for the fused attention kernels."""
    build = (backend.build_attention_operand if attention
             else backend.build_spmm_operand)
    fwd = build(weighted, br=br, bc=bc, fmt=fmt)
    bwd = build(weighted.transpose(), br=br, bc=bc,
                fmt=getattr(fwd, "format", "bsr"))
    return fwd, bwd


@span("graph_op")
def make_fused_aggregate(
    graph: CSRGraph,
    aggregation: Aggregation = "gcn",
    br: int = 8,
    bc: int | None = None,
    interpret: bool | None = None,
    engine: "str | Backend | None" = None,  # registry name; None = auto-select
    build_attention: bool = False,
    fmt: str = "auto",
) -> FusedGraphOp:
    """One-time lowering: weight the adjacency, build the forward/backward
    operand pair on the selected backend, return a differentiable fused
    operator (``spmm_transposed_vjp`` from the registry). ``bc=None`` takes
    the adaptive fallback width; the lowering pass passes a ``LayoutPlan``'s
    tile.

    ``build_attention`` additionally binds the backend's fused
    ``spmm_attention`` over the same pair (attention ignores the edge
    weights — the nonzero pattern is the adjacency mask, so the weighted
    operands double as attention masks at zero extra memory), in either
    format. ``fmt`` is A's operand format on backends that have two
    (``"bsr"`` | ``"gather"`` | ``"auto"``: by fill); Aᵀ takes A's."""
    backend = select_backend(engine)
    weighted = _weighted_graph(graph, aggregation)
    src_np, dst_np = weighted.edge_list()

    if aggregation == "max":
        # max is not expressible as a matmul: segment path on all backends
        src = jnp.asarray(src_np)
        dst = jnp.asarray(dst_np)
        w = jnp.asarray(weighted.data)
        n = weighted.n_rows

        def agg_max(x):
            return gather_scatter_aggregate(src, dst, w, x, n, "max")

        agg_attention = None
        fwd = bwd = None
        if build_attention:
            fwd, bwd = _operand_pair(backend, weighted, br, bc, fmt,
                                     attention=True)
            agg_attention = backend.spmm_attention(fwd, bwd,
                                                   interpret=interpret)

        return FusedGraphOp(
            aggregate=agg_max, n_nodes=n, aggregation="max",
            fwd_bytes=int(src_np.nbytes + dst_np.nbytes),
            src=src, dst=dst, weights=w, backend=backend.name,
            aggregate_attention=agg_attention,
            fwd_operand=fwd, bwd_operand=bwd,
        )

    # (A, Aᵀ) operands — the paper's CSR-forward / CSC-backward pairing
    fwd, bwd = _operand_pair(backend, weighted, br, bc, fmt,
                             attention=build_attention)
    agg = backend.spmm_transposed_vjp(fwd, bwd, interpret=interpret)
    agg_epilogue = backend.spmm_fused_epilogue(fwd, bwd, interpret=interpret)
    agg_attention = None
    if build_attention:
        agg_attention = backend.spmm_attention(fwd, bwd, interpret=interpret)

    return FusedGraphOp(
        aggregate=agg,
        aggregate_epilogue=agg_epilogue,
        aggregate_attention=agg_attention,
        n_nodes=weighted.n_rows,
        aggregation=aggregation,
        fwd_bytes=int(backend.operand_bytes(fwd) + backend.operand_bytes(bwd)),
        src=jnp.asarray(src_np),
        dst=jnp.asarray(dst_np),
        weights=jnp.asarray(weighted.data),
        backend=backend.name,
        fwd_operand=fwd,
        bwd_operand=bwd,
    )


def fused_aggregate(
    graph: CSRGraph, x: jax.Array, aggregation: Aggregation = "gcn", **kw
) -> jax.Array:
    """One-shot convenience (builds the operator each call — prefer
    ``make_fused_aggregate`` inside training loops)."""
    return make_fused_aggregate(graph, aggregation, **kw).aggregate(x)
