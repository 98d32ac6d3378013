"""Gather-scatter backend — the PyG/DGL execution model as a registered peer.

Edge-list operands, per-edge gather + segment-sum (paper §II, Eq. 12). It
materialises the O(|E|·F) edge-message tensor the fused backends avoid, so
its priority is lowest; it exists as the measured baseline and as the
universal fall-back (no layout conversion, works for any op).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.backends.registry import Backend, edge_softmax_aggregate
from repro.graph.csr import CSRGraph


@dataclasses.dataclass
class EdgeListOperand:
    """Device-resident COO view: Y = A @ X as gather/scale/segment-sum."""

    src: jax.Array      # [E] int32 — column index (gather rows of X)
    dst: jax.Array      # [E] int32 — output row
    weights: jax.Array  # [E] float32
    n_rows: int


class GatherBackend(Backend):
    name = "gather"

    def availability(self) -> tuple[bool, str]:
        return True, "segment-sum baseline on any platform"

    def priority(self) -> int:
        return 10

    def build_spmm_operand(self, csr: CSRGraph, br: int = 8, bc=None,
                           fmt="bsr"):
        # br/bc/fmt are BSR hints; the edge-list layout has no blocks
        src, dst = csr.edge_list()
        return EdgeListOperand(
            src=jnp.asarray(src), dst=jnp.asarray(dst),
            weights=jnp.asarray(csr.data), n_rows=csr.n_rows,
        )

    def operand_bytes(self, operand) -> int:
        return int(operand.src.nbytes + operand.dst.nbytes + operand.weights.nbytes)

    def spmm(self, operand, x: jax.Array, *, interpret: Optional[bool] = None) -> jax.Array:
        msgs = x[operand.src] * operand.weights[:, None]  # the [E, F] tensor
        return jax.ops.segment_sum(msgs, operand.dst, num_segments=operand.n_rows)

    def sparse_mha(self, fwd_operand, bwd_operand, *,
                   interpret: Optional[bool] = None):
        """Attention on this backend *is* the gather path — serve the
        ``sparse_mha`` contract over the edge-list operand so the vocabulary
        stays complete (and the fused/gather benchmark has a peer to call),
        while the plans that bind ``gather`` report the unfused primitive."""
        op = fwd_operand

        def mha(z, a_src, a_dst):
            return edge_softmax_aggregate(z, a_src, a_dst, op.src, op.dst,
                                          op.n_rows)

        return mha
