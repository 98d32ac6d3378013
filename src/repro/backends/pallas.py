"""Pallas (TPU) backend — BSR or CSR operands, each with its own kernels.

Two operand formats, chosen per graph by fill (``core/layout.py``
``operand_format``): where nonzeros cluster into blocks, CSR -> BSR once
(the MXU consumes dense (BR, BC) tiles, the DMA engine moves whole blocks)
and every ``spmm`` runs ``kernels/bsr_spmm.py``; where they do not, the
operand stays CSR and every ``spmm`` sums one source row per nonzero,
read from a VMEM window of the source (``kernels/csr_gather_spmm.py``);
attention follows the same rule, with its own kernels for each format
(``kernels/bsr_attention.py``, ``kernels/csr_gather_attention.py``), over
a row-ordered CSR (``build_attention_operand``). Off-TPU
the kernels run in Pallas interpret mode — numerically exact but slow,
which is why ``priority()`` drops off-TPU and auto-selection prefers the
XLA backend there.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.backends.registry import Backend
from repro.graph.csr import CSRGraph, adaptive_bc, bsr_block_count, csr_to_bsr
from repro.kernels import ops as kops


class PallasBackend(Backend):
    name = "pallas"

    def availability(self) -> tuple[bool, str]:
        if jax.default_backend() == "tpu":
            return True, "native Pallas kernels on TPU"
        return True, "interpret mode (exact, but Python-speed off-TPU)"

    def priority(self) -> int:
        return 100 if jax.default_backend() == "tpu" else 5

    def build_spmm_operand(self, csr: CSRGraph, br: int = 8,
                           bc: Optional[int] = None, fmt: str = "bsr"):
        # the SpMM reads CSR by tile, sorted by column within each tile
        return self._operand(csr, br, bc, fmt, column_tile=kops.WINDOW_TILE)

    def build_attention_operand(self, csr: CSRGraph, br: int = 8,
                                bc: Optional[int] = None, fmt: str = "bsr"):
        # the gather-attention kernels read CSR row by row
        return self._operand(csr, br, bc, fmt, column_tile=None)

    @staticmethod
    def _operand(csr, br, bc, fmt, column_tile):
        if fmt == "auto":
            from repro.core.layout import operand_format

            bc_eff = adaptive_bc(csr.n_cols) if bc is None else bc
            fmt = operand_format(csr.nnz, bsr_block_count(csr, br, bc_eff))
        if fmt == "gather":
            return kops.CSRDevice.from_csr(csr, column_tile=column_tile)
        return kops.BSRDevice.from_bsr(csr_to_bsr(csr, br=br, bc=bc))

    def operand_bytes(self, operand) -> int:
        if operand.format == "gather":
            return operand.nbytes
        return int(operand.blocks.nbytes)

    def spmm(self, operand, x: jax.Array, *, interpret: Optional[bool] = None) -> jax.Array:
        return operand.matmul(x, interpret=interpret)

    def spmm_fused_epilogue(self, fwd_operand, bwd_operand, *,
                            interpret: Optional[bool] = None):
        """The native fused kernels: epilogue applied in VMEM where a row
        completes; the VJP folds the activation mask into the transposed
        SpMM (``bsr_spmm_masked``, ``csr_gather_spmm_masked``). BSR
        operands take the per-call ``feature_tile`` lane tile; CSR operands
        gather whole rows and take no lane tile."""
        if fwd_operand.format == "gather":
            return kops.build_gather_fused_epilogue(fwd_operand, bwd_operand,
                                                    interpret=interpret)
        return kops.build_fused_epilogue(fwd_operand, bwd_operand, "pallas",
                                         interpret=interpret)

    def sparse_mha(self, fwd_operand, bwd_operand, *,
                   interpret: Optional[bool] = None):
        """The native fused attention kernels (DESIGN.md §10): online
        segment softmax + aggregation in one VMEM pass, recompute VJP from
        the saved per-row (max, denominator) stats, over BSR blocks or CSR
        row gathers (``csr_gather_attention``)."""
        if fwd_operand.format == "gather":
            return kops.build_gather_mha(fwd_operand, bwd_operand,
                                         interpret=interpret)
        return kops.build_sparse_mha(fwd_operand, bwd_operand, "pallas",
                                     interpret=interpret)
