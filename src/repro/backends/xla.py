"""XLA backend — the same BSR layout lowered as block-gather + einsum.

Shares the Pallas backend's one-time CSR -> BSR lowering but executes each
``spmm`` as a compiled XLA program (``kernels/ref.py:bsr_spmm_ref``). This is
the compiled-path stand-in off-TPU: it measures the *layout*, not the Pallas
Python interpreter, so it is the auto-selected backend on CPU/GPU.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.backends.registry import Backend
from repro.graph.csr import CSRGraph, csr_to_bsr
from repro.kernels import ops as kops


class XLABackend(Backend):
    name = "xla"

    def availability(self) -> tuple[bool, str]:
        return True, "compiled block einsum on any XLA platform"

    def priority(self) -> int:
        return 60

    def build_spmm_operand(self, csr: CSRGraph, br: int = 8,
                           bc: Optional[int] = None, fmt: str = "bsr"):
        return kops.BSRDevice.from_bsr(csr_to_bsr(csr, br=br, bc=bc))

    def operand_bytes(self, operand) -> int:
        return int(operand.blocks.nbytes)

    def spmm(self, operand, x: jax.Array, *, interpret: Optional[bool] = None) -> jax.Array:
        # interpret is a Pallas-only concept; the XLA lowering ignores it.
        return operand.matmul_ref(x)

    def spmm_fused_epilogue(self, fwd_operand, bwd_operand, *,
                            interpret: Optional[bool] = None):
        """lax-composed fused epilogue over the same custom VJP as the
        Pallas kernel (``kernels/ref.py:bsr_spmm_fused_ref`` inner): XLA
        fuses the epilogue chain into the block einsum's consumer, and the
        backward applies the saved activation mask as one fused multiply
        before the transposed SpMM — CPU parity and wall-time benchmarks
        measure the identical algebra."""
        return kops.build_fused_epilogue(fwd_operand, bwd_operand, "xla",
                                         interpret=interpret)

    def sparse_mha(self, fwd_operand, bwd_operand, *,
                   interpret: Optional[bool] = None):
        """Fused attention over the same custom VJP as the Pallas kernel,
        with the lax-composed block reference as the executor
        (``kernels/ref.py:bsr_attention_ref`` / ``bsr_attention_bwd_ref``) —
        identical recompute-from-(m, l) algebra, so parity holds across
        inners and plans bind one primitive name."""
        return kops.build_sparse_mha(fwd_operand, bwd_operand, "xla",
                                     interpret=interpret)
