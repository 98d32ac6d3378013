"""Backend primitive registry — the library of backend-specialized primitives.

Morphling's synthesizer lowers a high-level GNN spec onto a *library* of
backend-specialized primitives (§IV: the CPU backend emits per-row AVX FMA
loops, the GPU backend block-per-row CUDA kernels). Here each backend is a
registered object implementing the shared op vocabulary (DESIGN.md §2):

  spmm                       Y = A @ X for a pre-built sparse operand A
  spmm_transposed_vjp        differentiable spmm; dX = Aᵀ @ dY via a
                             pre-built transposed operand (the paper's
                             CSR-forward / CSC-backward pairing, §IV-B.b)
  feature_matmul_sparse      Y = X @ W with X sparse (Alg-1 sparse path);
                             dW = Xᵀ @ dY, dX never formed (X is the input)
  feature_matmul_dense       Y = X @ W on the dense MXU path
  segment_softmax_aggregate  edge-softmax attention aggregation (GAT) on
                             the segment (gather) path — the universal
                             fallback lowering for attention
  sparse_mha                 differentiable fused multi-head edge-softmax
                             attention over a pre-built sparse pair
                             (DESIGN.md §10): Pallas runs the flash-style
                             online segment softmax + aggregation in one
                             VMEM pass with a recompute VJP; XLA serves the
                             same contract via the lax-composed block
                             reference under the same custom VJP; gather
                             lowers to the segment path. ``None`` from a
                             backend means "no fused attention here" and
                             the plan falls back to the segment primitive
  spmm_attention             ``sparse_mha`` in the trainers' calling
                             convention: heads folded into the feature dim
                             ([N, H*Dh] in/out of the per-layer closure)
  spmm_fused_epilogue        differentiable act(A @ X + α·self + bias) with
                             the epilogue fused into the aggregation
                             (DESIGN.md §8): Pallas applies it in VMEM at
                             ``last_in_row`` and folds the activation mask
                             into the transposed-SpMM VJP; every other
                             backend serves the same contract lax-composed
                             (XLA fuses the elementwise chain into the SpMM
                             consumer), so plans bind one primitive name and
                             parity holds across backends

``core/lowering.py`` consumes this registry: it picks a backend (explicit
``engine=...`` or best-available auto-selection), builds operands once, and
records the chosen primitive per layer in the ExecutionPlan.

Backends self-describe availability and a per-platform priority so that the
best one is auto-selected: Pallas on TPU (native kernels), XLA elsewhere
(the Pallas interpreter would execute Python per block — correct but not a
sensible default off-TPU).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph, csr_from_dense

#: the op vocabulary every backend must serve (DESIGN.md §2)
OP_VOCABULARY = (
    "spmm",
    "spmm_transposed_vjp",
    "spmm_fused_epilogue",
    "segment_softmax_aggregate",
    "sparse_mha",
    "spmm_attention",
    "feature_matmul_sparse",
    "feature_matmul_dense",
)

#: the distributed (MPI-analog) op vocabulary (DESIGN.md §6) — served by
#: ``backends/distributed.py`` as halo-exchange compositions of the local
#: primitives; ``lower_distributed`` binds these per layer.
DIST_OP_VOCABULARY = (
    "dist_spmm",
    "dist_spmm_transposed_vjp",
    "dist_spmm_fused_epilogue",
    "dist_segment_softmax_aggregate",
    "dist_spmm_attention",
    "dist_segment_max",
    "dist_feature_matmul_sparse",
)


def apply_epilogue(
    y: jax.Array,
    self_term: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    alpha: Optional[jax.Array] = None,
    activation: str = "none",
) -> jax.Array:
    """The epilogue algebra, lax-composed: act(y + alpha * self_term + bias).

    The shared epilogue contract every ``spmm_fused_epilogue`` implementation
    follows — the Pallas kernel executes the same sequence in VMEM at
    ``last_in_row``; compositions route through here and let XLA fuse the
    elementwise chain into the producing op.
    """
    if self_term is not None:
        a = 1.0 if alpha is None else alpha
        y = y + a * self_term
    if bias is not None:
        y = y + bias
    if activation == "relu":
        y = jax.nn.relu(y)
    elif activation != "none":
        raise ValueError(f"unsupported fused activation {activation!r}")
    return y


def edge_softmax_aggregate(
    z: jax.Array,      # [N, H, Dh] projected features (src index space)
    a_src: jax.Array,  # [H, Dh]
    a_dst: jax.Array,  # [H, Dh]
    src: jax.Array,    # [E]
    dst: jax.Array,    # [E]
    n_out: int,
    valid: Optional[jax.Array] = None,  # [E] bool; None = all edges real
) -> jax.Array:
    """GAT edge-softmax aggregation on the segment (gather) path — the one
    definition every backend's ``segment_softmax_aggregate`` delegates to.

    Numerically hardened: a *true* segment-max subtraction before ``exp``
    (high-degree hubs after degree reordering concentrate large logit sums
    in one segment), with the max treated as a constant shift
    (``stop_gradient`` — softmax is shift-invariant, so no cotangent should
    flow through it) and edge-less segments guarded against the -inf that
    ``segment_max`` yields on empty segments.

    ``valid`` handles -1-padded edge lists (distributed local edges, sampled
    batches): invalid edges are routed to a dump segment past ``n_out`` and
    zero-masked so they contribute nothing, value or gradient.
    """
    if valid is None:
        seg, n_seg = dst, n_out
        src_c, dst_c = src, dst
    else:
        src_c = jnp.where(valid, src, 0)
        dst_c = jnp.where(valid, dst, 0)
        seg = jnp.where(valid, dst, n_out)  # dump slot for padding
        n_seg = n_out + 1
    alpha_src = jnp.einsum("nhd,hd->nh", z, a_src)
    alpha_dst = jnp.einsum("nhd,hd->nh", z, a_dst)
    e = jax.nn.leaky_relu(alpha_src[src_c] + alpha_dst[dst_c], 0.2)  # [E, H]
    e_max = jax.ops.segment_max(e, seg, num_segments=n_seg)
    e_max = jax.lax.stop_gradient(
        jnp.where(jnp.isfinite(e_max), e_max, 0.0))
    ee = jnp.exp(e - e_max[seg])
    if valid is not None:
        ee = jnp.where(valid[:, None], ee, 0.0)
    denom = jax.ops.segment_sum(ee, seg, num_segments=n_seg)
    att = ee / (denom[seg] + 1e-9)
    msgs = z[src_c] * att[..., None]  # [E, H, Dh]
    if valid is not None:
        msgs = jnp.where(valid[:, None, None], msgs, 0.0)
    out = jax.ops.segment_sum(msgs, seg, num_segments=n_seg)
    return out[:n_out] if valid is not None else out


def compose_epilogue(agg: Callable) -> Callable:
    """Wrap an aggregation ``u -> A @ u`` into the fused-epilogue contract
    ``(u, self_term, bias, alpha, activation) -> act(agg(u) + α·self + b)``
    via ``apply_epilogue`` — the one definition of the composition used by
    every backend without a native fused kernel (gather, distributed, the
    mini-batch per-block operands)."""

    def fused(u, self_term=None, bias=None, alpha=None, activation="none"):
        return apply_epilogue(agg(u), self_term, bias, alpha, activation)

    return fused


class Backend:
    """Base class: operand construction + the op vocabulary.

    Subclasses implement ``build_spmm_operand`` / ``spmm`` / ``operand_bytes``
    for their native sparse layout; the differentiable compositions
    (``spmm_transposed_vjp``, ``feature_matmul_sparse``) and the segment-path
    ops are shared.
    """

    name: str = "abstract"

    # -- self-description ----------------------------------------------------

    def availability(self) -> tuple[bool, str]:
        """(usable-now, human-readable reason)."""
        return True, "always available"

    def priority(self) -> int:
        """Higher wins in auto-selection; may depend on the live platform."""
        return 0

    # -- operand construction (one-time lowering, O(nnz)) --------------------

    def build_spmm_operand(self, csr: CSRGraph, br: int = 8,
                           bc: Optional[int] = None, fmt: str = "bsr"):
        """Build this backend's sparse operand at the given BSR tile.
        ``bc=None`` is the default: adaptive to ``n_cols``
        (``graph.csr.adaptive_bc``) so small graphs stop lane-padding; the
        lowering pass passes the ``LayoutPlan``'s tile explicitly.
        ``fmt`` picks the format where a backend has two (Pallas):
        ``"bsr"``, ``"gather"`` (CSR row gather), or ``"auto"`` (by fill,
        ``core/layout.py:operand_format``). Backends with one format
        ignore it."""
        raise NotImplementedError

    def build_attention_operand(self, csr: CSRGraph, br: int = 8,
                                bc: Optional[int] = None, fmt: str = "bsr"):
        """The operand for ``spmm_attention`` (and the SpMMs bound beside
        it), with ``build_spmm_operand``'s arguments; the same operand
        unless a backend's attention kernels read another layout."""
        return self.build_spmm_operand(csr, br=br, bc=bc, fmt=fmt)

    def operand_bytes(self, operand) -> int:
        raise NotImplementedError

    # -- primitives ----------------------------------------------------------

    def spmm(self, operand, x: jax.Array, *, interpret: Optional[bool] = None) -> jax.Array:
        """Y = A @ X (not differentiable through the operand)."""
        raise NotImplementedError

    def feature_matmul_dense(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """Dense MXU path — identical on every backend (XLA GEMM)."""
        return x @ w

    def segment_softmax_aggregate(
        self,
        z: jax.Array,        # [N, H, Dh] projected features
        a_src: jax.Array,    # [H, Dh]
        a_dst: jax.Array,    # [H, Dh]
        src: jax.Array,      # [E]
        dst: jax.Array,      # [E]
        n_nodes: int,
    ) -> jax.Array:
        """GAT edge-softmax aggregation, [N, H, Dh] out, on the segment
        (gather) path — the universal attention lowering (see
        ``edge_softmax_aggregate`` for the hardening notes)."""
        return edge_softmax_aggregate(z, a_src, a_dst, src, dst, n_nodes)

    def sparse_mha(self, fwd_operand, bwd_operand, *,
                   interpret: Optional[bool] = None) -> Optional[Callable]:
        """Differentiable fused multi-head attention ``(z [N,H,Dh], a_src,
        a_dst) -> [n_dst,H,Dh]`` over a pre-built operand pair, or ``None``
        when this backend has no fused attention lowering (the planner then
        binds the segment-path primitive instead)."""
        return None

    def spmm_attention(self, fwd_operand, bwd_operand, *,
                       interpret: Optional[bool] = None) -> Optional[Callable]:
        """``sparse_mha`` in the trainers' calling convention:
        ``(z [N, H*Dh], a_src, a_dst, heads) -> [n_dst, H, Dh]``."""
        mha = self.sparse_mha(fwd_operand, bwd_operand, interpret=interpret)
        if mha is None:
            return None

        def attention(z, a_src, a_dst, heads):
            z3 = z.reshape(z.shape[0], heads, z.shape[-1] // heads)
            return mha(z3, a_src, a_dst)

        return attention

    # -- differentiable compositions ----------------------------------------

    def spmm_transposed_vjp(
        self, fwd_operand, bwd_operand, *, interpret: Optional[bool] = None
    ) -> Callable[[jax.Array], jax.Array]:
        """Differentiable ``x -> A @ x`` whose VJP multiplies by the
        pre-built transposed operand (dX = Aᵀ @ dY) — conflict-free by
        construction, no atomics, no autodiff through the sparse layout."""

        @jax.custom_vjp
        def mm(x):
            return self.spmm(fwd_operand, x, interpret=interpret).astype(x.dtype)

        def mm_fwd(x):
            return mm(x), None

        def mm_bwd(_, dy):
            dx = self.spmm(bwd_operand, dy.astype(jnp.float32), interpret=interpret)
            return (dx.astype(dy.dtype),)

        mm.defvjp(mm_fwd, mm_bwd)
        return mm

    def spmm_fused_epilogue(
        self, fwd_operand, bwd_operand, *, interpret: Optional[bool] = None,
    ) -> Callable:
        """Differentiable ``(u, self_term, bias, alpha, activation) ->
        act(A @ u + alpha * self_term + bias)`` over the pre-built pair.

        Base implementation: the transposed-VJP spmm composed with
        ``apply_epilogue`` — the universal (gather/edge-list) lowering.
        Backends with a native fused kernel (Pallas) or a compiled layout
        that benefits from the shared custom VJP (XLA) override this.
        """
        return compose_epilogue(
            self.spmm_transposed_vjp(fwd_operand, bwd_operand,
                                     interpret=interpret))

    def feature_matmul_sparse(
        self,
        x_np: np.ndarray,
        *,
        br: int = 8,
        bc: Optional[int] = None,
        interpret: Optional[bool] = None,
    ) -> Callable[[jax.Array], jax.Array]:
        """Differentiable ``w -> X @ w`` with X (the feature matrix) held in
        this backend's sparse layout. Forward uses the operand of X, backward
        computes dW = Xᵀ @ dY via the pre-transposed operand. Both O(nnz)
        conversions happen here, once (Alg 1 'DenseToCSR')."""
        x_csr = csr_from_dense(np.asarray(x_np))
        fwd = self.build_spmm_operand(x_csr, br=br, bc=bc, fmt="auto")
        bwd = self.build_spmm_operand(x_csr.transpose(), br=br, bc=bc,
                                      fmt=getattr(fwd, "format", "bsr"))
        return self.spmm_transposed_vjp(fwd, bwd, interpret=interpret)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Backend] = {}


def register_backend(backend: Backend) -> Backend:
    """Register (or replace) a backend under ``backend.name``."""
    _REGISTRY[backend.name] = backend
    return backend


def registered_backends() -> dict[str, Backend]:
    return dict(_REGISTRY)


def available_backends() -> dict[str, tuple[bool, str]]:
    """name -> (usable-now, reason) for every registered backend."""
    return {name: b.availability() for name, b in _REGISTRY.items()}


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def select_backend(preference: "str | Backend | None" = None) -> Backend:
    """Resolve an ``engine=`` preference to a Backend.

    * a Backend instance passes through;
    * a name selects that backend explicitly (legacy ``engine="xla"`` call
      sites land here);
    * ``None`` / ``"auto"`` picks the available backend with the highest
      priority on the current platform (Pallas on TPU, XLA elsewhere).
    """
    if isinstance(preference, Backend):
        return preference
    if preference is not None and preference != "auto":
        return get_backend(preference)
    candidates = [b for b in _REGISTRY.values() if b.availability()[0]]
    if not candidates:
        raise RuntimeError("no backend available (none registered?)")
    return max(candidates, key=lambda b: b.priority())
