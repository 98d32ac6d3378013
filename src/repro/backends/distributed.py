"""Distributed backend — the MPI-analog op vocabulary, as registry primitives.

The paper's point is that *one* spec lowers onto *every* backend, the MPI
one included. This backend serves the distributed op vocabulary
(``DIST_OP_VOCABULARY`` in ``registry.py``) by *composing* the single-rank
primitives with the halo exchange:

  dist_spmm[_transposed_vjp]     ghost-features in (``halo_exchange``) →
                                 fused local BSR SpMM over the contiguous
                                 [local|ghost] buffer. The VJP multiplies by
                                 the pre-built transposed local operand and
                                 returns ghost gradients to their owners via
                                 ``halo_exchange_transpose`` (the exchange's
                                 custom VJP) — the same CSR-fwd/CSC-bwd
                                 pairing as single-device, plus the reverse
                                 exchange.
  dist_feature_matmul_sparse     Alg-1 sparse input path per rank:
                                 ``w -> X_local @ w`` over pre-built stacked
                                 BSR(X_local)/BSR(X_localᵀ). No exchange —
                                 layer-0 features are rank-resident.
  dist_segment_softmax_aggregate GAT edge-softmax over the local edge list
                                 (src ∈ [local|ghost], dst local). Every
                                 destination's in-edges live on its owning
                                 rank, so the softmax normalisation is
                                 complete locally.
  dist_segment_max               max aggregation on the same segment path.

Local SpMMs dispatch on an *inner* backend — the Pallas kernel on TPU, the
compiled XLA block-gather elsewhere — mirroring ``select_backend``'s
priorities, so the distributed composition rides whichever local lowering
is best for the platform.

All primitives take their per-rank arrays as *arguments* (stacked on a
leading rank axis outside, squeezed inside ``shard_map``) — no closures
over device arrays, per the shard_map SPMD requirements.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.backends.registry import (
    Backend,
    compose_epilogue,
    edge_softmax_aggregate,
)
from repro.core.halo import halo_exchange
from repro.kernels.ops import (
    bsr_spmm_pair,
    derive_last_in_row,
    feature_tile,
    sparse_mha_pair,
)


class DistributedBackend(Backend):
    """Halo-exchange compositions of the local primitives (the MPI analog).

    Never auto-selected for single-device lowering (priority 0);
    ``lower_distributed`` requests it by name.
    """

    name = "distributed"

    def __init__(self, inner: Optional[str] = None):
        self._inner = inner

    def inner(self) -> str:
        """The local-SpMM executor: Pallas on TPU, compiled XLA elsewhere
        (same rationale as ``select_backend``'s priorities)."""
        if self._inner is not None:
            return self._inner
        return "pallas" if jax.default_backend() == "tpu" else "xla"

    def availability(self) -> tuple[bool, str]:
        return True, f"halo-exchange compositions over the {self.inner()} local backend"

    def priority(self) -> int:
        return 0

    # -- distributed op vocabulary ------------------------------------------

    def dist_spmm(self, fwd_arrays, bwd_arrays, u, send_idx, recv_slot,
                  n_local: int, n_ghost: int, axis_name: str, *,
                  shifts=None, interpret: Optional[bool] = None) -> jax.Array:
        """One-shot Y = A_local @ [u | halo(u)]."""
        agg = self.dist_spmm_transposed_vjp(
            fwd_arrays, bwd_arrays, send_idx, recv_slot, n_local, n_ghost,
            axis_name, shifts=shifts, interpret=interpret)
        return agg(u)

    def dist_spmm_transposed_vjp(self, fwd_arrays, bwd_arrays, send_idx,
                                 recv_slot, n_local: int, n_ghost: int,
                                 axis_name: str, *, shifts=None,
                                 interpret: Optional[bool] = None) -> Callable:
        """Differentiable ``u -> A_local @ [u | halo(u)]``. The VJP is the
        paper's backward: dbuf = A_localᵀ @ dY, then ghost-slot gradients
        return to owners through the exchange's transpose."""
        inner = self.inner()

        def agg(u: jax.Array) -> jax.Array:
            ghost = halo_exchange(u, send_idx, recv_slot, n_ghost, axis_name,
                                  shifts)
            buf = jnp.concatenate([u, ghost], axis=0)
            f = buf.shape[-1]
            bf, f_pad = feature_tile(f)
            buf_p = jnp.pad(buf.astype(jnp.float32), ((0, 0), (0, f_pad - f)))
            y = bsr_spmm_pair(fwd_arrays, bwd_arrays, buf_p, n_local, bf,
                              interpret, inner)
            return y[:, :f].astype(u.dtype)

        return agg

    def dist_spmm_split_transposed_vjp(
            self, int_fwd, int_bwd, bnd_fwd, bnd_bwd, send_idx, recv_slot,
            n_local: int, n_ghost: int, axis_name: str, *, shifts=None,
            interpret: Optional[bool] = None) -> Callable:
        """Split-phase form of ``dist_spmm_transposed_vjp`` (DESIGN.md §11).

        The halo exchange is issued first; the *interior* SpMM consumes only
        the local feature rows, so it carries no dataflow edge to the
        collective and XLA's latency-hiding scheduler runs it while the
        ``ppermute`` rounds are in flight. The *boundary* SpMM reads the
        [local | ghost] buffer and fires once ghosts land; both streams
        cover every local block-row (zero blocks on the rows the other
        stream owns), so ``y = y_int + y_bnd`` stitches rows back exactly.

        The backward overlaps the same way by construction: the interior
        pair's transposed SpMM depends only on ``dy``, while only the
        boundary pair's ghost-row cotangents feed the reverse exchange —
        the interior transposed-SpMM runs while the ghost-gradient
        ``ppermute``s drain."""
        inner = self.inner()

        def agg(u: jax.Array) -> jax.Array:
            ghost = halo_exchange(u, send_idx, recv_slot, n_ghost, axis_name,
                                  shifts)
            f = u.shape[-1]
            bf, f_pad = feature_tile(f)
            u_p = jnp.pad(u.astype(jnp.float32), ((0, 0), (0, f_pad - f)))
            # interior pass: local columns only — independent of the exchange
            y_int = bsr_spmm_pair(int_fwd, int_bwd, u_p, n_local, bf,
                                  interpret, inner)
            ghost_p = jnp.pad(ghost.astype(jnp.float32),
                              ((0, 0), (0, f_pad - f)))
            buf_p = jnp.concatenate([u_p, ghost_p], axis=0)
            # boundary pass: waits on ghosts, covers the remaining rows
            y_bnd = bsr_spmm_pair(bnd_fwd, bnd_bwd, buf_p, n_local, bf,
                                  interpret, inner)
            return (y_int + y_bnd)[:, :f].astype(u.dtype)

        return agg

    def dist_spmm_fused_epilogue(self, fwd_arrays, bwd_arrays, send_idx,
                                 recv_slot, n_local: int, n_ghost: int,
                                 axis_name: str, *, shifts=None,
                                 interpret: Optional[bool] = None) -> Callable:
        """Fused-epilogue form of ``dist_spmm_transposed_vjp``: the halo
        exchange + local SpMM composed with the shared epilogue contract
        (``registry.compose_epilogue``). The self-term and bias are
        rank-local (dst rows live on their owning rank), so no extra
        communication — XLA fuses the epilogue into the local SpMM's
        consumer, and the plans bind the same per-layer epilogue record as
        single-device."""
        return compose_epilogue(self.dist_spmm_transposed_vjp(
            fwd_arrays, bwd_arrays, send_idx, recv_slot, n_local, n_ghost,
            axis_name, shifts=shifts, interpret=interpret))

    def dist_spmm_fused_epilogue_split(
            self, int_fwd, int_bwd, bnd_fwd, bnd_bwd, send_idx, recv_slot,
            n_local: int, n_ghost: int, axis_name: str, *, shifts=None,
            interpret: Optional[bool] = None) -> Callable:
        """Fused-epilogue form of the split-phase aggregation: the epilogue
        lands on the stitched ``y_int + y_bnd`` (rank-local rows, no extra
        communication), same contract as ``dist_spmm_fused_epilogue``."""
        return compose_epilogue(self.dist_spmm_split_transposed_vjp(
            int_fwd, int_bwd, bnd_fwd, bnd_bwd, send_idx, recv_slot,
            n_local, n_ghost, axis_name, shifts=shifts, interpret=interpret))

    def dist_feature_matmul_sparse(self, feat_fwd, feat_bwd, n_local: int,
                                   f_pad: int, *,
                                   interpret: Optional[bool] = None) -> Callable:
        """Differentiable ``w -> X_local @ w`` over pre-built per-rank
        BSR(X_local)/BSR(X_localᵀ); dW = X_localᵀ @ dY (then psum'd with the
        rest of the weight gradients — X rows are disjoint across ranks, so
        the psum of per-rank dW *is* the global Xᵀ @ dY)."""
        inner = self.inner()

        def xw(w: jax.Array) -> jax.Array:
            f, h = w.shape
            bf, h_pad = feature_tile(h)
            w_p = jnp.pad(w.astype(jnp.float32),
                          ((0, f_pad - f), (0, h_pad - h)))
            y = bsr_spmm_pair(feat_fwd, feat_bwd, w_p, n_local, bf,
                              interpret, inner)
            return y[:, :h]

        return xw

    def dist_segment_softmax_aggregate(self, z_buf: jax.Array, a_src, a_dst,
                                       src, dst, n_local: int) -> jax.Array:
        """GAT edge-softmax over the local [local|ghost] buffer.

        ``src``/``dst`` are the -1-padded local edge list; invalid edges are
        routed to a dump segment and zero-masked so they contribute nothing
        (value or gradient). Every dst's in-edges are rank-local by
        construction (each edge lives on its destination's owner), so the
        per-destination softmax is exact without further communication —
        one ``valid``-masked call into the shared segment-path definition
        (``registry.edge_softmax_aggregate``).
        """
        return edge_softmax_aggregate(z_buf, a_src, a_dst, src, dst,
                                      n_local, valid=src >= 0)

    def dist_spmm_attention(self, fwd_arrays, bwd_arrays, send_idx,
                            recv_slot, n_local: int, n_ghost: int,
                            axis_name: str, *, shifts=None,
                            interpret: Optional[bool] = None) -> Callable:
        """Fused attention composition: ghost features in via the halo
        exchange, then the fused sparse-MHA pair over the contiguous
        [local | ghost] buffer (destinations = the leading ``n_local`` rows,
        exactly the pair's uniform contract). Ghost-row cotangents return to
        their owners through the exchange's transposed VJP, so the whole
        composition differentiates like single-device.

        ``fwd_arrays``/``bwd_arrays`` are the per-rank 4-tuples of
        BSR(A_local [n_local × n_buf]) / BSR(A_localᵀ); ``last_in_row`` is
        derived from the sorted block-row stream (the stacked operands don't
        carry it).
        """
        inner = self.inner()

        def attention(z, a_src, a_dst, heads):
            ghost = halo_exchange(z, send_idx, recv_slot, n_ghost, axis_name,
                                  shifts)
            buf = jnp.concatenate([z, ghost], axis=0)
            n_buf = buf.shape[0]
            z3 = buf.reshape(n_buf, heads, buf.shape[-1] // heads)
            rows, cols, first, blocks = fwd_arrays
            fwd5 = (rows, cols, first, derive_last_in_row(rows), blocks)
            geom = (n_local, n_buf, n_local, n_buf, n_buf, n_local)
            return sparse_mha_pair(fwd5, bwd_arrays, z3, a_src, a_dst,
                                   geom, 0, interpret, inner)

        return attention

    def dist_spmm_attention_split(
            self, int_fwd, int_bwd, bnd_fwd, bnd_bwd, send_idx, recv_slot,
            n_local: int, n_ghost: int, axis_name: str, *, shifts=None,
            interpret: Optional[bool] = None) -> Callable:
        """Split-phase fused attention (DESIGN.md §11).

        The row split is softmax-exact: a destination's *whole* in-edge set
        lives in exactly one stream (block-row granularity), so each
        stream's online segment softmax is already fully normalised and the
        other stream contributes exact zeros there (empty rows finalise to
        0 in the kernel). The interior MHA consumes only local source rows
        — it runs while the exchange is in flight, and its recompute VJP
        stays off the reverse-exchange path; only the boundary pair's
        ghost-row cotangents ride ``halo_exchange_transpose``."""
        inner = self.inner()

        def attention(z, a_src, a_dst, heads):
            ghost = halo_exchange(z, send_idx, recv_slot, n_ghost, axis_name,
                                  shifts)
            dh = z.shape[-1] // heads
            z3_local = z.reshape(n_local, heads, dh)
            i_rows, i_cols, i_first, i_blocks = int_fwd
            int5 = (i_rows, i_cols, i_first, derive_last_in_row(i_rows),
                    i_blocks)
            geom_int = (n_local,) * 6
            out_int = sparse_mha_pair(int5, int_bwd, z3_local, a_src, a_dst,
                                      geom_int, 0, interpret, inner)
            buf = jnp.concatenate([z, ghost], axis=0)
            n_buf = buf.shape[0]
            z3_buf = buf.reshape(n_buf, heads, dh)
            b_rows, b_cols, b_first, b_blocks = bnd_fwd
            bnd5 = (b_rows, b_cols, b_first, derive_last_in_row(b_rows),
                    b_blocks)
            geom_bnd = (n_local, n_buf, n_local, n_buf, n_buf, n_local)
            out_bnd = sparse_mha_pair(bnd5, bnd_bwd, z3_buf, a_src, a_dst,
                                      geom_bnd, 0, interpret, inner)
            return out_int + out_bnd

        return attention

    def dist_segment_max(self, buf: jax.Array, src, dst,
                         n_local: int) -> jax.Array:
        """Max aggregation over the local edge list. Edge-less rows (padded
        local slots) yield 0 rather than -inf so padding never poisons the
        backward pass with NaNs."""
        valid = (src >= 0)[:, None]
        src_c = jnp.where(src >= 0, src, 0)
        dst_seg = jnp.where(src >= 0, dst, n_local)
        msgs = jnp.where(valid, buf[src_c], -jnp.inf)
        out = jax.ops.segment_max(msgs, dst_seg, num_segments=n_local + 1)
        return jnp.where(jnp.isfinite(out), out, 0.0)[:n_local]


def debug_halo_check(dist, features=None, mesh=None) -> None:
    """Debug-mode runtime guard (DESIGN.md §14): run one real halo
    exchange over ``dist`` and verify the transit checksum — the
    position-and-shift-weighted sum of rows shipped equals the sum of
    rows received into valid ghost slots, psum'd over the mesh. Raises
    ``RuntimeError`` on mismatch (in-transit corruption or a send/recv
    schedule desync between ranks). Needs ``dist.n_ranks`` devices;
    ``features`` defaults to the partitioned feature stack.
    """
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    from repro.core.halo import halo_exchange_debug

    P_ranks = dist.n_ranks
    if len(jax.devices()) < P_ranks:
        raise RuntimeError(
            f"debug_halo_check needs {P_ranks} devices, have "
            f"{len(jax.devices())}")
    if mesh is None:
        mesh = Mesh(np.asarray(jax.devices()[:P_ranks]), axis_names=("data",))
    x = np.asarray(dist.features if features is None else features,
                   dtype=np.float32)

    def body(x_local, send_idx, recv_slot):
        _, shipped, received = halo_exchange_debug(
            x_local[0], send_idx[0], recv_slot[0], dist.n_ghost, "data",
            tuple(dist.live_shifts))
        return shipped[None], received[None]

    shipped, received = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"))))(
            x, np.asarray(dist.send_idx), np.asarray(dist.recv_slot))
    s, r = float(np.asarray(shipped)[0]), float(np.asarray(received)[0])
    # Both sides reduce the same weighted terms in float32 but grouped
    # differently (per-sender vs per-receiver before the psum), so healthy
    # exchanges carry rounding skew that grows with the term count; scale
    # the tolerance with sqrt(n_terms) (RMS rounding growth) and checksum
    # magnitude instead of a fixed 1e-5 that large meshes would trip.
    n_terms = (max(len(dist.live_shifts), 1)
               * int(np.asarray(dist.send_idx).shape[-1]) * x.shape[-1])
    tol = max(64.0 * np.finfo(np.float32).eps * np.sqrt(n_terms)
              * max(abs(s), abs(r)), 1e-5)
    if abs(s - r) > tol:
        raise RuntimeError(
            f"halo-exchange checksum mismatch: shipped {s:.6g} != "
            f"received {r:.6g} — ghost rows were lost, duplicated, or "
            f"corrupted in transit (send/recv schedule desync?)")
