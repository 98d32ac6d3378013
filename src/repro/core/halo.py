"""Distributed GNN runtime — the JAX/TPU analog of the paper's MPI backend.

Paper §IV-E2 maps as follows:

* **G2L contiguous layout**: each rank's feature buffer is
  ``[local_nodes | ghost_nodes]`` — local slots [0, n_local) followed by
  ghosts, so kernels see dense index ranges (identical to the paper's
  layout enabling AVX on local tensors; here it enables one BSR over the
  concatenated buffer).
* **Asynchronous halo exchange** (MPI_Isend/Irecv): ``ppermute`` rounds over
  ring shifts. XLA's latency-hiding scheduler overlaps the collective DMA
  with independent compute, which is the paper's parallel-pack /
  non-blocking-issue / wait-free-unpack protocol expressed declaratively.
* **BSP step**: one jitted shard_map program per training step; the jit
  boundary is the barrier.

Everything here is SPMD-uniform: per-rank structures are padded to fleet
maxima and stacked on a leading rank axis, which is what makes the same
program runnable on 8 CPU host-devices in tests and 512 TPU chips in the
dry-run.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partitioner import PartitionResult, build_local_views
from repro.graph.csr import CSRGraph, csr_from_edges, csr_to_bsr


def _ceil_to(x: int, m: int) -> int:
    return max(-(-x // m) * m, m)


@dataclasses.dataclass
class DistributedGraph:
    """Host-built SPMD plan: stacked per-rank BSR + halo schedules.

    When built with ``split_phase=True`` (the default) the forward operand
    is additionally split per rank into an *interior* operand — block-rows
    whose columns are all local, runnable while the halo exchange is still
    in flight — and a *boundary* operand — block-rows that may read ghost
    columns — each with its transpose for the overlapped backward
    (DESIGN.md §11). Both split streams cover every local block-row with
    explicit zero blocks (the Pallas kernel's row-coverage contract), so
    ``y = y_interior + y_boundary`` stitches rows back exactly.
    """

    n_ranks: int
    n_local: int  # padded, uniform across ranks, multiple of 128
    n_ghost: int  # padded, uniform, multiple of 128
    max_send: int
    # stacked fwd BSR of local graphs: rows=[local], cols=[local|ghost]
    fwd: dict  # rows/cols/first [P, B], blocks [P, B, br, bc]
    bwd: dict  # BSR of transpose: rows=[local|ghost], cols=[local]
    send_idx: np.ndarray  # [P, P-1, max_send] local idx to send at shift s (-1 pad)
    recv_slot: np.ndarray  # [P, P-1, max_send] ghost slot (0-based in ghost region)
    features: np.ndarray  # [P, n_local, F]
    labels: np.ndarray  # [P, n_local]
    mask: np.ndarray  # [P, n_local] bool (False on padding)
    br: int
    bc: int
    # per-rank unpadded node counts — the lowering pass's per-rank Alg-1
    # statistics are computed over these rows only (padding is all-zero)
    n_valid: Optional[np.ndarray] = None  # [P] int32
    # stacked local edge lists (src indexes [local|ghost] slots, dst local
    # rows; -1 padded) — the segment path for GAT edge-softmax / max agg
    edge_src: Optional[np.ndarray] = None  # [P, max_edges] int32
    edge_dst: Optional[np.ndarray] = None  # [P, max_edges] int32
    aggregation: str = "sum"  # weighting applied to the local adjacencies
    # within-rank node order the local views were built with ("none" |
    # "degree" | "rcm") — recorded so lower_distributed's LayoutPlan can
    # say what layout the stacked operands carry
    reorder: str = "none"
    # -- split-phase operands (None when built with split_phase=False) -----
    # interior: rows=[local], cols=[local] only; boundary: rows=[local],
    # cols=[local|ghost]. Each stream covers all local block-rows.
    fwd_interior: Optional[dict] = None
    bwd_interior: Optional[dict] = None  # transpose: [local] x [local]
    fwd_boundary: Optional[dict] = None
    bwd_boundary: Optional[dict] = None  # transpose: [local|ghost] x [local]
    n_interior: Optional[np.ndarray] = None  # [P] leading interior local slots
    interior_blocks: Optional[np.ndarray] = None  # [P] per-rank stream length
    boundary_blocks: Optional[np.ndarray] = None  # [P]
    # ring shifts with at least one live (send_idx >= 0) entry on any rank;
    # a ppermute is collective, so the set is any-over-ranks (host-computed)
    live_shifts: Optional[tuple] = None

    def __post_init__(self):
        split = [self.fwd_interior, self.bwd_interior,
                 self.fwd_boundary, self.bwd_boundary]
        if any(s is not None for s in split):
            if any(s is None for s in split):
                raise ValueError(
                    "split-phase operands must be constructed together "
                    "(fwd/bwd x interior/boundary)")
            nrb = self.n_local // self.br
            ncb_local = self.n_local // self.bc
            if int(self.fwd_interior["cols"].max(initial=0)) >= ncb_local:
                raise ValueError(
                    "interior operand references a ghost column: "
                    f"max block-col {int(self.fwd_interior['cols'].max())} "
                    f">= {ncb_local}")
            if int(self.fwd_interior["rows"].max(initial=0)) >= nrb:
                raise ValueError("interior operand row outside local region")
            if int(self.fwd_boundary["rows"].max(initial=0)) >= nrb:
                raise ValueError("boundary operand row outside local region")
            if (self.n_interior is not None and self.n_valid is not None
                    and bool((np.asarray(self.n_interior)
                              > np.asarray(self.n_valid)).any())):
                raise ValueError("n_interior exceeds per-rank valid rows")
        if self.live_shifts is not None:
            bad = [s for s in self.live_shifts
                   if not 1 <= int(s) < max(self.n_ranks, 2)]
            if bad:
                raise ValueError(f"live shifts {bad} outside [1, P)")


def stack_bsr_matrices(bsrs, br: int, bc: int) -> dict:
    """Stack per-rank BSR matrices on a leading rank axis, padded to the
    fleet-max block count (zero blocks accumulate 0 into the last row)."""
    P = len(bsrs)
    n_blocks = max(b.n_blocks for b in bsrs)
    rows = np.zeros((P, n_blocks), dtype=np.int32)
    cols = np.zeros((P, n_blocks), dtype=np.int32)
    first = np.zeros((P, n_blocks), dtype=np.int32)
    blocks = np.zeros((P, n_blocks, br, bc), dtype=np.float32)
    for p, b in enumerate(bsrs):
        k = b.n_blocks
        rows[p, :k] = b.block_rows
        cols[p, :k] = b.block_cols
        first[p, :k] = b.first_in_row
        blocks[p, :k] = b.blocks
        if k < n_blocks:  # zero-block padding accumulates 0 into last row
            rows[p, k:] = b.block_rows[-1] if k else 0
            cols[p, k:] = 0
    return {"rows": rows, "cols": cols, "first": first, "blocks": blocks}


def build_distributed_graph(
    graph: CSRGraph,
    features: np.ndarray,
    labels: np.ndarray,
    train_mask: np.ndarray,
    partition: PartitionResult,
    br: int = 8,
    bc: int = 128,
    aggregation: str = "sum",
    reorder: str = "none",
    split_phase: bool = True,
) -> DistributedGraph:
    """Build the SPMD plan. ``aggregation`` weights the *global* adjacency
    (``"sum"`` keeps it raw — pass pre-weighted graphs that way) before the
    per-rank views are cut, so degree normalisation sees global degrees.
    ``reorder`` renumbers each rank's local block (degree / RCM on the
    rank's induced subgraph) before the per-rank BSR is materialised —
    denser local blocks, no semantic change (the halo schedule and the
    feature/label/mask stacking all follow the permuted ``global_ids``).

    ``split_phase`` additionally splits each rank's forward operand by
    block-row into interior (all columns local) / boundary (may read ghost
    columns) streams, with transposes, and computes the live ring-shift set
    — the operands of the overlapped runtime (DESIGN.md §11). The bulk
    ``fwd``/``bwd`` pair is always built; ``split_phase=False`` is the
    fallback that skips the extra streams."""
    if aggregation != "sum":
        from repro.core.aggregate import _weighted_graph

        graph = _weighted_graph(graph, aggregation)
    P = partition.k
    views = build_local_views(graph, partition.assignment, P, reorder=reorder)
    n_local = _ceil_to(max(v.n_local for v in views), bc)
    n_ghost = _ceil_to(max(max(v.n_ghost for v in views), 1), bc)

    f_dim = features.shape[1]
    feats = np.zeros((P, n_local, f_dim), dtype=np.float32)
    labs = np.zeros((P, n_local), dtype=np.int32)
    mask = np.zeros((P, n_local), dtype=bool)

    # -- halo schedule: for ring shift s, rank r sends to (r+s)%P ----------
    # pair_nodes[(o, r)] = ordered list of global ids owner o sends to r
    pair_nodes: dict[tuple[int, int], list[int]] = {}
    for v in views:
        for slot, (gid, owner) in enumerate(
            zip(v.global_ids[v.n_local:], v.ghost_owner)
        ):
            pair_nodes.setdefault((int(owner), v.rank), []).append(int(gid))
    max_send = max((len(v) for v in pair_nodes.values()), default=1)
    send_idx = np.full((P, P - 1, max_send), -1, dtype=np.int32)
    recv_slot = np.full((P, P - 1, max_send), -1, dtype=np.int32)

    g2l_local = []  # global -> local index among owned nodes, per rank
    for v in views:
        g2l_local.append({int(g): i for i, g in enumerate(v.global_ids[: v.n_local])})
    ghost_slot_of = []  # global -> slot within ghost region, per rank
    for v in views:
        ghost_slot_of.append(
            {int(g): i for i, g in enumerate(v.global_ids[v.n_local:])}
        )

    for (o, r), nodes in pair_nodes.items():
        s = (r - o) % P
        assert s != 0
        for j, gid in enumerate(nodes):
            send_idx[o, s - 1, j] = g2l_local[o][gid]
            recv_slot[r, s - 1, j] = ghost_slot_of[r][gid]

    # -- per-rank local BSR (padded coords) + local COO edge lists ---------
    fwd_stack, bwd_stack = [], []
    int_fwd, int_bwd, bnd_fwd, bnd_bwd = [], [], [], []
    edge_lists: list[tuple[np.ndarray, np.ndarray]] = []
    for v in views:
        # remap ghost columns from (v.n_local + j) to (n_local + j)
        src, dst = v.local_graph.edge_list()
        src = src.astype(np.int64)
        dst = dst.astype(np.int64)
        ghost_sel = src >= v.n_local
        src[ghost_sel] = src[ghost_sel] - v.n_local + n_local
        lg = csr_from_edges(
            src=src, dst=dst, n_rows=n_local, n_cols=n_local + n_ghost,
            data=v.local_graph.data, dedupe=False,
        )
        fwd_stack.append(csr_to_bsr(lg, br=br, bc=bc))
        bwd_stack.append(csr_to_bsr(lg.transpose(), br=br, bc=bc))
        edge_lists.append((src.astype(np.int32), dst.astype(np.int32)))
        feats[v.rank, : v.n_local] = features[v.global_ids[: v.n_local]]
        labs[v.rank, : v.n_local] = labels[v.global_ids[: v.n_local]]
        mask[v.rank, : v.n_local] = train_mask[v.global_ids[: v.n_local]]

        if split_phase:
            # block-row granularity split: a block-row is boundary iff any
            # of its edges reads a ghost column. The [interior | boundary]
            # node order of build_local_views confines mixing to at most
            # the one block-row straddling the segment boundary.
            nrb = n_local // br
            boundary_row = np.zeros(nrb, dtype=bool)
            boundary_row[(dst[ghost_sel] // br)] = True
            eb = boundary_row[dst // br]
            ipair, bpair = _split_pair(
                src, dst, np.asarray(v.local_graph.data), eb,
                n_local, n_ghost, br, bc)
            int_fwd.append(ipair[0])
            int_bwd.append(ipair[1])
            bnd_fwd.append(bpair[0])
            bnd_bwd.append(bpair[1])

    max_edges = max(max(len(s) for s, _ in edge_lists), 1)
    edge_src = np.full((P, max_edges), -1, dtype=np.int32)
    edge_dst = np.full((P, max_edges), -1, dtype=np.int32)
    for p, (s, d) in enumerate(edge_lists):
        edge_src[p, : len(s)] = s
        edge_dst[p, : len(d)] = d

    live_shifts = tuple(
        int(s) for s in range(1, P) if bool((send_idx[:, s - 1] >= 0).any()))

    split_kw = {}
    if split_phase:
        split_kw = dict(
            fwd_interior=stack_bsr_matrices(int_fwd, br, bc),
            bwd_interior=stack_bsr_matrices(int_bwd, br, bc),
            fwd_boundary=stack_bsr_matrices(bnd_fwd, br, bc),
            bwd_boundary=stack_bsr_matrices(bnd_bwd, br, bc),
            n_interior=np.asarray([v.n_interior for v in views],
                                  dtype=np.int32),
            interior_blocks=np.asarray([b.n_blocks for b in int_fwd],
                                       dtype=np.int64),
            boundary_blocks=np.asarray([b.n_blocks for b in bnd_fwd],
                                       dtype=np.int64),
        )

    return DistributedGraph(
        n_ranks=P, n_local=n_local, n_ghost=n_ghost, max_send=max_send,
        fwd=stack_bsr_matrices(fwd_stack, br, bc),
        bwd=stack_bsr_matrices(bwd_stack, br, bc),
        send_idx=send_idx, recv_slot=recv_slot,
        features=feats, labels=labs, mask=mask, br=br, bc=bc,
        n_valid=np.asarray([v.n_local for v in views], dtype=np.int32),
        edge_src=edge_src, edge_dst=edge_dst, aggregation=aggregation,
        reorder=reorder, live_shifts=live_shifts, **split_kw,
    )


def _empty_csr(n_rows: int, n_cols: int) -> CSRGraph:
    return CSRGraph(
        indptr=np.zeros(n_rows + 1, dtype=np.int64),
        indices=np.zeros(0, dtype=np.int32),
        data=np.zeros(0, dtype=np.float32),
        n_rows=n_rows, n_cols=n_cols,
    )


def _split_pair(src, dst, data, boundary_edge, n_local, n_ghost, br, bc):
    """Cut one rank's edge set into interior / boundary CSR→BSR pairs.

    Both streams span all ``n_local`` rows — ``csr_to_bsr`` inserts an
    explicit zero block for every uncovered block-row (the kernel's
    row-coverage contract), so the two partial SpMMs add back to the bulk
    result row-exactly. The interior operand's column space is local-only
    (``n_cols = n_local``): its SpMM consumes no ghost slot and therefore
    never waits on the halo exchange."""
    def one(sel, n_cols):
        if sel.any():
            csr = csr_from_edges(
                src=src[sel], dst=dst[sel], n_rows=n_local, n_cols=n_cols,
                data=data[sel], dedupe=False)
        else:
            csr = _empty_csr(n_local, n_cols)
        return (csr_to_bsr(csr, br=br, bc=bc),
                csr_to_bsr(csr.transpose(), br=br, bc=bc))

    return one(~boundary_edge, n_local), one(boundary_edge, n_local + n_ghost)


# ---------------------------------------------------------------------------
# In-step primitives (run inside shard_map, per-rank views)
# ---------------------------------------------------------------------------

def _norm_shifts(shifts) -> Optional[tuple]:
    """Normalise a live-shift set to a hashable tuple (None = all P-1)."""
    if shifts is None:
        return None
    return tuple(int(s) for s in shifts)


def _halo_exchange_impl(
    x_local: jax.Array,  # [n_local, F]
    send_idx: jax.Array,  # [P-1, max_send]
    recv_slot: jax.Array,  # [P-1, max_send]
    n_ghost: int,
    axis_name: str,
    shifts: Optional[tuple] = None,
) -> jax.Array:
    """Raw exchange body — a linear map of ``x_local`` (gather, ppermute,
    scatter-add are all linear), kept un-wrapped so tests can take its
    ``jax.linear_transpose`` and compare against ``halo_exchange_transpose``.

    ``shifts`` restricts the unrolled ring shifts to the given live set
    (host-computed in ``build_distributed_graph``); a shift whose
    ``send_idx`` row is all -1 on *every* rank exchanges nothing, so
    skipping it is exact. ``None`` issues all P-1 shifts."""
    P = jax.lax.axis_size(axis_name)
    f = x_local.shape[-1]
    ghost = jnp.zeros((n_ghost, f), dtype=x_local.dtype)
    for s in (range(1, P) if shifts is None else shifts):
        idx = send_idx[s - 1]
        valid_send = (idx >= 0)[:, None]
        payload = jnp.where(valid_send, x_local[jnp.clip(idx, 0), :], 0)
        perm = [(r, (r + s) % P) for r in range(P)]
        received = jax.lax.ppermute(payload, axis_name, perm)
        slot = recv_slot[s - 1]
        valid_recv = (slot >= 0)[:, None]
        ghost = ghost.at[jnp.clip(slot, 0)].add(
            jnp.where(valid_recv, received, 0)
        )
    return ghost


def halo_exchange_debug(
    x_local: jax.Array,  # [n_local, F]
    send_idx: jax.Array,  # [P-1, max_send]
    recv_slot: jax.Array,  # [P-1, max_send]
    n_ghost: int,
    axis_name: str,
    shifts: Optional[tuple] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``_halo_exchange_impl`` plus a transit checksum (DESIGN.md §14).

    Returns ``(ghost, shipped, received)`` where the two scalars are
    position-and-shift-weighted sums of the valid payload rows, psum'd
    over the mesh. ``ppermute`` preserves send-buffer position end to
    end, so the weighting detects payload corruption, a valid-mask
    (send/recv schedule) mismatch, and shift desync — a plain sum would
    miss the row-for-row swaps the position weights catch. It does *not*
    detect misrouting among valid ghost slots (a corrupted ``recv_slot``
    value routing a row to a different valid slot leaves both sums
    equal, since ``received`` is summed before the ghost scatter); that
    class is covered by the static ``halo.slot_unique`` /
    ``halo.schedule_paired`` checks in ``core/verify.py``. The host-side
    ``debug_halo_check`` turns a nonzero difference into an error.
    """
    P = jax.lax.axis_size(axis_name)
    f = x_local.shape[-1]
    ghost = jnp.zeros((n_ghost, f), dtype=x_local.dtype)
    shipped = jnp.zeros((), jnp.float32)
    received_sum = jnp.zeros((), jnp.float32)
    for s in (range(1, P) if shifts is None else shifts):
        idx = send_idx[s - 1]
        valid_send = (idx >= 0)[:, None]
        payload = jnp.where(valid_send, x_local[jnp.clip(idx, 0), :], 0)
        w = (jnp.arange(payload.shape[0], dtype=jnp.float32) + 1.0) * float(s)
        shipped = shipped + (
            payload.astype(jnp.float32).sum(axis=-1) * w).sum()
        perm = [(r, (r + s) % P) for r in range(P)]
        received = jax.lax.ppermute(payload, axis_name, perm)
        slot = recv_slot[s - 1]
        valid_recv = (slot >= 0)[:, None]
        kept = jnp.where(valid_recv, received, 0)
        received_sum = received_sum + (
            kept.astype(jnp.float32).sum(axis=-1) * w).sum()
        ghost = ghost.at[jnp.clip(slot, 0)].add(kept)
    shipped = jax.lax.psum(shipped, axis_name)
    received_sum = jax.lax.psum(received_sum, axis_name)
    return ghost, shipped, received_sum


def halo_exchange_transpose(
    ghost: jax.Array,  # [n_ghost, F] ghost-slot cotangents
    send_idx: jax.Array,  # [P-1, max_send]
    recv_slot: jax.Array,  # [P-1, max_send]
    n_local: int,
    axis_name: str,
    shifts: Optional[tuple] = None,
) -> jax.Array:
    """The linear transpose of ``_halo_exchange_impl``: ghost-slot values
    return to their owning ranks. Each shift transposes gather/ppermute/
    scatter into scatter/reverse-ppermute/gather — the reverse exchange the
    backward pass issues for ghost gradients. ``shifts`` mirrors the
    forward's live-shift set (a dead forward shift is dead in reverse)."""
    P = jax.lax.axis_size(axis_name)
    out = jnp.zeros((n_local, ghost.shape[-1]), dtype=ghost.dtype)
    for s in (range(1, P) if shifts is None else shifts):
        slot = recv_slot[s - 1]
        valid = (slot >= 0)[:, None]
        payload = jnp.where(valid, ghost[jnp.clip(slot, 0), :], 0)
        perm = [((r + s) % P, r) for r in range(P)]  # reverse direction
        received = jax.lax.ppermute(payload, axis_name, perm)
        idx = send_idx[s - 1]
        valid_r = (idx >= 0)[:, None]
        out = out.at[jnp.clip(idx, 0)].add(jnp.where(valid_r, received, 0))
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _halo_exchange_vjp(
    x_local: jax.Array,  # [n_local, F]
    send_idx: jax.Array,  # [P-1, max_send]
    recv_slot: jax.Array,  # [P-1, max_send]
    n_ghost: int,
    axis_name: str,
    shifts: Optional[tuple],
) -> jax.Array:
    return _halo_exchange_impl(
        x_local, send_idx, recv_slot, n_ghost, axis_name, shifts)


def _halo_fwd(x_local, send_idx, recv_slot, n_ghost, axis_name, shifts):
    ghost = _halo_exchange_impl(
        x_local, send_idx, recv_slot, n_ghost, axis_name, shifts)
    return ghost, (send_idx, recv_slot, x_local.shape[0])


def _halo_bwd(n_ghost, axis_name, shifts, res, g):
    send_idx, recv_slot, n_local = res
    dx = halo_exchange_transpose(
        g, send_idx, recv_slot, n_local, axis_name, shifts)
    # integer schedule arrays carry symbolic-zero (float0) cotangents
    zero = lambda a: np.zeros(a.shape, dtype=jax.dtypes.float0)
    return dx, zero(send_idx), zero(recv_slot)


_halo_exchange_vjp.defvjp(_halo_fwd, _halo_bwd)


def halo_exchange(
    x_local: jax.Array,  # [n_local, F]
    send_idx: jax.Array,  # [P-1, max_send]
    recv_slot: jax.Array,  # [P-1, max_send]
    n_ghost: int,
    axis_name: str,
    shifts=None,
) -> jax.Array:
    """Ghost-feature exchange: returns [n_ghost, F].

    Each ring shift is: pack (gather) -> ppermute -> unpack (scatter). The
    packs of shift s+1 are independent of the unpacks of shift s, so XLA
    overlaps communication with the next round's packing — the paper's
    split-phase protocol. The custom VJP pins the backward pass to
    ``halo_exchange_transpose`` (the explicit reverse schedule), so ghost
    gradients return to owners without autodiff re-deriving the exchange.

    ``shifts`` unrolls only the given live ring shifts (see
    ``DistributedGraph.live_shifts``); ``None`` issues all P-1.
    """
    return _halo_exchange_vjp(
        x_local, send_idx, recv_slot, n_ghost, axis_name,
        _norm_shifts(shifts))


class GhostBufferRing:
    """Static double-buffer schedule for per-layer ghost buffers.

    Under XLA's SSA program form there is no mutable buffer to rotate —
    each layer's ghost tensor is a fresh value. What the ring encodes is
    the *allocation contract*: consecutive layers draw from distinct slots
    of an ``n_slots``-deep pool, so layer k+1's exchange never has a
    write-after-read hazard on layer k's ghost value and buffer assignment
    is free to keep both live while the collectives overlap. The trainer
    acquires one slot per layer at trace time; ``schedule()`` exposes the
    rotation for plan dumps and tests (DESIGN.md §11).
    """

    def __init__(self, n_slots: int = 2):
        if n_slots < 2:
            raise ValueError("double buffering needs at least 2 slots")
        self.n_slots = int(n_slots)
        self._schedule: list[int] = []

    def acquire(self, layer: int) -> int:
        slot = int(layer) % self.n_slots
        if self._schedule and self._schedule[-1] == slot:
            raise ValueError(
                f"slot {slot} acquired twice in a row — adjacent layers "
                f"must rotate ghost buffers")
        self._schedule.append(slot)
        return slot

    def schedule(self) -> tuple:
        return tuple(self._schedule)


# The fused local aggregation over the contiguous [local|ghost] buffer now
# lives in ``backends/distributed.py`` (``dist_spmm[_transposed_vjp]``),
# composed from ``halo_exchange`` + ``kernels.ops.bsr_spmm_pair`` — the
# distributed backend owns the composition, this module owns the exchange.
