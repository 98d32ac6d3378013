"""The lowering pass: GNN spec -> per-layer ExecutionPlans (DESIGN.md §3).

This is the explicit form of Morphling's "code synthesis" step. Where the
paper's synthesizer emits backend-specialized source per layer, ``lower``
emits a ``ModelPlan`` — an inspectable list of ``LayerPlan`` records, each
naming the op kind, the dense/sparse feature path, the backend primitive
chosen from the registry (``repro.backends``), and carrying any pre-built
sparse operands (BSR of X and Xᵀ for the layer-0 sparse path; the weighted
graph's BSR/CSC pair shared by all layers).

The Algorithm-1 sparsity engine runs *per layer*, not just for layer 0:

* layer 0 — measured input-feature sparsity (``decide_execution_path``,
  exactly the single decision the seed repo made);
* hidden layers — post-activation sparsity estimates
  (``estimate_activation_sparsity``): ReLU zeroes ≈ half the entries, which
  stays below τ = 1 - γ for the paper's γ ≈ 0.2, so hidden layers land on
  the dense MXU path unless γ says otherwise.

A sparse *decision* only binds a sparse *primitive* when a pre-built operand
exists (layer 0, whose X is known at lowering time); hidden layers with a
sparse-profitable estimate record the decision and fall back to the dense
primitive, with the fallback noted in the plan — the plan never lies about
what will execute.

Three passes lower a spec, one per graph source: ``lower`` (the full
graph), ``lower_sampled`` (neighbour-sampled mini-batches) and
``lower_distributed`` (rank-partitioned). Each keeps only its graph-source
prologue and builds its ``LayerPlan`` records through one planner,
``_plan_layers`` (DESIGN.md §3).

``GNNModel.apply`` executes plans directly; nothing monkey-patches model
methods anymore.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import numpy as np

from repro.backends import Backend, select_backend
from repro.common.spans import count, span
from repro.core.aggregate import FusedGraphOp, _weighted_graph, make_fused_aggregate
from repro.core.layout import (
    GATHER_FILL,
    LayoutPlan,
    _select_order,
    default_layout,
    operand_format,
)
from repro.core.sparsity import (
    PAPER_GAMMA_DEFAULT,
    SparsityDecision,
    decide_execution_path,
    decide_execution_path_from_stats,
    estimate_activation_sparsity,
)
from repro.core.verify import check_plan
from repro.graph.csr import CSRGraph, permute_graph


@dataclasses.dataclass(frozen=True)
class EpiloguePlan:
    """One layer's fused-epilogue record (DESIGN.md §8).

    Declares which epilogue operands the layer's aggregation fuses —
    ``alpha * self_term + bias`` then an optional activation — applied on
    the output tile while it is still resident (in VMEM on the Pallas
    backend, as an XLA-fused consumer elsewhere). ``apply_layer`` owns the
    per-arch algebra; this record is the plan's visible commitment plus the
    per-layer fallback gate (``None`` = unfused sequence of ops).
    """

    self_term: bool         # fuse alpha * self_term into the aggregation
    bias: bool              # fuse the bias add
    activation: str         # "relu" (mask saved for the VJP) | "none"
    formula: str            # human-readable algebra, for plan dumps

    def describe(self) -> str:
        return self.formula


@dataclasses.dataclass(frozen=True)
class AttentionPlan:
    """One layer's fused-attention record (DESIGN.md §10).

    The attention sibling of ``EpiloguePlan``: declares how a GAT /
    GraphTransformer layer's edge-softmax aggregation executes. ``fused``
    means the flash-style kernels (online segment softmax + aggregation in
    one pass, per-edge scores never materialised) over the layer's operand
    (``operand``: BSR blocks or CSR row gather, by the fill rule), with the
    recompute VJP from the saved per-row (max, denominator) stats; unfused
    is the segment (gather) path with autodiff through the per-edge
    tensors.
    """

    heads: int
    head_dim: int
    fused: bool
    vjp: str                # "recompute(m,l)" | "autodiff"
    formula: str            # human-readable algebra, for plan dumps
    operand: str = "bsr"    # "bsr" | "gather": the fused kernels' operand

    def describe(self) -> str:
        mode = f"fused-{self.operand}" if self.fused else "segment"
        return (f"{self.heads}h x {self.head_dim} {mode} vjp={self.vjp} "
                f"{self.formula}")


def attention_head_dim(kind: str, heads: int, d_out: int,
                       is_last: bool) -> int:
    """Per-head width of an attention layer with ``d_out`` outputs. GAT
    concatenates its heads in hidden layers (``d_out = heads · D``) and
    averages them in the last (``D = d_out``); GT projects the concatenated
    heads back to ``d_out``."""
    if kind == "GAT" and is_last:
        return d_out
    return max(d_out // heads, 1)


def _attention_binding(kind: str, heads: int, d_out: int, is_last: bool,
                       fused: bool, operand: str = "bsr") -> AttentionPlan:
    return AttentionPlan(
        heads=heads, head_dim=attention_head_dim(kind, heads, d_out, is_last),
        fused=fused, vjp="recompute(m,l)" if fused else "autodiff",
        formula="softmax_j(leaky_relu(a_dst·z_i + a_src·z_j))·z_j",
        operand=operand)


def is_attention_arch(kind: str) -> bool:
    """Archs whose aggregation is the edge-softmax attention primitive."""
    return kind in ("GAT", "GT")


@dataclasses.dataclass(frozen=True)
class OverlapPlan:
    """The distributed plan's split-phase execution record (DESIGN.md §11).

    Declares that every matmul/attention aggregation layer runs the
    interior SpMM (local columns only) concurrently with the halo
    exchange's ``ppermute`` rounds, then the boundary SpMM once ghosts
    land — forward and backward both (the interior transposed-SpMM is off
    the reverse-exchange path by construction). ``live_shifts`` is the
    host-computed set of ring shifts with at least one live send on any
    rank; dead shifts are not unrolled. ``double_buffer_slots`` is the
    ghost-buffer rotation depth the trainer's ``GhostBufferRing`` schedules
    (adjacent layers never share a slot). ``prefetch_depth`` > 0 marks
    host-streamed operands (``runtime/streaming.py``): strips staged that
    many steps ahead of the consuming SpMM.
    """

    interior_blocks: int        # fleet-total interior stream length
    boundary_blocks: int        # fleet-total boundary stream length
    live_shifts: tuple          # ring shifts actually unrolled
    total_shifts: int           # P - 1
    double_buffer_slots: int = 2
    prefetch_depth: int = 0     # 0 = device-resident operands

    def describe(self) -> str:
        line = (f"split-phase int={self.interior_blocks}b "
                f"bnd={self.boundary_blocks}b "
                f"shifts={len(self.live_shifts)}/{self.total_shifts} "
                f"ghost-slots={self.double_buffer_slots}")
        if self.prefetch_depth:
            line += f" prefetch={self.prefetch_depth}"
        return line


@dataclasses.dataclass
class LayerPlan:
    """One layer's synthesized execution record."""

    index: int
    op_kind: str            # GCN | SAGE | GIN | GAT | GT
    d_in: int
    d_out: int
    feature_path: str       # "sparse" | "dense" — the path that will execute
    primitive: str          # backend primitive for the feature transform
    agg_primitive: str      # backend primitive for neighbour aggregation
    decision: SparsityDecision  # this layer's Alg-1 decision
    # differentiable w -> X @ w over pre-built BSR(X)/BSR(Xᵀ); only set when
    # feature_path == "sparse" (layer 0 with a known feature matrix)
    sparse_xw: Optional[Callable] = dataclasses.field(
        default=None, repr=False, compare=False)
    note: str = ""
    # fused-epilogue binding; None = unfused aggregation + separate XLA ops
    epilogue: Optional[EpiloguePlan] = None
    # attention binding (GAT / GT layers); None for non-attention archs
    attention: Optional[AttentionPlan] = None
    # the layout the layer's sparse operands were built at (shared across a
    # plan's layers); None = pre-layout-stage plans
    layout: Optional[LayoutPlan] = None
    # format of the aggregation operands: "gather" (CSR row gather) |
    # "bsr"; "" where the aggregation has no matmul operand
    operand: str = ""

    def describe(self) -> str:
        d = self.decision
        line = (
            f"layer {self.index}: {self.op_kind:4s} [{self.d_in} -> {self.d_out}]  "
            f"path={self.feature_path:6s} primitive={self.primitive}  "
            f"agg={self.agg_primitive}  "
            f"s={d.sparsity:.3f} tau={d.threshold:.2f} mode={d.mode}"
        )
        if self.operand:
            line += f"  operand={self.operand}"
        if self.epilogue is not None:
            line += f"  epilogue[{self.epilogue.describe()}]"
        if self.attention is not None:
            line += f"  attention[{self.attention.describe()}]"
        if self.layout is not None:
            line += f"  layout[{self.layout.describe()}]"
        if self.note:
            line += f"  ({self.note})"
        return line


@dataclasses.dataclass
class ModelPlan:
    """The synthesized program, made visible: per-layer plans + shared ops."""

    layers: list[LayerPlan]
    backend: str            # registry name of the chosen backend
    gamma: float
    arch: str
    aggregation: str        # effective aggregation ("gcn", "sum", ...)
    feature_sparsity: float  # measured input sparsity (0.0 if unknown)
    graph_op: FusedGraphOp = dataclasses.field(repr=False)
    # the layout stage's decision: node order + BSR tile the operands were
    # materialised at; carries perm/inv_perm when the order permutes
    layout: Optional[LayoutPlan] = None

    @property
    def input_decision(self) -> SparsityDecision:
        """Layer 0's decision — the seed repo's single ``sparsity_decision``."""
        return self.layers[0].decision

    def describe(self) -> str:
        head = (
            f"ModelPlan: arch={self.arch} backend={self.backend} "
            f"aggregation={self.aggregation} gamma={self.gamma:.2f} "
            f"input_sparsity={self.feature_sparsity:.3f} "
            f"layers={len(self.layers)}"
        )
        return "\n".join([head] + ["  " + l.describe() for l in self.layers])


@dataclasses.dataclass
class DistributedModelPlan:
    """The synthesized *distributed* program: per-layer plans whose
    aggregation primitives are the halo-exchange compositions from
    ``backends/distributed.py``, plus the stacked per-rank sparse operands
    for the layer-0 Alg-1 input path (DESIGN.md §6)."""

    layers: list[LayerPlan]
    backend: str            # "distributed"
    inner: str              # local SpMM executor: "pallas" | "xla"
    gamma: float
    arch: str
    aggregation: str
    n_ranks: int
    feature_sparsity: float             # pooled over valid rows, all ranks
    per_rank_sparsity: np.ndarray       # [P] measured per-rank input sparsity
    # stacked per-rank BSR(X_local) / BSR(X_localᵀ) — bound iff layer 0 took
    # the sparse path; passed into shard_map as sharded arguments
    feat_fwd: Optional[dict] = dataclasses.field(default=None, repr=False)
    feat_bwd: Optional[dict] = dataclasses.field(default=None, repr=False)
    feat_f_pad: int = 0                 # shared padded feature dim of the pair
    # within-rank order + the tile the stacked operands were built at; the
    # permutation is baked into the data distribution (perm=None here)
    layout: Optional[LayoutPlan] = None
    # split-phase overlap record; None = bulk-synchronous fallback (the
    # overlap=False flag, or a DistributedGraph built without split operands,
    # or an aggregation with no overlapped composition)
    overlap: Optional[OverlapPlan] = None

    @property
    def input_decision(self) -> SparsityDecision:
        return self.layers[0].decision

    def describe(self) -> str:
        s = self.per_rank_sparsity
        head = (
            f"DistributedModelPlan: arch={self.arch} backend={self.backend} "
            f"inner={self.inner} ranks={self.n_ranks} "
            f"aggregation={self.aggregation} gamma={self.gamma:.2f} "
            f"input_sparsity={self.feature_sparsity:.3f} "
            f"per_rank_s=[{s.min():.3f}, {s.max():.3f}] layers={len(self.layers)}"
        )
        if self.overlap is not None:
            head += f"\n  overlap[{self.overlap.describe()}]"
        return "\n".join([head] + ["  " + l.describe() for l in self.layers])


@dataclasses.dataclass
class SampledModelPlan:
    """The synthesized *mini-batch* program (DESIGN.md §7): per-layer plans
    whose aggregation primitives run on the sampler's bucketed
    ``SampledBlock`` operands, plus the template-batch Alg-1 decision for
    the per-batch sparse input path. The third consumer of the plan
    pipeline, and the first whose graph size is independent of device
    memory."""

    layers: list[LayerPlan]
    backend: str
    gamma: float
    arch: str
    aggregation: str
    feature_sparsity: float   # measured on the template batch's frontier
    fanouts: tuple[int, ...]
    batch_size: int
    n_buckets: int
    sampler: object = dataclasses.field(repr=False)  # graph.sampling.NeighborSampler
    # full-graph order the sampler's CSR was renumbered with (the trainer
    # maps user node ids through inv_perm) + the sampler's block tile
    layout: Optional[LayoutPlan] = None
    # serving plans: the trainer never builds loss/grad closures — the
    # compiled artifact is the infer path only (DESIGN.md §12)
    infer_only: bool = False

    @property
    def input_decision(self) -> SparsityDecision:
        return self.layers[0].decision

    def describe(self) -> str:
        head = (
            f"SampledModelPlan: arch={self.arch} backend={self.backend} "
            f"aggregation={self.aggregation} gamma={self.gamma:.2f} "
            f"fanouts={list(self.fanouts)} batch={self.batch_size} "
            f"buckets={self.n_buckets} "
            f"frontier_sparsity={self.feature_sparsity:.3f} "
            f"layers={len(self.layers)}"
            + (" infer_only" if self.infer_only else "")
        )
        lines = [head] + ["  " + l.describe() for l in self.layers]
        for b in self.sampler.buckets:
            lines.append(
                f"  bucket[seed_cap={b.seed_cap}]: node_caps={list(b.node_caps)} "
                f"nnz_caps={list(b.nnz_caps)} feat_nnz_cap={b.feat_nnz_cap}")
        return "\n".join(lines)


@span("lower")
def lower_sampled(
    config,
    graph: CSRGraph,
    features: np.ndarray,
    *,
    fanouts,
    batch_size: int = 256,
    n_buckets: int = 2,
    gamma: float = PAPER_GAMMA_DEFAULT,
    engine: "str | Backend | None" = None,
    br: int = 8,
    bc: int = 8,
    seed: int = 0,
    use_sparse_input: bool = True,
    feat_slack: float = 2.0,
    fuse_epilogue: bool = True,
    fuse_attention: bool = True,
    layout: "LayoutPlan | str | None" = None,
    infer_only: bool = False,
    validate: str = "fast",
) -> SampledModelPlan:
    """Lower a GNN spec onto the neighbour-sampled mini-batch path.

    The graph is pre-weighted for the spec's aggregation (full-graph
    normalisation, the parity anchor with the full-batch path) and handed
    to a ``NeighborSampler`` whose bucketed shape caps bound jit retraces
    to one per bucket. The Algorithm-1 engine runs on the *gathered
    frontier features of a template batch*: a sampled batch is simply a
    smaller operand with a fresh sparsity decision. A sparse layer-0
    decision binds the gather-layout ``feature_matmul_sparse`` primitive —
    the batch's feature matrix is a runtime value, so the sampler streams
    per-batch COO operands (capped at ``feat_slack`` times the template's
    measured density; denser batches fall back to the dense MXU path and
    are counted by the trainer).

    ``layout`` requests the reorder stage (DESIGN.md §9): the full graph is
    renumbered before the sampler is built, so every sampled block's source
    frontier clusters renumbered neighbours and the per-batch CSR→BSR packs
    denser blocks. The plan's ``layout.perm``/``inv_perm`` is the id map
    ``MiniBatchTrainer`` applies at its boundary (user node ids in,
    seed-ordered logits out — the permutation never reaches the caller).
    The block tile stays the sampler's ``(br, bc)``: bucketed rectangular
    operands do not share the full-graph tile geometry.

    ``infer_only=True`` marks the plan as a serving artifact (DESIGN.md
    §12): the trainer executing it never builds loss/grad closures.
    """
    from repro.graph.sampling import NeighborSampler

    backend = select_backend(engine)
    if backend.name == "distributed":
        raise ValueError("use lower_distributed for the distributed backend")
    kind = config.kind
    dims = list(config.layer_dims)
    features = np.asarray(features)
    if features.shape[-1] != dims[0]:
        raise ValueError(
            f"layer_dims[0]={dims[0]} != feature dim {features.shape[-1]}")
    if isinstance(fanouts, int):
        fanouts = (fanouts,) * config.n_layers
    fanouts = tuple(int(f) for f in fanouts)
    if len(fanouts) != config.n_layers:
        raise ValueError(
            f"need one fanout per layer ({config.n_layers}), got {fanouts!r}")

    lp = _resolve_layout(graph, layout, int(br), int(bc), source="sampled")
    if lp.permutes:
        graph = (lp.reordered_graph if lp.reordered_graph is not None
                 else permute_graph(graph, lp.inv_perm))
        features = features[lp.perm]
    if lp.reordered_graph is not None:  # sampler holds its own weighted copy
        lp = dataclasses.replace(lp, reordered_graph=None)

    agg = effective_aggregation(config)
    weighted = _weighted_graph(graph, agg)
    is_attn = is_attention_arch(kind)
    # matmul-expressible aggregations ride the BSR operands; attention archs
    # join them when the fused attention kernel is on (the per-batch BSR
    # nonzero pattern doubles as the attention mask); max stays edge-valued
    emit_attn = (fuse_attention and is_attn
                 and backend.name in ("pallas", "xla"))
    emit_bsr = (backend.name in ("pallas", "xla")
                and (emit_attn if is_attn else agg != "max"))
    sampler = NeighborSampler(
        weighted, fanouts, batch_size, n_buckets=n_buckets, br=br, bc=bc,
        seed=seed, emit_bsr=emit_bsr)

    # template batch: Alg-1 input statistics on a gathered frontier
    t_rng = np.random.default_rng(seed ^ 0x5EED)
    t_seeds = t_rng.choice(
        graph.n_rows, size=min(batch_size, graph.n_rows), replace=False)
    template = sampler.sample_batch(t_seeds, rng=t_rng)
    frontier0 = template.blocks[0].src_nodes
    rows = features[frontier0]
    s_frontier = 1.0 - np.count_nonzero(rows) / max(rows.size, 1)

    emit_epilogue = fuse_epilogue and epilogue_fusable(config, agg)
    if is_attn:
        agg_primitive = (f"{backend.name}.spmm_attention" if emit_attn
                         else f"{backend.name}.segment_softmax_aggregate")
    elif agg == "max":
        agg_primitive = "gather.segment_max"
    elif emit_epilogue:
        # same labeling as lower(): the executed contract is the fused
        # epilogue over whatever aggregation the backend serves
        agg_primitive = f"{backend.name}.spmm_fused_epilogue"
    elif backend.name == "gather":
        agg_primitive = "gather.segment_sum_baseline"
    else:
        agg_primitive = f"{backend.name}.spmm_transposed_vjp"

    def bind_sparse():
        # per-batch feature matrices are runtime values: the sampler streams
        # COO operands in the gather backend's edge-list layout, capped by
        # the template's measured density
        caps = [
            max(min(int(np.ceil(b.node_caps[0] * dims[0]
                                * (1.0 - s_frontier) * feat_slack)),
                    b.node_caps[0] * dims[0]), 1)
            for b in sampler.buckets
        ]
        sampler.set_feature_caps(caps)
        return ("gather.feature_matmul_sparse", None,
                f"per-batch COO operand streamed by the sampler "
                f"(slack={feat_slack:g})")

    n_frontier = int(frontier0.shape[0])
    layers = _plan_layers(
        config, gamma=gamma, n_rows=n_frontier,
        decision0=decide_execution_path_from_stats(
            s_frontier, n_frontier, dims[0], dims[1], gamma=gamma),
        sparse_input=use_sparse_input,
        off_note="sparse profitable but disabled (use_sparse_input=False)",
        bind_sparse=bind_sparse,
        dense_primitive=f"{backend.name}.feature_matmul_dense",
        agg_primitive=agg_primitive, epilogue=emit_epilogue,
        attention_fused=emit_attn, layout=lp)

    plan = SampledModelPlan(
        layers=layers, backend=backend.name, gamma=gamma, arch=kind,
        aggregation=agg, feature_sparsity=float(s_frontier), fanouts=fanouts,
        batch_size=int(batch_size), n_buckets=int(n_buckets), sampler=sampler,
        layout=lp, infer_only=bool(infer_only),
    )
    check_plan(plan, mode=validate)
    return plan


def effective_aggregation(config) -> str:
    """The aggregation the spec actually lowers to (the seed model's
    normalisation): GCN always uses symmetric-normalised weights, GIN's sum
    is fixed by the arch, everything else takes ``config.aggregation``.
    Shared by ``lower``/``lower_distributed`` and every call site that
    pre-weights a ``DistributedGraph``."""
    if config.kind == "GCN":
        return "gcn"
    if config.kind == "GIN":
        return "sum"
    return config.aggregation


@span("lower")
def lower_distributed(
    config,
    dist,  # core.halo.DistributedGraph
    features: Optional[np.ndarray] = None,  # [P, n_local, F]; default dist's
    *,
    gamma: float = PAPER_GAMMA_DEFAULT,
    inner: Optional[str] = None,
    use_sparse_input: bool = True,
    fuse_epilogue: bool = True,
    fuse_attention: bool = True,
    overlap: bool = True,
    validate: str = "fast",
) -> DistributedModelPlan:
    """Lower a GNN spec onto the distributed backend: the MPI-analog
    synthesis step.

    The Alg-1 layer-0 decision runs on *per-rank* feature statistics
    (padding rows excluded via ``dist.n_valid``). The bound path must be
    SPMD-uniform — one jitted program across ranks — so the sparse input
    path binds iff **every** rank's decision is sparse; a mixed fleet falls
    back to dense with the per-rank spread recorded in the plan note. When
    the sparse path binds, the per-rank BSR(X_local)/BSR(X_localᵀ) pairs
    are built here, stacked on the rank axis like the graph operands.

    ``overlap=True`` (the default) binds the split-phase compositions —
    interior SpMM concurrent with the halo exchange, boundary SpMM after —
    recorded as an ``OverlapPlan`` on the returned plan. It falls back to
    the bulk-synchronous primitives (``overlap=None`` on the plan) when
    the ``DistributedGraph`` carries no split operands, or when the
    aggregation has no overlapped form (``max`` and the unfused segment
    attention path consume the ghost buffer directly)."""
    from repro.backends import get_backend
    from repro.core.halo import stack_bsr_matrices
    from repro.graph.csr import csr_from_dense, csr_to_bsr

    backend = get_backend("distributed")
    inner_name = inner or backend.inner()
    kind = config.kind
    dims = list(config.layer_dims)
    P = dist.n_ranks

    agg = effective_aggregation(config)
    if dist.aggregation not in ("sum", agg):
        raise ValueError(
            f"DistributedGraph was weighted for {dist.aggregation!r} but the "
            f"spec needs {agg!r}; rebuild with build_distributed_graph(..., "
            f"aggregation={agg!r})")

    emit_epilogue = fuse_epilogue and epilogue_fusable(config, agg)
    is_attn = is_attention_arch(kind)
    # the distributed inner executor is always pallas/xla, so the fused
    # attention composition is available whenever the flag is on
    emit_attn = fuse_attention and is_attn
    # split-phase overlap: needs the split operands on the DistributedGraph
    # and an aggregation with an overlapped composition (matmul or fused
    # attention; max / segment attention consume the ghost buffer directly)
    split_built = getattr(dist, "fwd_interior", None) is not None
    emit_overlap = (overlap and split_built and agg != "max"
                    and (emit_attn if is_attn else True))
    if is_attn:
        if emit_attn:
            agg_primitive = ("distributed.dist_spmm_attention_split"
                             if emit_overlap
                             else "distributed.dist_spmm_attention")
        else:
            agg_primitive = "distributed.dist_segment_softmax_aggregate"
    elif agg == "max":
        agg_primitive = "distributed.dist_segment_max"
    elif emit_epilogue:
        agg_primitive = ("distributed.dist_spmm_fused_epilogue_split"
                         if emit_overlap
                         else "distributed.dist_spmm_fused_epilogue")
    else:
        agg_primitive = ("distributed.dist_spmm_split_transposed_vjp"
                         if emit_overlap
                         else "distributed.dist_spmm_transposed_vjp")

    overlap_plan = None
    if emit_overlap:
        overlap_plan = OverlapPlan(
            interior_blocks=int(np.asarray(dist.interior_blocks).sum()),
            boundary_blocks=int(np.asarray(dist.boundary_blocks).sum()),
            live_shifts=tuple(dist.live_shifts or ()),
            total_shifts=P - 1,
        )

    feats = np.asarray(dist.features if features is None else features)
    if feats.shape[0] != P or feats.shape[1] != dist.n_local:
        raise ValueError(
            f"features must be rank-stacked [P={P}, n_local={dist.n_local}, F]")
    f_dim = feats.shape[-1]
    if dims[0] != f_dim:
        raise ValueError(f"layer_dims[0]={dims[0]} != feature dim {f_dim}")

    # within-rank order + tile the stacked operands were built at
    # (build_distributed_graph applied the reorder per rank; the
    # permutation is baked into the data distribution, so no
    # trainer-boundary perm — loss and grads are order-invariant)
    lp = LayoutPlan(order=getattr(dist, "reorder", "none"),
                    br=dist.br, bc=dist.bc, source="distributed")

    n_valid = (np.asarray(dist.n_valid) if dist.n_valid is not None
               else np.full(P, dist.n_local))
    per_rank_s = np.zeros(P)
    nnz_total = 0
    for p in range(P):
        rows = feats[p, : n_valid[p]]
        nnz = np.count_nonzero(rows)
        per_rank_s[p] = 1.0 - nnz / max(rows.size, 1)
        nnz_total += nnz
    pooled_s = 1.0 - nnz_total / max(int(n_valid.sum()) * f_dim, 1)

    # per-rank Alg-1 decisions for layer 0 (the sparse path needs them all);
    # the pooled decision is the plan's record
    n_sparse = sum(
        decide_execution_path_from_stats(
            per_rank_s[p], int(n_valid[p]), dims[0], dims[1], gamma=gamma
        ).mode == "sparse"
        for p in range(P))

    feat_fwd = feat_bwd = None
    f_pad = 0

    def bind_sparse():
        # build the stacked per-rank sparse operands once, here
        nonlocal feat_fwd, feat_bwd, f_pad
        br, bc = dist.br, dist.bc
        mult = int(np.lcm(br, bc))
        f_pad = -(-f_dim // mult) * mult
        fwd_stack, bwd_stack = [], []
        for p in range(P):
            x_csr = csr_from_dense(feats[p])
            x_csr = dataclasses.replace(x_csr, n_cols=f_pad)
            fwd_stack.append(csr_to_bsr(x_csr, br=br, bc=bc))
            bwd_stack.append(csr_to_bsr(x_csr.transpose(), br=br, bc=bc))
        feat_fwd = stack_bsr_matrices(fwd_stack, br, bc)
        feat_bwd = stack_bsr_matrices(bwd_stack, br, bc)
        return ("distributed.dist_feature_matmul_sparse", None,
                f"per-rank BSR(X_local); s in "
                f"[{per_rank_s.min():.3f}, {per_rank_s.max():.3f}]")

    n_rows = int(n_valid.sum())
    layers = _plan_layers(
        config, gamma=gamma, n_rows=n_rows,
        decision0=decide_execution_path_from_stats(
            pooled_s, n_rows, dims[0], dims[1], gamma=gamma),
        sparse_input=use_sparse_input,
        off_note="sparse profitable but disabled (use_sparse_input=False)",
        veto=("" if n_sparse == P else
              f"mixed fleet: {n_sparse}/{P} ranks sparse — SPMD-uniform "
              f"dense fallback"),
        bind_sparse=bind_sparse,
        dense_primitive="distributed.feature_matmul_dense",
        agg_primitive=agg_primitive, epilogue=emit_epilogue,
        attention_fused=emit_attn, layout=lp)

    plan = DistributedModelPlan(
        layers=layers, backend="distributed", inner=inner_name, gamma=gamma,
        arch=kind, aggregation=agg, n_ranks=P, feature_sparsity=pooled_s,
        per_rank_sparsity=per_rank_s, feat_fwd=feat_fwd, feat_bwd=feat_bwd,
        feat_f_pad=f_pad, layout=lp, overlap=overlap_plan,
    )
    check_plan(plan, mode=validate, dist=dist)
    return plan


def epilogue_fusable(config, aggregation: str) -> bool:
    """Can this spec's aggregate layers take a fused epilogue at all?

    The epilogue rides the matmul-form aggregation: attention archs
    (GAT/GT) aggregate through the attention primitive instead (their
    fusion story is ``AttentionPlan``, DESIGN.md §10) and ``max`` is not a
    matmul — both keep the unfused epilogue sequence.
    """
    return not is_attention_arch(config.kind) and aggregation != "max"


def _epilogue_binding(config, is_last: bool,
                      sparse_path: bool) -> Optional[EpiloguePlan]:
    """The per-layer epilogue record (DESIGN.md §8 grammar).

    Only a ReLU activation lowers into the kernel (the mask-VJP contract);
    any other ``config.activation`` fuses self-term/bias and leaves the
    activation outside. Per arch:

    * GCN  — ``relu(A·(X·W) + b)``: bias + post-activation.
    * SAGE — ``relu(A·(X·Wn) + X·Ws + b)``: the self/neigh combine. The
      neighbour transform reassociates ``A(X)·Wn == A(X·Wn)`` (A is linear),
      so the self term, bias and activation all land on the SpMM output.
    * GIN  — sparse-reassociated layers fuse the whole MLP input
      ``act(A·u + (1+eps)·u + b1), u = X·W1``; dense layers fuse the
      self-term combine ``A·x + (1+eps)·x`` (bias/activation belong to the
      dense MLP matmul that follows, which XLA fuses on its own).
    """
    kind = config.kind
    relu_ok = config.activation is jax.nn.relu
    post = "relu" if (relu_ok and not is_last) else "none"
    if kind == "GCN":
        f = "A·(X·W) + b"
        return EpiloguePlan(self_term=False, bias=True, activation=post,
                            formula=f"relu({f})" if post == "relu" else f)
    if kind == "SAGE":
        f = "A·(X·Wn) + X·Ws + b"
        return EpiloguePlan(self_term=True, bias=True, activation=post,
                            formula=f"relu({f})" if post == "relu" else f)
    if kind == "GIN":
        if sparse_path:
            act = "relu" if relu_ok else "none"
            f = "A·u + (1+eps)·u + b1, u = X·W1"
            return EpiloguePlan(self_term=True, bias=True, activation=act,
                                formula=f"relu({f})" if act == "relu" else f)
        return EpiloguePlan(self_term=True, bias=False, activation="none",
                            formula="A·x + (1+eps)·x")
    return None


def _sparse_expressible(kind: str) -> tuple[bool, str]:
    """Can the layer-0 X @ W be served by ``feature_matmul_sparse``?

    GCN/SAGE/GAT/GT multiply raw X by a weight directly. GIN's MLP input is
    (1+eps)·X + A·X, but its aggregation is the linear "sum" operator, so
    z @ W1 re-associates to (1+eps)·(X@W1) + A·(X@W1) — the sparse matmul
    applies there too (and shrinks the aggregation from F to H columns).
    """
    if kind in ("GCN", "SAGE", "GAT", "GT"):
        return True, ""
    if kind == "GIN":
        return True, "reassociated: z@W1 = (1+eps)(X@W1) + A(X@W1)"
    return False, f"no sparse lowering for {kind}"


def _plan_layers(
    config,
    *,
    gamma: float,
    n_rows: int,
    decision0: SparsityDecision,
    sparse_input: bool,
    off_note: str,
    bind_sparse: Callable[[], tuple[str, Optional[Callable], str]],
    dense_primitive: str,
    agg_primitive: str,
    epilogue: bool,
    attention_fused: bool,
    layout: LayoutPlan,
    veto: str = "",
    operand: str = "",
    operand_note: str = "",
) -> list[LayerPlan]:
    """The per-layer planner of all three lowering passes.

    Each pass brings what its graph source decides: layer 0's Alg-1
    ``decision0`` on that source's input statistics, the row count
    ``n_rows`` the hidden-layer estimates are taken at, and
    ``bind_sparse`` — called at most once, when layer 0 takes the sparse
    input path — which builds that source's sparse feature operand and
    returns ``(primitive, sparse_xw, note)``. Layer 0 binds it when its
    decision is sparse, ``sparse_input`` is on (else ``off_note``), the
    arch can express it (``_sparse_expressible``) and the pass raised no
    ``veto`` note. Hidden layers record their estimate and run dense: their
    inputs are runtime values with no pre-built operand.

    Every layer shares the aggregation primitive, the layout and the
    operand format; ``epilogue`` and ``attention_fused`` bind the per-layer
    epilogue and attention records.
    """
    kind, dims = config.kind, list(config.layer_dims)
    last = config.n_layers - 1
    layers: list[LayerPlan] = []
    for i in range(config.n_layers):
        d_in, d_out = dims[i], dims[i + 1]
        decision = decision0 if i == 0 else decide_execution_path_from_stats(
            estimate_activation_sparsity(config.activation), n_rows, d_in,
            d_out, gamma=gamma)
        path, primitive, sparse_xw, note = "dense", dense_primitive, None, ""
        if decision.mode == "sparse":
            expressible, expr_note = _sparse_expressible(kind)
            if i > 0:
                note = ("sparse profitable but activations are runtime "
                        "values; no pre-built operand — dense fallback")
            elif not sparse_input:
                note = off_note
            elif not expressible:
                note = expr_note
            elif veto:
                note = veto
            else:
                primitive, sparse_xw, note = bind_sparse()
                path = "sparse"
                note = "; ".join(filter(None, (note, expr_note)))
        note = "; ".join(filter(None, (note, operand_note)))
        layers.append(LayerPlan(
            index=i, op_kind=kind, d_in=d_in, d_out=d_out,
            feature_path=path, primitive=primitive,
            agg_primitive=agg_primitive, decision=decision,
            sparse_xw=sparse_xw, note=note,
            epilogue=(_epilogue_binding(config, is_last=(i == last),
                                        sparse_path=(path == "sparse"))
                      if epilogue else None),
            attention=(_attention_binding(kind, config.gat_heads, d_out,
                                          i == last, attention_fused,
                                          operand or "bsr")
                       if is_attention_arch(kind) else None),
            layout=layout, operand=operand,
        ))
    return layers


@span("layout")
def _resolve_layout(
    graph: CSRGraph,
    layout: "LayoutPlan | str | None",
    br: Optional[int] = None,
    bc: Optional[int] = None,
    *,
    source: Optional[str] = None,
) -> LayoutPlan:
    """Turn a ``layout=`` argument into a concrete ``LayoutPlan``: the one
    resolver of ``lower`` and ``lower_sampled``.

    * ``None`` / ``"none"`` — identity order.
    * ``"auto"`` — ``_select_order``'s block-count rule: degree or RCM,
      whichever packs fewer BSR blocks at the default tile, and only when
      it cuts the count by at least 10%; otherwise the identity.
    * ``"degree"`` / ``"rcm"`` — that order.
    * a ``LayoutPlan`` — passes through untouched.

    The tile is ``br``/``bc`` (``None``: 8 rows, adaptive ``bc``), with
    the graph's block count taken at it. Explicit ``br``/``bc`` with a
    ``LayoutPlan`` is a conflict (the plan carries the tile) and raises.
    A pass whose operands are not cut from the full graph names itself in
    ``source`` (``"sampled"``: bucketed per-batch blocks): its ``br``/``bc``
    replace any plan's tile, and no block count is taken.
    """
    if isinstance(layout, LayoutPlan):
        if source is not None:
            return dataclasses.replace(layout, br=br, bc=bc, n_blocks=0,
                                       padding_waste=0.0, source=source)
        if br is not None or bc is not None:
            raise ValueError(
                "explicit br/bc conflict with a LayoutPlan: the layout "
                "carries the tile — pass one or the other")
        return layout
    mode, g_r, perm, inv = _select_order(graph, layout or "none")
    if source is not None:
        lp = LayoutPlan(order="none", br=br, bc=bc, source=source)
    else:
        lp = default_layout(g_r, br=br, bc=bc)
        if layout == "auto":
            lp.source = "auto"
        elif mode != "none":
            lp.source = "requested"
        elif br is not None or bc is not None:
            lp.source = "explicit"
    if mode == "none":
        return lp
    return dataclasses.replace(lp, order=mode, perm=perm, inv_perm=inv,
                               reordered_graph=g_r)


@span("lower")
def lower(
    config,
    graph: CSRGraph,
    features: Optional[np.ndarray] = None,
    *,
    gamma: float = PAPER_GAMMA_DEFAULT,
    engine: "str | Backend | None" = None,
    interpret: Optional[bool] = None,
    use_fused: bool = True,
    fuse_epilogue: bool = True,
    fuse_attention: bool = True,
    br: Optional[int] = None,
    bc: Optional[int] = None,
    layout: "LayoutPlan | str | None" = None,
    validate: str = "fast",
) -> ModelPlan:
    """Lower a GNN spec onto backend primitives: the synthesis step.

    ``config`` is a ``models.gnn.GNNConfig`` (duck-typed: ``kind``,
    ``layer_dims``, ``aggregation``, ``activation``, ``n_layers``).
    ``features=None`` means the input matrix is unknown at lowering time
    (direct ``GNNModel`` construction); every layer then takes the dense
    path. ``use_fused=False`` keeps the plan but executes aggregation on the
    gather-scatter baseline and disables sparse feature binding, preserving
    the seed repo's A/B-comparison semantics. ``fuse_epilogue=False`` keeps
    the fused aggregation but unbinds the per-layer epilogue (bias /
    self-term / activation run as separate XLA ops) — the A/B lever
    ``benchmarks/bench_fusion.py`` sweeps. ``fuse_attention=False`` keeps
    attention archs (GAT / GT) on the segment-softmax gather path — the
    A/B lever ``benchmarks/bench_attention.py`` sweeps; by default they
    lower onto the fused flash-attention kernels on pallas/xla, over the
    operand format the fill rule picks (pallas: BSR or CSR row gather).

    ``layout`` selects the node order of the layout stage (DESIGN.md §9,
    ``_resolve_layout``): ``None`` / ``"none"`` keep the identity,
    ``"degree"`` / ``"rcm"`` reorder, and ``"auto"`` reorders only when
    that cuts the BSR block count by at least 10% — the rule
    ``lower_sampled`` applies. Every sparse operand is then built once from
    the reordered graph, and the plan carries ``perm``/``inv_perm`` so
    ``GNNModel.apply`` permutes features in and un-permutes outputs —
    results are bit-for-bit up to the permutation. ``br``/``bc`` set the
    tile (``bc=None`` adapts to the graph instead of lane-padding small
    graphs to 128); with a ``LayoutPlan``, which carries its own tile, they
    raise instead of silently dropping the caller's tile.
    """
    backend = select_backend(engine)
    kind = config.kind
    dims = list(config.layer_dims)

    agg = effective_aggregation(config)

    emit_epilogue = (use_fused and fuse_epilogue
                     and epilogue_fusable(config, agg))
    is_attn = is_attention_arch(kind)
    emit_attn = (use_fused and fuse_attention and is_attn
                 and backend.name in ("pallas", "xla"))
    lp = _resolve_layout(graph, layout, br, bc)
    if lp.permutes:
        graph_exec = (lp.reordered_graph if lp.reordered_graph is not None
                      else permute_graph(graph, lp.inv_perm))
        features_exec = (None if features is None
                         else np.asarray(features)[lp.perm])
    else:
        graph_exec = graph
        features_exec = None if features is None else np.asarray(features)

    # the aggregation operand's format, by the fill the layout counted
    # (backends with one format ignore it); attention takes it as the SpMM
    # does, with kernels of its own for each format
    fmt = (operand_format(graph_exec.nnz, lp.n_blocks) if lp.n_blocks
           else "auto")
    graph_op = make_fused_aggregate(
        graph_exec, agg, br=lp.br, bc=lp.bc, interpret=interpret,
        engine=backend, build_attention=emit_attn, fmt=fmt)
    operand = getattr(graph_op.fwd_operand, "format", "")
    operand_note = f"{operand} operand" if operand else ""
    if operand and lp.n_blocks:
        operand_note += (
            f": {graph_exec.nnz / lp.n_blocks:.2f} nonzeros per "
            f"{lp.br}x{lp.bc} block, pallas gathers below {GATHER_FILL:g}")
    # operands are built — drop the layout's host-side graph copy so the
    # plan (held for the model's lifetime) doesn't duplicate the graph
    if lp.reordered_graph is not None:
        lp = dataclasses.replace(lp, reordered_graph=None)

    attn_bound = emit_attn and graph_op.aggregate_attention is not None
    if is_attn:
        agg_primitive = (f"{backend.name}.spmm_attention" if attn_bound
                         else f"{backend.name}.segment_softmax_aggregate")
    elif agg == "max":
        agg_primitive = "gather.segment_max"  # not a matmul on any backend
    elif not use_fused:
        # GNNModel._aggregate routes to the gather-scatter baseline
        agg_primitive = "gather.segment_sum_baseline"
    elif emit_epilogue:
        agg_primitive = f"{backend.name}.spmm_fused_epilogue"
    else:
        agg_primitive = f"{backend.name}.spmm_transposed_vjp"

    def bind_sparse():
        # operand of the (possibly reordered) feature matrix; bc adapts to
        # the feature dim — X's columns are features, not graph nodes, so
        # the adjacency tile does not apply
        return (f"{backend.name}.feature_matmul_sparse",
                backend.feature_matmul_sparse(
                    features_exec, br=lp.br, bc=None, interpret=interpret),
                "")

    with span("decide"):
        formats = [getattr(op, "format", None)
                   for op in (graph_op.fwd_operand, graph_op.bwd_operand)]
        for name in ("gather", "bsr"):
            count(f"operand_{name}", formats.count(name))
            if is_attn:  # attention layers on each format's fused kernels
                count(f"attention_{name}", config.n_layers
                      if attn_bound and operand == name else 0)
        if features is not None:
            decision0 = decide_execution_path(
                features, gamma=gamma, n_hidden=dims[1])
        else:
            decision0 = decide_execution_path_from_stats(
                0.0, graph_exec.n_rows, dims[0], dims[1], gamma=gamma)
        layers = _plan_layers(
            config, gamma=gamma, n_rows=graph_exec.n_rows,
            decision0=decision0, sparse_input=use_fused,
            off_note="sparse profitable but fusion disabled (use_fused=False)",
            veto=("" if features is not None
                  else "feature matrix unknown at lowering time"),
            bind_sparse=bind_sparse,
            dense_primitive=f"{backend.name}.feature_matmul_dense",
            agg_primitive=agg_primitive, epilogue=emit_epilogue,
            attention_fused=attn_bound, layout=lp, operand=operand,
            operand_note=operand_note)

    plan = ModelPlan(
        layers=layers, backend=backend.name, gamma=gamma, arch=kind,
        aggregation=agg,
        feature_sparsity=decision0.sparsity if features is not None else 0.0,
        graph_op=graph_op, layout=lp,
    )
    check_plan(plan, mode=validate, graph=graph_exec)
    return plan
