"""Training drivers.

* ``FullBatchTrainer`` — single-device full-batch GNN training (paper §V-C
  protocol: per-epoch forward + backward + optimizer), with checkpointing
  and heartbeat hooks.
* ``MiniBatchTrainer`` — neighbour-sampled mini-batch training
  (DESIGN.md §7): seed-node batching over the train mask with per-epoch
  reshuffles, executing a ``SampledModelPlan``
  (``core/lowering.py:lower_sampled``) whose bucketed block operands bound
  jit retraces to one per bucket. Loss is taken on batch seeds only; the
  same ``models.gnn.apply_layer`` algebra runs with ``LayerOps`` bound to
  per-batch bipartite operands.
* ``DistributedGNNTrainer`` — the MPI-backend analog, now a *plan
  executor*: it takes a ``GNNConfig`` and a ``DistributedModelPlan``
  (``core/lowering.py:lower_distributed``) and runs the same
  ``models.gnn.apply_layer`` algebra as the single-device model, with the
  aggregation/input primitives bound to the distributed backend
  (halo exchange + local BSR SpMM). Parameters come from the shared
  ``models.gnn.init_params`` — the trainer no longer forks model semantics
  or initialisation.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.backends import DistributedBackend, compose_epilogue, get_backend
from repro.backends.gather import EdgeListOperand
from repro.common.jit import jit_hoisted
from repro.common.spans import span
from repro.core.aggregate import gather_scatter_aggregate
from repro.core.halo import DistributedGraph, GhostBufferRing, halo_exchange
from repro.core.lowering import (
    DistributedModelPlan,
    SampledModelPlan,
    lower_distributed,
    lower_sampled,
)
from repro.core.pipeline import arch_layer_fns, pipelined_value_and_grad
from repro.core.sparsity import PAPER_GAMMA_DEFAULT
from repro.graph.csr import CSRGraph
from repro.graph.sampling import SampledBatch
from repro.kernels import ops as kops
from repro.models.gnn import GNNConfig, GNNModel, LayerOps, apply_layer, init_params
from repro.runtime.checkpoint import restore_checkpoint, save_checkpoint
from repro.runtime.resilience import (
    FaultInjector,
    GuardPolicy,
    GuardRunner,
    guarded_update,
    pack_rng_state,
    unpack_rng_state,
)
from repro.training.optimizer import Optimizer


@dataclasses.dataclass
class TrainResult:
    losses: list
    epoch_times: list
    final_params: dict
    restored_from: Optional[int] = None
    guard: Optional[dict] = None  # GuardRunner.stats() when guarded


class FullBatchTrainer:
    """Single-device full-batch training, optionally under a guarded step.

    ``guard`` (a :class:`~repro.runtime.resilience.GuardPolicy`) arms the
    resilience ladder (DESIGN.md §13): each step's candidate params + loss
    pass through one fused on-device non-finite reduction and commit only
    when finite; consecutive bad steps escalate skip → LR backoff →
    rollback to the last checkpoint. ``injector`` is the deterministic
    fault source — its ``grad`` site adds NaN/inf to every gradient leaf
    on fired steps (a 0.0 add otherwise, so clean numerics are bitwise
    unchanged and nothing retraces).
    """

    def __init__(self, model: GNNModel, opt: Optimizer,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 10,
                 guard: Optional[GuardPolicy] = None,
                 injector: Optional[FaultInjector] = None):
        self.model = model
        self.opt = opt
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.injector = injector
        self.guard = GuardRunner(guard) if guard is not None else None

        # the model's plan operands ride as arguments, not program constants
        @jit_hoisted
        def step(params, opt_state, x, labels, mask):
            loss, grads = model.loss_and_grads(params, x, labels, mask)
            with jax.named_scope("optimizer"):
                params, opt_state = opt.update(grads, opt_state, params)
            return params, opt_state, loss

        @jit_hoisted
        def step_guarded(params, opt_state, x, labels, mask, scale, poison):
            loss, grads = model.loss_and_grads(params, x, labels, mask)
            grads = jax.tree_util.tree_map(
                lambda g: g + poison.astype(g.dtype), grads)
            with jax.named_scope("optimizer"):
                p_new, s_new = opt.update(grads, opt_state, params)
            return guarded_update(params, opt_state, p_new, s_new, loss, scale)

        self._step = step
        self._step_guarded = step_guarded

    def fit(self, params, x, labels, mask, epochs: int,
            start_epoch: int = 0) -> TrainResult:
        opt_state = self.opt.init(params)
        restored = None
        if self.ckpt_dir:
            (params, opt_state), restored = restore_checkpoint(
                self.ckpt_dir, (params, opt_state)
            )
            if restored is not None:
                start_epoch = restored
        x, labels, mask = jnp.asarray(x), jnp.asarray(labels), jnp.asarray(mask)
        losses, times = [], []
        for epoch in range(start_epoch, epochs):
            with span("epoch", step=epoch) as timed:
                if self.guard is None:
                    params, opt_state, loss = self._step(
                        params, opt_state, x, labels, mask)
                else:
                    poison = (self.injector.grad_poison(epoch)
                              if self.injector is not None else 0.0)
                    params, opt_state, loss, ok = self._step_guarded(
                        params, opt_state, x, labels, mask,
                        jnp.float32(self.guard.scale), jnp.float32(poison))
                    action = self.guard.after_step(bool(ok), step=epoch)
                    if action == "rollback" and self.ckpt_dir:
                        (params, opt_state), _ = restore_checkpoint(
                            self.ckpt_dir, (params, opt_state))
                with span("loss_read"):
                    losses.append(float(loss))
            times.append(timed.seconds)
            if self.ckpt_dir and (epoch + 1) % self.ckpt_every == 0:
                save_checkpoint(self.ckpt_dir, epoch + 1, (params, opt_state),
                                injector=self.injector)
        return TrainResult(losses=losses, epoch_times=times, final_params=params,
                           restored_from=restored,
                           guard=self.guard.stats() if self.guard else None)


class MiniBatchTrainer:
    """Neighbour-sampled mini-batch GNN training — the third consumer of the
    plan pipeline, and the first whose graph size is independent of device
    memory.

    Per epoch: reshuffle the train seeds, batch them, sample the L-layer
    block stack per batch (``graph/sampling.py``), and run one optimizer
    step per batch with the loss on batch seeds only. Every layer runs
    ``models.gnn.apply_layer`` with ``LayerOps`` bound to the batch's
    bipartite operands: matmul aggregations ride the padded BSR pair
    through ``kops.bsr_spmm_pair`` (pallas|xla inner, the plan's backend),
    GAT/max ride the padded edge lists, and the Alg-1 sparse input path
    (when the plan bound it) streams per-batch COO feature operands.

    Compile discipline: the jitted step is shape-driven — all static
    bounds are read off array shapes, which the sampler's buckets
    quantise — so it retraces at most once per bucket *per input-path
    variant*: dense plans retrace ≤ n_buckets times; sparse plans can add
    one more trace per bucket if a batch overflows the COO cap and drops
    to the dense input path (the ``feat`` operand leaves the pytree).
    ``n_traces`` / ``n_infer_traces`` count retraces (incremented at
    trace time only); ``n_feature_overflows`` counts the overflow batches.
    """

    def __init__(
        self,
        config: GNNConfig,
        graph: Optional[CSRGraph],
        features: np.ndarray,
        labels: Optional[np.ndarray],
        train_mask: Optional[np.ndarray],
        opt: Optional[Optimizer],
        *,
        plan: Optional[SampledModelPlan] = None,
        fanouts=None,
        batch_size: int = 256,
        n_buckets: int = 2,
        engine: "str | None" = None,
        interpret: Optional[bool] = None,
        gamma: float = PAPER_GAMMA_DEFAULT,
        seed: int = 0,
        layout: "str | None" = None,
        infer_only: bool = False,
        guard: Optional[GuardPolicy] = None,
        injector: Optional[FaultInjector] = None,
        ckpt_dir: Optional[str] = None,
        ckpt_every: int = 5,
    ):
        if plan is None:
            if graph is None or fanouts is None:
                raise ValueError("need either a plan or (graph, fanouts)")
            plan = lower_sampled(
                config, graph, features, fanouts=fanouts,
                batch_size=batch_size, n_buckets=n_buckets, gamma=gamma,
                engine=engine, seed=seed, layout=layout,
                infer_only=infer_only)
        self.config = config
        self.plan = plan
        self.sampler = plan.sampler
        self.backend = get_backend(plan.backend)
        self.opt = opt
        self.interpret = interpret
        # permutation contract (DESIGN.md §9): a reordered plan's sampler
        # walks the renumbered graph, so the trainer holds features/labels
        # in execution order and maps every user-facing node id through
        # inv_perm; logits come back per seed in request order, so no
        # output permutation exists to leak
        lp = plan.layout
        self._inv_perm_np = (np.asarray(lp.inv_perm, dtype=np.int64)
                             if lp is not None and lp.permutes else None)
        self.features = np.asarray(features, dtype=np.float32)
        self.n_nodes = int(self.features.shape[0])
        # infer-only serving: no labels / train split / optimizer required,
        # and the loss/grad closures are never built (plan.infer_only, or
        # simply constructing without an optimizer)
        self.infer_only = bool(getattr(plan, "infer_only", False) or opt is None)
        self.labels_np = (np.zeros(self.n_nodes, dtype=np.int32)
                          if labels is None
                          else np.asarray(labels, dtype=np.int32))
        if self._inv_perm_np is not None:
            self.features = self.features[lp.perm]
            self.labels_np = self.labels_np[lp.perm]
        self.train_ids = (np.zeros(0, dtype=np.int64) if train_mask is None
                          else self._to_exec(
                              np.flatnonzero(np.asarray(train_mask))))
        self.params = init_params(config, jax.random.PRNGKey(seed))
        self.opt_state = opt.init(self.params) if opt is not None else None
        self._shuffle_rng = np.random.default_rng(seed + 1)
        # resilience (DESIGN.md §13): guarded steps + checkpoints that
        # capture the sampler/epoch RNG state, so a resume replays the
        # exact batch sequence a straight run would have drawn
        self.injector = injector
        self.guard = (GuardRunner(guard, restore_fn=self.restore)
                      if guard is not None else None)
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = int(ckpt_every)
        self._epoch_idx = 0
        self._global_step = 0

        self._sparse0 = plan.layers[0].feature_path == "sparse"
        self._is_gat = config.kind in ("GAT", "GT")
        self._is_max = plan.aggregation == "max"
        # fused BSR flash-attention: the plan bound spmm_attention and the
        # sampler emits the per-batch BSR pair to run it on
        self._fuse_attention = (self.sampler.emit_bsr and any(
            l.agg_primitive.endswith("spmm_attention") for l in plan.layers))
        self._agg_mode = ("bsr" if self.sampler.emit_bsr
                          else "max" if self._is_max else "segment")
        self._inner = plan.backend if plan.backend in ("pallas", "xla") else "xla"

        self.n_traces = 0
        self.n_infer_traces = 0
        self.n_feature_overflows = 0
        self._build()

    def _to_exec(self, node_ids: np.ndarray) -> np.ndarray:
        """User node ids -> the reordered plan's execution ids (identity
        for unreordered plans). Rejects out-of-range ids with a clear
        error: a negative id would otherwise wrap through ``inv_perm``
        (or the graph's indptr) and silently gather another node's
        neighbourhood."""
        node_ids = np.asarray(node_ids, dtype=np.int64)
        bad = node_ids[(node_ids < 0) | (node_ids >= self.n_nodes)]
        if bad.size:
            raise ValueError(
                f"node ids out of range [0, {self.n_nodes}): "
                f"{bad[:8].tolist()}{'...' if bad.size > 8 else ''}")
        if self._inv_perm_np is None:
            return node_ids
        return self._inv_perm_np[node_ids]

    # -- per-batch LayerOps bindings ----------------------------------------

    def _make_agg(self, blk: dict, n_out: int):
        mode, inner, interpret = self._agg_mode, self._inner, self.interpret
        if mode == "bsr":
            fwd = (blk["fwd"]["rows"], blk["fwd"]["cols"],
                   blk["fwd"]["first"], blk["fwd"]["blocks"])
            bwd = (blk["bwd"]["rows"], blk["bwd"]["cols"],
                   blk["bwd"]["first"], blk["bwd"]["blocks"])

            def agg(u):
                d = u.shape[-1]
                if inner == "pallas":  # MXU feature tiling needs F % bf == 0
                    f_pad = -(-d // 128) * 128
                    u_in = jnp.pad(u, ((0, 0), (0, f_pad - d)))
                else:
                    u_in = u
                y = kops.bsr_spmm_pair(fwd, bwd, u_in, n_out, 128,
                                       interpret, inner)
                return y[:, :d].astype(u.dtype)

            return agg
        # segment paths reuse the shared gather-scatter primitive (the same
        # op the full-batch baseline and gather backend execute)
        src, dst, w = blk["edge_src"], blk["edge_dst"], blk["edge_w"]
        seg_kind = "max" if mode == "max" else "sum"

        def agg(u):
            return gather_scatter_aggregate(src, dst, w, u, n_out, seg_kind)

        return agg

    def _make_gat(self, blk: dict, n_out: int, n_in: int):
        if self._fuse_attention:
            # fused flash-attention over the batch's padded bipartite BSR
            # pair; caps are lcm(br,bc)-aligned, so they ARE the padded dims
            fwd, bwd = blk["fwd"], blk["bwd"]
            fwd5 = (fwd["rows"], fwd["cols"], fwd["first"],
                    kops.derive_last_in_row(fwd["rows"]), fwd["blocks"])
            bwd4 = (bwd["rows"], bwd["cols"], bwd["first"], bwd["blocks"])
            geom = (n_out, n_in, n_out, n_in, n_in, n_out)
            inner, interpret = self._inner, self.interpret

            def gat_attention(z, a_src, a_dst, heads):
                z3 = z.reshape(z.shape[0], heads, -1)
                return kops.sparse_mha_pair(fwd5, bwd4, z3, a_src, a_dst,
                                            geom, 0, interpret, inner)

            return gat_attention
        backend = self.backend
        src, dst = blk["edge_src"], blk["edge_dst"]

        def gat_attention(z, a_src, a_dst, heads):
            z3 = z.reshape(z.shape[0], heads, -1)
            return backend.segment_softmax_aggregate(
                z3, a_src, a_dst, src, dst, n_out)

        return gat_attention

    def _make_xw(self, data: dict):
        # the plan's "gather.feature_matmul_sparse": the per-batch COO is
        # exactly the gather backend's edge-list operand with W as the
        # gathered matrix, so bind that registry primitive directly
        rows, cols, vals = data["feat"]
        operand = EdgeListOperand(
            src=cols, dst=rows, weights=vals,
            n_rows=data["valid"][0].shape[0])
        gather = get_backend("gather")

        def xw(w):
            return gather.spmm(operand, w)

        return xw

    def _logits(self, params, data, collect=False):
        config = self.config
        n = config.n_layers
        x = data["x"]
        levels = []
        for i in range(n):
            blk = data["blocks"][i]
            n_out = data["valid"][i + 1].shape[0]
            n_in = data["valid"][i].shape[0]
            agg = self._make_agg(blk, n_out)
            # the plan's fused-epilogue binding over the per-batch bipartite
            # operand: same contract as the full-batch op, XLA fuses the
            # epilogue into the aggregation's consumer
            fe = (compose_epilogue(agg)
                  if self.plan.layers[i].epilogue is not None else None)
            ops = LayerOps(
                aggregate=agg,
                xw=(self._make_xw(data) if i == 0 and "feat" in data else None),
                gat_attention=(self._make_gat(blk, n_out, n_in)
                               if self._is_gat else None),
                restrict=lambda u, _n=n_out: u[:_n],
                fused_epilogue=fe,
            )
            x = apply_layer(config, params["layers"][i], x, ops,
                            is_last=(i == n - 1))
            # re-zero padded rows: keeps dump-row garbage (and -inf from
            # empty max segments) out of the next layer's operands
            x = jnp.where(data["valid"][i + 1][:, None], x, 0.0)
            if collect:
                levels.append(x)
        if collect:
            # per-level activations: levels[l] rows are the level-(l+1)
            # frontier (blocks[l].dst_nodes); levels[-1] is the logits —
            # the serving engine's historical-embedding feed
            return tuple(levels)
        return x  # [node_caps[L], n_classes], padded rows zero

    def _build(self):
        opt = self.opt

        def loss_fn(params, data):
            logits = self._logits(params, data)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, data["labels"][:, None].astype(jnp.int32), axis=-1)[:, 0]
            seed_mask = data["valid"][-1]
            denom = jnp.maximum(seed_mask.sum(), 1)
            return jnp.where(seed_mask, nll, 0.0).sum() / denom

        def step(params, opt_state, data):
            self.n_traces += 1  # trace-time side effect: the compile counter
            loss, grads = jax.value_and_grad(loss_fn)(params, data)
            params, opt_state = opt.update(grads, opt_state, params)
            return params, opt_state, loss

        def step_guarded(params, opt_state, data, scale, poison):
            self.n_traces += 1
            loss, grads = jax.value_and_grad(loss_fn)(params, data)
            grads = jax.tree_util.tree_map(
                lambda g: g + poison.astype(g.dtype), grads)
            p_new, s_new = opt.update(grads, opt_state, params)
            return guarded_update(params, opt_state, p_new, s_new, loss, scale)

        def value_and_grad(params, data):
            return jax.value_and_grad(loss_fn)(params, data)

        def infer(params, data):
            self.n_infer_traces += 1
            return self._logits(params, data)

        def infer_levels(params, data):
            self.n_infer_traces += 1
            return self._logits(params, data, collect=True)

        if self.infer_only:
            def _no_train(*_a, **_k):
                raise RuntimeError(
                    "trainer is infer-only (plan.infer_only or no optimizer):"
                    " loss/grad closures were not built")
            self._step = self._value_and_grad = _no_train
            self._step_guarded = _no_train
        else:
            self._step = jax.jit(step)
            self._step_guarded = jax.jit(step_guarded)
            self._value_and_grad = jax.jit(value_and_grad)
        self._infer = jax.jit(infer)
        self._infer_levels = jax.jit(infer_levels)

    # -- host-side batch marshalling ----------------------------------------

    def _batch_arrays(self, batch: SampledBatch) -> dict:
        blocks = []
        for blk in batch.blocks:
            d = {
                "edge_src": jnp.asarray(blk.edge_src),
                "edge_dst": jnp.asarray(blk.edge_dst),
                "edge_w": jnp.asarray(blk.edge_w),
            }
            if self._agg_mode == "bsr":
                d["fwd"] = {k: jnp.asarray(v) for k, v in blk.fwd_bsr.items()}
                d["bwd"] = {k: jnp.asarray(v) for k, v in blk.bwd_bsr.items()}
            blocks.append(d)
        data = {
            "x": jnp.asarray(batch.x),
            "labels": jnp.asarray(batch.labels),
            "valid": tuple(jnp.asarray(v) for v in batch.valid),
            "blocks": tuple(blocks),
        }
        if self._sparse0:
            if batch.feat_coo is not None:
                data["feat"] = tuple(jnp.asarray(a) for a in batch.feat_coo)
            else:  # denser than the template's cap: dense-path fallback
                self.n_feature_overflows += 1
        return data

    # -- training -----------------------------------------------------------

    def train_epoch(self) -> float:
        """One reshuffled pass over the train seeds; mean seed-weighted loss."""
        if self.infer_only:
            raise RuntimeError(
                "trainer is infer-only (plan.infer_only or no optimizer): "
                "training is unavailable")
        total, count = 0.0, 0
        batches = self.sampler.epoch_batches(
            self.train_ids, self.features, self.labels_np,
            rng=self._shuffle_rng)
        n_batches = -(-self.train_ids.shape[0] // self.sampler.batch_size)
        with span("epoch", step=self._epoch_idx):
            for _ in range(n_batches):
                with span("batch"):
                    with span("sample"):
                        batch = next(batches)
                    with span("upload"):
                        data = self._batch_arrays(batch)
                    with span("dispatch"):
                        if self.guard is None:
                            self.params, self.opt_state, loss = self._step(
                                self.params, self.opt_state, data)
                        else:
                            poison = (self.injector.grad_poison(
                                self._global_step)
                                if self.injector is not None else 0.0)
                            self.params, self.opt_state, loss, ok = \
                                self._step_guarded(
                                    self.params, self.opt_state, data,
                                    jnp.float32(self.guard.scale),
                                    jnp.float32(poison))
                    if self.guard is not None:
                        # rollback (the runner's restore_fn == self.restore)
                        # also rewinds the rng streams, so the replayed
                        # epochs redraw the exact batches the first attempt
                        # drew
                        self.guard.after_step(bool(ok), step=self._global_step)
                    self._global_step += 1
                    with span("loss_read"):
                        total += float(loss) * batch.n_seeds
                    count += batch.n_seeds
        return total / max(count, 1)

    # -- checkpoint / resume (DESIGN.md §13 RNG-state contract) -------------

    def _ckpt_state(self) -> dict:
        return {
            "params": self.params,
            "opt": self.opt_state,
            "epoch": np.int64(self._epoch_idx),
            "global_step": np.int64(self._global_step),
            "shuffle_rng": pack_rng_state(self._shuffle_rng),
            "sampler_rng": pack_rng_state(self.sampler.rng),
        }

    def save(self) -> Optional[str]:
        """Checkpoint params + optimizer state + epoch/step counters + the
        shuffle and sampler RNG states — everything a deterministic resume
        needs (restored runs replay the exact batch sequence)."""
        if not self.ckpt_dir:
            return None
        return save_checkpoint(self.ckpt_dir, self._epoch_idx,
                               self._ckpt_state(), injector=self.injector)

    def restore(self) -> Optional[int]:
        """Restore the latest checkpoint (params, opt state, RNG streams,
        counters); returns the restored epoch or None if no checkpoint."""
        if not self.ckpt_dir:
            return None
        state, step = restore_checkpoint(self.ckpt_dir, self._ckpt_state())
        if step is None:
            return None
        self.params = state["params"]
        self.opt_state = state["opt"]
        self._epoch_idx = int(state["epoch"])
        self._global_step = int(state["global_step"])
        unpack_rng_state(self._shuffle_rng, state["shuffle_rng"])
        unpack_rng_state(self.sampler.rng, state["sampler_rng"])
        return step

    def fit(self, epochs: int) -> TrainResult:
        restored = self.restore() if self.ckpt_dir else None
        losses, times = [], []
        while self._epoch_idx < epochs:
            t0 = time.perf_counter()
            losses.append(self.train_epoch())
            times.append(time.perf_counter() - t0)
            self._epoch_idx += 1
            if self.ckpt_dir and self._epoch_idx % self.ckpt_every == 0:
                self.save()
        return TrainResult(losses=losses, epoch_times=times,
                           final_params=self.params, restored_from=restored,
                           guard=self.guard.stats() if self.guard else None)

    def loss_and_grads(self, seeds: Optional[np.ndarray] = None):
        """Loss + grads at the current params for one batch (no update) —
        the probe the full-fanout parity tests use. ``seeds`` are user
        node ids (mapped through the reordered plan's inv_perm)."""
        seeds = self.train_ids if seeds is None else self._to_exec(seeds)
        batch = self.sampler.sample_batch(seeds, self.features, self.labels_np)
        return self._value_and_grad(self.params, self._batch_arrays(batch))

    # -- inference ----------------------------------------------------------

    def infer_logits(self, node_ids: np.ndarray) -> np.ndarray:
        """Sampled-neighbourhood logits for arbitrary nodes (user ids);
        row i is the logits of ``node_ids[i]``, in request order.

        The request may be any size (chunked through the sampler's
        ``split_request``), unsorted, and contain duplicates: ids are
        deduplicated before sampling — a repeated seed would otherwise
        collide in the sampler's global->local relabel table — and the
        unique rows are scattered back so duplicates get identical rows.
        Out-of-range ids raise ``ValueError`` (see ``_to_exec``)."""
        node_ids = np.asarray(node_ids, dtype=np.int64).reshape(-1)
        exec_ids = self._to_exec(node_ids)
        uniq, inv = np.unique(exec_ids, return_inverse=True)
        rows = np.zeros((uniq.shape[0], self.config.layer_dims[-1]),
                        np.float32)
        off = 0
        for chunk in self.sampler.split_request(uniq):
            batch = self.sampler.sample_batch(chunk, self.features)
            logits = self._infer(self.params, self._batch_arrays(batch))
            rows[off: off + chunk.shape[0]] = np.asarray(logits)[: chunk.shape[0]]
            off += chunk.shape[0]
        return rows[inv]

    def evaluate(self, mask: np.ndarray) -> float:
        """Accuracy on the masked nodes (mask in user node order).

        An all-``False`` mask returns 0.0 by contract (there is nothing
        to be right about) rather than dividing by zero."""
        ids = np.flatnonzero(np.asarray(mask))
        if ids.shape[0] == 0:
            return 0.0
        pred = np.argmax(self.infer_logits(ids), axis=-1)
        return float(np.mean(pred == self.labels_np[self._to_exec(ids)]))


class DistributedGNNTrainer:
    """Node-sharded GNN training on a 1-D 'data' mesh (the MPI analog).

    The per-step program (inside shard_map, per rank):
      1. halo_exchange            — ghost features in          (paper 2)
      2. fused local aggregation  — BSR SpMM on [local|ghost]  (paper Alg 2/3)
      3. dense / Alg-1 sparse transforms per the plan          (paper Alg 1)
      4. pipelined backward       — psum(dW_l) issued before layer l-1
                                    (paper 3); ghost grads return through
                                    the halo exchange's custom VJP
      5. optimizer                — replicated update          (paper 4)

    Every layer runs ``models.gnn.apply_layer`` — the same algebra as the
    single-device model — with ``LayerOps`` bound to the distributed
    backend primitives the ``DistributedModelPlan`` selected.
    """

    def __init__(self, dist: DistributedGraph, config: GNNConfig,
                 opt: Optimizer, mesh: Optional[Mesh] = None,
                 interpret: Optional[bool] = None, seed: int = 0,
                 plan: Optional[DistributedModelPlan] = None,
                 gamma: float = PAPER_GAMMA_DEFAULT,
                 guard: Optional[GuardPolicy] = None,
                 injector: Optional[FaultInjector] = None,
                 monitor=None, clock=None):
        self.dist = dist
        self.config = config
        self.opt = opt
        if plan is None:
            plan = lower_distributed(config, dist, gamma=gamma)
        self.plan = plan
        self.backend = DistributedBackend(inner=plan.inner)
        devices = np.asarray(jax.devices()[: dist.n_ranks])
        if mesh is None:
            mesh = Mesh(devices, axis_names=("data",))
        self.mesh = mesh
        self.interpret = interpret
        self.params = init_params(config, jax.random.PRNGKey(seed))
        self.opt_state = opt.init(self.params)
        # resilience control plane (DESIGN.md §13): guarded steps commit
        # only finite updates (the non-finite census rides the pipelined
        # backward, fused per layer); every step feeds per-rank heartbeats
        # into ``monitor`` (a HeartbeatMonitor) with injector-dictated
        # suppression (rank_dead) / inflation (rank_slow), against
        # ``clock`` (a VirtualClock advanced by measured step time)
        self.injector = injector
        self.monitor = monitor
        self.clock = clock
        # accept an existing runner so the ladder state (scale, counters)
        # survives trainer rebuilds across elastic recoveries
        self.guard = (guard if isinstance(guard, GuardRunner)
                      else GuardRunner(guard) if guard is not None else None)
        self._step_idx = 0
        self._build_step()

    def set_rollback(self, restore_fn) -> None:
        """Install the guard ladder's rollback hook (rung 2)."""
        if self.guard is not None:
            self.guard.restore_fn = restore_fn

    def guard_stats(self) -> dict:
        return self.guard.stats() if self.guard is not None else {}

    def _build_step(self):
        dist, plan, config = self.dist, self.plan, self.config
        backend = self.backend
        n_local, n_ghost = dist.n_local, dist.n_ghost
        interpret = self.interpret
        opt = self.opt
        sparse0 = plan.layers[0].feature_path == "sparse"
        is_gat = config.kind in ("GAT", "GT")
        is_max = plan.aggregation == "max"
        fuse_attn = is_gat and "dist_spmm_attention" in (
            plan.layers[0].agg_primitive)
        # split-phase overlap (DESIGN.md §11): the plan bound the split
        # compositions; ship the interior/boundary streams instead of the
        # bulk pair and unroll only the live ring shifts
        ov = plan.overlap
        use_split = ov is not None
        shifts = ov.live_shifts if use_split else None
        # ghost-buffer rotation contract: adjacent layers draw from
        # distinct slots so layer k+1's exchange can start before layer
        # k's boundary pass retires (buffer assignment keeps both live)
        self.ghost_ring = GhostBufferRing(
            ov.double_buffer_slots if use_split else 2)
        self.ghost_slots = tuple(self.ghost_ring.acquire(i)
                                 for i in range(config.n_layers))

        def _arrays(d):
            return (d["rows"], d["cols"], d["first"], d["blocks"])

        def rank_compute(params, data, with_guard=False):
            # squeeze the leading (sharded) rank axis
            data = jax.tree_util.tree_map(lambda a: a[0], data)
            send_idx, recv_slot = data["send_idx"], data["recv_slot"]

            def with_ghosts(u):
                ghost = halo_exchange(u, send_idx, recv_slot, n_ghost,
                                      "data", shifts)
                return jnp.concatenate([u, ghost], axis=0)

            fused_agg = None
            gat_attention = None
            if is_max:
                def agg(u):
                    return backend.dist_segment_max(
                        with_ghosts(u), data["edge_src"], data["edge_dst"],
                        n_local)
            elif use_split:
                int_fwd, int_bwd = _arrays(data["fwd_int"]), _arrays(
                    data["bwd_int"])
                bnd_fwd, bnd_bwd = _arrays(data["fwd_bnd"]), _arrays(
                    data["bwd_bnd"])
                agg = backend.dist_spmm_split_transposed_vjp(
                    int_fwd, int_bwd, bnd_fwd, bnd_bwd, send_idx, recv_slot,
                    n_local, n_ghost, "data", shifts=shifts,
                    interpret=interpret)
                fused_agg = backend.dist_spmm_fused_epilogue_split(
                    int_fwd, int_bwd, bnd_fwd, bnd_bwd, send_idx, recv_slot,
                    n_local, n_ghost, "data", shifts=shifts,
                    interpret=interpret)
                if fuse_attn:
                    gat_attention = backend.dist_spmm_attention_split(
                        int_fwd, int_bwd, bnd_fwd, bnd_bwd, send_idx,
                        recv_slot, n_local, n_ghost, "data", shifts=shifts,
                        interpret=interpret)
            else:
                fwd_arrays = _arrays(data["fwd"])
                bwd_arrays = _arrays(data["bwd"])
                agg = backend.dist_spmm_transposed_vjp(
                    fwd_arrays, bwd_arrays, send_idx, recv_slot,
                    n_local, n_ghost, "data", interpret=interpret)
                fused_agg = backend.dist_spmm_fused_epilogue(
                    fwd_arrays, bwd_arrays, send_idx, recv_slot,
                    n_local, n_ghost, "data", interpret=interpret)
                if fuse_attn:
                    # fused flash-attention composition: halo exchange + the
                    # sparse-MHA pair over the local [local|ghost] operands
                    gat_attention = backend.dist_spmm_attention(
                        fwd_arrays, bwd_arrays, send_idx, recv_slot,
                        n_local, n_ghost, "data", interpret=interpret)

            xw0 = None
            if sparse0:
                ff, fb = data["feat_fwd"], data["feat_bwd"]
                xw0 = backend.dist_feature_matmul_sparse(
                    _arrays(ff), _arrays(fb),
                    n_local, plan.feat_f_pad, interpret=interpret)

            if is_gat and gat_attention is None:
                def gat_attention(z, a_src, a_dst, heads):
                    buf = with_ghosts(z)
                    z3 = buf.reshape(buf.shape[0], heads, -1)
                    return backend.dist_segment_softmax_aggregate(
                        z3, a_src, a_dst, data["edge_src"], data["edge_dst"],
                        n_local)

            layer_ops = [
                LayerOps(aggregate=agg, xw=(xw0 if i == 0 else None),
                         gat_attention=gat_attention,
                         fused_epilogue=(fused_agg
                                         if plan.layers[i].epilogue is not None
                                         else None))
                for i in range(config.n_layers)
            ]
            layer_fns = arch_layer_fns(config, layer_ops)
            return pipelined_value_and_grad(
                layer_fns, params, data["x"], data["labels"], data["mask"],
                axis_name="data", with_guard=with_guard)

        def rank_step(params, opt_state, data):
            loss, grads = rank_compute(params, data)
            params_new, opt_state_new = opt.update(grads, opt_state, params)
            return params_new, opt_state_new, loss

        def rank_step_guarded(params, opt_state, data, scale, poison):
            # the backward's own non-finite census (fused per layer inside
            # pipelined_value_and_grad) folds into the commit decision
            loss, grads, bad = rank_compute(params, data, with_guard=True)
            grads = jax.tree_util.tree_map(
                lambda g: g + poison.astype(g.dtype), grads)
            params_new, opt_state_new = opt.update(grads, opt_state, params)
            return guarded_update(params, opt_state, params_new,
                                  opt_state_new, loss, scale, extra_bad=bad)

        # -- device-resident sharded inputs --------------------------------
        data_np = dict(
            send_idx=dist.send_idx, recv_slot=dist.recv_slot,
            x=dist.features, labels=dist.labels, mask=dist.mask,
        )
        if use_split and not is_max:
            data_np["fwd_int"] = dist.fwd_interior
            data_np["bwd_int"] = dist.bwd_interior
            data_np["fwd_bnd"] = dist.fwd_boundary
            data_np["bwd_bnd"] = dist.bwd_boundary
        elif not is_max:
            data_np["fwd"] = dist.fwd
            data_np["bwd"] = dist.bwd
        if sparse0:
            data_np["feat_fwd"] = plan.feat_fwd
            data_np["feat_bwd"] = plan.feat_bwd
        if is_gat or is_max:
            data_np["edge_src"] = dist.edge_src
            data_np["edge_dst"] = dist.edge_dst

        sharded = jax.tree_util.tree_map(lambda _: P("data"), data_np)
        replicated = P()
        self._step = jax.jit(shard_map(
            rank_step,
            mesh=self.mesh,
            in_specs=(replicated, replicated, sharded),
            out_specs=(replicated, replicated, replicated),
            check_vma=False,
        ))
        self._step_guarded = jax.jit(shard_map(
            rank_step_guarded,
            mesh=self.mesh,
            in_specs=(replicated, replicated, sharded, replicated,
                      replicated),
            out_specs=(replicated, replicated, replicated, replicated),
            check_vma=False,
        ))
        self._value_and_grad = jax.jit(shard_map(
            rank_compute,
            mesh=self.mesh,
            in_specs=(replicated, sharded),
            out_specs=(replicated, replicated),
            check_vma=False,
        ))

        dev = lambda arr: jax.device_put(
            np.asarray(arr), NamedSharding(self.mesh, P("data"))
        )
        self._data = jax.tree_util.tree_map(dev, data_np)

    def train_epoch(self) -> float:
        with span("epoch", step=self._step_idx) as timed:
            with span("dispatch"):
                if self.guard is None:
                    self.params, self.opt_state, loss = self._step(
                        self.params, self.opt_state, self._data,
                    )
                else:
                    poison = (self.injector.grad_poison(self._step_idx)
                              if self.injector is not None else 0.0)
                    self.params, self.opt_state, loss, ok = self._step_guarded(
                        self.params, self.opt_state, self._data,
                        jnp.float32(self.guard.scale), jnp.float32(poison))
            if self.guard is not None:
                self.guard.after_step(bool(ok), step=self._step_idx)
            with span("loss_read"):
                loss = float(loss)  # blocks: the step's wall time is complete
        self._feed_heartbeats(timed.seconds)
        self._step_idx += 1
        return loss

    def _feed_heartbeats(self, dt: float) -> None:
        """Per-step heartbeat feed (DESIGN.md §13): every rank reports its
        step duration to the HeartbeatMonitor. The injector stands in for
        real hardware faults — a ``rank_dead`` fire suppresses that rank's
        heartbeat entirely, ``rank_slow`` inflates its reported step time;
        the VirtualClock (advanced by measured wall time) lets DEAD
        classification trip on simulated rather than wall-clock timeouts."""
        if self.monitor is None:
            return
        if self.clock is not None:
            self.clock.advance(dt)
        for r in range(self.dist.n_ranks):
            if (self.injector is not None
                    and self.injector.fires("rank_dead", self._step_idx,
                                            rank=r)):
                continue  # a dead rank stops heartbeating
            factor = (self.injector.slow_factor(self._step_idx, r)
                      if self.injector is not None else 1.0)
            self.monitor.heartbeat(r, dt * factor)

    def loss_and_grads(self):
        """Global loss + psum'd grads at the current params (no update) —
        the probe the distributed-vs-single-device parity tests use."""
        return self._value_and_grad(self.params, self._data)
