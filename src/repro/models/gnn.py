"""GNN model zoo — GCN, GraphSAGE, GIN, GAT (paper §III-A).

GAT is DGL's ogbn-arxiv ``GATConv`` stack: per head ``k``,
``O^k = Σ_j softmax_j(leaky_relu(a_dst^k·Z_i^k + a_src^k·Z_j^k)) Z_j^k
+ (H·W_res)^k`` with ``Z = H·W``; hidden layers concatenate the heads
(``d_out = heads · D``), add a bias and apply the activation; the last
averages the heads and adds its bias.

Functional style: ``init(key) -> params`` and ``apply(params, x) -> logits``.
A model executes a ``ModelPlan`` produced by the lowering pass
(``core/lowering.py``): each layer's feature transform and aggregation run
the backend primitives the plan selected, so there is no runtime dispatch —
and no method patching — on the hot path. Constructing a ``GNNModel``
without a plan lowers one on the spot (dense paths everywhere, since the
feature matrix is unknown at that point).

Attention archs (GAT, and the GT graph-transformer layer) lower onto the
fused flash-attention primitive ``spmm_attention`` by default on
pallas/xla (BSR or CSR row-gather kernels, by the fill rule) — per-edge
scores and weights never materialise in HBM — and
fall back to the ``segment_softmax_aggregate`` gather path when the plan
was lowered with ``fuse_attention=False`` or on the gather backend.

Note: a plan whose layer 0 chose the sparse path embeds BSR(X)/BSR(Xᵀ) of
the feature matrix it was lowered against; ``apply`` then specialises layer
0 to that X (the paper's synthesized programs are specialised the same way).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Literal, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.backends import get_backend
from repro.core.lowering import LayerPlan, ModelPlan, lower
from repro.graph.csr import CSRGraph

GNNKind = Literal["GCN", "SAGE", "GIN", "GAT", "GT"]


def xavier_init(key, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[0], shape[-1]
    scale = jnp.sqrt(2.0 / (fan_in + fan_out))
    return jax.random.normal(key, shape, dtype) * scale


@dataclasses.dataclass
class GNNConfig:
    kind: GNNKind
    layer_dims: Sequence[int]  # [in, hidden..., out] — paper uses 3-layer, h=32
    aggregation: str = "gcn"  # sum | mean | gcn | max
    activation: Callable = jax.nn.relu
    gat_heads: int = 4
    dropout: float = 0.0

    @property
    def n_layers(self) -> int:
        return len(self.layer_dims) - 1


def init_params(config: GNNConfig, key) -> dict:
    """Xavier parameter pytree for any arch — the single init shared by
    single-device models and the distributed trainer (which used to fork a
    private GCN-only scheme)."""
    params: dict = {"layers": []}
    keys = jax.random.split(key, config.n_layers * 4)
    for i in range(config.n_layers):
        d_in, d_out = config.layer_dims[i], config.layer_dims[i + 1]
        k0, k1, k2, k3 = keys[4 * i: 4 * i + 4]
        if config.kind == "GCN":
            layer = {"w": xavier_init(k0, (d_in, d_out)), "b": jnp.zeros((d_out,))}
        elif config.kind == "SAGE":
            layer = {
                "w_self": xavier_init(k0, (d_in, d_out)),
                "w_neigh": xavier_init(k1, (d_in, d_out)),
                "b": jnp.zeros((d_out,)),
            }
        elif config.kind == "GIN":
            layer = {
                "eps": jnp.zeros(()),
                "w1": xavier_init(k0, (d_in, d_out)),
                "b1": jnp.zeros((d_out,)),
                "w2": xavier_init(k1, (d_out, d_out)),
                "b2": jnp.zeros((d_out,)),
            }
        elif config.kind == "GAT":
            # DGL's GATConv (ogbn-arxiv example): heads concatenated in
            # hidden layers (d_out = heads · D), averaged in the last (D =
            # d_out); a bias-free residual projection to heads · D
            h = config.gat_heads
            is_last = i == config.n_layers - 1
            if not is_last and d_out % h:
                raise ValueError(f"GAT hidden width {d_out} is not a "
                                 f"multiple of {h} heads")
            dh = d_out if is_last else d_out // h
            layer = {
                "w": xavier_init(k0, (d_in, h * dh)),
                "a_src": xavier_init(k1, (h, dh)),
                "a_dst": xavier_init(k2, (h, dh)),
                "w_res": xavier_init(k3, (d_in, h * dh)),
                "b": jnp.zeros((dh if is_last else h * dh,)),
            }
        elif config.kind == "GT":
            h = config.gat_heads
            dh = max(d_out // h, 1)
            layer = {
                "w": xavier_init(k0, (d_in, h * dh)),
                "a_src": xavier_init(k1, (h, dh)),
                "a_dst": xavier_init(k2, (h, dh)),
                "b": jnp.zeros((d_out,)),
                "proj": xavier_init(k3, (h * dh, d_out)),
                # graph-transformer residual branch (pre-attention input)
                "w_res": xavier_init(jax.random.fold_in(k3, 1),
                                     (d_in, d_out)),
            }
        else:
            raise ValueError(config.kind)
        params["layers"].append(layer)
    return params


@dataclasses.dataclass
class LayerOps:
    """The execution primitives one layer's algebra runs on.

    ``apply_layer`` is the single definition of each arch's per-layer math;
    bindings differ by context: the single-device model wires ``aggregate``
    to the plan's fused graph op, the distributed trainer wires it to the
    halo-exchange + local-BSR composition (``backends/distributed.py``).
    """

    aggregate: Callable[[jax.Array], jax.Array]  # u -> A @ u
    # layer-0 Alg-1 sparse binding: w -> X @ w over pre-built BSR(X); None
    # means the dense MXU path (x @ w)
    xw: Optional[Callable] = None
    # GAT edge-softmax: (z [N, heads*dh], a_src, a_dst, heads) -> [N, heads, dh]
    gat_attention: Optional[Callable] = None
    # bipartite mini-batch blocks: maps a src-frontier tensor onto the dst
    # frontier (destinations occupy the leading rows of the src frontier, so
    # this is a leading-row slice). None = full-graph, src set == dst set.
    restrict: Optional[Callable] = None
    # fused-epilogue aggregation (DESIGN.md §8):
    # (u, self_term=None, bias=None, alpha=None, activation="none") ->
    # act(A·u + alpha·self_term + bias). Bound iff the layer's plan carries
    # an ``EpiloguePlan``; when None the algebra runs the unfused sequence.
    fused_epilogue: Optional[Callable] = None


def _scoped(name: str, fn: Optional[Callable]) -> Optional[Callable]:
    """``fn`` run under ``jax.named_scope(name)``, so that its device ops
    carry the layer's phase in their metadata; None stays None."""
    if fn is None:
        return None

    def run(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    return run


def apply_layer(config: GNNConfig, layer: dict, x: jax.Array, ops: LayerOps,
                is_last: bool) -> jax.Array:
    """One layer of any arch, on the given primitives (the shared algebra).

    When ``ops.fused_epilogue`` is bound (the plan carried an
    ``EpiloguePlan``), the bias add / self-term combine / ReLU run inside
    the aggregation primitive instead of as separate ops — same algebra,
    re-associated so the epilogue lands on the SpMM output tile:

    * GCN  — ``relu(A·(X·W) + b)``
    * SAGE — ``A(X)·Wn == A(X·Wn)`` (A is linear), so
             ``relu(A·(X·Wn) + X·Ws + b)`` is one fused aggregation
    * GIN  — sparse path fuses the full MLP input
             ``act(A·u + (1+eps)·u + b1)``; dense path fuses the self-term
             combine ``A·x + (1+eps)·x``

    Only ReLU lowers into the primitive (the saved-mask VJP contract); any
    other ``config.activation`` stays outside the fused call. The gating
    here must stay in sync with ``core/lowering.py:_epilogue_binding`` —
    the plan's ``EpiloguePlan`` records what this function executes
    (``tests/test_fused_epilogue.py`` pins both sides).
    """
    kind = config.kind
    xw = _scoped("transform", ops.xw)
    mm = xw if xw is not None else _scoped("transform", lambda w: x @ w)
    res = ops.restrict if ops.restrict is not None else (lambda u: u)
    fe = _scoped("aggregate", ops.fused_epilogue)
    ops = dataclasses.replace(
        ops, aggregate=_scoped("aggregate", ops.aggregate),
        gat_attention=_scoped("aggregate", ops.gat_attention))
    relu_ok = config.activation is jax.nn.relu
    post = "relu" if (relu_ok and not is_last) else "none"
    if kind == "GCN":
        # transform-then-aggregate (standard GCN ordering A (X W))
        if fe is not None:
            y = fe(mm(layer["w"]), bias=layer["b"], activation=post)
            return y if (is_last or post == "relu") else config.activation(y)
        y = ops.aggregate(mm(layer["w"])) + layer["b"]
    elif kind == "SAGE":
        if fe is not None:
            y = fe(mm(layer["w_neigh"]), self_term=res(mm(layer["w_self"])),
                   bias=layer["b"], activation=post)
            return y if (is_last or post == "relu") else config.activation(y)
        y = res(mm(layer["w_self"])) + ops.aggregate(x) @ layer["w_neigh"] + layer["b"]
    elif kind == "GIN":
        if xw is not None:
            # "sum" aggregation is linear, so z@W1 re-associates to
            # (1+eps)(X@W1) + A(X@W1) — sparse matmul first, then an
            # aggregation over H (<= F) columns
            u = xw(layer["w1"])
            if fe is not None:
                act = "relu" if relu_ok else "none"
                h = fe(u, self_term=res(u), bias=layer["b1"],
                       alpha=1.0 + layer["eps"], activation=act)
                if act == "none":
                    h = config.activation(h)
            else:
                z1 = (1.0 + layer["eps"]) * res(u) + ops.aggregate(u) + layer["b1"]
                h = config.activation(z1)
            y = h @ layer["w2"] + layer["b2"]
        else:
            if fe is not None:
                z = fe(x, self_term=res(x), alpha=1.0 + layer["eps"])
            else:
                z = (1.0 + layer["eps"]) * res(x) + ops.aggregate(x)
            z1 = z @ layer["w1"] + layer["b1"]
            y = config.activation(z1) @ layer["w2"] + layer["b2"]
    elif kind == "GAT":
        # O^k = Σ_j α_ij^k Z_j^k + (H·W_res)^k per head; hidden layers
        # concatenate the heads, the last averages them
        out = ops.gat_attention(mm(layer["w"]), layer["a_src"],
                                layer["a_dst"], config.gat_heads)
        n, h, dh = out.shape
        out = out + res(mm(layer["w_res"])).reshape(n, h, dh)
        y = (out.mean(axis=1) if is_last else out.reshape(n, h * dh)) \
            + layer["b"]
    elif kind == "GT":
        z = mm(layer["w"])  # [N, heads*dh]
        out = ops.gat_attention(z, layer["a_src"], layer["a_dst"],
                                config.gat_heads)  # [N, heads, dh]
        y = out.reshape(out.shape[0], -1) @ layer["proj"] + layer["b"]
        # transformer-style residual around the attention block; the
        # restrict maps the (possibly wider) src frontier onto dst rows
        y = y + res(x) @ layer["w_res"]
    else:
        raise ValueError(kind)
    return y if is_last else config.activation(y)


class GNNModel:
    """A GNN executing a synthesized per-layer ExecutionPlan."""

    def __init__(self, config: GNNConfig, graph: CSRGraph, interpret: bool | None = None,
                 use_fused: bool = True, engine: "str | None" = None,
                 plan: Optional[ModelPlan] = None):
        self.config = config
        self.graph = graph
        self.use_fused = use_fused
        if plan is None:
            plan = lower(config, graph, features=None, engine=engine,
                         interpret=interpret, use_fused=use_fused)
        self.plan = plan
        self.backend = get_backend(plan.backend)
        self.engine = plan.backend  # legacy attribute, now the registry name
        self.op = plan.graph_op
        # permutation contract (DESIGN.md §9): a reordered plan's operands
        # live in the renumbered space; apply() gathers features in through
        # perm and un-permutes outputs through inv_perm, so callers only
        # ever see the original node order
        lp = plan.layout
        if lp is not None and lp.permutes:
            self._perm = jnp.asarray(lp.perm, dtype=jnp.int32)
            self._inv_perm = jnp.asarray(lp.inv_perm, dtype=jnp.int32)
        else:
            self._perm = self._inv_perm = None
        # legacy flag the seed set when monkey-patching the input path
        self.sparse_input_bound = any(
            l.feature_path == "sparse" for l in plan.layers)
        # fused flash-attention (BSR or CSR): bound iff the plan's aggregation
        # primitive is spmm_attention AND the graph op carries the operator
        self._fuse_attention = (
            use_fused and self.op.aggregate_attention is not None
            and any(l.agg_primitive.endswith("spmm_attention")
                    for l in plan.layers))

    # -- parameters ---------------------------------------------------------

    def init(self, key) -> dict:
        return init_params(self.config, key)

    # -- forward ------------------------------------------------------------

    def _aggregate(self, x: jax.Array) -> jax.Array:
        if self.use_fused:
            return self.op.aggregate(x)
        return self.op.baseline(x)

    def _gat_attention(self, z: jax.Array, a_src, a_dst, heads: int) -> jax.Array:
        """Edge-softmax attention: the fused flash-attention operator
        when the plan bound one, else the backend's segment primitive."""
        if self._fuse_attention:
            return self.op.aggregate_attention(z, a_src, a_dst, heads)
        n = z.shape[0]
        z3 = z.reshape(n, heads, z.shape[-1] // heads)
        return self.backend.segment_softmax_aggregate(
            z3, a_src, a_dst, self.op.src, self.op.dst, n)

    def _layer_ops(self, plan_layer: Optional[LayerPlan]) -> LayerOps:
        sparse_xw = None
        if plan_layer is not None and plan_layer.feature_path == "sparse":
            sparse_xw = plan_layer.sparse_xw
        fe = None
        if (self.use_fused and plan_layer is not None
                and plan_layer.epilogue is not None):
            fe = self.op.aggregate_epilogue
        return LayerOps(aggregate=self._aggregate, xw=sparse_xw,
                        gat_attention=self._gat_attention, fused_epilogue=fe)

    def _layer(self, layer: dict, x: jax.Array, is_last: bool,
               plan_layer: Optional[LayerPlan] = None) -> jax.Array:
        return apply_layer(self.config, layer, x, self._layer_ops(plan_layer),
                           is_last)

    def apply(self, params: dict, x: jax.Array) -> jax.Array:
        n = self.config.n_layers
        if self._perm is not None:
            x = x[self._perm]
        for i, layer in enumerate(params["layers"]):
            plan_layer = self.plan.layers[i] if i < len(self.plan.layers) else None
            with jax.named_scope(f"layer{i}"):
                x = self._layer(layer, x, is_last=(i == n - 1),
                                plan_layer=plan_layer)
        if self._inv_perm is not None:
            x = x[self._inv_perm]
        return x

    def loss_fn(self, params: dict, x: jax.Array, labels: jax.Array,
                mask: jax.Array) -> jax.Array:
        with jax.named_scope("forward"):
            logits = self.apply(params, x)
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
            denom = jnp.maximum(mask.sum(), 1)
            return jnp.where(mask, nll, 0.0).sum() / denom

    def loss_and_grads(self, params: dict, x: jax.Array, labels: jax.Array,
                       mask: jax.Array) -> tuple[jax.Array, dict]:
        """``jax.value_and_grad(loss_fn)``, with the backward pass under
        the ``backward`` named scope."""
        loss, pullback = jax.vjp(
            lambda p: self.loss_fn(p, x, labels, mask), params)
        with jax.named_scope("backward"):
            (grads,) = pullback(jnp.ones_like(loss))
        return loss, grads

    def accuracy(self, params: dict, x, labels, mask) -> jax.Array:
        pred = jnp.argmax(self.apply(params, x), axis=-1)
        denom = jnp.maximum(mask.sum(), 1)
        return jnp.where(mask, pred == labels, False).sum() / denom
