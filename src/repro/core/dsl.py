"""Morphling DSL front-end — the JAX analog of paper Listing 1.

The paper's program::

    function SAGE(Graph g, GNN gnn, container<int>& neuronsPerLayer, ...) {
        gnn.load(g, Dataset);
        gnn.initializeLayers(neuronsPerLayer, "xaviers");
        for epoch { for l gnn.forwardPass(l, "SAGE", "Max");
                    for l gnn.backPropagation(l);
                    gnn.optimizer("adam", 0.01, 0.9, 0.999); } }

maps here to::

    gnn = GNNProgram.load(dataset, arch="SAGE", aggregation="max")
    gnn.initialize_layers([in, 32, n_classes], "xavier", seed=0)
    gnn.set_optimizer("adam", 0.01, 0.9, 0.999)
    compiled = gnn.compile()          # <- the "code synthesis" step
    for epoch in range(E): metrics = compiled.train_epoch()

``compile()`` runs the explicit lowering pass (``core/lowering.py``): the
Algorithm-1 sparsity engine decides a dense/sparse path *per layer*
(measured input sparsity for layer 0, activation-sparsity estimates for
hidden layers), binds each decision to a primitive from the backend
registry (``repro.backends``), and returns the per-layer ExecutionPlans on
``CompiledProgram.plan`` — the paper's "synthesized program", inspectable.
The whole epoch is one jitted program (forward + backward + fused optimizer
— no interpreter in the loop, the paper's "without interpreter overhead").
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.jit import jit_hoisted
from repro.common.spans import span
from repro.core.lowering import ModelPlan, lower
from repro.core.sparsity import PAPER_GAMMA_DEFAULT, SparsityDecision
from repro.graph.csr import CSRGraph
from repro.graph.datasets import GraphDataset
from repro.models.gnn import GNNConfig, GNNModel
from repro.training.optimizer import Optimizer, get_optimizer


@dataclasses.dataclass
class CompiledProgram:
    """The synthesized training program: one jitted epoch step + its plan."""

    model: GNNModel
    params: dict
    opt: Optimizer
    opt_state: object
    x: jax.Array
    labels: jax.Array
    train_mask: jax.Array
    plan: ModelPlan
    _train_step: object = None
    _epoch: int = 0

    @property
    def sparsity_decision(self) -> SparsityDecision:
        """Backward-compat shim: layer 0's Alg-1 decision (the seed repo's
        single decision). The full per-layer record lives on ``plan``."""
        return self.plan.input_decision

    def describe_plan(self) -> str:
        return self.plan.describe()

    def _step(self):
        if self._train_step is None:
            model, opt = self.model, self.opt

            @jit_hoisted  # the plan's operands ride as arguments
            def step(params, opt_state, x, labels, mask):
                loss, grads = model.loss_and_grads(params, x, labels, mask)
                with jax.named_scope("optimizer"):
                    new_params, new_opt_state = opt.update(grads, opt_state, params)
                return new_params, new_opt_state, loss

            self._train_step = step
        return self._train_step

    def compile_step(self) -> None:
        """Compile the epoch step ahead of the first ``train_epoch``."""
        self._step().compile(self.params, self.opt_state, self.x,
                             self.labels, self.train_mask)

    def train_epoch(self) -> dict:
        with span("epoch", step=self._epoch):
            self.params, self.opt_state, loss = self._step()(
                self.params, self.opt_state, self.x, self.labels, self.train_mask
            )
            with span("loss_read"):
                loss = float(loss)
        self._epoch += 1
        return {"epoch": self._epoch, "loss": loss}

    def accuracy(self) -> float:
        return float(self.model.accuracy(self.params, self.x, self.labels, self.train_mask))


class GNNProgram:
    """Listing-1 front-end object. Methods mirror the DSL's gnn.* calls."""

    def __init__(self, graph: CSRGraph, features: np.ndarray, labels: np.ndarray,
                 train_mask: np.ndarray, n_classes: int,
                 arch: str = "GCN", aggregation: str = "gcn",
                 gat_heads: int = 4):
        self.graph = graph
        self.features = np.asarray(features, dtype=np.float32)
        self.labels = np.asarray(labels)
        self.train_mask = np.asarray(train_mask)
        self.n_classes = int(n_classes)
        self.arch = arch
        self.aggregation = aggregation
        self.gat_heads = int(gat_heads)
        self._layer_dims: Optional[Sequence[int]] = None
        self._seed = 0
        self._opt_spec = ("adam", 0.01, 0.9, 0.999)
        self.gamma = PAPER_GAMMA_DEFAULT

    # -- gnn.load -----------------------------------------------------------
    @classmethod
    def load(cls, dataset: GraphDataset, arch: str = "GCN",
             aggregation: str = "gcn", gat_heads: int = 4) -> "GNNProgram":
        return cls(
            graph=dataset.graph, features=dataset.features, labels=dataset.labels,
            train_mask=dataset.train_mask, n_classes=dataset.n_classes,
            arch=arch, aggregation=aggregation, gat_heads=gat_heads,
        )

    # -- gnn.initializeLayers ------------------------------------------------
    def initialize_layers(self, neurons_per_layer: Sequence[int],
                          init: str = "xavier", seed: int = 0):
        if init not in ("xavier", "xaviers"):
            raise ValueError("only xavier init is supported (as in the paper)")
        dims = list(neurons_per_layer)
        if dims[0] != self.features.shape[1]:
            dims = [self.features.shape[1], *dims]
        if dims[-1] != self.n_classes:
            dims = [*dims, self.n_classes]
        self._layer_dims = dims
        self._seed = seed
        return self

    # -- gnn.optimizer --------------------------------------------------------
    def set_optimizer(self, name: str, lr: float, *args, **kw):
        self._opt_spec = (name, lr, *args)
        self._opt_kw = kw
        return self

    # -- synthesis ------------------------------------------------------------
    def compile(self, interpret: Optional[bool] = None, use_fused: bool = True,
                fused_optimizer: bool = False,
                engine: Optional[str] = None,
                layout: "str | None" = None,
                fuse_attention: bool = True,
                validate: str = "fast") -> CompiledProgram:
        """Lower the spec to per-layer ExecutionPlans and jit the epoch.

        ``engine`` names a registered backend ("pallas" | "xla" | "gather");
        ``None`` auto-selects the best available one for this platform.
        ``layout`` reorders the graph's nodes (DESIGN.md §9): ``"degree"``
        or ``"rcm"`` by name, ``"auto"`` only where a reorder cuts the BSR
        block count by at least 10%; ``None`` keeps the given order.
        ``fuse_attention=False`` drops GAT/GT back to the gather-style
        segment softmax instead of the fused BSR kernel (DESIGN.md §10).
        ``validate`` selects the plan-contract verification depth
        ("full" | "fast" | "off", DESIGN.md §14).
        """
        if self._layer_dims is None:
            raise RuntimeError("call initialize_layers first")

        config = GNNConfig(
            kind=self.arch,  # type: ignore[arg-type]
            layer_dims=self._layer_dims,
            aggregation=self.aggregation.lower(),
            gat_heads=self.gat_heads,
        )

        # Alg 1 Phase 1, per layer: runtime analysis & lowering
        plan = lower(
            config, self.graph, self.features, gamma=self.gamma,
            engine=engine, interpret=interpret, use_fused=use_fused,
            layout=layout, fuse_attention=fuse_attention, validate=validate,
        )
        with span("init"):
            model = GNNModel(config, self.graph, interpret=interpret,
                             use_fused=use_fused, plan=plan)

            params = model.init(jax.random.PRNGKey(self._seed))
            name, lr, *rest = self._opt_spec
            opt = get_optimizer(name, lr, *rest, fused=fused_optimizer,
                                **getattr(self, "_opt_kw", {}))
            opt_state = opt.init(params)
            return CompiledProgram(
                model=model, params=params, opt=opt, opt_state=opt_state,
                x=jnp.asarray(self.features), labels=jnp.asarray(self.labels),
                train_mask=jnp.asarray(self.train_mask),
                plan=plan,
            )
