"""Full-batch training: one step is one epoch over the whole graph.

Builds the program through its public entry point,
``GNNProgram.load(...).initialize_layers(...).set_optimizer(...).compile()``
with the default engine and layout, hands it the benchmark's weights, and
compiles its step. The window then drives ``CompiledProgram.train_epoch``,
the same call that the three checked steps of set-up went through.
"""
from __future__ import annotations

import time

import jax
import numpy as np


class Session:
    def __init__(self, cfg: dict, data, dims: list[int], params0,
                 spans: dict):
        from repro.core.dsl import GNNProgram
        from repro.graph.csr import CSRGraph
        from repro.graph.datasets import GraphDataset

        n = data.n_nodes
        graph = CSRGraph(indptr=data.indptr, indices=data.indices,
                         data=np.ones(data.nnz, np.float32), n_rows=n,
                         n_cols=n, validate=False)
        ds = GraphDataset(name="chipbench", graph=graph,
                          features=data.features, labels=data.labels,
                          n_classes=data.n_classes,
                          train_mask=data.train_mask, spec=None)
        opt = cfg["optimizer"]
        self.beta1 = opt["beta1"]
        prog = (GNNProgram.load(ds, arch=cfg["arch"],
                                aggregation=cfg["aggregation"],
                                gat_heads=cfg.get("heads", 1))
                .initialize_layers(dims[1:-1], "xavier")
                .set_optimizer("adam", cfg["lr"], opt["beta1"], opt["beta2"],
                               eps=opt["eps"]))
        t = time.perf_counter()
        self.program = prog.compile()
        spans["lower_s"] = time.perf_counter() - t
        self.program.params = params0
        self.program.opt_state = self.program.opt.init(params0)
        t = time.perf_counter()
        self.program.compile_step()
        spans["compile_s"] = time.perf_counter() - t

    def binding(self) -> dict:
        """What the plan bound: backend, aggregation primitives of every
        layer, and layer 0's feature-transform primitive."""
        plan = self.program.plan
        return {"backend": plan.backend,
                "agg": sorted({layer.agg_primitive for layer in plan.layers}),
                "layer0": plan.layers[0].primitive}

    def step(self) -> float:
        return self.program.train_epoch()["loss"]

    def sync(self) -> None:
        jax.block_until_ready(self.program.params)

    def params(self):
        return self.program.params

    def first_gradient(self):
        """The first step's gradient as Adam got it: ``m / (1 - beta1)``
        (valid after exactly one step)."""
        return jax.tree_util.tree_map(lambda m: m / (1 - self.beta1),
                                      self.program.opt_state.m)
