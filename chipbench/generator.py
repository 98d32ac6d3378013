"""The benchmark's traffic generator: synthetic node-classification graphs.

A frozen copy of the parts of ``repro.graph.datasets.generate_dataset``
that the mixes draw, as it stood when the benchmark was defined
(power-law in-degrees, uniform sources, self loops, Gaussian features
zeroed at the spec's sparsity, uniform labels, a 70/15/15 split; one
connected component, no node cap). It is kept here so that a change to
the program's generator never moves the yardstick; ``tests/chipbench``
pins the arrays it gave then.

The program under test receives only the arrays made here. A traffic mix
(``chipbench/traffic/<mix>.json``) gives the spec, the scale and a fixed
``topology_seed``: every ``--seed`` runs on the same graph (so the same
block counts, shapes and compiled programs), while the features, labels,
split and weights are drawn from ``--seed``.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    name: str
    n_nodes: int
    n_edges: int
    n_features: int
    n_classes: int
    feature_sparsity: float  # fraction of zero entries in X
    power_law_alpha: float = 2.1


@dataclasses.dataclass
class GraphData:
    """Host arrays of one generated graph: CSR with row = destination."""

    indptr: np.ndarray  # [n + 1] int32
    indices: np.ndarray  # [nnz] int32, sorted within each row
    features: np.ndarray  # [n, f] float32
    labels: np.ndarray  # [n] int32
    train_mask: np.ndarray  # [n] bool
    val_mask: np.ndarray
    test_mask: np.ndarray
    n_classes: int

    @property
    def n_nodes(self) -> int:
        return int(self.indptr.shape[0] - 1)

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def edges(self) -> tuple[np.ndarray, np.ndarray]:
        """(src, dst) of every nonzero, in CSR order."""
        dst = np.repeat(np.arange(self.n_nodes, dtype=np.int32),
                        np.diff(self.indptr))
        return self.indices, dst


def sizes(spec: Spec, scale: float):
    """(nodes, features, mean in-degree) at ``scale``."""
    n = max(int(spec.n_nodes * scale), 32)
    f = max(int(spec.n_features * min(scale * 4, 1.0)), 8)
    e_target = max(int(spec.n_edges * scale
                       * (n / max(int(spec.n_nodes * scale), 1))), n)
    return n, f, max(e_target / n, 1.0)


def topology(spec: Spec, scale: float,
             rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """CSR ``(indptr, indices)`` of the deduplicated graph with self loops."""
    n, _, mean_deg = sizes(spec, scale)
    raw = rng.pareto(spec.power_law_alpha - 1.0, size=n) + 1.0
    deg = np.maximum((raw / raw.mean() * mean_deg).round().astype(np.int64), 1)
    dst = np.repeat(np.arange(n, dtype=np.int64), deg)
    src = rng.integers(0, n, size=dst.shape[0])
    src = np.concatenate([src, np.arange(n)])
    dst = np.concatenate([dst, np.arange(n)])
    key = np.unique(dst * n + src)  # sorted by (dst, src), duplicates gone
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(key // n, minlength=n), out=indptr[1:])
    return indptr.astype(np.int32), (key % n).astype(np.int32)


def node_data(spec: Spec, n: int, f: int, rng: np.random.Generator) -> dict:
    """Features at the spec's sparsity, labels and the 70/15/15 split."""
    x = rng.standard_normal((n, f)).astype(np.float32)
    if spec.feature_sparsity > 0:
        x[rng.random((n, f)) < spec.feature_sparsity] = 0.0
    labels = rng.integers(0, spec.n_classes, size=n).astype(np.int32)
    u = rng.random(n)
    return dict(features=x, labels=labels, train_mask=u < 0.7,
                val_mask=(u >= 0.7) & (u < 0.85), test_mask=u >= 0.85)


def generate(mix: dict, seed: int) -> GraphData:
    """A mix's graph from its ``topology_seed``, node data from ``seed``."""
    spec, scale = Spec(**mix["spec"]), mix["scale"]
    indptr, indices = topology(spec, scale,
                               np.random.default_rng(mix["topology_seed"]))
    n, f, _ = sizes(spec, scale)
    return GraphData(indptr, indices, n_classes=spec.n_classes,
                     **node_data(spec, n, f, np.random.default_rng(seed)))
