"""Useful work (``chipbench/work``): hand counts on a tiny graph, and the
count does not move when the same graph is tiled or numbered otherwise."""
import dataclasses

import numpy as np
import pytest

from chipbench import generator
from chipbench.harness import graph_stats
from chipbench.work import gcn

TINY = {"n": 4, "nnz": 6, "x_nnz": 12, "dims": [3, 4, 2]}


def test_gcn_hand_count_dense_features():
    w = gcn.epoch_work(TINY)
    # X·W and dW (2·2·12·4), layer-1 H·W, dW, dH (3·2·4·4·2), and the
    # aggregations forward and backward (2·2·6·4 + 2·2·6·2)
    assert w["flops"] == 192 + 192 + 96 + 48
    assert w["sparse_flops"] == 96 + 48
    # each SpMM: 6 indices + values, 5 row pointers, 4 rows in and out
    assert w["sparse_bytes"] == 2 * (48 + 20 + 32 * 4) + 2 * (48 + 20 + 32 * 2)


def test_gcn_hand_count_sparse_features():
    w = gcn.epoch_work({**TINY, "x_nnz": 5})
    assert w["flops"] == 80 + 192 + 96 + 48
    assert w["sparse_flops"] == 80 + 96 + 48
    x_bytes = (40 + 20 + 7 * 4 * 4) + (40 + 16 + 7 * 4 * 4)
    assert w["sparse_bytes"] == x_bytes + 656


def _tiny_graph():
    mix = {"spec": {"name": "t", "n_nodes": 300, "n_edges": 2400,
                    "n_features": 64, "n_classes": 5,
                    "feature_sparsity": 0.9},
           "scale": 1.0, "topology_seed": 1}
    return generator.generate(mix, seed=2)


def test_count_ignores_numbering():
    data = _tiny_graph()
    perm = np.random.default_rng(0).permutation(data.n_nodes)  # new -> old
    inv = np.argsort(perm)
    src, dst = data.edges()
    key = np.sort(inv[dst].astype(np.int64) * data.n_nodes + inv[src])
    indptr = np.zeros(data.n_nodes + 1, np.int64)
    np.cumsum(np.bincount(key // data.n_nodes, minlength=data.n_nodes),
              out=indptr[1:])
    moved = dataclasses.replace(data, indptr=indptr.astype(np.int32),
                                indices=(key % data.n_nodes).astype(np.int32),
                                features=data.features[perm])
    assert not np.array_equal(moved.indices, data.indices)
    dims = [64, 32, 5]
    assert (gcn.epoch_work(graph_stats(moved, {}, dims))
            == gcn.epoch_work(graph_stats(data, {}, dims)))


@pytest.mark.parametrize("bc", [16, 128])
def test_count_ignores_tiling(bc):
    """The stats count nonzeros, which any tiling stores once, while the
    padded block cells (what a block stream would count) differ."""
    from repro.graph.csr import CSRGraph, csr_to_bsr

    data = _tiny_graph()
    n = data.n_nodes
    bsr = csr_to_bsr(CSRGraph(data.indptr, data.indices,
                              np.ones(data.nnz, np.float32), n, n), 8, bc)
    assert np.count_nonzero(bsr.blocks) == graph_stats(data, {}, [64, 5])["nnz"]
    assert bsr.blocks.size != data.nnz
