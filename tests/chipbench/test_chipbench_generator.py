"""The benchmark's frozen generator, and what a mix draws from the seed.

The digests were taken from ``repro.graph.datasets.generate_dataset(name,
scale, seed=5)`` when the benchmark was defined, for the arxiv mix and for
a spec with mostly-zero features (graph indptr, indices,
features, labels, train/val/test masks, in that order). They are recorded
rather than recomputed so that the program's generator may change later
while the benchmark's copy, and the traffic it makes, stay as they were.
"""
import hashlib

import numpy as np
import pytest

from chipbench import generator
from chipbench.harness import HERE, read_json

PHYSICS = {"name": "physics", "n_nodes": 34_493, "n_edges": 495_924,
            "n_features": 8_415, "n_classes": 5, "feature_sparsity": 0.95}
DIGESTS = [
    ("arxiv-full", 0.01,
     "dd9a33313fe6bd1f8d795f02eb3fe74518334f189168e6f4bfa502485e1e5fc3"),
    (PHYSICS, 0.01,
     "7c5acfbdecb1fc5db3e38e5fd03e288db07aa78003c0f1323b2fa71f0d76bb0f"),
]


def _digest(d: generator.GraphData) -> str:
    h = hashlib.sha256()
    for a in (d.indptr, d.indices, d.features, d.labels, d.train_mask,
              d.val_mask, d.test_mask):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("spec,scale,digest", DIGESTS,
                         ids=["arxiv", "physics"])
def test_frozen_copy_gives_the_program_generators_arrays(spec, scale, digest):
    """The program's generator drew the topology and then the node data
    from one stream of ``seed``; so do the copy's two parts here."""
    if isinstance(spec, str):
        spec = read_json(HERE / "traffic" / f"{spec}.json")["spec"]
    spec = generator.Spec(**spec)
    rng = np.random.default_rng(5)
    indptr, indices = generator.topology(spec, scale, rng)
    n, f, _ = generator.sizes(spec, scale)
    data = generator.GraphData(indptr, indices, n_classes=spec.n_classes,
                               **generator.node_data(spec, n, f, rng))
    assert _digest(data) == digest


def test_seed_draws_node_data_on_one_graph():
    mix = {**read_json(HERE / "traffic" / "arxiv-full.json"), "scale": 0.01}
    a, b = generator.generate(mix, 1), generator.generate(mix, 2**33 + 1)
    np.testing.assert_array_equal(a.indptr, b.indptr)
    np.testing.assert_array_equal(a.indices, b.indices)
    assert not np.array_equal(a.features, b.features)
    np.testing.assert_array_equal(a.features,
                                  generator.generate(mix, 1).features)
