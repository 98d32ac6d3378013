"""Layout stage (DESIGN.md §9): reorder correctness, RCM bandwidth
reduction, adaptive-bc fallback, the ``"auto"`` order rule, and the
permutation round-trip contract — reordered plans must match the
unreordered baseline (fwd + grads, 1e-4) across single-device, distributed
and mini-batch trainers, in the caller's node order."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.layout import _select_order, choose_order
from repro.core.lowering import lower, lower_sampled
from repro.graph.csr import (
    adaptive_bc,
    bsr_block_count,
    csr_from_edges,
    csr_to_bsr,
    rcm_order,
    reorder_graph,
)
from repro.graph.datasets import generate_dataset
from repro.models.gnn import GNNConfig, GNNModel, init_params
from repro.training.optimizer import adam
from repro.training.trainer import MiniBatchTrainer

pytestmark = pytest.mark.layout

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _graph(rng, n=48, e=260):
    return csr_from_edges(
        np.concatenate([rng.integers(0, n, e), np.arange(n)]),
        np.concatenate([rng.integers(0, n, e), np.arange(n)]),
        n,
    )


def _features(rng, n, f, sparsity):
    x = rng.standard_normal((n, f)).astype(np.float32)
    if sparsity > 0:
        x[rng.random((n, f)) < sparsity] = 0.0
    return x


# ---------------------------------------------------------------------------
# Reordering primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["degree", "rcm"])
def test_reorder_is_symmetric_permutation(rng, mode):
    """P A Pᵀ exactly: dense(reordered)[i, j] == dense(A)[perm[i], perm[j]],
    and perm/inv_perm are mutually inverse bijections."""
    g = _graph(rng)
    g_r, perm, inv = reorder_graph(g, mode)
    assert sorted(perm) == list(range(g.n_rows))
    np.testing.assert_array_equal(perm[inv], np.arange(g.n_rows))
    np.testing.assert_array_equal(inv[perm], np.arange(g.n_rows))
    dense = g.to_dense()
    np.testing.assert_array_equal(g_r.to_dense(), dense[np.ix_(perm, perm)])
    assert g_r.nnz == g.nnz


def test_rcm_recovers_shuffled_ring_bandwidth(rng):
    """A ring relabeled randomly has bandwidth ~n; RCM recovers the chain
    structure (bandwidth <= 2 — each node's neighbours are adjacent)."""
    n = 64
    shuffle = rng.permutation(n)
    src = shuffle[np.arange(n)]
    dst = shuffle[(np.arange(n) + 1) % n]
    g = csr_from_edges(np.concatenate([src, dst]),
                       np.concatenate([dst, src]), n)
    assert g.bandwidth() > 8  # the shuffle destroyed locality
    g_r, _, _ = reorder_graph(g, "rcm")
    assert g_r.bandwidth() <= 2


@pytest.mark.parametrize("name,scale", [
    ("nell", 0.004), ("corafull", 0.004), ("stargraph", 0.02),
    ("ogbn-arxiv", 0.001),
])
def test_rcm_bandwidth_monotone_on_generated_datasets(name, scale):
    g = generate_dataset(name, scale=scale, seed=0).graph
    g_r, _, _ = reorder_graph(g, "rcm")
    assert g_r.bandwidth() <= g.bandwidth()


def test_reordering_reduces_blocks_on_skewed_graphs():
    """The bench claim, pinned: on the power-law nell/stargraph analogs the
    best reorder mode strictly reduces the BSR block count at the
    fallback tile."""
    for name, scale in [("nell", 0.004), ("stargraph", 0.02)]:
        g = generate_dataset(name, scale=scale, seed=0).graph
        bc = adaptive_bc(g.n_cols)
        base = bsr_block_count(g, 8, bc)
        best = min(bsr_block_count(reorder_graph(g, m)[0], 8, bc)
                   for m in ("degree", "rcm"))
        assert best < base, (name, base, best)


# ---------------------------------------------------------------------------
# Adaptive bc fallback + BSR stats (satellites)
# ---------------------------------------------------------------------------

def test_adaptive_bc_small_graph_stops_lane_padding(rng):
    """nell-analog regression: 263 nodes under bc=128 ship a mostly-zero
    padded block-column; the adaptive default picks a narrower tile with
    strictly less stored padding."""
    g = generate_dataset("nell", scale=0.004, seed=0).graph
    assert g.n_rows == 263
    assert adaptive_bc(g.n_rows) < 128
    default = csr_to_bsr(g)          # bc=None -> adaptive
    wide = csr_to_bsr(g, bc=128)
    assert default.bc == adaptive_bc(g.n_rows)
    assert default.n_blocks * default.br * default.bc < \
        wide.n_blocks * wide.br * wide.bc
    # big graphs keep the full lane tile
    assert adaptive_bc(10_000) == 128


def test_bsr_stats_and_block_count(rng):
    g = _graph(rng, n=40)
    for br, bc in [(8, 8), (8, 16), (16, 8)]:
        bsr = csr_to_bsr(g, br=br, bc=bc)
        assert bsr.n_blocks == bsr_block_count(g, br, bc)
        assert 0.0 <= bsr.padding_waste() < 1.0
        assert bsr.avg_row_blocks() == bsr.n_blocks / (bsr.padded_rows // br)
    aligned = csr_to_bsr(g, br=8, bc=8)  # 40 divides both tiles
    assert aligned.padding_waste() == 0.0
    ragged = csr_to_bsr(g, br=16, bc=16)  # 40 -> 48: overhang on both axes
    assert ragged.padding_waste() > 0.0


# ---------------------------------------------------------------------------
# Order selection
# ---------------------------------------------------------------------------

def test_choose_order_needs_meaningful_gain(rng):
    """A near-diagonal graph reordering cannot improve must stay 'none' —
    the permutation is never paid for marginal block savings."""
    n = 64
    idx = np.arange(n)
    g = csr_from_edges(np.concatenate([idx, idx[:-1]]),
                       np.concatenate([idx, idx[1:]]), n)
    assert choose_order(g, "auto") == "none"
    with pytest.raises(ValueError):
        choose_order(g, "zigzag")


# ---------------------------------------------------------------------------
# Permutation round-trip: reordered execution == baseline, user order
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,agg", [
    ("GCN", "gcn"), ("SAGE", "mean"), ("GIN", "sum"), ("GAT", "sum"),
])
@pytest.mark.parametrize("sparsity", [0.95, 0.0], ids=["sparse", "dense"])
def test_reordered_model_matches_baseline(rng, arch, agg, sparsity):
    """lower(layout="rcm") must be numerically identical (1e-4, fwd +
    grads) to the unreordered plan — outputs arrive in the caller's node
    order, the permutation never leaks."""
    n, f, h, c = 48, 32, 12, 5
    g = _graph(rng)
    x = _features(rng, n, f, sparsity)
    cfg = GNNConfig(kind=arch, layer_dims=[f, h, c], aggregation=agg)
    labels = jnp.asarray(rng.integers(0, c, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < 0.6)
    xj = jnp.asarray(x)

    base = GNNModel(cfg, g, plan=lower(cfg, g, x, engine="xla"))
    reord = GNNModel(cfg, g, plan=lower(cfg, g, x, engine="xla",
                                        layout="rcm"))
    assert reord.plan.layout.order == "rcm"
    assert reord.plan.layout.permutes

    params = base.init(jax.random.PRNGKey(0))
    y0 = base.apply(params, xj)
    y1 = reord.apply(params, xj)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               atol=1e-4, rtol=1e-4)
    l0, g0 = jax.value_and_grad(base.loss_fn)(params, xj, labels, mask)
    l1, g1 = jax.value_and_grad(reord.loss_fn)(params, xj, labels, mask)
    assert abs(float(l0) - float(l1)) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("mode", ["degree", "rcm"])
def test_degree_mode_and_describe(rng, mode):
    n, f = 48, 32
    g = _graph(rng)
    x = _features(rng, n, f, 0.95)
    cfg = GNNConfig(kind="GCN", layer_dims=[f, 12, 4])
    plan = lower(cfg, g, x, engine="xla", layout=mode)
    dump = plan.describe()
    assert f"layout[{mode}" in dump  # the per-layer layout line (satellite)
    assert all(l.layout is plan.layout for l in plan.layers)


@pytest.mark.sampling
@pytest.mark.parametrize("arch,agg,sparsity", [
    ("GCN", "gcn", 0.95), ("SAGE", "mean", 0.0),
])
def test_reordered_minibatch_full_fanout_parity(rng, arch, agg, sparsity):
    """Full-fanout mini-batch on a degree-reordered plan == unreordered
    full-batch loss + grads (1e-4). Seeds/labels/masks cross the trainer
    boundary in user order; the id map is internal."""
    n, f, h, c = 48, 32, 12, 5
    g = _graph(rng)
    x = _features(rng, n, f, sparsity)
    labels = rng.integers(0, c, n).astype(np.int32)
    train_mask = rng.random(n) < 0.6
    max_indeg = int(np.diff(g.indptr).max())
    cfg = GNNConfig(kind=arch, layer_dims=[f, h, c], aggregation=agg)

    plan = lower_sampled(cfg, g, x, fanouts=(max_indeg, max_indeg),
                         batch_size=int(train_mask.sum()), n_buckets=1,
                         engine="xla", layout="degree")
    assert plan.layout.order == "degree"
    tr = MiniBatchTrainer(cfg, None, x, labels, train_mask, adam(0.01),
                          plan=plan, interpret=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tr.params = params
    loss_mb, grads_mb = tr.loss_and_grads()

    model = GNNModel(cfg, g, plan=lower(cfg, g, x, engine="xla"))
    loss_fb, grads_fb = jax.value_and_grad(model.loss_fn)(
        params, jnp.asarray(x), jnp.asarray(labels),
        jnp.asarray(train_mask))
    assert abs(float(loss_mb) - float(loss_fb)) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(grads_mb),
                    jax.tree_util.tree_leaves(grads_fb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)
    # evaluate() takes user-order masks and maps ids internally
    acc = tr.evaluate(train_mask)
    assert 0.0 <= acc <= 1.0


def test_reordered_minibatch_inference_in_user_order(rng):
    """infer_logits rows follow the requested user node ids, reordered or
    not: both trainers agree on a full-fanout neighbourhood."""
    n, f, c = 48, 24, 4
    g = _graph(rng)
    x = _features(rng, n, f, 0.0)
    labels = rng.integers(0, c, n).astype(np.int32)
    mask = rng.random(n) < 0.6
    max_indeg = int(np.diff(g.indptr).max())
    cfg = GNNConfig(kind="GCN", layer_dims=[f, 8, c])
    params = init_params(cfg, jax.random.PRNGKey(1))
    ids = np.asarray([3, 17, 41, 0])
    outs = {}
    for mode in (None, "rcm"):
        tr = MiniBatchTrainer(
            cfg, g, x, labels, mask, adam(0.01),
            fanouts=(max_indeg, max_indeg), batch_size=8, n_buckets=1,
            engine="xla", interpret=True, layout=mode)
        tr.params = params
        outs[mode] = tr.infer_logits(ids)
    np.testing.assert_allclose(outs[None], outs["rcm"],
                               atol=1e-4, rtol=1e-4)


_DIST_CODE = """
    import json
    import jax, jax.numpy as jnp
    from repro.graph.datasets import generate_dataset
    from repro.core.partitioner import hierarchical_partition
    from repro.core.halo import build_distributed_graph
    from repro.core.lowering import (effective_aggregation, lower,
                                     lower_distributed)
    from repro.models.gnn import GNNConfig, GNNModel, init_params
    from repro.training.trainer import DistributedGNNTrainer
    from repro.training.optimizer import adam

    ds = generate_dataset("corafull", scale=0.004, seed=0)
    cfg = GNNConfig(kind="GCN",
                    layer_dims=[ds.features.shape[1], 16, ds.n_classes],
                    aggregation="gcn")
    part = hierarchical_partition(ds.graph, 2)
    model = GNNModel(cfg, ds.graph,
                     plan=lower(cfg, ds.graph, ds.features, engine="xla"))
    params = init_params(cfg, jax.random.PRNGKey(3))
    ref_loss, ref_grads = jax.value_and_grad(model.loss_fn)(
        params, jnp.asarray(ds.features), jnp.asarray(ds.labels),
        jnp.asarray(ds.train_mask))
    out = {}
    for mode in ("degree", "rcm"):
        dist = build_distributed_graph(
            ds.graph, ds.features, ds.labels, ds.train_mask, part,
            br=8, bc=32, aggregation=effective_aggregation(cfg),
            reorder=mode)
        plan = lower_distributed(cfg, dist)
        tr = DistributedGNNTrainer(dist, cfg, adam(0.01), interpret=True,
                                   seed=3, plan=plan)
        loss, grads = tr.loss_and_grads()
        gd = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree_util.tree_leaves(grads),
            jax.tree_util.tree_leaves(ref_grads)))
        out[mode] = {"loss_diff": abs(float(loss) - float(ref_loss)),
                     "grad_diff": gd,
                     "layout": plan.layout.order}
    print("RESULT:" + json.dumps(out))
"""


@pytest.mark.slow
def test_distributed_within_rank_reorder_parity():
    """Within-rank degree/RCM reordering must leave distributed loss +
    grads identical (1e-4) to the unreordered single-device reference —
    the permutation is baked into the data distribution, never visible."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_DIST_CODE)], env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT:")][-1]
    res = json.loads(line[len("RESULT:"):])
    for mode, r in res.items():
        assert r["loss_diff"] < 1e-4, (mode, r)
        assert r["grad_diff"] < 1e-4, (mode, r)
        assert r["layout"] == mode


# ---------------------------------------------------------------------------
# Lowering integration
# ---------------------------------------------------------------------------

def test_lower_auto_uses_cost_model_in_interpret_mode(rng, tmp_path,
                                                      monkeypatch):
    """layout="auto" means one thing in both passes: ``_select_order``'s
    block-count rule at the default tile, with no timing and no file
    written. ``lower`` and ``lower_sampled`` pick the same order, on a
    graph where a reorder pays and on one where it does not."""
    monkeypatch.setenv("HOME", str(tmp_path))
    n, f = 48, 32
    cfg = GNNConfig(kind="GCN", layer_dims=[f, 12, 4])
    idx = np.arange(n)
    shuffled = rng.permutation(n)
    ring = csr_from_edges(np.concatenate([idx, shuffled]),
                          np.concatenate([idx, np.roll(shuffled, 1)]), n)
    path = csr_from_edges(np.concatenate([idx, idx[:-1]]),
                          np.concatenate([idx, idx[1:]]), n)
    orders = set()
    for g in (ring, path):
        x = _features(rng, n, f, 0.95)
        want = _select_order(g, "auto")[0]
        full = lower(cfg, g, x, engine="pallas", interpret=True,
                     layout="auto")
        sampled = lower_sampled(cfg, g, x, fanouts=(4, 4), batch_size=16,
                                engine="xla", seed=0, layout="auto")
        assert full.layout.order == sampled.layout.order == want
        assert (full.layout.source, sampled.layout.source) == \
            ("auto", "sampled")
        if full.layout.permutes:
            np.testing.assert_array_equal(full.layout.perm,
                                          sampled.layout.perm)
        orders.add(want)
    assert "none" in orders and len(orders) == 2  # one reorder, one not
    assert list(tmp_path.iterdir()) == []  # no cache file anywhere


def test_default_lowering_keeps_identity_order(rng):
    """No layout request -> no permutation (back-compat: plans built the
    PR-4 way only gain the adaptive-bc fallback)."""
    n, f = 48, 32
    g = _graph(rng)
    x = _features(rng, n, f, 0.95)
    cfg = GNNConfig(kind="GCN", layer_dims=[f, 12, 4])
    plan = lower(cfg, g, x, engine="xla")
    assert plan.layout.order == "none"
    assert not plan.layout.permutes
    assert plan.layout.bc == adaptive_bc(g.n_rows)
    explicit = lower(cfg, g, x, engine="xla", br=8, bc=128)
    assert (explicit.layout.br, explicit.layout.bc) == (8, 128)
    assert explicit.layout.source == "explicit"
