"""Fused BSR flash-attention kernel family (DESIGN.md §10).

Four layers of coverage:

* kernel vs edge-list oracle — forward + grads at 1e-4 across square /
  bipartite geometries, both inners (Pallas-interpret and XLA reference),
  single- and multi-head, with and without a ``bf`` lane tile;
* online-softmax recurrence goldens — a hand-built two-block row whose
  second block raises the running max, pinning the rescale path and the
  saved (m, l) statistics against closed-form values;
* padded-block masking — empty destination rows (explicit zero blocks)
  produce zero output, finite (m=0, l=0) stats, and finite gradients;
* plan bindings + end-to-end parity — GAT/GT lower onto
  ``spmm_attention`` by default on pallas/xla (``fuse_attention=False``
  falls back to the segment path), and the fused model matches the
  segment model to 1e-4 (fwd + grads) on all three trainers.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import get_backend
from repro.backends.registry import edge_softmax_aggregate
from repro.core.lowering import lower, lower_sampled
from repro.graph.csr import csr_from_edges
from repro.kernels import ops as kops
from repro.kernels.bsr_attention import bsr_attention_fwd
from repro.models.gnn import GNNConfig, GNNModel, init_params
from repro.training.optimizer import sgd
from repro.training.trainer import MiniBatchTrainer

pytestmark = pytest.mark.attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _graph(rng, n=33, e=200):
    """Square graph with self-loops (every row non-empty)."""
    return csr_from_edges(
        np.concatenate([rng.integers(0, n, e), np.arange(n)]),
        np.concatenate([rng.integers(0, n, e), np.arange(n)]),
        n,
    )


def _mha_and_oracle(graph, inner, rng, heads, dh, bf=None, br=8, bc=8):
    backend = get_backend("pallas" if inner == "pallas" else "xla")
    fwd = backend.build_spmm_operand(graph, br=br, bc=bc)
    bwd = backend.build_spmm_operand(graph.transpose(), br=br, bc=bc)
    mha = kops.build_sparse_mha(fwd, bwd, inner, interpret=True, bf=bf)
    src, dst = graph.edge_list()
    src, dst = jnp.asarray(src), jnp.asarray(dst)
    n = graph.n_rows

    def oracle(z, a_src, a_dst):
        return edge_softmax_aggregate(z, a_src, a_dst, src, dst, n)

    z = jnp.asarray(rng.standard_normal((graph.n_cols, heads, dh)),
                    jnp.float32)
    a_src = jnp.asarray(rng.standard_normal((heads, dh)), jnp.float32)
    a_dst = jnp.asarray(rng.standard_normal((heads, dh)), jnp.float32)
    return mha, oracle, (z, a_src, a_dst)


def _grads(fn, cot, *args):
    def loss(z, a_src, a_dst):
        return jnp.sum(fn(z, a_src, a_dst) * cot)

    return jax.grad(loss, argnums=(0, 1, 2))(*args)


# ---------------------------------------------------------------------------
# Kernel vs edge-list oracle: forward + grads at 1e-4
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("inner", ["pallas", "xla"])
@pytest.mark.parametrize("heads,dh", [(1, 8), (3, 5)])
def test_sparse_mha_matches_edge_oracle(rng, inner, heads, dh):
    g = _graph(rng)
    mha, oracle, (z, a_src, a_dst) = _mha_and_oracle(g, inner, rng, heads, dh)
    out = mha(z, a_src, a_dst)
    ref = oracle(z, a_src, a_dst)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    cot = jnp.asarray(rng.standard_normal(ref.shape), jnp.float32)
    for a, b in zip(_grads(mha, cot, z, a_src, a_dst),
                    _grads(oracle, cot, z, a_src, a_dst)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("inner", ["pallas", "xla"])
def test_sparse_mha_bf_head_tiling(rng, inner):
    """A cached lane tile narrower than the head dim pads the head to a
    multiple of bf; results are identical to the un-tiled call."""
    g = _graph(rng)
    mha, oracle, (z, a_src, a_dst) = _mha_and_oracle(
        g, inner, rng, heads=2, dh=6, bf=4)
    out = mha(z, a_src, a_dst)
    ref = oracle(z, a_src, a_dst)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    cot = jnp.asarray(rng.standard_normal(ref.shape), jnp.float32)
    for a, b in zip(_grads(mha, cot, z, a_src, a_dst),
                    _grads(oracle, cot, z, a_src, a_dst)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# Online-softmax recurrence goldens (hand-built two-block row)
# ---------------------------------------------------------------------------

def test_online_softmax_recurrence_golden():
    """One destination row spanning two 4x4 blocks whose SECOND block holds
    the max score — the running max must be raised mid-row and the partial
    accumulator rescaled by exp(m_prev - m_new). Pinned against the direct
    dense softmax and closed-form (m, l)."""
    br = bc = 4
    # row block 0 covers dst rows 0..3; two column blocks (src 0..3, 4..7)
    blocks = np.zeros((2, br, bc), np.float32)
    blocks[0, 0, :2] = 1.0   # dst 0 attends src {0, 1} in block 0
    blocks[1, 0, 2:] = 1.0   # ... and src {6, 7} in block 1
    blocks[0, 1, 1] = 1.0    # dst 1 attends src {1} only (single block)
    block_rows = np.array([0, 0], np.int32)
    block_cols = np.array([0, 1], np.int32)
    first = np.array([1, 0], np.int32)
    last = np.array([0, 1], np.int32)

    heads, dh = 1, 4
    rng = np.random.default_rng(7)
    z = rng.standard_normal((8, dh)).astype(np.float32)
    # score = leaky_relu(adst_i + asrc_j); make block-1 sources dominate
    adst = np.array([[0.3], [-0.2], [0.0], [0.0],
                     [0], [0], [0], [0]], np.float32)[:4]
    asrc = np.array([[-1.0], [0.5], [0.0], [0.0],
                     [0.0], [0.0], [4.0], [6.0]], np.float32)

    out, m, l = bsr_attention_fwd(
        jnp.asarray(block_rows), jnp.asarray(block_cols),
        jnp.asarray(first), jnp.asarray(last), jnp.asarray(blocks),
        jnp.asarray(adst), jnp.asarray(asrc), jnp.asarray(z),
        n_rows_padded=4, heads=heads, dh=dh, interpret=True)

    def leaky(v):
        return np.where(v >= 0, v, 0.2 * v)

    for i, nbrs in ((0, [0, 1, 6, 7]), (1, [1])):
        s = leaky(adst[i, 0] + asrc[nbrs, 0])
        att = np.exp(s - s.max())
        att /= att.sum()
        np.testing.assert_allclose(np.asarray(out)[i], att @ z[nbrs],
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(float(np.asarray(m)[i, 0]), s.max(),
                                   atol=1e-6)
        np.testing.assert_allclose(float(np.asarray(l)[i, 0]),
                                   np.exp(s - s.max()).sum(), atol=1e-5)
    # the max of dst 0 lives in block 1 — the recurrence must have rescaled
    assert float(np.asarray(m)[0, 0]) == pytest.approx(
        leaky(adst[0, 0] + asrc[7, 0]), abs=1e-6)


def test_padded_block_masking(rng):
    """Empty destination rows (all-zero mask) give zero output, clamped
    finite stats (m=0, l=0), and finite grads — NEG_INF never leaks."""
    n = 24
    # dsts 16..23 have NO in-edges; sources cover the full range
    src = np.concatenate([rng.integers(0, n, 120), np.arange(16)])
    dst = np.concatenate([rng.integers(0, 16, 120), np.arange(16)])
    g = csr_from_edges(src, dst, n)
    for inner in ("pallas", "xla"):
        mha, _, (z, a_src, a_dst) = _mha_and_oracle(g, inner, rng, 2, 4)
        out = mha(z, a_src, a_dst)
        assert np.all(np.asarray(out)[16:] == 0.0), inner
        assert np.all(np.isfinite(np.asarray(out))), inner
        cot = jnp.ones_like(out)
        for gr in _grads(mha, cot, z, a_src, a_dst):
            assert np.all(np.isfinite(np.asarray(gr))), inner


# ---------------------------------------------------------------------------
# Plan bindings: spmm_attention by default, segment under the A/B lever
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["GAT", "GT"])
@pytest.mark.parametrize("engine", ["pallas", "xla"])
def test_plan_binds_fused_attention_by_default(rng, kind, engine):
    n, f, c = 32, 12, 4
    g = _graph(rng, n=n)
    x = rng.standard_normal((n, f)).astype(np.float32)
    cfg = GNNConfig(kind=kind, layer_dims=[f, 16, c], aggregation="gcn",
                    gat_heads=4)
    plan = lower(cfg, g, x, engine=engine, interpret=True)
    assert plan.layers[0].agg_primitive == f"{engine}.spmm_attention"
    for layer in plan.layers:
        assert layer.attention is not None and layer.attention.fused
        assert layer.attention.heads == 4
        assert layer.attention.vjp == "recompute(m,l)"
        assert "attention[" in layer.describe()
        assert layer.epilogue is None  # attention archs never bind one

    seg = lower(cfg, g, x, engine=engine, interpret=True,
                fuse_attention=False)
    assert seg.layers[0].agg_primitive == \
        f"{engine}.segment_softmax_aggregate"
    assert all(not l.attention.fused for l in seg.layers)

    gather = lower(cfg, g, x, engine="gather")
    assert gather.layers[0].agg_primitive == \
        "gather.segment_softmax_aggregate"


# ---------------------------------------------------------------------------
# End-to-end parity: fused vs segment, all three trainers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["GAT", "GT"])
@pytest.mark.parametrize("engine", ["pallas", "xla"])
def test_fused_attention_model_parity(rng, kind, engine):
    n, f, c = 40, 12, 4
    g = _graph(rng, n=n)
    x = rng.standard_normal((n, f)).astype(np.float32)
    cfg = GNNConfig(kind=kind, layer_dims=[f, 16, c], aggregation="gcn",
                    gat_heads=4)
    fused = GNNModel(cfg, g, plan=lower(cfg, g, x, engine=engine,
                                        interpret=True))
    seg = GNNModel(cfg, g, plan=lower(cfg, g, x, engine=engine,
                                      interpret=True, fuse_attention=False))
    assert fused._fuse_attention and not seg._fuse_attention
    params = init_params(cfg, jax.random.PRNGKey(1))
    xj = jnp.asarray(x)
    labels = jnp.asarray(rng.integers(0, c, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < 0.7)
    lf, gf = jax.value_and_grad(fused.loss_fn)(params, xj, labels, mask)
    ls, gs = jax.value_and_grad(seg.loss_fn)(params, xj, labels, mask)
    assert abs(float(lf) - float(ls)) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.sampling
@pytest.mark.parametrize("kind", ["GAT", "GT"])
def test_minibatch_fused_attention_full_fanout_parity(rng, kind):
    """Full fanout makes the sampled neighbourhood exact, so the fused
    mini-batch GAT must match the segment path bit-for-bit at 1e-4."""
    n, f, c = 48, 10, 4
    g = _graph(rng, n=n, e=260)
    x = rng.standard_normal((n, f)).astype(np.float32)
    labels = rng.integers(0, c, n)
    mask = np.zeros(n, bool)
    mask[:24] = True
    cfg = GNNConfig(kind=kind, layer_dims=[f, 12, c], aggregation="gcn",
                    gat_heads=2)
    results = {}
    for tag, fa in (("fused", True), ("segment", False)):
        plan = lower_sampled(cfg, g, x, fanouts=(n, n), batch_size=24,
                             n_buckets=1, engine="xla", seed=0,
                             fuse_attention=fa)
        tr = MiniBatchTrainer(cfg, None, x, labels, mask, sgd(0.1),
                              plan=plan, seed=0)
        assert tr._fuse_attention is fa
        assert plan.sampler.emit_bsr is fa
        loss, grads = tr.loss_and_grads(np.flatnonzero(mask))
        results[tag] = (float(loss), grads)
    lf, gf = results["fused"]
    ls, gs = results["segment"]
    assert abs(lf - ls) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


_DIST_CODE = """
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.graph.datasets import generate_dataset
    from repro.core.partitioner import hierarchical_partition
    from repro.core.halo import build_distributed_graph
    from repro.core.lowering import (effective_aggregation, lower,
                                     lower_distributed)
    from repro.models.gnn import GNNConfig, GNNModel, init_params
    from repro.training.trainer import DistributedGNNTrainer
    from repro.training.optimizer import adam

    out = {}
    ds = generate_dataset("corafull", scale=0.004, seed=0)
    part = hierarchical_partition(ds.graph, 4)
    for kind in ("GAT", "GT"):
        cfg = GNNConfig(kind=kind,
                        layer_dims=[ds.features.shape[1], 16, ds.n_classes],
                        aggregation="sum")
        dist = build_distributed_graph(
            ds.graph, ds.features, ds.labels, ds.train_mask, part,
            br=8, bc=32, aggregation=effective_aggregation(cfg))
        plan = lower_distributed(cfg, dist)
        tr = DistributedGNNTrainer(dist, cfg, adam(0.01), interpret=True,
                                   seed=3, plan=plan)
        loss, grads = tr.loss_and_grads()
        model = GNNModel(cfg, ds.graph,
                         plan=lower(cfg, ds.graph, ds.features, engine="xla"))
        params = init_params(cfg, jax.random.PRNGKey(3))
        ref_loss, ref_grads = jax.value_and_grad(model.loss_fn)(
            params, jnp.asarray(ds.features), jnp.asarray(ds.labels),
            jnp.asarray(ds.train_mask))
        gd = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree_util.tree_leaves(grads),
            jax.tree_util.tree_leaves(ref_grads)))
        out[kind] = {
            "primitive": plan.layers[0].agg_primitive,
            "loss_diff": abs(float(loss) - float(ref_loss)),
            "grad_diff": gd,
        }
    print("RESULT:" + json.dumps(out))
"""


@pytest.mark.slow
def test_distributed_fused_attention_parity():
    """The dist_spmm_attention composition (halo exchange + fused sparse
    MHA over the [local|ghost] buffer) matches the single-device fused
    model's loss and grads to 1e-4 for GAT and GT."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_DIST_CODE)], env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT:")][-1]
    res = json.loads(line[len("RESULT:"):])
    for kind in ("GAT", "GT"):
        r = res[kind]
        # split-phase overlap is the default distributed attention binding
        assert r["primitive"] == "distributed.dist_spmm_attention_split", r
        assert r["loss_diff"] < 1e-4, r
        assert r["grad_diff"] < 1e-4, r


def test_gt_layer_residual_and_training_step(rng):
    """GT smoke: the residual branch exists (w_res), contributes to the
    output, and one optimizer step reduces the loss."""
    n, f, c = 40, 12, 4
    g = _graph(rng, n=n)
    x = rng.standard_normal((n, f)).astype(np.float32)
    cfg = GNNConfig(kind="GT", layer_dims=[f, 16, c], aggregation="gcn",
                    gat_heads=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert all("w_res" in layer for layer in params["layers"])
    model = GNNModel(cfg, g, plan=lower(cfg, g, x, engine="xla"))
    xj = jnp.asarray(x)
    y0 = model.apply(params, xj)
    # zeroing the residual weights must change the output
    p_no_res = jax.tree_util.tree_map(lambda a: a, params)
    p_no_res["layers"] = [dict(layer, w_res=jnp.zeros_like(layer["w_res"]))
                          for layer in params["layers"]]
    y1 = model.apply(p_no_res, xj)
    assert float(jnp.abs(y0 - y1).max()) > 1e-4
    labels = jnp.asarray(rng.integers(0, c, n).astype(np.int32))
    mask = jnp.ones(n, bool)
    loss0, grads = jax.value_and_grad(model.loss_fn)(params, xj, labels, mask)
    stepped = jax.tree_util.tree_map(lambda p, g_: p - 0.1 * g_, params, grads)
    loss1 = model.loss_fn(stepped, xj, labels, mask)
    assert float(loss1) < float(loss0)


@pytest.mark.parametrize("window", [1, 4])
def test_windowed_attention_matches_whole_stream(rng, window):
    """All three attention kernels over windows smaller than a hub row
    (block-row 0 touches every block-column) equal the one-window run: the
    (out, m, l) / dc / (dzv, dd) tiles resume across window boundaries and
    the forward finalises once, at the true last_in_row."""
    from repro.graph.csr import csr_to_bsr
    from repro.kernels.bsr_attention import (bsr_attention_bwd_col,
                                             bsr_attention_bwd_row)

    n, heads, dh = 40, 2, 8
    g = csr_from_edges(
        np.concatenate([rng.integers(0, n, 150), np.arange(n)]),
        np.concatenate([rng.integers(0, n, 150), np.zeros(n, np.int64)]), n)
    a, at = csr_to_bsr(g, br=8, bc=8), csr_to_bsr(g.transpose(), br=8, bc=8)
    assert np.bincount(a.block_rows)[0] > window
    arr = lambda v: jnp.asarray(rng.standard_normal(v).astype(np.float32))
    stat, feat = (n, heads), (n, heads * dh)
    adst, asrc, z, dy, r = arr(stat), arr(stat), arr(feat), arr(feat), arr(stat)
    fwd_idx = [jnp.asarray(v) for v in (a.block_rows, a.block_cols,
                                        a.first_in_row)]
    bwd_idx = [jnp.asarray(v) for v in (at.block_rows, at.block_cols,
                                        at.first_in_row)]
    kw = dict(n_rows_padded=n, heads=heads, dh=dh, interpret=True)
    for w in (None, window):
        out, m, l = bsr_attention_fwd(
            *fwd_idx, jnp.asarray(a.last_in_row), jnp.asarray(a.blocks),
            adst, asrc, z, window=w, **kw)
        dc = bsr_attention_bwd_row(*fwd_idx, jnp.asarray(a.blocks), adst,
                                   asrc, z, dy, r, m, l, window=w, **kw)
        dzv, dd = bsr_attention_bwd_col(*bwd_idx, jnp.asarray(at.blocks),
                                        asrc, adst, z, dy, r, m, l,
                                        window=w, **kw)
        got = [np.asarray(v) for v in (out, m, l, dc, dzv, dd)]
        if w is None:
            whole = got
    for g_, w_ in zip(got, whole):
        np.testing.assert_allclose(g_, w_, atol=1e-5, rtol=1e-5)
