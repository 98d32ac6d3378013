"""Split-phase overlap tests (DESIGN.md §11).

Covers the interior/boundary operand split, live-shift skipping, the
overlap-vs-bulk execution parity of the distributed trainer, the
``OverlapPlan`` surface on distributed plans, and host-streamed shards.

Multi-device tests run in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so this test
process keeps seeing 1 device (per the harness requirement).
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.overlap


def _run_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT:")][-1]
    return json.loads(line[len("RESULT:"):])


def _dist(k=4, name="corafull", aggregation="gcn", br=8, bc=32,
          split_phase=True):
    from repro.core.halo import build_distributed_graph
    from repro.core.partitioner import hierarchical_partition
    from repro.graph.datasets import generate_dataset

    ds = generate_dataset(name, scale=0.004, seed=0)
    part = hierarchical_partition(ds.graph, k)
    dist = build_distributed_graph(
        ds.graph, ds.features, ds.labels, ds.train_mask, part,
        br=br, bc=bc, aggregation=aggregation, split_phase=split_phase)
    return ds, dist


def _dense(stacked, p, n_rows, n_cols, br, bc):
    """Densify rank ``p`` of a stacked BSR operand dict."""
    out = np.zeros((n_rows, n_cols), np.float32)
    rows = np.asarray(stacked["rows"])[p]
    cols = np.asarray(stacked["cols"])[p]
    blocks = np.asarray(stacked["blocks"])[p]
    for b in range(rows.shape[0]):
        r, c = int(rows[b]) * br, int(cols[b]) * bc
        out[r:r + br, c:c + bc] += blocks[b]
    return out


# --------------------------------------------------------------------------
# structural invariants of the interior/boundary split (host-side, 1 device)
# --------------------------------------------------------------------------

def test_interior_operand_never_reads_ghost_columns():
    """The defining property of the split: every interior block column
    indexes a LOCAL node, so interior SpMM has no dataflow edge to the
    halo exchange — this is what lets XLA overlap the two."""
    _, dist = _dist(k=4)
    bc = 32
    n_local_bc = dist.n_local // bc
    cols = np.asarray(dist.fwd_interior["cols"])
    assert cols.max(initial=0) < n_local_bc
    # boundary operand is the one allowed to read the ghost range
    assert np.asarray(dist.fwd_boundary["cols"]).max() >= 0


def test_split_reconstructs_bulk_operand_exactly():
    """interior + boundary = the original operand, per rank, forward and
    pre-transposed backward — the parity guarantee of y_int + y_bnd."""
    _, dist = _dist(k=4)
    br, bc = 8, 32
    n_l, n_b = dist.n_local, dist.n_local + dist.n_ghost
    for p in range(4):
        whole = _dense(dist.fwd, p, n_l, n_b, br, bc)
        split = (_dense(dist.fwd_interior, p, n_l, n_b, br, bc)
                 + _dense(dist.fwd_boundary, p, n_l, n_b, br, bc))
        np.testing.assert_array_equal(whole, split)
        whole_t = _dense(dist.bwd, p, n_b, n_l, br, bc)
        split_t = (_dense(dist.bwd_interior, p, n_b, n_l, br, bc)
                   + _dense(dist.bwd_boundary, p, n_b, n_l, br, bc))
        np.testing.assert_array_equal(whole_t, split_t)


def test_interior_node_ordering_and_counts():
    """build_local_views orders [interior | boundary]; the recorded
    n_interior is consistent with the per-rank valid-node counts."""
    _, dist = _dist(k=4)
    n_int = np.asarray(dist.n_interior)
    assert n_int.shape == (4,)
    assert (n_int >= 0).all()
    assert (n_int <= np.asarray(dist.n_valid)).all()
    blocks = np.asarray(dist.interior_blocks) + np.asarray(
        dist.boundary_blocks)
    assert (blocks > 0).all()


def test_live_shifts_cover_exactly_the_used_ring_distances():
    """Satellite: a shift is live iff SOME rank sends at that ring
    distance (any-over-ranks — ppermute is a collective, so the set must
    be uniform). Dead shifts have an all-empty send schedule."""
    _, dist = _dist(k=4)
    send = np.asarray(dist.send_idx)  # [P, P-1, max_send]
    P = send.shape[0]
    live = set(dist.live_shifts)
    assert live <= set(range(1, P))
    for s in range(1, P):
        used = bool((send[:, s - 1] >= 0).any())
        assert (s in live) == used, (s, live)


def test_post_init_rejects_interior_ghost_reads():
    """DistributedGraph.__post_init__ validates the split: an interior
    operand whose columns stray into the ghost range is rejected."""
    _, dist = _dist(k=2)
    bad_int = dict(dist.fwd_interior)
    bad_int["cols"] = np.full_like(
        np.asarray(dist.fwd_interior["cols"]),
        (dist.n_local + dist.n_ghost) // 32 - 1)
    with pytest.raises(ValueError, match="interior"):
        dataclasses.replace(dist, fwd_interior=bad_int)


def test_split_phase_off_builds_no_split_operands():
    """The overlap=False escape hatch: split_phase=False yields a graph
    without split operands, and lowering it emits the bulk primitives
    with no OverlapPlan."""
    from repro.core.lowering import lower_distributed
    from repro.models.gnn import GNNConfig

    ds, dist = _dist(k=2, split_phase=False)
    assert dist.fwd_interior is None and dist.fwd_boundary is None
    cfg = GNNConfig(kind="GCN",
                    layer_dims=[ds.features.shape[1], 8, ds.n_classes],
                    aggregation="gcn")
    plan = lower_distributed(cfg, dist)
    assert plan.overlap is None
    assert plan.layers[0].agg_primitive.endswith("dist_spmm_fused_epilogue")


def test_overlap_plan_surface():
    """OverlapPlan reaches the plan dump: block-count breakdown, live
    shifts, and the double-buffer contract; overlap=False falls back."""
    from repro.core.lowering import lower_distributed
    from repro.models.gnn import GNNConfig

    ds, dist = _dist(k=4)
    cfg = GNNConfig(kind="GCN",
                    layer_dims=[ds.features.shape[1], 8, ds.n_classes],
                    aggregation="gcn")
    plan = lower_distributed(cfg, dist)
    ov = plan.overlap
    assert ov is not None
    assert ov.interior_blocks == int(np.asarray(dist.interior_blocks).sum())
    assert ov.boundary_blocks == int(np.asarray(dist.boundary_blocks).sum())
    assert ov.live_shifts == tuple(dist.live_shifts)
    assert ov.total_shifts == 3
    assert ov.double_buffer_slots == 2
    assert "overlap[" in plan.describe()
    assert "split-phase" in plan.describe()
    assert plan.layers[0].agg_primitive.endswith("_split")

    bulk = lower_distributed(cfg, dist, overlap=False)
    assert bulk.overlap is None
    assert not bulk.layers[0].agg_primitive.endswith("_split")


def test_ghost_buffer_ring_contract():
    """Double-buffer contract: adjacent layers draw distinct slots; a
    repeat acquisition of the same slot (would overwrite a live ghost
    buffer) and a single-slot ring are rejected."""
    from repro.core.halo import GhostBufferRing

    ring = GhostBufferRing(n_slots=2)
    slots = [ring.acquire(i) for i in range(4)]
    assert slots == [0, 1, 0, 1]
    assert all(a != b for a, b in zip(slots, slots[1:]))
    assert ring.schedule() == (0, 1, 0, 1)
    with pytest.raises(ValueError):
        ring.acquire(3)  # same layer parity twice in a row
    with pytest.raises(ValueError):
        GhostBufferRing(n_slots=1)


# --------------------------------------------------------------------------
# host-streamed shards (single device)
# --------------------------------------------------------------------------

def test_streamed_spmm_matches_resident_oracle():
    """Forward and grad of the host-streamed SpMM match the fully
    device-resident operand to float32 round-off, while keeping at most
    two strips of either operand on device."""
    import jax
    import jax.numpy as jnp
    from repro.core.aggregate import _weighted_graph
    from repro.graph.csr import permute_graph
    from repro.graph.datasets import generate_dataset
    from repro.runtime.streaming import build_streamed_operand, streamed_spmm

    ds = generate_dataset("corafull", scale=0.008, seed=0)
    op = build_streamed_operand(ds.graph, aggregation="gcn", k_shards=4,
                                budget_bytes=48 * 1024)
    assert op.fwd.n_strips > 1 and op.bwd.n_strips > 1
    assert op.device_nbytes() <= 48 * 1024
    assert op.total_nbytes() > op.device_nbytes()

    inv = np.empty_like(op.order)
    inv[op.order] = np.arange(op.n_nodes)
    W = _weighted_graph(permute_graph(ds.graph, inv), "gcn")
    dense = np.zeros((op.n_nodes, op.n_nodes), np.float32)
    rows = np.repeat(np.arange(op.n_nodes), np.diff(W.indptr))
    dense[rows, W.indices] = W.data

    rng = np.random.default_rng(0)
    x = rng.standard_normal((op.n_nodes, 12)).astype(np.float32)
    y = jax.jit(lambda u: streamed_spmm(op.fwd, op.bwd, u))(x)
    np.testing.assert_allclose(np.asarray(y), dense @ x, atol=1e-4)

    f = jax.jit(jax.grad(
        lambda u: jnp.sum(streamed_spmm(op.fwd, op.bwd, u) ** 2)))
    gref = 2.0 * dense.T @ (dense @ x)
    np.testing.assert_allclose(np.asarray(f(x)), gref,
                               atol=1e-3, rtol=1e-4)


def test_streamed_training_parity_vs_resident():
    """A 2-layer GCN trained on streamed operands produces the same loss
    and grads as the same model with a fully-resident dense aggregate."""
    import jax
    import jax.numpy as jnp
    from repro.core.aggregate import _weighted_graph
    from repro.core.pipeline import arch_layer_fns, pipelined_value_and_grad
    from repro.graph.csr import permute_graph
    from repro.graph.datasets import generate_dataset
    from repro.models.gnn import GNNConfig, LayerOps, init_params
    from repro.runtime.streaming import build_streamed_operand

    ds = generate_dataset("corafull", scale=0.006, seed=0)
    cfg = GNNConfig(kind="GCN",
                    layer_dims=[ds.features.shape[1], 8, ds.n_classes],
                    aggregation="gcn")
    op = build_streamed_operand(ds.graph, aggregation="gcn", k_shards=2,
                                budget_bytes=32 * 1024)
    x = jnp.asarray(ds.features[op.order])
    labels = jnp.asarray(ds.labels[op.order])
    mask = jnp.asarray(ds.train_mask[op.order])
    params = init_params(cfg, jax.random.PRNGKey(1))

    inv = np.empty_like(op.order)
    inv[op.order] = np.arange(op.n_nodes)
    W = _weighted_graph(permute_graph(ds.graph, inv), "gcn")
    dense = np.zeros((op.n_nodes, op.n_nodes), np.float32)
    rows = np.repeat(np.arange(op.n_nodes), np.diff(W.indptr))
    dense[rows, W.indices] = W.data
    dense_j = jnp.asarray(dense)

    def run(aggregate):
        ops = [LayerOps(aggregate=aggregate) for _ in range(cfg.n_layers)]
        fns = arch_layer_fns(cfg, ops)
        return pipelined_value_and_grad(fns, params, x, labels, mask)

    loss_s, grads_s = jax.jit(lambda: run(op.aggregate))()
    loss_r, grads_r = jax.jit(lambda: run(lambda u: dense_j @ u))()
    assert abs(float(loss_s) - float(loss_r)) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(grads_s),
                    jax.tree_util.tree_leaves(grads_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# --------------------------------------------------------------------------
# overlap-vs-bulk execution parity (multi-device subprocess)
# --------------------------------------------------------------------------

_OVERLAP_PARITY_CODE = """
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.graph.datasets import generate_dataset
    from repro.core.partitioner import hierarchical_partition
    from repro.core.halo import build_distributed_graph
    from repro.core.lowering import effective_aggregation, lower_distributed
    from repro.models.gnn import GNNConfig
    from repro.training.trainer import DistributedGNNTrainer
    from repro.training.optimizer import adam

    K = {k}
    out = {{}}
    # corafull analog: 95%-sparse features; flickr analog: dense regime
    cases = [("GCN", "gcn", "corafull"), ("SAGE", "mean", "corafull"),
             ("GIN", "sum", "corafull"), ("GAT", "sum", "corafull"),
             ("GT", "sum", "corafull"), ("GCN", "gcn", "flickr")]
    data = {{name: generate_dataset(name, scale=0.004, seed=0)
            for name in {{c[2] for c in cases}}}}
    parts = {{name: hierarchical_partition(ds.graph, K)
             for name, ds in data.items()}}
    for kind, agg, dsname in cases:
        ds, part = data[dsname], parts[dsname]
        cfg = GNNConfig(kind=kind,
                        layer_dims=[ds.features.shape[1], 16, ds.n_classes],
                        aggregation=agg)
        dist = build_distributed_graph(
            ds.graph, ds.features, ds.labels, ds.train_mask, part,
            br=8, bc=32, aggregation=effective_aggregation(cfg))
        res = {{}}
        for ov in (True, False):
            plan = lower_distributed(cfg, dist, overlap=ov)
            tr = DistributedGNNTrainer(dist, cfg, adam(0.01), interpret=True,
                                       seed=3, plan=plan)
            loss, grads = tr.loss_and_grads()
            res[ov] = (float(loss),
                       [np.asarray(g) for g in
                        jax.tree_util.tree_leaves(grads)])
        dl = abs(res[True][0] - res[False][0])
        dg = max(float(np.abs(a - b).max())
                 for a, b in zip(res[True][1], res[False][1]))
        plan = lower_distributed(cfg, dist)
        out[f"{{kind}}/{{dsname}}"] = {{
            "loss_diff": dl, "grad_diff": dg,
            "primitive": plan.layers[0].agg_primitive,
            "live_shifts": len(plan.overlap.live_shifts),
            "interior_blocks": plan.overlap.interior_blocks,
            "boundary_blocks": plan.overlap.boundary_blocks,
        }}
    print("RESULT:" + json.dumps(out))
"""


@pytest.mark.slow
@pytest.mark.parametrize("k", [2, 4])
def test_overlap_parity_all_archs(k):
    """Split-phase overlapped execution matches bulk execution to 1e-4
    (loss + per-layer grads) for GCN/SAGE/GIN/GAT/GT and both sparsity
    regimes, with the split primitives bound and a non-trivial
    interior/boundary block breakdown."""
    res = _run_subprocess(textwrap.dedent(_OVERLAP_PARITY_CODE).format(k=k))
    assert set(res) == {"GCN/corafull", "SAGE/corafull", "GIN/corafull",
                        "GAT/corafull", "GT/corafull", "GCN/flickr"}
    for name, r in res.items():
        assert r["loss_diff"] < 1e-4, (name, r)
        assert r["grad_diff"] < 1e-4, (name, r)
        assert r["primitive"].endswith("_split"), (name, r)
        assert r["interior_blocks"] > 0, (name, r)
        assert r["boundary_blocks"] > 0, (name, r)
        assert 1 <= r["live_shifts"] <= k - 1, (name, r)


@pytest.mark.slow
def test_live_shift_exchange_matches_full_ring():
    """Unrolling only the live shifts produces the same ghost buffer as
    the full P-1 round ring exchange."""
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core.halo import build_distributed_graph, halo_exchange
        from repro.core.partitioner import hierarchical_partition
        from repro.graph.datasets import generate_dataset

        ds = generate_dataset("corafull", scale=0.004, seed=0)
        part = hierarchical_partition(ds.graph, 8)
        dist = build_distributed_graph(
            ds.graph, ds.features, ds.labels, ds.train_mask, part,
            br=8, bc=32, aggregation="gcn")
        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.standard_normal(
            (8, dist.n_local, 5)).astype(np.float32))
        send = jnp.asarray(dist.send_idx)
        recv = jnp.asarray(dist.recv_slot)

        def run(shifts):
            def f(x, s, r):
                return halo_exchange(x[0], s[0], r[0], dist.n_ghost,
                                     "data", shifts)[None]
            return shard_map(f, mesh=mesh, in_specs=(P("data"),) * 3,
                             out_specs=P("data"), check_vma=False)(
                                 X, send, recv)

        full = run(None)
        live = run(dist.live_shifts)
        print("RESULT:" + json.dumps({
            "diff": float(jnp.abs(full - live).max()),
            "n_live": len(dist.live_shifts),
            "norm": float(jnp.abs(full).max())}))
    """)
    res = _run_subprocess(code)
    assert res["norm"] > 0.0, res
    assert res["diff"] == 0.0, res
    assert 1 <= res["n_live"] <= 7, res
