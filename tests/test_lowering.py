"""Lowering pass + backend registry: plan-selection goldens, plan-executed
gradient parity for every arch, and the no-monkey-patching contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import (
    OP_VOCABULARY,
    available_backends,
    get_backend,
    select_backend,
)
from repro.core.dsl import GNNProgram
from repro.core.halo import build_distributed_graph
from repro.core.lowering import (
    effective_aggregation,
    lower,
    lower_distributed,
    lower_sampled,
)
from repro.core.partitioner import hierarchical_partition
from repro.core.sparsity import PAPER_GAMMA_DEFAULT, decide_execution_path
from repro.graph.csr import csr_from_edges
from repro.graph.datasets import DATASET_SPECS
from repro.models.gnn import GNNConfig, GNNModel


def _graph(rng, n=48, e=300):
    g = csr_from_edges(
        np.concatenate([rng.integers(0, n, e), np.arange(n)]),
        np.concatenate([rng.integers(0, n, e), np.arange(n)]),
        n,
    )
    return g


def _features(rng, n, f, sparsity):
    x = rng.standard_normal((n, f)).astype(np.float32)
    if sparsity > 0:
        x[rng.random((n, f)) < sparsity] = 0.0
    return x


# ---------------------------------------------------------------------------
# Plan selection across the paper's dataset regimes (Table II analogs)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(DATASET_SPECS))
def test_plan_selection_golden_per_regime(rng, name):
    """Layer 0's plan decision must equal Alg 1 exactly, in every feature
    regime; hidden layers stay dense under the paper's γ."""
    spec = DATASET_SPECS[name]
    n, f = 48, 64
    x = _features(rng, n, f, spec.feature_sparsity)
    g = _graph(rng, n)
    cfg = GNNConfig(kind="GCN", layer_dims=[f, 16, 4])
    plan = lower(cfg, g, x, engine="xla")

    ref = decide_execution_path(x, gamma=PAPER_GAMMA_DEFAULT, n_hidden=16)
    assert plan.layers[0].decision == ref  # exact: same dataclass fields
    assert plan.layers[0].feature_path == ref.mode
    # post-ReLU hidden estimates (0.5) stay below tau=0.8 -> dense MXU path
    assert all(l.feature_path == "dense" for l in plan.layers[1:])
    assert all(l.decision.mode == "dense" for l in plan.layers[1:])


def test_plan_golden_nell_sparse_reddit_dense(rng):
    """The paper's headline regimes: NELL ≈99.2% sparse -> sparse path,
    Reddit dense -> dense path."""
    n, f = 48, 64
    g = _graph(rng, n)
    cfg = GNNConfig(kind="GCN", layer_dims=[f, 16, 4])

    nell = lower(cfg, g, _features(rng, n, f, DATASET_SPECS["nell"].feature_sparsity),
                 engine="xla")
    assert nell.layers[0].feature_path == "sparse"
    assert nell.layers[0].primitive == "xla.feature_matmul_sparse"
    assert nell.layers[0].sparse_xw is not None

    reddit = lower(cfg, g, _features(rng, n, f, DATASET_SPECS["reddit"].feature_sparsity),
                   engine="xla")
    assert reddit.layers[0].feature_path == "dense"
    assert reddit.layers[0].primitive == "xla.feature_matmul_dense"
    assert reddit.layers[0].sparse_xw is None


def test_per_layer_decisions_all_archs(rng):
    """Per-layer decisions exist for every arch (the seed only decided for
    layer 0 of GCN/SAGE)."""
    n, f = 48, 64
    g = _graph(rng, n)
    x = _features(rng, n, f, 0.95)
    for kind in ("GCN", "SAGE", "GIN", "GAT"):
        cfg = GNNConfig(kind=kind, layer_dims=[f, 16, 16, 4])
        plan = lower(cfg, g, x, engine="xla")
        assert len(plan.layers) == cfg.n_layers
        assert plan.layers[0].feature_path == "sparse", kind
        assert all(l.decision is not None for l in plan.layers)
        dump = plan.describe()
        assert kind in dump and "feature_matmul_sparse" in dump


def test_gamma_threshold_moves_decisions(rng):
    """γ -> 0 forces every layer dense (bench_throughput's fused_dense_in
    variant relies on this)."""
    n, f = 48, 64
    g = _graph(rng, n)
    x = _features(rng, n, f, 0.99)
    cfg = GNNConfig(kind="GCN", layer_dims=[f, 16, 4])
    plan = lower(cfg, g, x, gamma=1e-4, engine="xla")
    assert all(l.feature_path == "dense" for l in plan.layers)
    # hidden layers may turn sparse-profitable under a huge gamma, but they
    # must fall back to dense execution (no pre-built operand) and say so
    plan_hi = lower(cfg, g, x, gamma=0.6, engine="xla")
    hidden = plan_hi.layers[1]
    assert hidden.decision.mode == "sparse"
    assert hidden.feature_path == "dense"
    assert "fallback" in hidden.note


# ---------------------------------------------------------------------------
# One planner behind the three lowering passes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,agg", [
    ("GCN", "gcn"), ("SAGE", "mean"), ("GIN", "sum"), ("GAT", "sum"),
])
@pytest.mark.parametrize("sparsity", [0.95, 0.0], ids=["sparse", "dense"])
def test_lowering_passes_share_layer_records(rng, arch, agg, sparsity):
    """``lower``, full-fanout ``lower_sampled`` and two-rank
    ``lower_distributed`` see the same rows and features, so their layer
    records agree: every layer's decision and feature path, its epilogue
    and its attention record. Primitive names and notes name each pass's
    own operands, and are not compared."""
    n, f, c = 48, 32, 4
    g = _graph(rng, n)
    x = _features(rng, n, f, sparsity)
    cfg = GNNConfig(kind=arch, layer_dims=[f, 8, 8, c], aggregation=agg,
                    gat_heads=2 if arch == "GAT" else 1)
    dist = build_distributed_graph(
        g, x, rng.integers(0, c, n).astype(np.int32), np.ones(n, bool),
        hierarchical_partition(g, 2), aggregation=effective_aggregation(cfg))
    plans = [
        lower(cfg, g, x, engine="xla"),
        lower_sampled(cfg, g, x, fanouts=n, batch_size=n, n_buckets=1,
                      engine="xla", seed=0),
        lower_distributed(cfg, dist),
    ]
    want = "sparse" if sparsity else "dense"
    assert [p.layers[0].feature_path for p in plans] == [want] * 3
    for first, *rest in zip(*(p.layers for p in plans)):
        assert (first.epilogue is None) == (arch == "GAT")
        assert (first.attention is None) == (arch != "GAT")
        for other in rest:
            assert other.decision == first.decision, first.index
            assert other.feature_path == first.feature_path, first.index
            assert other.epilogue == first.epilogue, first.index
            assert other.attention == first.attention, first.index
    hidden = [l for p in plans for l in p.layers[1:]]
    assert all(l.feature_path == "dense" for l in hidden)


# ---------------------------------------------------------------------------
# Plan-executed gradient parity: fused/sparse vs gather-scatter/dense
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,agg", [
    ("GCN", "gcn"), ("SAGE", "mean"), ("GIN", "sum"), ("GAT", "sum"),
])
def test_fused_vs_baseline_gradient_parity(rng, arch, agg):
    n, f, h, c = 40, 32, 12, 5
    g = _graph(rng, n, e=200)
    x = _features(rng, n, f, 0.95)
    cfg = GNNConfig(kind=arch, layer_dims=[f, h, c], aggregation=agg)

    fused_plan = lower(cfg, g, x, engine="xla")
    assert fused_plan.layers[0].feature_path == "sparse"
    fused = GNNModel(cfg, g, plan=fused_plan)
    baseline = GNNModel(cfg, g, use_fused=False, engine="xla")
    assert baseline.plan.layers[0].feature_path == "dense"

    params = fused.init(jax.random.PRNGKey(0))
    xj = jnp.asarray(x)
    labels = jnp.asarray(rng.integers(0, c, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < 0.6)

    lf, gf = jax.value_and_grad(fused.loss_fn)(params, xj, labels, mask)
    lb, gb = jax.value_and_grad(baseline.loss_fn)(params, xj, labels, mask)
    assert abs(float(lf) - float(lb)) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(gf), jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

def test_registry_contents_and_vocabulary():
    avail = available_backends()
    assert set(avail) >= {"pallas", "xla", "gather"}
    for name in ("pallas", "xla", "gather"):
        b = get_backend(name)
        ok, reason = b.availability()
        assert ok and reason
        for op in OP_VOCABULARY:
            assert hasattr(b, op), f"{name} missing {op}"
    with pytest.raises(KeyError):
        get_backend("tpuv7-secret")


def test_auto_selection_prefers_compiled_backend_off_tpu():
    best = select_backend(None)
    if jax.default_backend() == "tpu":
        assert best.name == "pallas"
    else:
        assert best.name == "xla"
    # explicit preference always wins
    assert select_backend("gather").name == "gather"


@pytest.mark.parametrize("engine", ["xla", "gather", "pallas"])
def test_compile_engine_call_sites_route_through_registry(rng, engine):
    """Every legacy compile(engine=...) spelling still works."""
    n, f = 32, 24
    g = _graph(rng, n, e=120)
    x = _features(rng, n, f, 0.9)
    labels = rng.integers(0, 4, n).astype(np.int32)
    mask = rng.random(n) < 0.7
    gnn = GNNProgram(g, x, labels, mask, n_classes=4, arch="GCN")
    gnn.initialize_layers([f, 8, 4], "xavier", seed=0)
    prog = gnn.compile(engine=engine, interpret=True)
    assert prog.plan.backend == engine
    losses = [prog.train_epoch()["loss"] for _ in range(3)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] + 1e-3


# ---------------------------------------------------------------------------
# The synthesized program is data, not patched methods
# ---------------------------------------------------------------------------

def test_no_runtime_method_patching(rng):
    n, f = 32, 24
    g = _graph(rng, n, e=120)
    x = _features(rng, n, f, 0.95)
    gnn = GNNProgram(g, x, rng.integers(0, 4, n).astype(np.int32),
                     rng.random(n) < 0.7, n_classes=4, arch="GCN")
    gnn.initialize_layers([f, 8, 4], "xavier", seed=0)
    prog = gnn.compile(engine="xla")
    # sparse path chosen, yet the bound method is still the class's own
    assert prog.plan.layers[0].feature_path == "sparse"
    assert "_layer" not in prog.model.__dict__
    assert prog.model._layer.__func__ is GNNModel._layer


def test_sparsity_decision_backward_compat_shim(rng):
    n, f = 32, 24
    g = _graph(rng, n, e=120)
    x = _features(rng, n, f, 0.95)
    gnn = GNNProgram(g, x, rng.integers(0, 4, n).astype(np.int32),
                     rng.random(n) < 0.7, n_classes=4, arch="GCN")
    gnn.initialize_layers([f, 8, 4], "xavier", seed=0)
    prog = gnn.compile(engine="xla")
    assert prog.sparsity_decision is prog.plan.layers[0].decision
    assert prog.sparsity_decision == decide_execution_path(
        x, gamma=PAPER_GAMMA_DEFAULT, n_hidden=8)
