"""The per-layer metrics that read the program's own spans and counters,
after a whole traced run of ``harness.run_cell`` on the CPU at a tiny
size."""
import json
import math

import jax
import pytest

from chipbench import harness
from chipbench.peaks import PEAKS
from repro.common import spans

SPAN_METRICS = ["bsr_build_s", "trace_s", "lower_mlir_s", "xla_compile_s",
                "dispatch_ms", "epoch_compiles"]


_RUN: dict = {}


@pytest.fixture
def traced(shrink, monkeypatch):
    """One traced run of the cell, shared by this file's tests, past the
    look for a chip (the CPU's device kind borrows the v5e's peaks, which
    no metric read here uses)."""
    if not _RUN:
        with open(harness.ROOT / "BENCHMARK.json") as f:
            _RUN["bench"] = json.load(f)
        monkeypatch.setattr(harness, "peak_for",
                            lambda kind: PEAKS["TPU v5 lite"])
        shrink(0.002, hidden=32, expect_engine="xla")
        spans.reset()
        _RUN["result"] = harness.run_cell(
            _RUN["bench"], "gcn-3x256.arxiv-full", 2**33 + 5, 0.2, True,
            t0=0.0, devices=jax.devices())
    return _RUN["bench"], _RUN["result"]


def test_metrics_are_declared_for_the_cell(traced):
    bench, _ = traced
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in SPAN_METRICS:
        assert per_layer[name]["workloads"] == ["gcn-3x256.arxiv-full"]


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_reader_gives_a_finite_non_negative_number(traced, name):
    _, result = traced
    assert result["correct"], result["checks"]
    value = result["metrics"][name]["value"]
    assert math.isfinite(value) and value >= 0


def test_compile_phases_fit_inside_compile_s(traced):
    m = traced[1]["metrics"]
    phases = sum(m[k]["value"] for k in ("trace_s", "lower_mlir_s",
                                         "xla_compile_s"))
    assert 0 < phases <= m["compile_s"]["value"]
    assert m["bsr_build_s"]["value"] <= m["lower_s"]["value"]
    assert m["epoch_compiles"]["value"] == 0
