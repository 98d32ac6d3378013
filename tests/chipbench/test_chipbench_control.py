"""The control: the reference put in the program's place and computed in
bfloat16, one precision below the configuration's float32, must fail the
committed limits of each cell. Here on the CPU at a size a test run can
hold; the readings that set the limits were taken on the chip at the
cells' own sizes (``chipbench/calibrate.py``)."""
import json

import jax.numpy as jnp
import pytest

from chipbench import compare, harness

CELLS = [("gcn-3x256.arxiv-full", 0.02)]


@pytest.mark.parametrize("workload,scale", CELLS)
def test_bfloat16_control_is_not_correct(workload, scale, shrink):
    shrink(scale)
    with open(harness.ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    r = harness.inputs(bench, workload, seed=2**31 + 9)
    want = harness.reference(r)
    got = harness.reference(r, dtype=jnp.bfloat16)
    values = compare.readings(got, want, compare.leaves(r["params0"]))
    limits = {k: v for k, v in r["expect"]["limits"].items() if k in values}
    assert not compare.passed(compare.judge(values, limits)), values
