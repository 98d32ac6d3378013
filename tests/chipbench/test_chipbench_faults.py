"""Whole runs of ``harness.run_cell`` on the CPU at a tiny size, past the
look for a chip, with the timed path sound and with it broken underneath:
the comparison must read ``correct`` false for each fault a training cell
can have on one chip (a step that leaves its state unchanged; half of the
batch left out, the mean taken over the rest), and for a plan that does
not bind the cell's Pallas primitives."""
import json

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness
from repro.core.dsl import CompiledProgram
from repro.models.gnn import GNNModel

CELLS = ["gcn-3x256.arxiv-full"]


def run(workload):
    with open(harness.ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    return harness.run_cell(bench, workload, 2**32 + 17, 0.2, False, t0=0.0,
                            devices=jax.devices())


def unchanged_state(monkeypatch):
    step = CompiledProgram.train_epoch

    def frozen(self):
        params, opt_state = self.params, self.opt_state
        out = step(self)
        self.params, self.opt_state = params, opt_state
        return out

    monkeypatch.setattr(CompiledProgram, "train_epoch", frozen)


def half_batch(monkeypatch):
    loss = GNNModel.loss_fn

    def half(self, params, x, labels, mask):
        return loss(self, params, x, labels,
                    mask & (jnp.cumsum(mask) % 2 == 1))

    monkeypatch.setattr(GNNModel, "loss_fn", half)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [None, unchanged_state, half_batch],
                         ids=["sound", "unchanged-state", "half-batch"])
def test_fault_reads_not_correct(workload, fault, monkeypatch, shrink):
    shrink(0.002, hidden=32, expect_engine="xla")
    if fault is not None:
        fault(monkeypatch)
    result = run(workload)
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"


def test_pallas_run_is_correct_and_other_binding_is_not(shrink):
    shrink(0.002, hidden=32, engine="pallas", interpret=True)
    sound = run("gcn-3x256.arxiv-full")
    assert sound["correct"], sound["checks"]
    shrink(0.002, hidden=32)  # compiles on xla, the cell expects pallas
    wrong = run("gcn-3x256.arxiv-full")
    assert not wrong["correct"]
    assert wrong["checks"]["binding_mismatches"]["value"] > 0
