"""The benchmark's tests import ``chipbench`` from the checkout root, and
drive its cells on the CPU at a size a test run can hold."""
import json
import sys
from pathlib import Path

import pytest

ROOT = str(Path(__file__).resolve().parents[2])
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture
def shrink(monkeypatch):
    """``shrink(scale, ...)`` makes every cell that the harness resolves
    from here on run at ``scale`` of its mix, with ``hidden`` channels and
    the mix's ``spec`` updated where given, and has the program compile on
    ``engine`` (``interpret`` for Pallas off the chip). ``expect_engine``
    renames the backend the cell expects to bind to that engine."""
    from chipbench import harness
    from repro.core.dsl import GNNProgram

    def apply(scale, hidden=None, spec=None, engine="xla", interpret=False,
              expect_engine=None):
        resolve = harness.resolve

        def small(bench, workload):
            r = resolve(bench, workload)
            r["traffic"]["scale"] = scale
            r["traffic"]["spec"].update(spec or {})
            if hidden:
                r["config"]["hidden_channels"] = hidden
            if expect_engine:
                r["expect"]["binding"] = json.loads(json.dumps(
                    r["expect"]["binding"]).replace("pallas", expect_engine))
            return r

        compile_ = GNNProgram.compile

        def compile_on(self, **kw):
            return compile_(self, **{"engine": engine,
                                     "interpret": interpret, **kw})

        monkeypatch.setattr(harness, "resolve", small)
        monkeypatch.setattr(GNNProgram, "compile", compile_on)

    return apply
