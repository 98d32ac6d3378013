"""The benchmark is driven by data: every name in ``BENCHMARK.json``
resolves to its file, a new config, mix, cell and metric are found as new
files with no edit to an existing one, and ``run.py`` refuses to run where
it finds no TPU or an unknown device kind."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from chipbench import harness, peaks

ROOT = harness.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_name_resolves_to_its_file():
    archs = set()
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        archs.add(cfg["arch"].lower())
    for w in BENCH["workloads"]:
        r = harness.resolve(BENCH, w["name"])
        assert r["traffic"]["spec"] and r["expect"]["limits"]
        harness.load_module("loops", r["traffic"]["loop"]).Session
    for arch in archs:
        harness.load_module("work", arch).epoch_work
        harness.load_module("reference", arch).logits
    for m in BENCH["per_layer"]:
        harness.load_module("metrics", m["name"]).read
    assert all(p == "chipbench" or (ROOT / p).is_dir()
               for p in BENCH["paths"])


def test_new_files_are_found_without_an_edit(tmp_path, monkeypatch):
    """A throwaway config, mix, cell and metric placed as new files in a
    copy of the benchmark are resolved and read; only BENCHMARK.json, the
    list of names, changes."""
    here = tmp_path / "chipbench"
    shutil.copytree(harness.HERE, here)
    cfg = json.loads((here / "configs" / "gcn-3x256.json").read_text())
    (here / "configs" / "tiny.json").write_text(
        json.dumps({**cfg, "name": "tiny", "hidden_channels": 8}))
    mix = json.loads((here / "traffic" / "arxiv-full.json").read_text())
    (here / "traffic" / "tiny-mix.json").write_text(
        json.dumps({**mix, "scale": 0.001}))
    (here / "cells" / "tiny.tiny-mix.json").write_text(json.dumps(
        {"binding": {}, "limits": {"loss_gap": 1.0}}))
    (here / "metrics" / "nodes.py").write_text(
        "def read(ctx):\n    return ctx['n']\n")
    bench = {**BENCH,
             "configs": [{"name": "tiny", "file": "chipbench/configs/tiny.json",
                          "source": "x", "reduced": [], "why": "x"}],
             "workloads": [{"name": "tiny.tiny-mix", "config": "tiny",
                            "traffic": "tiny-mix", "chips": 1, "why": "x"}]}
    monkeypatch.setattr(harness, "HERE", here)
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    r = harness.inputs(bench, "tiny.tiny-mix", seed=1)
    assert r["dims"][1:-1] == [8, 8] and r["data"].n_nodes == 169
    assert r["expect"]["limits"] == {"loss_gap": 1.0}
    assert harness.load_module("metrics", "nodes").read({"n": 3}) == 3


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="TPU v99"):
        peaks.peak_for("TPU v99")


def test_run_exits_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "gcn-3x256.arxiv-full", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and proc.stdout == ""


def test_run_exits_on_an_unknown_device_kind(monkeypatch):
    import jax

    run = harness.load_module(".", "run")

    fake = types.SimpleNamespace(device_kind="TPU v99", platform="tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    with pytest.raises(KeyError, match="TPU v99"):
        run.chips(1)
    monkeypatch.setattr(jax, "devices", lambda: [])
    with pytest.raises(SystemExit):
        run.chips(1)
