"""Readings that the limits in ``cells/<cell>.json`` are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,13

For each seed, in one process on the chip, the compared numbers of:

- ``control``: the reference put in the program's place and computed in
  bfloat16 (the nearest precision below the configuration's float32),
  against the float32 reference at the highest precision;
- ``half_batch``: the reference put in the program's place with half of
  the training nodes left out, the mean taken over the rest.

A step that leaves its state unchanged reads 1 on ``update_norm_gap`` and
``grad_norm_gap`` by their definition and needs no run. The program's own
readings come from ``run.py``'s runs, which print them. Benchmark runs
never run this. One JSON line per seed and reading goes to standard output.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import compare, harness
    from repro.common.jit import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.inputs(bench, args.workload, seed)
        host0 = compare.leaves(r["params0"])
        t = time.perf_counter()
        want = harness.reference(r)
        train = np.flatnonzero(r["data"].train_mask)
        half = np.zeros_like(r["data"].train_mask)
        half[train[::2]] = True
        runs = {"control": harness.reference(r, dtype=jnp.bfloat16),
                "half_batch": harness.reference(r, train_mask=half)}
        for name, got in runs.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": name,
                              **compare.readings(got, want, host0)}),
                  flush=True)
        harness.log(f"seed {seed}: {time.perf_counter() - t:.1f} s")


if __name__ == "__main__":
    main()
