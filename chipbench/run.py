"""Run one benchmark cell on the accelerator this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell comes from ``BENCHMARK.json``;
the files it names are found by ``chipbench/harness.py``. Set-up (data from
the seed, lowering, compile or compile-cache load, three checked steps)
is timed from process start; then whole steps run until ``--seconds``
have passed, traced by the profiler with ``--trace 1``. The run exits
non-zero, printing no result, where JAX finds no TPU, fewer chips than the
cell asks for, or a device kind without published peaks. The last lines
of standard error give each compared number beside its limit; the last
line of standard output is the result as one JSON object.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips(n: int):
    """The first ``n`` TPU devices, or exit: a run never falls back to the
    CPU and never computes a share of an unknown peak."""
    import jax

    from chipbench.peaks import peak_for

    if jax.default_backend() != "tpu":
        sys.exit(f"no TPU: JAX backend is {jax.default_backend()!r}")
    devices = jax.devices()
    if len(devices) < n:
        sys.exit(f"the cell needs {n} chips, JAX finds {len(devices)}")
    peak_for(devices[0].device_kind)
    return devices[:n]


def main(argv=None) -> None:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
    if cell is None:
        sys.exit(f"no workload {args.workload!r} in BENCHMARK.json")
    devices = chips(cell["chips"])

    import jax

    from chipbench import harness
    from repro.common.jit import configure_compile_cache

    harness.log(f"compile cache {configure_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                              bool(args.trace), t0=T0, devices=devices)
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
