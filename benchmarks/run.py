"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus a header per section).

  bench_throughput  — Fig 2/3: fused vs gather-scatter per-epoch time
  bench_fusion      — §8: (br, bc, bf) tile sweep × fused-vs-unfused
                      epilogue; emits BENCH_fusion.json
  bench_attention   — §10: fused BSR flash-attention vs the gather
                      edge-softmax (GAT epochs + op-level, 1/4 heads);
                      emits BENCH_attention.json
  bench_memory      — Table III / Fig 8: peak memory, Eq. 12 vs 13
  bench_sampling    — mini-batch vs full-batch step time + peak memory
  bench_serving     — §12: online serving p50/p99 latency + throughput
                      under Poisson arrivals (wave window x buckets x
                      cache on/off); emits BENCH_serving.json
  bench_partitioner — Table I / Alg 4: strategies + load balance
  bench_sparsity    — §IV-B Eq. 1-5: dense/sparse crossover vs 1-γ
  bench_distributed — Fig 6/7: rank scaling (8 host devices, subprocess)
  bench_moe_dispatch— beyond paper: fused MoE combine vs dense
  bench_resilience  — §13: guarded-step overhead (<2% target),
                      rank-death recovery time, degraded-mode serving
                      p50/p99 under overload; emits BENCH_resilience.json
  bench_verify      — §14: contract-verifier overhead per plan family
                      (off/fast/full lowering wall-time, fast <5%
                      target); emits BENCH_verify.json
  chaos_soak        — §14: seeded randomized fault schedules across all
                      trainers + serving, end-state property assertions
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (
        bench_attention,
        bench_distributed,
        bench_fusion,
        bench_memory,
        bench_moe_dispatch,
        bench_partitioner,
        bench_resilience,
        bench_sampling,
        bench_serving,
        bench_sparsity,
        bench_throughput,
        bench_verify,
        chaos_soak,
    )

    print("name,us_per_call,derived")
    failed = []
    for mod in (bench_throughput, bench_fusion,
                bench_attention, bench_memory, bench_sampling,
                bench_serving, bench_partitioner, bench_sparsity,
                bench_distributed, bench_moe_dispatch, bench_resilience,
                bench_verify, chaos_soak):
        try:
            for row in mod.run():
                print(row)
        except Exception as e:  # keep the harness running
            traceback.print_exc()
            failed.append(mod.__name__)
            print(f"{mod.__name__},0.0,ERROR:{type(e).__name__}")
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
