"""Morphling's primary contribution, as composable JAX modules.

- sparsity.py    — Alg 1 sparsity-aware execution engine (Eq. 1-5)
- aggregate.py   — fused neighbour aggregation (no O(|E|·F) edge tensors),
                   with custom VJP using the pre-transposed graph (CSC analog)
- layout.py      — layout stage: node-order selection, the BSR tile and
                   the operand format (LayoutPlan, threaded by lowering.py)
- partitioner.py — Alg 4 hierarchical constraint-relaxation partitioner
- halo.py        — distributed halo exchange (MPI backend analog, shard_map)
- pipeline.py    — pipelined backward: overlap dW psum with dX compute
- dsl.py         — Listing-1-style spec -> compiled training program
"""
from repro.core.sparsity import (
    SparsityDecision,
    feature_sparsity,
    efficiency_ratio_threshold,
    decide_execution_path,
    calibrate_gamma,
)
from repro.core.partitioner import hierarchical_partition, PartitionResult
from repro.core.layout import LayoutPlan
