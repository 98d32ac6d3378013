"""Attention layers lowered onto the CSR row-gather attention kernels: the
program's ``lower/decide/attention_gather`` counter (``core/lowering.py``),
over the run. Every attention layer of a cell on the gather side of the
fill rule counts once; a change that loses that path reads 0. Nothing
where the program has no such counter. Moves ``epoch_s``."""
from chipbench.program_spans import snapshot


def read(ctx):
    snap = snapshot()
    if snap is None:
        return None
    return snap["counters"].get("lower/decide/attention_gather")
