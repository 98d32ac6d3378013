"""Lowering of the traced epoch to StableHLO, the Mosaic lowering of every
window's ``pallas_call`` included: seconds in the program's
``compile_step/lower`` span (``common/jit.py:jit_hoisted``). Moves
``setup_s``."""
from chipbench.program_spans import span_total


def read(ctx):
    return span_total("compile_step/lower")
