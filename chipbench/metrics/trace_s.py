"""Python tracing of the epoch step to a jaxpr: seconds in the program's
``compile_step/trace`` span (``common/jit.py:jit_hoisted``). Moves
``setup_s``."""
from chipbench.program_spans import span_total


def read(ctx):
    return span_total("compile_step/trace")
