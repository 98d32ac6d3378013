"""XLA compile of the epoch step, or its load from the persistent compile
cache (the span's ``cache_hits`` counter tells which): seconds in the
program's ``compile_step/compile`` span (``common/jit.py:jit_hoisted``).
Moves ``setup_s``."""
from chipbench.program_spans import span_total


def read(ctx):
    return span_total("compile_step/compile")
