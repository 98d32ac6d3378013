"""Compiles inside epoch steps over the whole run: the program's
``backend_compiles`` counters under its ``epoch`` span (an XLA compile or a
load from the persistent compile cache, each counted once; a
``jit_hoisted`` rebuild makes one). Expected 0: set-up compiles the step.
Moves ``epoch_s``."""
from chipbench.program_spans import snapshot


def read(ctx):
    snap = snapshot()
    if snap is None or "epoch" not in snap["spans"]:
        return None
    return sum(n for path, n in snap["counters"].items()
               if path.startswith("epoch/")
               and path.endswith("/backend_compiles"))
