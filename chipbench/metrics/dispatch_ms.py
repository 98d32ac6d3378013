"""Host cost of launching one epoch step: mean milliseconds of the
program's ``epoch/dispatch`` span (``common/jit.py:jit_hoisted.__call__``:
flatten the arguments, look up the compiled program, enqueue it), over
every step of the run. Moves ``epoch_s``."""
from chipbench.program_spans import snapshot


def read(ctx):
    snap = snapshot()
    s = None if snap is None else snap["spans"].get("epoch/dispatch")
    return None if s is None else 1e3 * s["total_s"] / s["count"]
