"""Lowering's host build of the sparse operands: seconds in every
``bsr_build`` span (``graph/csr.py:csr_to_bsr``) and every ``transpose``
span (the CSC view Aᵀ is built from) nested under the program's ``lower``
span. Moves ``setup_s``."""
from chipbench.program_spans import snapshot

BUILD = ("bsr_build", "transpose")


def read(ctx):
    snap = snapshot()
    if snap is None or "lower" not in snap["spans"]:
        return None
    return sum(s["total_s"] for path, s in snap["spans"].items()
               if path.startswith("lower/")
               and path.rsplit("/", 1)[1] in BUILD)
