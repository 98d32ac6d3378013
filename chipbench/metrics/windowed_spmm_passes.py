"""SpMM passes of the traced epoch step that read their source rows from a
VMEM-resident window of the source (the ``csr_window_spmm*`` kernels): the
program's ``spmm_window`` counter under ``compile_step/trace``, which its
SpMM dispatch (``kernels/ops.py`` ``gather_pass``) adds 1 to per pass
while tracing. A three-layer GCN on the gather side of the fill rule
counts 6 (three forward passes, three backward); a change that loses the
path reads 0. Nothing where the program has no such counter. Moves
``epoch_s``."""
from chipbench.program_spans import snapshot


def read(ctx):
    snap = snapshot()
    if snap is None:
        return None
    hits = [n for path, n in snap["counters"].items()
            if path.endswith("compile_step/trace/spmm_window")]
    return sum(hits) if hits else None
