"""Device milliseconds per step spent in Pallas (Mosaic) kernels, summed
over every window's call, from the device trace. In the full-batch cells
every Pallas call is a sparse-operand product (aggregation, attention,
sparse feature product). Nothing when the trace holds no Pallas op.
Moves ``epoch_s``."""


def read(ctx):
    ns = ctx["trace"].pallas_ns
    return ns / ctx["steps"] / 1e6 if ns > 0 else None
