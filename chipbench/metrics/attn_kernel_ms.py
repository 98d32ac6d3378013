"""Device milliseconds per step in the attention kernels: every Pallas op
whose name starts with ``csr_gather_attention`` (the row-gather kernels)
or ``bsr_attention`` (the BSR ones), so that either operand format is read
against the same metric. Nothing when the trace holds none. Moves
``epoch_s``."""

PREFIXES = ("csr_gather_attention", "bsr_attention")


def attention_ns(red) -> dict:
    """``{"fwd" | "bwd_row" | "bwd_col": ns}`` of the attention kernels in a
    reduced trace, summed over both formats."""
    out: dict = {}
    for name, ns in red.pallas_by_op:
        if name.startswith(PREFIXES):
            kind = name.rsplit("attention_", 1)[1]
            out[kind] = out.get(kind, 0.0) + ns
    return out


def read(ctx):
    ns = sum(attention_ns(ctx["trace"]).values())
    return ns / ctx["steps"] / 1e6 if ns > 0 else None
