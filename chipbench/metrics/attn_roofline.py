"""Share of the roofline reached by the attention kernels: the least time
the step's attention could take on this chip (the larger of its useful
FLOPs over the peak and its least bytes over the HBM bandwidth,
``work/<arch>.py``'s ``attention``) over the device time the trace shows
in the attention kernels, in percent. ``bound`` says which of the two
limits it; ``fwd``, ``bwd_row`` and ``bwd_col`` give each kernel's own
share. Nothing when the trace or the work count has no attention. Moves
``epoch_s``."""
from chipbench.metrics.attn_kernel_ms import attention_ns


def _share(flops, nbytes, ns, steps, peak):
    t_flops = flops / peak.flops
    t_bytes = nbytes / peak.hbm_bytes_per_s
    return 100.0 * max(t_flops, t_bytes) / (ns / steps / 1e9), t_bytes >= t_flops


def read(ctx):
    work = ctx["work"].get("attention")
    times = attention_ns(ctx["trace"])
    if not work or not times:
        return None
    steps, peak = ctx["steps"], ctx["peak"]
    flops = sum(f for f, _ in work.values())
    nbytes = sum(b for _, b in work.values())
    value, by_bytes = _share(flops, nbytes, sum(times.values()), steps, peak)
    out = {"value": value, "bound": "bytes" if by_bytes else "flops"}
    for kind, ns in times.items():
        if kind in work and ns > 0:
            out[kind] = _share(*work[kind], ns, steps, peak)[0]
    return out
