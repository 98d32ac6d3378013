"""Share of the roofline reached by the Pallas kernels: the least time the
step's sparse-operand products could take on this chip (the larger of
their useful FLOPs over the peak and their least bytes over the HBM
bandwidth, ``work/<arch>.py``) over the device time the trace shows in
Pallas kernels, in percent. ``bound`` says which of the two limits it.
Nothing when the trace holds no Pallas op. Moves ``epoch_s``."""


def read(ctx):
    ns = ctx["trace"].pallas_ns
    if ns <= 0:
        return None
    work, peak = ctx["work"], ctx["peak"]
    t_flops = work["sparse_flops"] / peak.flops
    t_bytes = work["sparse_bytes"] / peak.hbm_bytes_per_s
    kernel_s = ns / ctx["steps"] / 1e9
    return {"value": 100.0 * max(t_flops, t_bytes) / kernel_s,
            "bound": "bytes" if t_bytes >= t_flops else "flops"}
