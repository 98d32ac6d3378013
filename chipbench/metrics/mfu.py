"""Model FLOP/s utilisation of the whole step: the epoch's useful FLOPs
(``work/<arch>.py``, counted from the graph and the widths) over the traced
run's host-clock epoch time, the chips and the chip's bf16 peak, in
percent. Moves ``epoch_s``."""


def read(ctx):
    flops = ctx["work"]["flops"]
    return 100.0 * flops / (ctx["epoch_s"] * ctx["chips"] * ctx["peak"].flops)
