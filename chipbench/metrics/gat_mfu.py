"""Model FLOP/s utilisation of the whole GAT step: the epoch's useful
FLOPs (``work/<arch>.py``, counted from the graph, the widths and the
heads) over the traced run's host-clock epoch time, the chips and the
chip's bf16 peak, in percent. The whole-step share of the attention cells
(``mfu`` reads the GCN cell). Moves ``epoch_s``."""


def read(ctx):
    flops = ctx["work"]["flops"]
    return 100.0 * flops / (ctx["epoch_s"] * ctx["chips"] * ctx["peak"].flops)
