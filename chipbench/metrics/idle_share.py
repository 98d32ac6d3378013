"""Share of the traced window in which no op ran on the device (one minus
the union of the ops' intervals over the window, averaged over chips), in
percent. Moves ``epoch_s``."""


def read(ctx):
    red = ctx["trace"]
    return 100.0 * (1.0 - red.busy_ns / red.window_ns)
