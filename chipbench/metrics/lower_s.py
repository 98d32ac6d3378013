"""Lowering: host seconds of ``GNNProgram.compile(...)`` (Alg-1 decisions,
BSR operand builds, plan verification), as the benchmark's clock read them
around the call. Moves ``setup_s``."""


def read(ctx):
    return ctx["spans"].get("lower_s")
