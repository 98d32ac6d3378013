"""Compilation: host seconds of ``CompiledProgram.compile_step()`` (a
compile, or a load from the persistent compile cache), as the benchmark's
clock read them around the call. Moves ``setup_s``."""


def read(ctx):
    return ctx["spans"].get("compile_s")
