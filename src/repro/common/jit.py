"""Compilation helpers: ``jit_hoisted`` and the persistent compile cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax

from repro.common.spans import count, span

# the checkout root (src/repro/common/jit.py -> three levels up)
_CHECKOUT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself, so nothing is set here). Otherwise the cache goes to one fixed
    path inside the checkout, ``<checkout>/.jax_cache`` (gitignored): the
    path is part of the cache key, so it never derives from a temporary
    name, a pid or the time.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class jit_hoisted:
    """``jax.jit(fn)``, except that the arrays ``fn`` closes over become
    arguments of the compiled program instead of constants inside it.

    ``jax.jit`` embeds closed-over arrays in the program as literals. A
    plan's sparse operands are closed over by its aggregation closures, and
    at ogbn-arxiv's published size they are gigabytes: embedded, they are
    copied into the program text, which then cannot be serialised or takes
    minutes to compile. Here ``fn`` is traced once per argument signature
    and compiled ahead of time, with the jaxpr's constants passed as
    ordinary device-resident arguments.

    Spans: ``compile_step`` around ``compile``, ``dispatch`` around a call;
    building an entry counts ``compiles`` and nests ``trace`` (the jaxpr),
    ``consts`` (their upload), ``lower`` (StableHLO, Mosaic included) and
    ``compile`` (XLA, or a load from the persistent cache).
    """

    def __init__(self, fn):
        self._fn = fn
        self._cache: dict = {}

    def _entry(self, args):
        leaves, tree = jax.tree_util.tree_flatten(args)
        key = (tree, tuple(jax.typeof(leaf) for leaf in leaves),
               jax.config.jax_default_matmul_precision)
        if key not in self._cache:
            count("compiles")
            with span("trace"):
                closed, out_shape = jax.make_jaxpr(
                    self._fn, return_shape=True)(*args)
            jaxpr = closed.jaxpr
            with span("consts"):
                consts = jax.device_put(closed.consts)
            run = jax.jit(lambda c, flat: jax.core.eval_jaxpr(jaxpr, c, *flat))
            with span("lower"):
                lowered = run.lower(consts, leaves)
            with span("compile"):
                compiled = lowered.compile()
            self._cache[key] = (compiled, consts,
                                jax.tree_util.tree_structure(out_shape))
        return self._cache[key], leaves

    @span("compile_step")
    def compile(self, *args) -> None:
        """Trace and compile for these arguments without running."""
        self._entry(args)

    @span("dispatch")
    def __call__(self, *args):
        (run, consts, out_tree), leaves = self._entry(args)
        return jax.tree_util.tree_unflatten(out_tree, run(consts, leaves))
