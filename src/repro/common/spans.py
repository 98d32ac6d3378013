"""Program spans and counters: the program's one tracing facility.

``span(name)`` times a block of host code. It opens a
``jax.profiler.TraceAnnotation("morphling.<name>")``, so that in a profiler
trace the span sits on the host plane, on the same clock as the device ops;
and it adds its duration to an in-memory aggregate kept per *path*: the
names of the open spans it nests in, joined by ``/`` (``lower/bsr_build``,
``epoch/dispatch``). Each path holds ``count``, ``total_s``, ``self_s``
(total minus the time its child spans cover) and ``max_s``.

``count(name, n)`` adds to a counter under the innermost open span's path.
A ``jax.monitoring`` listener, registered when this module is imported,
turns JAX's compile events into such counters (``cache_hits``,
``cache_misses``, ``backend_compiles``); events raised while no program span
is open on the raising thread are dropped.

Aggregation is always on; a profiler trace is what "tracing on" means.
Memory is bounded by the number of distinct paths: no list of events is
kept. Open spans only in host code, never inside a function that jit
traces (there a span would time the tracing); traced code takes
``jax.named_scope``.
"""
from __future__ import annotations

import functools
import threading
import time

import jax

PREFIX = "morphling."

_lock = threading.Lock()
_local = threading.local()
_spans: dict[str, list] = {}  # path -> [count, total_s, self_s, max_s]
_counters: dict[str, float] = {}


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """Context manager and decorator timing one named block of host code.

    ``step`` makes the profiler event a ``StepTraceAnnotation`` with that
    ``step_num``. After the block, ``seconds`` holds its duration.
    """

    def __init__(self, name: str, step: int | None = None):
        self.name = name
        self.step = step
        self.seconds: float | None = None

    def __enter__(self) -> "span":
        stack = _stack()
        self._path = f"{stack[-1]._path}/{self.name}" if stack else self.name
        self._children = 0.0
        label = PREFIX + self.name
        self._annotation = (
            jax.profiler.TraceAnnotation(label) if self.step is None
            else jax.profiler.StepTraceAnnotation(label, step_num=self.step))
        self._annotation.__enter__()
        stack.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        stack = _stack()
        stack.pop()
        self._annotation.__exit__(*exc)
        if stack:
            stack[-1]._children += dt
        self.seconds = dt
        with _lock:
            agg = _spans.get(self._path)
            if agg is None:
                agg = _spans[self._path] = [0, 0.0, 0.0, 0.0]
            agg[0] += 1
            agg[1] += dt
            agg[2] += dt - self._children
            agg[3] = max(agg[3], dt)

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with span(self.name, self.step):
                return fn(*args, **kwargs)

        return wrapped


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name`` under the innermost open span."""
    stack = _stack()
    path = f"{stack[-1]._path}/{name}" if stack else name
    with _lock:
        _counters[path] = _counters.get(path, 0) + n


def snapshot() -> dict:
    """``{"spans": {path: {count, total_s, self_s, max_s}},
    "counters": {path: n}}`` of this process so far."""
    with _lock:
        spans = {p: dict(zip(("count", "total_s", "self_s", "max_s"), a))
                 for p, a in _spans.items()}
        return {"spans": spans, "counters": dict(_counters)}


def reset() -> None:
    """Forget every aggregate and counter (spans still open are kept)."""
    with _lock:
        _spans.clear()
        _counters.clear()


_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def _on_event(event: str, **_) -> None:
    name = _EVENTS.get(event)
    if name is not None and _stack():
        count(name)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _BACKEND_COMPILE and _stack():
        count("backend_compiles")


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
