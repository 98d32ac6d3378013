"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

- busy: the union of the intervals of the ops on each device's
  ``XLA Ops`` line (the TensorCore's op stream; async copies overlap it and
  are not counted), clipped to the measured window, averaged over devices;
- window: the benchmark's ``chipbench_window`` annotation on the host;
- Pallas time: every op whose HLO is a ``tpu_custom_call`` (a Mosaic
  kernel), also grouped by HLO instruction name;
- the top device ops by summed time, grouped by HLO instruction name;
- the longest idle gaps inside the window, each labelled with the innermost
  host (Python) event running at its midpoint.

Device and host events of one trace share one time base (ns from the start
of the profile), so they can be set side by side.
"""
from __future__ import annotations

import dataclasses
import glob
import re

WINDOW = "chipbench_window"
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    dur: float  # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Reduced:
    window_ns: float
    busy_ns: float  # averaged over devices
    pallas_ns: float  # summed over devices
    pallas_by_op: list  # [(name, ns)], largest first
    top_ops: list  # [(name, ns)], largest first, at most TOP
    idle_gaps: list  # [(host label, ns)], longest first, at most TOP


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    head = hlo.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"\.\d+$", "", head)[:120]


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _label(host: list[Event], t: float) -> str:
    inside = [e for e in host if e.start <= t <= e.end]
    return min(inside, key=lambda e: e.dur).name[:120] if inside else "none"


def reduce(devices: list[list[Event]], host: list[Event],
           window: tuple[float, float]) -> Reduced:
    """``devices``: each device's ops; ``host``: host events for labels."""
    lo, hi = window
    busy, gaps = 0.0, []
    pallas: dict[str, float] = {}
    ops: dict[str, float] = {}
    for dev in devices:
        merged = _clip(union((e.start, e.end) for e in dev), lo, hi)
        busy += sum(e - s for s, e in merged)
        edges = [lo, *[t for iv in merged for t in iv], hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_label(host, (s + e) / 2), e - s))
        for ev in dev:
            name = op_name(ev.name)
            ops[name] = ops.get(name, 0.0) + ev.dur
            if PALLAS_MARK in ev.name:
                pallas[name] = pallas.get(name, 0.0) + ev.dur
    by = lambda kv: -kv[1]
    return Reduced(
        window_ns=hi - lo, busy_ns=busy / max(len(devices), 1),
        pallas_ns=sum(pallas.values()),
        pallas_by_op=sorted(pallas.items(), key=by),
        top_ops=sorted(ops.items(), key=by)[:TOP],
        idle_gaps=sorted(gaps, key=by)[:TOP])


def load(trace_dir: str, n_devices: int) -> Reduced:
    """Read the one ``.xplane.pb`` under ``trace_dir`` and reduce it."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}: {paths}")
    profile = ProfileData.from_file(paths[0])
    devices, host, windows = {}, [], []
    for plane in profile.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                devices[int(m.group(1))] = [
                    Event(e.name, e.start_ns, e.duration_ns)
                    for e in line.events]
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    ev = Event(e.name, e.start_ns, e.duration_ns)
                    (windows if e.name == WINDOW else host).append(ev)
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} span, found "
                           f"{len(windows)}")
    used = [devices.get(i, []) for i in range(n_devices)]
    return reduce(used, host, (windows[0].start, windows[0].end))
