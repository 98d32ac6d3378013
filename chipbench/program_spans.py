"""The program's own spans and counters (``repro.common.spans``), as the
per-layer metrics read them. A benchmark run is one process and one
program, so the process totals are the run's totals. A program without
that module has no spans: every reader then returns nothing."""
from __future__ import annotations


def snapshot() -> dict | None:
    try:
        from repro.common import spans
    except ImportError:
        return None
    return spans.snapshot()


def span_total(path: str) -> float | None:
    """Summed seconds of the spans at ``path``; nothing where none ran."""
    snap = snapshot()
    if snap is None or path not in snap["spans"]:
        return None
    return snap["spans"][path]["total_s"]
