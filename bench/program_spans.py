"""The program's own spans (``repro.tracing``) that began in a run's
traced window, for the per-layer metrics that read them.

The program records them only while a profile is taken, which a traced
run does for the first part of its window (``RunData.t0`` to ``t1``). A
program that has no ``repro.tracing`` has none to give: every reader
then returns None.
"""

from __future__ import annotations


def in_window(run) -> list:
    """The finished spans whose start lies in ``[run.t0, run.t1)``."""
    try:
        from repro import tracing
    except ImportError:
        return []
    return [s for s in tracing.spans()
            if s.end is not None and run.t0 <= s.start < run.t1]


def named(spans, name: str) -> list:
    return [s for s in spans if s.name == name]


def child(spans, parent, name: str):
    """The first span called ``name`` under ``parent``, or None."""
    for s in spans:
        if s.parent == parent.id and s.name == name:
            return s
    return None


def mean_ms(seconds) -> float | None:
    """The mean of ``seconds`` in milliseconds; None when it is empty."""
    xs = list(seconds)
    return 1e3 * sum(xs) / len(xs) if xs else None
