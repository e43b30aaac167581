"""Garbage-collector time per second of the traced window: the sum of
the program's ``host.gc`` spans over the window (0 where the recorder
ran and no pass did; None where it recorded nothing)."""

import program_spans as ps


def read(run):
    spans = ps.in_window(run)
    if not spans:
        return None
    return 1e3 * sum(s.dur for s in ps.named(spans, "host.gc")) \
        / run.window_s
