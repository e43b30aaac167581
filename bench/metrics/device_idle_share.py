"""Share of the window in which no operation ran on the device: one
minus the union of the device's operation intervals over the window."""

import trace_reduce


def read(run):
    tr = run.trace
    if tr is None or not tr.ops or tr.hi <= tr.lo:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_ns(tr) / (tr.hi - tr.lo))
