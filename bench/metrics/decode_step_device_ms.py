"""Device time of the fused decode programs per decode step (ticks
replayed in the window times ``decode_block``), from the trace."""

import trace_reduce


def read(run):
    n = len(run.spans_in("replay")) * run.decode_block
    if run.trace is None or not n:
        return None
    t = trace_reduce.module_time(run.trace, ("decode_block",))
    return 1e3 * t / n if t > 0 else None
