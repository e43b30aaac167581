"""Device time of the prefill programs (encoder included) per admission
in the window, from the trace."""

import trace_reduce


def read(run):
    n = len(run.spans_in("admit"))
    if run.trace is None or not n:
        return None
    t = trace_reduce.module_time(run.trace, ("prefill",))
    return 1e3 * t / n if t > 0 else None
