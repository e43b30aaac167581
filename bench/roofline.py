"""A kernel's share of its roofline, from the device trace.

For every device event of the kernel in the window, the least time the
chip could take for that call is the larger of its operations over the
peak rate and its bytes over the HBM bandwidth (the kernel's own
``cost``, from the call's shapes). The share is the sum of those least
times over the sum of the events' measured times.
"""

from __future__ import annotations

import spec
import trace_reduce


def share(run, kernel: str):
    """Percent of the roofline, or None where the kernel did not run."""
    if run.trace is None:
        return None
    km = spec.kernel_model(kernel)
    tr = run.trace
    least = spent = 0.0
    for ev in trace_reduce.clip(tr.ops, tr.lo, tr.hi):
        if not trace_reduce.matches(ev, km.TRACE_NAMES):
            continue
        flops, nbytes = km.cost(ev)
        least += max(flops / run.peaks["bf16_flops"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
        spent += ev.dur * 1e-9
    if spent == 0.0:
        return None
    return 100.0 * least / spent
