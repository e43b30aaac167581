"""95th percentile, over the requests due in the window, of the wait
from the due time to admission (``RequestRecord.admit_t``); a request
never admitted is a miss."""

import stats


def read(run):
    waits = []
    for d in run.due_in_window():
        rec = d.record
        at = None if rec is None else rec.admit_t
        waits.append(stats.latency(d.due, at, at is not None))
    return stats.reading(stats.percentile(waits, 95))
