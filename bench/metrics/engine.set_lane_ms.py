"""Mean host time of one write of a lane's decode state: the program's
``engine.set_lane`` span (``ServeEngine._set_lane``, run in each
admission and for each lane a replay frees)."""

import program_spans as ps


def read(run):
    return ps.mean_ms(s.dur for s in
                      ps.named(ps.in_window(run), "engine.set_lane"))
