"""Mean host time of one tick's replay: the program's ``engine.replay``
span (``ServeEngine.step_replay``, the bookkeeping of the fetched
tokens)."""

import program_spans as ps


def read(run):
    return ps.mean_ms(s.dur for s in
                      ps.named(ps.in_window(run), "engine.replay"))
