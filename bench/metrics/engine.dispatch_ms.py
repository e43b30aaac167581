"""Mean host time of one tick's dispatch: the program's
``engine.dispatch`` span (``ServeEngine.step_begin``, which launches the
fused decode program and returns without waiting for it)."""

import program_spans as ps


def read(run):
    return ps.mean_ms(s.dur for s in
                      ps.named(ps.in_window(run), "engine.dispatch"))
