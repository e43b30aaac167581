"""Mean host time of one admission less its wait for the first token:
each ``engine.admit`` span (``ServeEngine.admit``: inputs to the device,
the prefill's dispatch, the lane's set-up) less its
``engine.first_token`` child."""

import program_spans as ps


def read(run):
    spans = ps.in_window(run)
    out = []
    for a in ps.named(spans, "engine.admit"):
        ft = ps.child(spans, a, "engine.first_token")
        if ft is not None:
            out.append(a.dur - ft.dur)
    return ps.mean_ms(out)
