"""Mean wait of one admission for its first token: the
``engine.first_token`` span under each ``engine.admit`` (the host
blocks on the prefill program's argmax)."""

import program_spans as ps


def read(run):
    spans = ps.in_window(run)
    out = []
    for a in ps.named(spans, "engine.admit"):
        ft = ps.child(spans, a, "engine.first_token")
        if ft is not None:
            out.append(ft.dur)
    return ps.mean_ms(out)
