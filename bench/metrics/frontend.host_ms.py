"""Mean host time of one frontend call: the program's
``frontend.frames`` span (``audio_frames`` dispatching its operations,
without the wait for the device)."""

import program_spans as ps


def read(run):
    return ps.mean_ms(s.dur for s in
                      ps.named(ps.in_window(run), "frontend.frames"))
