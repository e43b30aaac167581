"""Whole-step model FLOP utilisation: the model FLOPs of the requests
completed in the traced window (``flops.request``: the encoder over the
frames, the prompt, one decoder step per further token) over the window
and the chip's bf16 peak."""

import flops

FRAMES_PER_S = 50


def read(run):
    done = run.completed_in_window()
    if not done or run.trace is None:
        return None
    total = sum(flops.request(run.cfg, int(d.req.audio_s * FRAMES_PER_S),
                              len(d.req.prompt), len(d.result.tokens))
                for d in done)
    return 100.0 * total / run.window_s / run.peaks["bf16_flops"]
