"""Whole-step model FLOP utilisation: the model FLOPs of the requests
completed in the traced window (the family's ``request_flops``; for
Whisper ``flops.request``: the encoder over the frames, the prompt, one
decoder step per further token) over the window and the chip's bf16
peak."""


def read(run):
    done = run.completed_in_window()
    if not done or run.trace is None:
        return None
    total = sum(run.family.request_flops(run.cfg, d.req,
                                         len(d.result.tokens))
                for d in done)
    return 100.0 * total / run.window_s / run.peaks["bf16_flops"]
