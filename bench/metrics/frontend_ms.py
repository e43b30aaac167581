"""Mean time of one frontend call (``audio_frames`` ended by
``block_until_ready``), harness span, in the window."""


def read(run):
    sp = run.spans_in("frontend")
    if not sp:
        return None
    return 1e3 * sum(b - a for a, b in sp) / len(sp)
