"""Mean time of one ``ServeEngine.stream_feed`` call (chunk encode,
cross-K/V extension), harness span, in the window."""


def read(run):
    sp = run.spans_in("stream_feed")
    if not sp:
        return None
    return 1e3 * sum(b - a for a, b in sp) / len(sp)
