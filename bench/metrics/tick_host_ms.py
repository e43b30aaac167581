"""Host time of the engine per tick in the window: its dispatch
(``step_begin``) plus its replay (``step_replay``). The wait for the
device between them, in which the gateway serves its clients, is not
counted."""


def read(run):
    replays = run.spans_in("replay")
    if not replays:
        return None
    host = sum(b - a for a, b in run.spans_in("dispatch") + replays)
    return 1e3 * host / len(replays)
