"""Mean time a finished tick waited for the gateway's event loop: the
end of each ``gateway.device_wait`` span less the end of the
``engine.fetch`` under it (the fetch runs on an executor thread; the
loop resumes the tick only once the clients holding it yield)."""

import program_spans as ps


def read(run):
    spans = ps.in_window(run)
    fetched = {s.parent: s.end for s in ps.named(spans, "engine.fetch")}
    return ps.mean_ms(s.end - fetched[s.id]
                      for s in ps.named(spans, "gateway.device_wait")
                      if s.id in fetched)
