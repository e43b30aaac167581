"""Mean share of the pool's lanes that a tick decodes: the ``lanes``
attribute of each ``engine.dispatch`` span over the deployment's
``n_slots``."""

import program_spans as ps


def read(run):
    lanes = [s.attrs["lanes"]
             for s in ps.named(ps.in_window(run), "engine.dispatch")]
    if not lanes:
        return None
    return 100.0 * sum(lanes) / len(lanes) / run.cfg["deployment"]["n_slots"]
