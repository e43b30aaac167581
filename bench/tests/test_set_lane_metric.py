"""``engine.set_lane_ms``: the mean host time of one ``engine.set_lane``
span (``ServeEngine._set_lane``), on a hand-made recorder snapshot, on
an empty one, on a program without the recorder, and in a traced run of
the harness on the CPU."""

import sys
import types

import pytest

import spec
from repro import tracing

T0 = 100.0
NAME = "engine.set_lane_ms"


def _run():
    return types.SimpleNamespace(t0=T0, t1=T0 + 5.0, window_s=5.0,
                                 cfg={"deployment": {"n_slots": 64}})


def _sp(name, start_ms, dur_ms, parent=None, **attrs):
    sp = tracing.Span(name, None if parent is None else parent.id,
                      T0 + start_ms * 1e-3)
    sp.end = sp.start + dur_ms * 1e-3
    sp.attrs.update(attrs)
    return sp


def _snapshot():
    """One lane write in a tick's replay and one in an admission inside
    the window, and one before it."""
    tick = _sp("gateway.tick", 0.0, 39.0)
    replay = _sp("engine.replay", 21.0, 0.5, tick)
    admit = _sp("engine.admit", 100.0, 10.0, uid=7)
    early = _sp("engine.replay", -50.0, 100.0)
    return [tick, replay, _sp("engine.set_lane", 21.1, 0.3, replay, slot=5),
            admit, _sp("engine.set_lane", 109.0, 0.5, admit, slot=2),
            early, _sp("engine.set_lane", -49.0, 90.0, early, slot=1)]


@pytest.fixture
def recorded(monkeypatch):
    def use(spans):
        monkeypatch.setattr(tracing, "spans", lambda: list(spans))
    return use


def _read(run):
    return spec.metric_reader(NAME).read(run)


def test_reads_mean_of_writes_in_window(recorded):
    recorded(_snapshot())
    assert _read(_run()) == pytest.approx(0.4)      # (0.3 + 0.5) / 2


def test_finds_nothing(recorded):
    recorded([s for s in _snapshot() if s.name != "engine.set_lane"])
    assert _read(_run()) is None


def test_without_the_recorder(monkeypatch):
    """A program that predates ``repro.tracing`` gives None, no error."""
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _read(_run()) is None


def test_traced_backlog_run_reads_it():
    """``run.run_cell`` with ``--trace 1`` on the CPU, a tiny backlog:
    the metric is listed for the cell and comes out a number."""
    import run
    import tiny
    bench = spec.load_benchmark()
    cell = "tiny_en.backlog"
    assert NAME in [m["name"] for m in
                    spec.cell_metrics(bench, cell, "per_layer")]
    c = {"name": cell, "config": "whisper-test", "traffic": "backlog",
         "chips": 1}
    out = run.run_cell(bench, c, tiny.config(), tiny.mix("backlog"),
                       spec.load_limits(cell), seed=2**32 + 7,
                       seconds=2.0, trace=True,
                       peaks=spec.load_peaks("TPU v5 lite"),
                       log=lambda m: None)
    value = out["metrics"].get(NAME, {}).get("value")
    assert isinstance(value, float) and value > 0, out["metrics"]
