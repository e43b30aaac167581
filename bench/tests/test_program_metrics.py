"""The per-layer metrics that read the program's own spans
(``repro.tracing``): each reader on a hand-made run and recorder
snapshot, on an empty one, on a program without the recorder, and in a
traced run of the harness on the CPU."""

import sys
import types

import pytest

import spec
from repro import tracing

T0 = 100.0
NAMES = ["engine.dispatch_ms", "engine.replay_ms", "gateway.resume_lag_ms",
         "engine.occupancy", "engine.admit_host_ms",
         "engine.first_token_ms", "frontend.host_ms", "host.gc_ms_per_s"]


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
    """Two ticks, two admissions (one refused before its prefill), two
    frontend calls and two collector passes in the window, and spans
    of every name before it."""
    out = []
    for t, lanes, disp in ((0.0, 32, 2.0), (40.0, 64, 4.0)):
        tick = _sp("gateway.tick", t, 39.0)
        wait = _sp("gateway.device_wait", t + 10, 10.0, tick)
        out += [tick, _sp("engine.dispatch", t + 1, disp, tick,
                          lanes=lanes, k=1),
                wait, _sp("engine.fetch", t + 11, 5.0 + t / 40.0, wait),
                _sp("engine.replay", t + 21, 0.5, tick)]
    admit = _sp("engine.admit", 100.0, 10.0, uid=7)
    out += [admit, _sp("engine.admit.inputs", 100.0, 3.0, admit),
            _sp("engine.first_token", 105.0, 4.0, admit),
            _sp("engine.admit", 200.0, 1.0, uid=8),
            _sp("frontend.frames", 300.0, 8.0),
            _sp("frontend.frames", 400.0, 12.0),
            _sp("host.gc", 500.0, 3.0, gen=0),
            _sp("host.gc", 600.0, 2.0, gen=2)]
    # before the window: read by none
    early = _sp("gateway.device_wait", -50.0, 10.0)
    out += [_sp(n, -50.0, 100.0, early, lanes=1) for n in (
        "engine.dispatch", "engine.replay", "engine.fetch",
        "frontend.frames", "host.gc")]
    return out + [early]


@pytest.fixture
def recorded(monkeypatch):
    def use(spans):
        monkeypatch.setattr(tracing, "spans", lambda: list(spans))
    return use


def _read(name, run):
    return spec.metric_reader(name).read(run)


@pytest.mark.parametrize("name,want", [
    ("engine.dispatch_ms", 3.0),            # (2 + 4) / 2 ticks
    ("engine.replay_ms", 0.5),
    ("gateway.resume_lag_ms", 3.5),         # waits end 20, 60; fetches 16, 57
    ("engine.occupancy", 75.0),             # (32 + 64) / 2 of 64 lanes
    ("engine.admit_host_ms", 6.0),          # 10 less its 4 ms first token
    ("engine.first_token_ms", 4.0),
    ("frontend.host_ms", 10.0),
    ("host.gc_ms_per_s", 1.0),              # 5 ms in 5 s
])
def test_reader_values(recorded, name, want):
    recorded(_snapshot())
    assert _read(name, _run()) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing(recorded, name):
    recorded([])
    assert _read(name, _run()) is None


def test_no_collector_pass_reads_zero(recorded):
    recorded([s for s in _snapshot() if s.name != "host.gc"])
    assert _read("host.gc_ms_per_s", _run()) == 0.0


@pytest.mark.parametrize("name", NAMES)
def test_reader_without_the_recorder(monkeypatch, name):
    """A program that predates ``repro.tracing`` gives None, no error."""
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert _read(name, _run()) is None


def test_traced_harness_run_reads_every_metric():
    """``run.run_cell`` with ``--trace 1`` on the CPU (a tiny backlog
    and a tiny poisson run): each new metric the cell lists comes out a
    number."""
    import run
    import tiny
    bench = spec.load_benchmark()
    for mixname, cell in (("backlog", "tiny_en.backlog"),
                          ("poisson", "tiny_en.poisson")):
        c = {"name": cell, "config": "whisper-test", "traffic": mixname,
             "chips": 1}
        out = run.run_cell(bench, c, tiny.config(), tiny.mix(mixname),
                           spec.load_limits(cell), seed=2**32 + 5,
                           seconds=2.0, trace=True,
                           peaks=spec.load_peaks("TPU v5 lite"),
                           log=lambda m: None)
        want = [m["name"] for m in spec.cell_metrics(bench, cell,
                                                     "per_layer")
                if m["name"] in NAMES]
        assert want
        for name in want:
            assert isinstance(out["metrics"].get(name, {}).get("value"),
                              float), (cell, name, out["metrics"])
        if "engine.occupancy" in want:
            assert 0 < out["metrics"]["engine.occupancy"]["value"] <= 100
