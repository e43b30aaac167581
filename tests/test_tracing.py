"""``repro.tracing``: spans of the served path, recorded while a JAX
profile is taken, and their mirror in the profile itself.

One module-scoped micro-whisper engine serves a few audio requests
through the gateway, once with no profile running and once under a CPU
``jax.profiler`` trace; the tests read what each run recorded.
"""

import asyncio
import collections
import dataclasses
import gc
import glob
import os

import jax
import numpy as np
import pytest

from repro import tracing
from repro.audio.features import audio_frames
from repro.configs import get_config, reduced
from repro.gateway import Gateway
from repro.models.model import build
from repro.serving.engine import ServeEngine

N_SLOTS = 4
DECODE_BLOCK = 2
N_REQUESTS = 3
PREFIXES = ("gateway.", "engine.", "frontend.", "host.")
TICK_CHILDREN = ["gateway.feed_streams", "gateway.admit", "engine.dispatch",
                 "gateway.select", "gateway.device_wait", "engine.replay",
                 "gateway.complete"]


@pytest.fixture(scope="module")
def engine():
    cfg = dataclasses.replace(
        reduced(get_config("whisper-tiny-en")),
        d_model=64, n_heads=2, n_kv_heads=2, d_ff=128, vocab=256,
        enc_layers=1, n_layers=1)
    model = build(cfg)
    params = model.init_values(jax.random.key(0))
    return ServeEngine(model, params, n_slots=N_SLOTS, max_len=64,
                       enc_len=64, decode_block=DECODE_BLOCK)


def _serve(engine):
    """Serve ``N_REQUESTS`` clips (0.5-0.7 s) through a gateway, each
    client making its frames with the frontend first."""
    wave = np.random.default_rng(0).standard_normal(16000) \
        .astype(np.float32) * 0.1

    async def main():
        async with Gateway(engine) as gw:
            async def one(i):
                fr = audio_frames(wave[:8000 + 1000 * i], 64)
                return await gw.submit_audio(fr, tokens=[1, 2], max_new=5)
            return await asyncio.gather(*[one(i)
                                          for i in range(N_REQUESTS)])

    return asyncio.run(main())


@pytest.fixture(scope="module")
def untraced(engine):
    _serve(engine)                     # compile outside any record
    tracing.clear()
    results = _serve(engine)
    return tracing.spans(), results


@pytest.fixture(scope="module")
def traced(engine, untraced, tmp_path_factory):
    """The same serve under a profile, then a forced collection and a
    fresh jit; automatic collection is off meanwhile, so that every
    ``host.gc`` span is the forced one."""
    tdir = str(tmp_path_factory.mktemp("profile"))
    tracing.clear()
    was = gc.isenabled()
    gc.disable()
    jax.profiler.start_trace(tdir)
    try:
        results = _serve(engine)
        gc.collect()
        jax.jit(lambda x: x * 3.0 + 1.0)(np.ones(3, np.float32))
    finally:
        jax.profiler.stop_trace()
        if was:
            gc.enable()
    spans = tracing.spans()
    tracing.clear()
    xplane = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                       recursive=True)
    return spans, results, xplane[0]


def _children(sp, spans):
    return sorted((c for c in spans if c.parent == sp.id),
                  key=lambda c: c.start)


# --------------------------------------------------------------- the gate
def test_nothing_recorded_without_a_profile(untraced):
    spans, results = untraced
    assert all(r.ok for r in results)
    assert spans == [] and tracing.dropped() == 0
    assert not tracing.on()
    # a span site costs the check alone: the same shared null context,
    # and the function itself for the executor
    assert tracing.begin("engine.dispatch") is None
    assert tracing.span("a") is tracing.span("b")
    with tracing.span("a") as sp:
        assert sp is None
    fn = len
    assert tracing.carry(fn) is fn


# ------------------------------------------------------------ the span tree
def test_each_tick_is_a_tree_of_its_layers(traced):
    spans, _, _ = traced
    ticks = [s for s in spans if s.name == "gateway.tick"]
    dispatched = [t for t in ticks if any(
        c.name == "engine.dispatch" for c in _children(t, spans))]
    assert len(dispatched) >= 3
    for t in dispatched:
        kids = _children(t, spans)
        names = [c.name for c in kids]
        assert names == TICK_CHILDREN, names
        for c in kids:
            assert t.start <= c.start <= c.end <= t.end
        dispatch = kids[2]
        assert 1 <= dispatch.attrs["lanes"] <= N_SLOTS
        assert dispatch.attrs["k"] == DECODE_BLOCK
    lanes = [c.attrs["lanes"] for t in dispatched
             for c in _children(t, spans) if c.name == "engine.dispatch"]
    assert max(lanes) == N_REQUESTS


def test_one_admission_span_per_request(traced):
    spans, results, _ = traced
    admits = {s.attrs["uid"]: s for s in spans if s.name == "engine.admit"}
    assert sorted(admits) == sorted(r.record.uid for r in results)
    by_id = {s.id: s for s in spans}
    for r in results:
        sp, rec = admits[r.record.uid], r.record
        assert rec.submit_t <= sp.start <= sp.end <= rec.first_token_t
        assert by_id[sp.parent].name == "gateway.admit"
        assert by_id[by_id[sp.parent].parent].name == "gateway.tick"
        assert [c.name for c in _children(sp, spans)] == [
            "engine.admit.inputs", "engine.prefill", "engine.first_token",
            "engine.set_lane"]
        assert sp.attrs["enc_s"] > 0 and sp.attrs["bucket"] >= 2
    names = collections.Counter(s.name for s in spans)
    assert names["frontend.frames"] == N_REQUESTS


def test_lane_writes_nest_under_admission_and_replay(traced):
    """``engine.set_lane`` runs once in each admission, after its first
    token, and once per lane freed in a replay, with the lane's slot;
    every lane an admission wrote is freed under a replay."""
    spans, results, _ = traced
    by_id = {s.id: s for s in spans}
    writes = [s for s in spans if s.name == "engine.set_lane"]
    under = collections.defaultdict(list)
    for w in writes:
        up = by_id[w.parent]
        assert up.start <= w.start <= w.end <= up.end
        assert 0 <= w.attrs["slot"] < N_SLOTS
        under[up.name].append(w.attrs["slot"])
    assert set(under) == {"engine.admit", "engine.replay"}
    assert len(under["engine.admit"]) == N_REQUESTS
    assert sorted(under["engine.replay"]) == sorted(under["engine.admit"])
    for w in writes:
        if by_id[w.parent].name == "engine.admit":
            ft = _children(by_id[w.parent], spans)[2]
            assert ft.name == "engine.first_token" and ft.end <= w.start


def test_fetch_runs_on_the_executor_under_its_tick(traced):
    spans, _, _ = traced
    by_id = {s.id: s for s in spans}
    fetches = [s for s in spans if s.name == "engine.fetch"]
    assert fetches
    for f in fetches:
        wait = by_id[f.parent]
        assert wait.name == "gateway.device_wait"
        assert by_id[wait.parent].name == "gateway.tick"
        assert f.thread != wait.thread
        assert wait.start <= f.start <= f.end <= wait.end


def test_self_time_is_the_span_less_its_children():
    def mk(name, start, end, parent=None):
        sp = tracing.Span(name, parent, start)
        sp.end = end
        return sp

    top = mk("top", 0.0, 10.0)
    kids = [mk("a", 1.0, 3.0, top.id), mk("b", 2.0, 4.0, top.id),
            mk("c", 9.0, 12.0, top.id), mk("d", 5.0, 6.0, 999)]
    grandchild = mk("e", 1.5, 2.5, kids[0].id)
    # a and b overlap (1-4 counted once); c is cut at the top's end;
    # d is another span's child and a grandchild is its parent's
    assert tracing.self_time(top, kids + [grandchild]) == \
        pytest.approx(10.0 - 3.0 - 1.0)
    assert tracing.self_time(kids[0], kids + [grandchild]) == \
        pytest.approx(1.0)


# ------------------------------------------------------------- host stalls
def test_gc_and_compile_are_recorded(traced):
    spans, _, _ = traced
    gcs = [s for s in spans if s.name == "host.gc"]
    assert len(gcs) == 1 and gcs[0].attrs["gen"] == 2 and gcs[0].dur > 0
    compiles = [s for s in spans if s.name == "host.compile"]
    assert any(s.attrs["event"] == "backend_compile" and s.dur > 0
               and "lambda" in s.attrs.get("fun", "") for s in compiles)


# ------------------------------------------------------ the profile's mirror
def test_spans_lie_in_the_profile(traced):
    """Every recorded span but ``host.compile`` (known only once it has
    ended) is a host event of the same name in the ``.xplane.pb``, in the
    same order on its thread. Each event encloses its span (the
    annotation is entered before the span's first clock read and left
    after its last); the durations agree within 50 us for 95 % of the
    spans and within 10 us in the median. A per-span bound would not
    hold: the thread can be descheduled between the two clock reads."""
    spans, _, path = traced
    ours = collections.defaultdict(list)
    for s in sorted(spans, key=lambda s: s.start):
        if s.name != "host.compile":
            ours[s.thread].append(s)
    pd = jax.profiler.ProfileData.from_file(path)
    lines = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted((ev for ev in line.events
                          if ev.name.startswith(PREFIXES)),
                         key=lambda ev: ev.start_ns)
            if evs:
                lines.append(evs)
    assert sorted(tuple(s.name for s in v) for v in ours.values()) == \
        sorted(tuple(ev.name for ev in evs) for evs in lines)
    excess = []
    for evs in lines:
        seq = tuple(ev.name for ev in evs)
        match = [v for v in ours.values()
                 if tuple(s.name for s in v) == seq][0]
        excess += [ev.duration_ns * 1e-9 - s.dur
                   for s, ev in zip(match, evs)]
    excess.sort()
    assert len(excess) >= 50
    assert excess[0] > -5e-6
    assert excess[len(excess) // 2] < 10e-6
    assert excess[int(0.95 * (len(excess) - 1))] < 50e-6


# ---------------------------------------------------- a profile from the chip
PROGRAM_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "data", "program_trace.xplane.pb")


@pytest.fixture(scope="module")
def chip():
    """``record_program_trace.py`` on one TPU v5e: one frontend call, one
    admission, three decode ticks. Returns the program's host spans and
    the device's program executions, ``name -> [(start, end)]`` in ns
    on the profile's clock, and for each execution how far its start
    lies before the host's enqueue of it (the profile links the two:
    ``_p`` of the host's ``DoEnqueueProgram`` is ``_c`` of the
    execution)."""
    pd = jax.profiler.ProfileData.from_file(PROGRAM_TRACE)
    host, device = collections.defaultdict(list), \
        collections.defaultdict(list)
    enqueued, launched = {}, []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if plane.name.startswith("/host:"):
                    if ev.name.startswith(PREFIXES):
                        host[ev.name].append(iv)
                    elif ev.name == "DoEnqueueProgram":
                        enqueued[dict(ev.stats).get("_p")] = ev.start_ns
                elif plane.name.startswith("/device:") and \
                        line.name == "XLA Modules":
                    device[ev.name.split("(", 1)[0]].append(iv)
                    launched.append((dict(ev.stats).get("_c"), iv[0]))
    for d in (host, device):
        for v in d.values():
            v.sort()
    leads = [enqueued[c] - t for c, t in launched if c in enqueued]
    assert len(leads) == len(launched)
    return host, device, leads


def test_chip_profile_holds_the_program_spans(chip):
    host, device, _ = chip
    assert {k: len(v) for k, v in host.items()} == {
        "frontend.frames": 1, "engine.admit": 1, "engine.admit.inputs": 1,
        "engine.prefill": 1, "engine.first_token": 1,
        "engine.dispatch": 3, "engine.fetch": 3, "engine.replay": 3}
    assert len(device["jit_prefill"]) == 1
    assert len(device["jit_decode_block"]) == 3


def test_chip_device_clock_leads_by_a_constant(chip):
    """The spans are on the host's clock; the profile's device line
    leads it by a constant: every program starts 1.1-1.3 ms before the
    host enqueued it, within 0.2 ms of each other."""
    _, _, leads = chip
    assert 0.5e6 < min(leads) and max(leads) < 2e6
    assert max(leads) - min(leads) < 0.2e6


def _on_host_clock(chip, name):
    """The device's executions of ``name``, moved onto the host's clock
    by the largest lead (no program then starts before its enqueue)."""
    _, device, leads = chip
    return [(s + max(leads), e + max(leads)) for s, e in device[name]]


def test_chip_first_token_ends_with_its_prefill(chip):
    host = chip[0]
    (_, ft_end), = host["engine.first_token"]
    (_, pf_end), = _on_host_clock(chip, "jit_prefill")
    assert pf_end <= ft_end <= pf_end + 1e6


def test_chip_tick_spans_bracket_their_programs(chip):
    """Each tick's dispatch starts before its decode program, and its
    fetch ends after it."""
    host = chip[0]
    for (d0, _), (_, f1), (p0, p1) in zip(
            host["engine.dispatch"], host["engine.fetch"],
            _on_host_clock(chip, "jit_decode_block")):
        assert d0 < p0
        assert f1 > p1
