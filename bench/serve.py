"""Drive one cell's traffic through the served path and time it.

The system under test is a ``repro.gateway.Gateway`` over a
``ServeEngine``, built as the configuration's ``deployment`` says
through its family module (``families/<family>.py``), which also says
how a request reaches the gateway (for Whisper, through the frontend,
inside the client coroutine, so that it is on the timed path). Spans
are taken from this file by wrapping calls into each layer: the engine
instance's ``admit`` and ``stream_feed``, and each tick from
``step_begin`` to the end of ``step_replay`` (a family adds its own,
such as Whisper's ``frontend``). With tracing on they also go into the
profiler's trace as ``TraceAnnotation``s, on the device trace's clock.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import dataclasses
import random
import time

import numpy as np

import traffic

DRAIN_S = 60.0       # how long past the window an open-loop request may take
STREAM_GRACE_S = 2.0
LANES_WAIT_S = 10.0  # how long past the window a lane snapshot may wait


class Spans:
    """Host spans on ``time.monotonic``; mirrored into the profiler's
    trace while ``tracing`` is set."""

    def __init__(self):
        self.tracing = False
        self.done = collections.defaultdict(list)   # name -> [(t0, t1)]

    def begin(self, name: str):
        ann = None
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
            ann.__enter__()
        return (name, time.monotonic(), ann)

    def end(self, tok) -> None:
        name, t0, ann = tok
        self.done[name].append((t0, time.monotonic()))
        if ann is not None:
            ann.__exit__(None, None, None)

    def drop(self, tok) -> None:
        if tok[2] is not None:
            tok[2].__exit__(None, None, None)

    @contextlib.contextmanager
    def span(self, name: str):
        tok = self.begin(name)
        try:
            yield
        finally:
            self.end(tok)


def instrument(engine, spans: Spans, after_replay=None) -> None:
    """Wrap the engine instance's layer entry points in spans: a tick is
    its ``dispatch`` (``step_begin``) and its host ``replay``
    (``step_replay``); the wait for the device between them is the
    gateway's. ``after_replay`` runs once each replay has returned,
    before the gateway dispatches anything else."""
    admit, feed = engine.admit, engine.stream_feed
    begin, replay = engine.step_begin, engine.step_replay

    def admit_(req):
        with spans.span("admit"):
            return admit(req)

    def feed_(st, frames):
        with spans.span("stream_feed"):
            return feed(st, frames)

    def begin_(k=None):
        tok = spans.begin("dispatch")
        pending = begin(k)
        if pending is None:
            spans.drop(tok)
        else:
            spans.end(tok)
        return pending

    def replay_(pending, tok_blk, emit_blk):
        with spans.span("replay"):
            out = replay(pending, tok_blk, emit_blk)
        if after_replay is not None:
            after_replay()
        return out

    engine.admit, engine.stream_feed = admit_, feed_
    engine.step_begin, engine.step_replay = begin_, replay_


@dataclasses.dataclass
class Done:
    """What one request (or streaming session) did, on the monotonic
    clock."""

    req: traffic.Req
    due: float
    result: object = None            # GatewayResult
    feeds: list = dataclasses.field(default_factory=list)  # (due, fed)

    @property
    def ok(self) -> bool:
        return self.result is not None and self.result.ok

    @property
    def record(self):
        return None if self.result is None else self.result.record


@dataclasses.dataclass
class Lane:
    """A one-shot request in flight at the window's close, as the engine
    held it after its last replayed tick: the tokens it had emitted and
    the planes its lane held, as the family's ``read_lane`` reads them,
    ``{kind: {name: float32 array}}`` (None where the lane's positions
    did not match its tokens)."""

    req: traffic.Req
    out: list
    planes: dict


def kv_planes(trees: dict, slot: int) -> dict:
    """One lane's K/V, read back from the engine's pool as float32 in one
    transfer: ``trees`` maps a kind to ``(planes, n)``, planes stacked
    ``(layer, lane, position, head, dim)``, of which the first ``n``
    positions are read; bf16 planes as they are, q8_0 planes (int8
    codes, one f16 scale per block along the head dim) decoded. Returns
    ``{kind: {"k"|"v": (layer, position, head, dim)}}``."""
    import jax
    got = jax.device_get({kind: {key: a[:, slot] for key, a in
                                 planes.items()}
                          for kind, (planes, _) in trees.items()})
    out = {}
    for kind, (_, n) in trees.items():
        p = got[kind]
        if set(p) == {"k", "v"}:
            out[kind] = {key: np.asarray(p[key][:, :n], np.float32)
                         for key in ("k", "v")}
        elif set(p) == {"kq", "ks", "vq", "vs"}:
            out[kind] = {}
            for key in ("k", "v"):
                q = np.asarray(p[key + "q"][:, :n], np.float32)
                sc = np.asarray(p[key + "s"][:, :n], np.float32)
                blocks = q.reshape(*q.shape[:-1], sc.shape[-1], -1)
                out[kind][key] = (blocks * sc[..., None]).reshape(q.shape)
        else:
            raise ValueError(f"no reader for cache planes {sorted(p)}")
    return out


class Runner:
    """One cell's run: build, warm up, lead in, measure, drain.
    ``family`` is the configuration's family module."""

    def __init__(self, cfg: dict, mix: dict, params, *, family, seed: int,
                 seconds: float, lanes: int = 0, log=print):
        from repro.gateway import Gateway
        from repro.gateway.slo import DEFAULT_CLASSES
        from repro.serving.engine import ServeEngine
        self.cfg, self.mix, self.seconds = cfg, mix, seconds
        self.family = family
        self.log = log
        self.engine = ServeEngine(
            _build(family.arch_config(cfg)), params,
            **family.engine_options(cfg["deployment"]))
        self.spans = Spans()
        instrument(self.engine, self.spans, self._after_replay)
        self.gw = Gateway(self.engine)
        self.slo = {c.name: c for c in DEFAULT_CLASSES}[mix["slo"]]
        self.inputs = family.inputs(seed)
        self.reqs = traffic.generate(mix, cfg, family.prompt, seed, seconds)
        self.done: list[Done] = []
        self.lateness: list[float] = []
        self.t0 = self.t1 = None
        # lanes in flight at the window's close (``take_lanes``)
        self.lanes_wanted, self.lanes = lanes, []
        self._lane_rng = random.Random(seed)
        self._want_lanes = None
        self._inflight = {}       # family.lane_key -> request

    # ------------------------------------------------------------ pieces
    @contextlib.contextmanager
    def in_flight(self, key, req):
        """Register ``req`` under ``key`` while the family's ``submit``
        awaits it: the lanes taken at the window's close are found by
        ``family.lane_key`` of the engine's request."""
        self._inflight[key] = req
        try:
            yield
        finally:
            self._inflight.pop(key, None)

    async def _oneshot(self, req, due: float, max_new=None) -> Done:
        d = Done(req, due)
        d.result = await self.family.submit(self, req,
                                            max_new or req.max_new)
        return d

    # ------------------------------------------------- lanes at the close
    async def take_lanes(self) -> None:
        """Keep, from the seed, ``lanes_wanted`` of the one-shot lanes in
        flight after the first tick that ends past the window's close."""
        if not self.lanes_wanted:
            return
        self._want_lanes = asyncio.Event()
        try:
            await asyncio.wait_for(self._want_lanes.wait(), LANES_WAIT_S)
        except asyncio.TimeoutError:
            self.log("no one-shot lane in flight after the window closed")
        self._want_lanes = None

    def _after_replay(self) -> None:
        if self._want_lanes is None or self._want_lanes.is_set():
            return
        eng = self.engine
        live = []
        for slot, st in sorted(eng.active.items()):
            req = self._inflight.get(self.family.lane_key(st.req))
            if req is not None:
                live.append((slot, st, req))
        if not live:
            return
        for slot, st, req in self._lane_rng.sample(
                live, min(len(live), self.lanes_wanted)):
            # positions written: the prompt, then each emitted token but
            # the newest, which the next tick would feed; a lane that
            # holds anything else is kept with no planes, and fails
            held = list(st.req.tokens) == list(req.prompt) and \
                st.pos == len(req.prompt) + len(st.out) - 1
            self.lanes.append(Lane(req, list(st.out), self.family.read_lane(
                eng.cache, slot, st) if held else None))
        self._want_lanes.set()

    async def sleep_until(self, t: float) -> None:
        dt = t - time.monotonic()
        if dt > 0:
            await asyncio.sleep(dt)

    # ---------------------------------------------------------- warm-up
    def _warm_reqs(self) -> list:
        """One request of every shape this cell's traffic uses: each
        prompt bucket at each audio length."""
        from repro.serving.engine import _bucket
        seen, out = set(), []
        for r in self.reqs:
            key = (_bucket(len(r.prompt)), r.audio_s)
            if key not in seen:
                seen.add(key)
                out.append(r)
        return out

    async def warm_up(self) -> None:
        """Compile (or load from the cache) every program the window
        will run, then settle the gateway's latency estimators on warm
        calls, so that the window compiles nothing."""
        warm = self._warm_reqs()
        if self.mix["kind"] == "stream":
            # later passes run warm: they drain compile time out of the
            # gateway's tick / admit estimators
            for _ in range(3):
                await asyncio.gather(*[
                    self.family.session(self, warm[0], None, paced=False,
                                        max_new=2, keep=False)
                    for _ in range(2)])
            return
        # one at a time first, each until it is served: while programs
        # compile, a queued request can pass its deadline and be shed,
        # and its shape would then compile inside the window
        for r in warm:
            for _ in range(5):
                if (await self._oneshot(r, time.monotonic(), 2)).ok:
                    break
            else:
                raise RuntimeError(f"warm-up request of {len(r.prompt)} "
                                   f"prompt tokens and {r.audio_s} s of "
                                   f"audio was never served")
        for n in (len(warm), 4, 4, 4):
            await asyncio.gather(*[self._oneshot(r, time.monotonic(), 2)
                                   for r in (warm * 4)[:n]])

    # ------------------------------------------------------- the traffic
    async def _closed(self) -> None:
        dep = self.cfg["deployment"]
        n_clients = int(round(self.mix["outstanding_per_slot"]
                              * dep["n_slots"]))
        it = iter(self.reqs)
        stop = False

        async def client():
            while not stop:
                try:
                    req = next(it)
                except StopIteration:
                    raise RuntimeError("closed-loop pool ran dry; raise "
                                       "the mix's pool") from None
                d = await self._oneshot(req, time.monotonic())
                if not stop:
                    self.done.append(d)

        lead = float(self.mix["lead_s"])
        t_start = time.monotonic()
        tasks = []
        for _ in range(n_clients):
            # ramp up without overrunning the admission queue
            while self.gw.n_queued >= self.gw.queue.limit - 2:
                await asyncio.sleep(0.002)
            tasks.append(asyncio.create_task(client()))
            await asyncio.sleep(0)
        await self.sleep_until(t_start + lead)
        self.done.clear()
        self.t0 = time.monotonic()
        self.on_window_start()
        await self.sleep_until(self.t0 + self.seconds)
        self.t1 = time.monotonic()
        self.on_window_end()
        await self.take_lanes()
        stop = True
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    async def _open(self) -> None:
        t_base = self.t_base = time.monotonic() + float(self.mix["lead_s"])

        async def one(req):
            due = t_base + req.due
            await self.sleep_until(due)
            self.lateness.append(time.monotonic() - due)
            d = await self._oneshot(req, due)
            self.done.append(d)

        tasks = [asyncio.create_task(one(r)) for r in self.reqs]
        await self.sleep_until(t_base)
        self.t0 = time.monotonic()
        self.on_window_start()
        await self.sleep_until(t_base + self.seconds)
        self.t1 = time.monotonic()
        self.on_window_end()
        await self.take_lanes()
        await asyncio.wait(tasks, timeout=DRAIN_S)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    async def _stream(self) -> None:
        lead = float(self.mix["lead_s"])
        t_base = time.monotonic() + lead

        async def start(req):
            # a session that began before the lead-in has its past audio
            # fed at once: the window opens on sessions at every stage
            await self.sleep_until(t_base + req.due)
            await self.family.session(self, req, t_base)

        live = [r for r in self.reqs if r.due + r.audio_s > -lead]
        tasks = [asyncio.create_task(start(r)) for r in live]
        await self.sleep_until(t_base)
        self.t0 = time.monotonic()
        self.on_window_start()
        await self.sleep_until(t_base + self.seconds)
        self.t1 = time.monotonic()
        self.on_window_end()
        await asyncio.sleep(STREAM_GRACE_S)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    # ------------------------------------------------------------ driver
    async def _main(self, on_start, on_end) -> None:
        self.on_window_start, self.on_window_end = on_start, on_end
        async with self.gw:
            t = time.monotonic()
            await self.warm_up()
            self.log(f"set-up: warm-up {time.monotonic() - t:.3f} s")
            await {"closed": self._closed, "open": self._open,
                   "stream": self._stream}[self.mix["kind"]]()
            await self.gw.close(drain=False)

    def run(self, on_start, on_end) -> None:
        """Warm up, lead in, measure ``seconds``, drain. ``on_start`` /
        ``on_end`` run at the window's edges (tracing, counters)."""
        asyncio.run(self._main(on_start, on_end))


def _build(arch):
    from repro.models.model import build
    return build(arch)
