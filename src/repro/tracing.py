"""Host spans of the served path, recorded while a JAX profile is taken.

A span is one stretch of host work at a layer boundary: its ``name``, its
``start`` and ``end`` on ``time.monotonic()`` (the clock of
``repro.gateway.metrics.RequestRecord``), the ``id`` of the span it ran
under (``parent``), the ``thread`` that ended it, and a few ``attrs``
(``uid``, ``lanes``, ``k``, ``bucket``, ``enc_s``, ``gen``, ...).

Recording follows the profiler: spans are kept only while a JAX profiler
session is active (``jax.profiler.start_trace``, or a capture through
``jax.profiler.start_server``), which ``TraceAnnotation.is_enabled()``
tells at the cost of one call. Then every span is also a
``jax.profiler.TraceAnnotation`` of the same name, so it lies in the
profile beside the device's operations, on the device trace's clock.
With no profile running, each span site costs that one check and
allocates nothing.

Spans nest through ``contextvars``: a span begun in an asyncio task is
the parent of the spans begun in that task until it ends, and no other
task sees it. ``begin``/``end`` hold a span open across an ``await``;
``carry`` runs a function on another thread under the current span
(``loop.run_in_executor`` copies no context).

The layers name their spans by prefix:

* ``gateway.``  — ``repro.gateway.Gateway``'s tick loop;
* ``engine.``   — ``repro.serving.ServeEngine``: dispatch, fetch, replay,
  admission and stream feeds;
* ``frontend.`` — ``repro.audio.features.audio_frames``;
* ``host.``     — stalls of the process, recorded here: ``host.gc``
  (one garbage-collector pass, ``gen``) and ``host.compile`` (an XLA
  compile or a compilation-cache load, from ``jax.monitoring``).
  ``host.compile`` is known only once it has ended, so it has no
  ``TraceAnnotation``: the profile shows XLA's own compile events there.

``spans()`` returns what was recorded, ``clear()`` empties the buffer;
it holds ``CAPACITY`` spans and counts those it had to drop
(``dropped()``).
"""

from __future__ import annotations

import contextvars
import gc
import itertools
import threading
import time
from typing import Callable, Optional

import jax
import jax.monitoring

CAPACITY = 1 << 16

# the compile events of jax.monitoring that become ``host.compile``
COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}

_enabled = jax.profiler.TraceAnnotation.is_enabled
_current: contextvars.ContextVar = contextvars.ContextVar(
    "repro_tracing_span", default=None)
_ids = itertools.count(1)
_lock = threading.RLock()   # a gc pass inside a held lock records too
_buf: list = []
_dropped = 0


class Span:
    """One recorded span; ``end`` is None while it is open."""

    __slots__ = ("name", "start", "end", "id", "parent", "thread", "attrs",
                 "_ann", "_token")

    def __init__(self, name: str, parent: Optional[int], start: float):
        self.name = name
        self.start = start
        self.end: Optional[float] = None
        self.id = next(_ids)
        self.parent = parent
        self.thread = ""
        self.attrs: dict = {}
        self._ann = self._token = None

    @property
    def dur(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        end(self)

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"start={self.start:.6f}, end={self.end}, {self.attrs})")


class _Off:
    """What ``span`` returns while nothing records."""

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> None:
        return None


_OFF = _Off()


def on() -> bool:
    """Whether spans are being recorded (a profiler session is active)."""
    return _enabled()


def begin(name: str) -> Optional[Span]:
    """Open a span under the current one, and make it the current one;
    None when nothing records. Close it with ``end``, in the same task
    or thread."""
    if not _enabled():
        return None
    up = _current.get()
    sp = Span(name, None if up is None else up.id, 0.0)
    sp._token = _current.set(sp)
    # the two clocks are read back to back, so the span and its
    # annotation differ by the calls between them
    sp._ann = jax.profiler.TraceAnnotation(name)
    sp._ann.__enter__()
    sp.start = time.monotonic()
    return sp


def end(sp: Optional[Span]) -> None:
    """Close a span from ``begin`` and record it (None, or a span already
    closed, is a no-op)."""
    if sp is None or sp.end is not None:
        return
    sp.end = time.monotonic()
    sp._ann.__exit__(None, None, None)
    try:
        _current.reset(sp._token)
    except ValueError:       # ended in another context than it began in
        pass
    sp._ann = sp._token = None
    _keep(sp)


def span(name: str):
    """``with span(name) as sp:`` — ``sp`` is the live ``Span``, or None
    when nothing records (set attributes only on a live one)."""
    sp = begin(name)
    return _OFF if sp is None else sp


def carry(fn: Callable) -> Callable:
    """``fn``, run under the current span when called from another thread
    (for ``run_in_executor``); ``fn`` itself when nothing records."""
    if not _enabled():
        return fn
    up = _current.get()

    def run(*args):
        token = _current.set(up)
        try:
            return fn(*args)
        finally:
            _current.reset(token)

    return run


def _keep(sp: Span) -> None:
    global _dropped
    sp.thread = threading.current_thread().name
    with _lock:
        if len(_buf) < CAPACITY:
            _buf.append(sp)
        else:
            _dropped += 1


def spans() -> list:
    """A snapshot of the recorded spans, in the order they ended."""
    with _lock:
        return list(_buf)


def clear() -> None:
    """Forget every recorded span and the count of dropped ones."""
    global _dropped
    with _lock:
        _buf.clear()
        _dropped = 0


def dropped() -> int:
    """Spans not kept since the last ``clear`` because the buffer was
    full."""
    return _dropped


def self_time(sp: Span, among) -> float:
    """``sp``'s duration less the part of it that its children in
    ``among`` cover."""
    iv = sorted((max(c.start, sp.start), min(c.end, sp.end)) for c in among
                if c.parent == sp.id and c.end is not None)
    covered, reach = 0.0, sp.start
    for s, t in iv:
        s = max(s, reach)
        if t > s:
            covered += t - s
            reach = t
    return sp.dur - covered


# ------------------------------------------------------------ host stalls
_gc_span: Optional[Span] = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if phase == "start":
        _gc_span = begin("host.gc")
        if _gc_span is not None:
            _gc_span.attrs["gen"] = info.get("generation")
    elif _gc_span is not None:
        sp, _gc_span = _gc_span, None
        end(sp)


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    kind = COMPILE_EVENTS.get(event)
    if kind is None or not _enabled():
        return
    t = time.monotonic()
    up = _current.get()
    sp = Span("host.compile", None if up is None else up.id,
              t - duration_secs)
    sp.end = t
    sp.attrs["event"] = kind
    if "fun_name" in kwargs:
        sp.attrs["fun"] = kwargs["fun_name"]
    _keep(sp)


gc.callbacks.append(_on_gc)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
