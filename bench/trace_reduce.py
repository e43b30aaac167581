"""From a profiler trace to device busy time, kernel time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes (with
``jax.profiler.ProfileData``, nothing else) into a ``Trace``: the
device's operations and programs, and the benchmark's own host spans
(``bench.*`` ``TraceAnnotation``s), all on the trace's clock in
nanoseconds. The reductions below are what every per-layer device
metric and the ``breakdown`` are computed from.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float            # ns
    end: float              # ns
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: list               # device operations, every device plane
    modules: list           # device program executions
    spans: list             # host spans named bench.*
    n_devices: int
    lo: float               # the measured window, ns
    hi: float

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def short_name(text: str) -> str:
    """``fusion.12`` from an operation's HLO text
    ``%fusion.12 = bf16[...] fusion(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _program(name: str) -> str:
    """``jit_prefill`` from a program event ``jit_prefill(1234...)``."""
    return name.split("(", 1)[0]


def load(path: str) -> Trace:
    """Read a trace. Device operations keep their short name, the
    program they ran in, and (for custom calls, which is where the
    Pallas kernels are) their HLO text, from which shapes are read."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    n_dev = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            n_dev += 1
            dev_mods = sorted((ev.start_ns, ev.end_ns, _program(ev.name))
                              for ev in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            modules.extend(Event(m, s, t) for s, t, m in dev_mods)
            starts = [m[0] for m in dev_mods]
            for ev in lines[OPS_LINE].events:
                text = ev.name
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                prog = dev_mods[i][2] if i >= 0 and \
                    ev.start_ns < dev_mods[i][1] else ""
                stats = {"program": prog, "device": n_dev - 1}
                if "custom-call(" in text:
                    stats["hlo"] = text
                ops.append(Event(short_name(text), ev.start_ns, ev.end_ns,
                                 stats))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Event(ev.name, ev.start_ns, ev.end_ns))
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if win:
        lo, hi = win[0].start, win[0].end
    else:
        pts = [e.start for e in ops] + [e.end for e in ops]
        lo, hi = (min(pts), max(pts)) if pts else (0.0, 0.0)
    return Trace(ops, modules, spans, max(n_dev, 1), lo, hi)


def clip(events, lo: float, hi: float) -> list:
    """Events overlapping ``[lo, hi)``, cut to it."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t, e.stats))
    return out


def merge(events) -> list:
    """The union of the events' intervals, as sorted disjoint
    ``(start, end)`` pairs."""
    iv = sorted((e.start, e.end) for e in events)
    out = []
    for s, t in iv:
        if out and s <= out[-1][1]:
            if t > out[-1][1]:
                out[-1] = (out[-1][0], t)
        else:
            out.append((s, t))
    return out


def busy_ns(trace: Trace) -> float:
    """Time in the window in which some operation ran, averaged over the
    devices."""
    per_dev = {}
    for e in clip(trace.ops, trace.lo, trace.hi):
        per_dev.setdefault(e.stats.get("device", 0), []).append(e)
    if not per_dev:
        return 0.0
    total = sum(sum(t - s for s, t in merge(evs)) for evs in per_dev.values())
    return total / trace.n_devices


def idle_gaps(trace: Trace) -> list:
    """``(start, end)`` of each stretch of the window in which no
    operation ran on the device, longest first."""
    busy = merge(clip(trace.ops, trace.lo, trace.hi))
    gaps, cur = [], trace.lo
    for s, t in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if trace.hi > cur:
        gaps.append((cur, trace.hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def host_activity(trace: Trace, lo: float, hi: float) -> str:
    """The host span that covers most of ``[lo, hi)`` — the innermost
    where spans nest — or ``"none"``."""
    best, best_key = "none", None
    for sp in trace.spans:
        if sp.name == WINDOW_SPAN:
            continue
        cover = min(sp.end, hi) - max(sp.start, lo)
        if cover <= 0:
            continue
        key = (cover, -sp.dur)
        if best_key is None or key > best_key:
            best, best_key = sp.name[len(SPAN_PREFIX):], key
    return best


def matches(ev: Event, names) -> bool:
    """Whether an operation is a call of the kernel traced as one of
    ``names`` (a Pallas call is named after its jitted wrapper)."""
    base = ev.name.rsplit(".", 1)[0]
    return "hlo" in ev.stats and base in names


def op_key(ev: Event) -> str:
    """``program/operation``, the operation's number dropped, so that
    one operation of a program sums over its calls."""
    base = ev.name.rsplit(".", 1)[0] if ev.name.rsplit(".", 1)[-1] \
        .isdigit() else ev.name
    prog = ev.stats.get("program", "")
    return f"{prog}/{base}" if prog else base


# operations that only wrap others (a scan's loop): their time is their
# body's, which the trace lists operation by operation
CONTAINERS = ("while", "conditional", "call")


def top_ops(trace: Trace, n: int = 10) -> list:
    """The device operations that took most time in the window,
    ``[program/operation, seconds]``, summed over their calls."""
    tot = {}
    for e in clip(trace.ops, trace.lo, trace.hi):
        if e.name.rsplit(".", 1)[0] in CONTAINERS:
            continue
        k = op_key(e)
        tot[k] = tot.get(k, 0.0) + e.dur
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(trace: Trace, n: int = 10) -> list:
    """The longest idle gaps, ``[host activity, seconds]``."""
    return [[host_activity(trace, s, t), (t - s) * 1e-9]
            for s, t in idle_gaps(trace)[:n]]


_SHAPE = re.compile(
    r"\b(bf16|f16|f32|s8|u8|s32|pred)\[([0-9,]*)\](\{[^}]*\})?")
DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "u8": 1, "s32": 4,
               "pred": 1}


def shapes_in(text: str) -> list:
    """``(dtype, shape, in_hbm)`` of each array type written in HLO text,
    in order. ``in_hbm`` is False for an array whose layout places it in
    the core's own memory (``S(1)``, VMEM): reading it costs no HBM
    bandwidth."""
    return [(m.group(1), tuple(int(d) for d in m.group(2).split(",") if d),
             "S(1)" not in (m.group(3) or ""))
            for m in _SHAPE.finditer(text)]


def module_time(trace: Trace, names) -> float:
    """Seconds the device spent in programs whose name contains one of
    ``names``, within the window."""
    return sum(e.dur for e in clip(trace.modules, trace.lo, trace.hi)
               if any(n in e.name for n in names)) * 1e-9


def call_shapes(ev: Event) -> list:
    """The array types of a custom call's HLO text: the result first,
    then the operands."""
    shapes = shapes_in(ev.stats.get("hlo", ""))
    if not shapes:
        raise ValueError(f"no shapes in the HLO text of {ev.name}")
    return shapes


def hbm_bytes(*arrays) -> int:
    """Bytes of the ``(dtype, shape, in_hbm)`` arrays that live in HBM."""
    total = 0
    for dtype, shape, in_hbm in arrays:
        if in_hbm:
            n = DTYPE_BYTES[dtype]
            for d in shape:
                n *= d
            total += n
    return total
