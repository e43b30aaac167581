#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell (``BENCHMARK.json``'s
``workloads``) names a configuration and a traffic mix; their files, the
configuration's family module, the correctness limits, the per-layer
metric readers, the kernel models and the peak table are found by name
under ``bench/`` (``spec.py``).

A run builds the deployment the configuration states, makes the weights
from the seed on the device, warms up every shape the cell's traffic
uses, leads in, measures ``--seconds`` of traffic through the gateway,
and drains. With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` a profiler trace of the window gives its
per-layer metrics and the ``breakdown``. At the window's close a
sample of the lanes in flight is read back from the engine's KV pool;
then the engine is freed, and a sample of the served requests and those
lanes are checked against the family's plain reference:
``correct`` is whether every number compared stays within its limit in
``limits/<cell>.json``.

The last line of standard output is the result as one JSON object; the
numbers compared are also the last lines of standard error. Without a
TPU, or with fewer chips than the cell asks for, the run prints no
result and exits 2. JAX's compilation cache lives at
``<checkout>/.jax_cache`` whatever the environment says.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import math
import os
import random
import shutil
import sys
import tempfile
import time

T_START = time.monotonic()
TRACE_S = 5.0      # a traced run traces this much of its window
KV_OFF = "_kv_off"  # suffix of the limit on one kind of lane planes
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


class RunData:
    """What a per-layer metric reader may read: the cell, the requests
    the run served, the host spans and (traced runs) the trace."""

    def __init__(self, *, cell, cfg, mix, peaks, runner, trace, t1):
        self.cell, self.cfg, self.mix, self.peaks = cell, cfg, mix, peaks
        self.family = runner.family
        self.t0, self.t1 = runner.t0, t1
        self.done = runner.done
        self.spans = runner.spans.done
        self.decode_block = runner.engine.decode_block
        self.trace = trace

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def spans_in(self, name: str) -> list:
        return [(a, b) for a, b in self.spans.get(name, ())
                if self.t0 <= a < self.t1]

    def due_in_window(self) -> list:
        return [d for d in self.done if self.t0 <= d.due < self.t1]

    def completed_in_window(self) -> list:
        return [d for d in self.done if d.ok
                and self.t0 <= d.record.done_t < self.t1]


# ----------------------------------------------------- end-to-end metrics
def end_to_end(runner, mix: dict) -> tuple:
    """(values by metric name, attempted, failed) of a finished run."""
    import stats
    t0, t1 = runner.t0, runner.t1
    out = {}
    if mix["kind"] == "stream":
        lags = []
        attended = []
        for d in runner.done:
            rec = d.record
            got = rec.chunk_lags if rec is not None else []
            for i, (due, fed) in enumerate(d.feeds):
                at = fed + got[i] if i < len(got) else None
                attended.append((at, float(mix["chunk_s"])))
                if t0 <= due < t1:
                    lags.append(stats.latency(due, at))
            for due, _, _ in _unfed(d, mix, runner):
                if t0 <= due < t1:
                    lags.append(stats.MISS)
        out["audio_s_per_s"] = stats.rate(
            [(a, s) for a, s in attended if a is not None], t0, t1)
        out["stream_lag_p95_s"] = stats.reading(stats.percentile(lags, 95))
        return out, len(lags), sum(math.isinf(x) for x in lags)
    done_at = [(d.record.done_t, d.req.audio_s) for d in runner.done
               if d.ok]
    out["audio_s_per_s"] = stats.rate(done_at, t0, t1)
    if mix["kind"] == "closed":
        judged = [d for d in runner.done if d.record is not None
                  and d.record.done_t is not None
                  and t0 <= d.record.done_t < t1]
        return out, len(judged), sum(not d.ok for d in judged)
    due = [d for d in runner.done if t0 <= d.due < t1]
    n_due = sum(1 for r in runner.reqs
                if t0 <= runner.t_base + r.due < t1)
    missing = n_due - len(due)           # never came back
    ttft = [stats.latency(d.due, d.record.first_token_t if d.ok else None)
            for d in due] + [stats.MISS] * missing
    e2e = [stats.latency(d.due, d.record.done_t if d.ok else None)
           for d in due] + [stats.MISS] * missing
    out["ttft_p95_s"] = stats.reading(stats.percentile(ttft, 95))
    out["e2e_p95_s"] = stats.reading(stats.percentile(e2e, 95))
    return out, n_due, n_due - sum(d.ok for d in due)


def _unfed(d, mix, runner):
    """Chunks of a session that were due before the run stopped feeding
    but never fed (the session failed first)."""
    import traffic
    base = d.due - d.req.due
    out = traffic.chunks_of(d.req, float(mix["chunk_s"]))[len(d.feeds):]
    return [(base + due, a, b) for due, a, b in out if base + due < runner.t1]


# ---------------------------------------------------------- correctness
def check(runner, cfg: dict, limits: dict, params, seed: int, log) -> tuple:
    """Judge what the timed path produced by the family's plain reference.

    Two samples, both drawn from the seed: completed requests, with the
    longest (prompt plus output) always in it, whose served tokens are
    judged; and the lanes in flight at the window's close
    (``Runner.take_lanes``), whose served tokens are judged and whose
    planes are compared with the reference's: for each kind ``<kind>``
    that the limits name as ``<kind>_kv_off``, the share of elements off
    it in the worst plane (``judge``). Returns (correct, compared) where
    compared maps each number to its value and limit; a number that
    could not be read (no lane in flight, a kind the family did not
    read) is None and fails."""
    ok = [d for d in runner.done if d.ok and d.result.tokens]
    if not ok:
        return False, {"checked_requests": {"value": 0,
                                            "limit": limits["requests"]}}
    longest = max(ok, key=lambda d: len(d.req.prompt) + len(d.result.tokens))
    rest = [d for d in ok if d is not longest]
    rng = random.Random(seed)
    sample = [longest] + rng.sample(rest, min(len(rest),
                                              limits["requests"] - 1))
    ref = runner.family.Reference(cfg, params)
    short, gaps = 0, []
    kinds = [k[:-len(KV_OFF)] for k in limits if k.endswith(KV_OFF)]
    kv = dict.fromkeys(kinds, 0.0) if runner.lanes else \
        dict.fromkeys(kinds)
    for d in sample:
        toks = list(d.result.tokens)
        short += len(toks) != d.req.max_new
        g, _ = ref.judge(runner, d.req, toks)
        gaps.extend(float(x) for x in g)
    for lane in runner.lanes:
        g, off = ref.judge(runner, lane.req, lane.out, lane.planes)
        gaps.extend(float(x) for x in g)
        for kind in kv:
            got = None if off is None else off.get(kind)
            kv[kind] = None if got is None or kv[kind] is None \
                else max(kv[kind], got)
    off = sorted(g for g in gaps if g > 0)
    log(f"checked {len(sample)} requests and {len(runner.lanes)} lanes in "
        f"flight, {len(gaps)} served tokens, {len(off)} off the "
        f"reference's best"
        + (f" (median gap {off[len(off) // 2]:.6g})" if off else ""))
    compared = {
        "token_gap": {"value": max(gaps), "limit": limits["token_gap"]},
        "short_outputs": {"value": short, "limit": 0},
    }
    if limits.get("lanes"):
        for kind, v in kv.items():
            compared[f"{kind}{KV_OFF}"] = {
                "value": v, "limit": limits[f"{kind}{KV_OFF}"]}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in compared.values())
    return correct, compared


# ------------------------------------------------------------------- run
def run_cell(bench: dict, cell: dict, cfg: dict, mix: dict, limits: dict,
             *, seed: int, seconds: float, trace: bool, peaks: dict,
             family=None, log=print) -> dict:
    """One run of ``cell``; returns the result object (without the
    device block's platform fields, which the caller adds). ``family``
    is the configuration's family module, by default the one its
    ``family`` names under ``bench/families/``."""
    import jax
    import jax.monitoring
    import model as bench_model
    import serve
    import spec
    import trace_reduce

    family = family or spec.family_module(cfg["family"])
    loads = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: loads.append(time.monotonic())
        if name == "/jax/compilation_cache/compile_requests_use_cache"
        else None)
    dep = cfg["deployment"]
    arch = family.arch_config(cfg)
    log(f"set-up: {time.monotonic() - T_START:.3f} s to JAX on "
        f"{jax.devices()[0].device_kind}")
    params = bench_model.make_weights(serve._build(arch), seed,
                                      family.init_leaf)
    runner = serve.Runner(cfg, mix, bench_model.served(params, dep),
                          family=family, seed=seed, seconds=seconds,
                          lanes=limits.get("lanes", 0), log=log)
    log(f"set-up: {time.monotonic() - T_START:.3f} s to weights, engine "
        f"and {len(runner.reqs)} requests")
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    window = {}

    def stop_tracing():
        # the trace is written from a worker thread, so that the event
        # loop keeps serving while it is
        if "ann" in window:
            window.pop("ann").__exit__(None, None, None)
            runner.spans.tracing = False
            asyncio.get_running_loop().run_in_executor(
                None, jax.profiler.stop_trace)

    def on_start():
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            window["ann"] = jax.profiler.TraceAnnotation(
                trace_reduce.WINDOW_SPAN)
            window["ann"].__enter__()
            runner.spans.tracing = True
            asyncio.get_running_loop().call_later(TRACE_S, stop_tracing)

    runner.run(on_start, stop_tracing)
    setup_s = runner.t0 - T_START
    in_window = sum(runner.t0 <= t < runner.t1 for t in loads)
    log(f"programs compiled or loaded inside the window: {in_window}")
    if runner.lateness:
        late = sorted(runner.lateness)
        log(f"generator lateness: median {late[len(late) // 2]:.6f} s, "
            f"max {late[-1]:.6f} s over {len(late)} arrivals")
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use",
                                                      0)
    e2e, attempted, failed = end_to_end(runner, mix)
    e2e["setup_s"] = setup_s
    metrics, device_extra, breakdown = {}, {}, None
    if trace:
        tr = trace_reduce.load(trace_reduce.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        # per-layer metrics read the traced part of the window only:
        # writing the trace out after it slows the host
        run = RunData(cell=cell, cfg=cfg, mix=mix, peaks=peaks,
                      runner=runner, trace=tr,
                      t1=min(runner.t1, runner.t0 + TRACE_S))
        for m in spec.cell_metrics(bench, cell["name"], "per_layer"):
            v = spec.metric_reader(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device_extra = {"busy_s": trace_reduce.busy_ns(tr) * 1e-9,
                        "window_s": tr.window_s}
        breakdown = {"device_ops": trace_reduce.top_ops(tr),
                     "idle_gaps": trace_reduce.top_gaps(tr)}
    else:
        for m in spec.cell_metrics(bench, cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    # free the program's state before the reference runs
    runner.engine = runner.gw = None
    gc.collect()
    correct, compared = check(runner, cfg, limits, params, seed, log)
    out = {"correct": bool(correct and attempted > 0),
           "attempted": attempted, "failed": failed, "metrics": metrics,
           "device": {"memory_peak_bytes": int(mem), **device_extra}}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # JAX's persistent cache at a fixed path inside the checkout, so only
    # the first run of a cell in a checkout compiles and nothing is
    # shared with another checkout
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import spec
    from repro import flags
    flags.use_compile_cache()
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    cfg = spec.load_config(bench, cell["config"])
    mix = spec.load_mix(cell["traffic"])
    limits = spec.load_limits(cell["name"])

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: this cell needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    peaks = spec.load_peaks(devs[0].device_kind)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    out = run_cell(bench, cell, cfg, mix, limits, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   peaks=peaks, log=log)
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    dev.update(out["device"])
    out["device"] = dev
    out["compared"] = out.pop("compared")
    for name, c in out["compared"].items():
        log(f"compared {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
