#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest rate it sustains.

    python bench/sweep.py --workload <cell> --rates 10,20,40 --seconds 8

runs the cell's mix at each offered rate (requests, or sessions, per
second) for a short window, in one process, and prints per rate what
came back: the requests due in the window and how many completed, the
tails, and whether the backlog grew (the latency of the work due in the
window's second half against its first half). The mix file then fixes
its ``rate_per_s`` at about 0.8 of the highest rate without a growing
backlog. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import statistics
import sys

import run


def growth(runner) -> float:
    """Median latency (due to done, or to attended for stream chunks) of
    work due in the second half of the window over the first half's."""
    t0, t1 = runner.t0, runner.t1
    mid = (t0 + t1) / 2
    halves = ([], [])
    for d in runner.done:
        if runner.mix["kind"] == "stream":
            lags = d.record.chunk_lags if d.record is not None else []
            for i, (due, fed) in enumerate(d.feeds):
                if t0 <= due < t1 and i < len(lags):
                    halves[due >= mid].append(fed + lags[i] - due)
        elif t0 <= d.due < t1 and d.ok:
            halves[d.due >= mid].append(d.record.done_t - d.due)
    if not halves[0] or not halves[1]:
        return float("nan")
    return statistics.median(halves[1]) / statistics.median(halves[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cache = os.path.join(run.ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import model as bench_model
    import serve
    import spec
    from repro import flags
    flags.use_compile_cache()
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    cfg = spec.load_config(bench, cell["config"])
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs the chip", file=sys.stderr)
        return 2
    family = spec.family_module(cfg["family"])
    params = bench_model.make_weights(
        serve._build(family.arch_config(cfg)), args.seed, family.init_leaf)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(spec.load_mix(cell["traffic"]))
        mix["rate_per_s"] = rate
        runner = serve.Runner(cfg, mix, params, family=family,
                              seed=args.seed, seconds=args.seconds,
                              log=lambda m: print(m, file=sys.stderr))
        runner.run(lambda: None, lambda: None)
        e2e, attempted, failed = run.end_to_end(runner, mix)
        late = sorted(runner.lateness) or [0.0]
        print(json.dumps({"rate_per_s": rate, "attempted": attempted,
                          "failed": failed, "metrics": e2e,
                          "growth": growth(runner),
                          "late_max_s": late[-1]}), flush=True)
        runner = None
    return 0


if __name__ == "__main__":
    sys.exit(main())
