#!/usr/bin/env python3
"""Readings for the correctness limits: one process, many seeds.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 4 \\
        [--tier q8_0]

runs the cell's own traffic and load once per seed for a short window,
checks every run as ``bench/run.py`` does, and prints each run's
numbers compared. ``--tier`` switches on the program's own quantized
path (weights and KV cache) in place of the tiers the configuration
states: the control, which the limits must fail. Without it the
readings are the program's as configured, from which the lower end of
each limit is taken. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--tier", default=None)
    args = ap.parse_args(argv)
    cache = os.path.join(run.ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import spec
    from repro import flags
    flags.use_compile_cache()
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    cfg = copy.deepcopy(spec.load_config(bench, cell["config"]))
    if args.tier:
        cfg["deployment"].update(weights=args.tier, cache_dtype=args.tier)
    mix = spec.load_mix(cell["traffic"])
    limits = spec.load_limits(cell["name"])
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("control: needs the chip", file=sys.stderr)
        return 2
    peaks = spec.load_peaks(dev.device_kind)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(bench, cell, cfg, mix, limits, seed=seed,
                           seconds=args.seconds, trace=False, peaks=peaks,
                           log=lambda m: print(m, file=sys.stderr,
                                               flush=True))
        print(json.dumps({"seed": seed, "tier": args.tier or "as stated",
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "compared": out["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
