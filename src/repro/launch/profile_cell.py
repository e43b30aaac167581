import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

"""Perf-loop profiler: lower one cell and print the heaviest HLO
instructions (trip-multiplied HBM bytes) and collectives, each with its
JAX-source op_name — the 'profile' the hypothesis loop reads.

Usage::

    PYTHONPATH=src python -m repro.launch.profile_cell \
        --arch mixtral-8x7b --shape train_4k [--top 25]
"""

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--n-micro", type=int, default=None)
    args = ap.parse_args()
    from repro import flags
    flags.use_compile_cache()

    from repro.analysis.hlo import HloAnalyzer

    # reuse dryrun's cell builder but keep the compiled text
    import repro.launch.dryrun as dr
    rec_holder = {}

    orig = dr.roofline_from_compiled

    def capture(compiled, **kw):
        rec_holder["text"] = kw.get("hlo_text") or compiled.as_text()
        return orig(compiled, **kw)

    dr.roofline_from_compiled = capture
    try:
        rec = dr.dryrun_cell(args.arch, args.shape,
                             multi_pod=args.multi_pod,
                             n_micro=args.n_micro, verbose=True)
    finally:
        dr.roofline_from_compiled = orig
    if rec.get("status") != "ok":
        print(rec)
        return

    an = HloAnalyzer(rec_holder["text"])
    print(f"\n== top {args.top} instructions by effective HBM bytes "
          "(per device) ==")
    for b, op, shape, name in an.top_instructions(args.top):
        print(f"  {b / 1e9:9.3f} GB  {op:20s} {shape:34.34s} {name[:90]}")
    print("\n== top collectives by effective payload ==")
    for b, op, shape, name in an.top_collectives(15):
        print(f"  {b / 1e9:9.3f} GB  {op:20s} {shape:34.34s} {name[:90]}")


if __name__ == "__main__":
    main()
