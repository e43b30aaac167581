"""Serving launcher: continuous-batching engine over synthetic requests.

Enc-dec archs (whisper-*) get synthetic encoder frames per request and
serve through the same scheduler as decoder-only models.

Usage::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
        --requests 16 --slots 4 [--q8] [--cache-dtype q8_0] \
        [--decode-block 16] [--platform imax3-28nm/32k]

``--decode-block K`` fuses K decode steps per scheduler tick (one host
sync per tick; tokens identical for any K).

``--platform`` serves against a registered hardware target
(``repro.platforms``): the kernel-dispatch context is derived from the
platform (LMM/VMEM budget, packing policy, pallas-eligibility) and the
run ends with the platform's energy report (joules/token, PDP).
"""

import argparse
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--q8", action="store_true",
                    help="serve Q8_0-quantized weights (paper variant)")
    ap.add_argument("--cache-dtype", choices=["bf16", "q8_0", "q4_0"],
                    default="bf16",
                    help="KV-cache storage: q8_0 streams ~0.53x the "
                         "bytes/step via the q8_decode_attention "
                         "kernel, q4_0 ~0.28x via q4_decode_attention")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="self-speculative decoding: draft spec_k-1 "
                         "tokens with q4_0-quantized weights and verify "
                         "all spec_k in one forward per round "
                         "(decode-block must be a multiple; greedy "
                         "token parity with plain decode)")
    ap.add_argument("--enc-len", type=int, default=64,
                    help="encoder-state pool length (enc-dec models)")
    ap.add_argument("--decode-block", type=int, default=1,
                    help="decode steps fused per tick (device-resident "
                         "loop; one host sync per tick)")
    ap.add_argument("--platform", default=None,
                    help="registered hardware target (repro.platforms; "
                         "e.g. imax3-28nm/32k, tpu-v5e); drives dispatch "
                         "and enables the energy report")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro import flags
    flags.use_compile_cache()

    import jax
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.models.model import build
    from repro.serving.engine import AudioRequest, Request, ServeEngine
    from repro.serving.scheduler import BatchScheduler

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build(cfg)
    params = model.init_values(jax.random.key(args.seed))
    if args.q8:
        from repro.core.quantize import quantize_tree
        params = quantize_tree(params)
        print("serving Q8_0-quantized weights")
    if args.cache_dtype in ("q8_0", "q4_0"):
        print(f"serving a {args.cache_dtype.upper()}-quantized KV cache")
    if args.spec_k:
        print(f"self-speculative decoding: spec_k={args.spec_k}")

    if args.platform:
        from repro.platforms import get_platform
        plat = get_platform(args.platform)   # fail fast on unknown names
        print(f"serving on platform {plat.name} "
              f"(LMM/VMEM budget {plat.vmem_budget} B)")
    engine = ServeEngine(model, params, n_slots=args.slots,
                         max_len=args.max_len, enc_len=args.enc_len,
                         cache_dtype=args.cache_dtype,
                         decode_block=args.decode_block,
                         spec_k=args.spec_k,
                         platform=args.platform)
    sched = BatchScheduler(engine)

    rng = np.random.default_rng(args.seed)
    for uid in range(args.requests):
        n = int(rng.integers(4, min(64, args.max_len - args.max_new - 1)))
        toks = rng.integers(3, cfg.vocab, size=n).tolist()
        if cfg.enc_dec:
            frames = rng.standard_normal(
                (int(rng.integers(4, args.enc_len + 1)), cfg.d_model)
            ).astype(np.float32) * 0.5
            sched.submit(AudioRequest(uid=uid, tokens=toks,
                                      max_new=args.max_new, eos_id=-1,
                                      enc_frames=frames))
        else:
            sched.submit(Request(uid=uid, tokens=toks,
                                 max_new=args.max_new, eos_id=-1))

    t0 = time.monotonic()
    sched.run_until_drained()
    dt = time.monotonic() - t0
    m = sched.metrics
    total_tokens = sum(len(st.out) for st in sched.results.values())
    print(f"{m.completed}/{args.requests} requests in {m.ticks} ticks "
          f"({dt:.1f}s), {total_tokens} tokens, "
          f"occupancy {m.mean_occupancy:.2f}, mean TTFT {m.mean_ttft:.1f} "
          f"ticks, {total_tokens/dt:.1f} tok/s, "
          f"decode block {args.decode_block} "
          f"({engine._host_syncs} decode host syncs)")
    if args.platform:
        er = engine.energy_report("q8_0" if args.q8 else "fp16")
        print(f"energy[{er['platform']}]: {er['joules_per_token']:.3e} "
              f"J/token, PDP {er['pdp_j']:.3e} J "
              f"(power {er['power_w']:.3f} W, {er['bound']}-bound, "
              f"cache stream {er['cache_energy_j']:.3e} J, "
              f"accel share {er['accel_flops_share']:.0%})")
    return m


if __name__ == "__main__":
    main()
