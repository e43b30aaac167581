"""End-to-end ASR launcher: synthetic waveform -> log-mel frontend ->
chunked encoder -> tokens, through the serving engine.

Usage::

    PYTHONPATH=src python -m repro.launch.transcribe \
        --platform imax3-28nm --cache-dtype q8_0 [--stream] \
        [--decode-block 16] [--seconds 1.0] [--arch whisper-tiny-en] \
        [--full]

``--stream`` serves through the chunk-at-a-time streaming path (one
audio chunk per scheduler tick, partial hypotheses printed as they
form); the final transcript is token-identical to the one-shot path.
``--platform`` routes every kernel through that target's dispatch
context and ends with the modeled energy report (joules/audio-second).
"""

import argparse


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="whisper-tiny-en")
    ap.add_argument("--full", action="store_true",
                    help="full-size config (default: reduced smoke size)")
    ap.add_argument("--seconds", type=float, default=1.0,
                    help="synthetic waveform length")
    ap.add_argument("--chunk-frames", type=int, default=16,
                    help="encoder chunk size (frame embeddings)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--stream", action="store_true",
                    help="serve via the streaming chunked-encode path")
    ap.add_argument("--cache-dtype", choices=["bf16", "q8_0", "q4_0"],
                    default="bf16")
    ap.add_argument("--decode-block", type=int, default=1,
                    help="decode steps fused per tick (one host sync "
                         "per tick; tokens identical for any value)")
    ap.add_argument("--platform", default=None,
                    help="registered hardware target (repro.platforms); "
                         "enables the energy report")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro import flags
    flags.use_compile_cache()

    from repro.audio.stream import synth_waveform
    from repro.audio.transcribe import transcribe

    wave = synth_waveform(args.seconds, seed=args.seed)
    print(f"transcribing {args.seconds:.2f}s synthetic waveform "
          f"({len(wave)} samples) with {args.arch}"
          f"{'' if args.full else ' (reduced)'}"
          f"{', streaming' if args.stream else ''}, "
          f"cache {args.cache_dtype}"
          + (f", platform {args.platform}" if args.platform else ""))
    r = transcribe(wave, 16_000, arch=args.arch, reduced=not args.full,
                   platform=args.platform, cache_dtype=args.cache_dtype,
                   decode_block=args.decode_block,
                   chunk_frames=args.chunk_frames, max_new=args.max_new,
                   stream=args.stream, seed=args.seed)
    if args.stream:
        for i, p in enumerate(r.partials):
            print(f"  partial[{i}]: {p}")
    print(f"tokens: {r.tokens}")
    print(f"{r.n_frames} encoder frames, {r.ticks} decode ticks "
          f"x block {r.decode_block} = {r.decode_steps} decode steps, "
          f"{r.host_syncs} decode host syncs, {r.wall_s:.2f}s wall "
          f"({r.compute_ms_per_audio_s:.0f} ms compute per audio-second, "
          f"includes jit)")
    if r.energy:
        e = r.energy
        print(f"energy[{e['platform']}]: "
              f"{e['joules_per_audio_s']:.3e} J/audio-s, "
              f"{e['joules_per_token']:.3e} J/token "
              f"(power {e['power_w']:.3f} W, {e['bound']}-bound, "
              f"accel share {e['accel_flops_share']:.0%})")
    return r


if __name__ == "__main__":
    main()
