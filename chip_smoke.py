#!/usr/bin/env python3
"""Bring-up smoke run: serve full-width whisper-tiny.en on one TPU.

Drives the main path once, in this one process, through the entry
points a user calls, at the published widths of whisper-tiny.en (4+4
layers, d_model 384, 6 heads of 64, d_ff 1536, vocab 51865) with random
weights from ``--seed``:

* ``oneshot_bf16`` / ``oneshot_q8_0`` — ``repro.transcribe`` on a 30 s
  synthetic waveform (1500 encoder frames) with each KV-cache tier;
* ``stream`` — the same audio through the streaming path; its final
  tokens must equal the one-shot tokens;
* ``serve`` — ``ServeEngine`` + ``BatchScheduler`` (4 slots, 8 decode
  steps per tick) serving 8 audio requests of 5-30 s; every request
  must complete;
* ``parity`` — one served request's tokens against the slot-free greedy
  forward of the same model (``repro.serving.reference``, near-tie rule);
* ``logits`` — prefill logits against the same forward on the ``ref``
  kernel backends (f32 arithmetic inside every kernel), within
  ``LOGIT_REL_TOL``.

Each phase prints its first-call seconds (compilation included) and its
warm seconds; these are smoke timings, not benchmark results. The run
fails if a phase fails, if an engine op binds to ``ref``, if any kernel
context runs Pallas in interpret mode, or if JAX finds no TPU — then no
result line is printed. The last line of a passing run is::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage: ``python chip_smoke.py [--seed N]`` from the root of a checkout.
It starts no other process and writes nothing but the compilation cache
(``repro.flags.use_compile_cache``).
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SR = 16_000
ARCH = "whisper-tiny-en"
PROMPT = (1,)               # stand-in for whisper's <|sot|> sequence
STREAM_CHUNK = 250          # 5 s of encoder frames per streamed chunk
# Pallas kernels vs the ref backends on the same weights: the largest
# |logit| difference over the real vocab, relative to the largest
# |ref logit|. Both paths keep bf16 activations between ops, so this
# bounds accumulation-order and in-kernel rounding only.
LOGIT_REL_TOL = 2e-2
# ops the served path must bind to Pallas on the chip
PALLAS_OPS = ("fp16_matmul", "flash_attention", "q8_decode_attention")


def expect(ok: bool, what: str) -> None:
    """A smoke check that holds under ``python -O`` too."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def phase_oneshot(model, params, wave, cache_dtype: str, *, max_new: int,
                  chunk_frames: int) -> dict:
    """``repro.transcribe`` twice on one engine: the first call
    compiles, the second runs warm; both must emit the same tokens."""
    from repro.audio.transcribe import transcribe
    kw = dict(model=model, params=params, max_new=max_new,
              chunk_frames=chunk_frames, prompt=PROMPT)
    r, first = _timed(lambda: transcribe(wave, SR, cache_dtype=cache_dtype,
                                         **kw))
    r2, warm = _timed(lambda: transcribe(wave, SR, engine=r.engine, **kw))
    expect(r2.tokens == r.tokens, f"warm call changed tokens: {r.tokens} "
           f"-> {r2.tokens}")
    expect(len(r.tokens) == max_new, f"{len(r.tokens)} tokens, not {max_new}")
    return dict(tokens=r.tokens, first_s=first, warm_s=warm,
                n_frames=r.n_frames, engine=r.engine)


def phase_stream(engine, wave, *, max_new: int, chunk_frames: int,
                 oneshot_tokens) -> dict:
    """The same audio streamed chunk by chunk through the one-shot
    engine: the final transcript must equal the one-shot tokens."""
    from repro.audio.transcribe import transcribe
    kw = dict(engine=engine, max_new=max_new, chunk_frames=chunk_frames,
              prompt=PROMPT, stream=True)
    r, first = _timed(lambda: transcribe(wave, SR, **kw))
    r2, warm = _timed(lambda: transcribe(wave, SR, **kw))
    expect(r.tokens == r2.tokens == list(oneshot_tokens),
           f"stream {r.tokens} / {r2.tokens} != one-shot {oneshot_tokens}")
    return dict(tokens=r.tokens, first_s=first, warm_s=warm,
                n_partials=len(r.partials))


def serve_requests(cfg, seed: int, n: int, seconds: tuple, max_new: int):
    """``n`` audio requests with seeded lengths in ``seconds`` (whole
    seconds) and short prompts; returns [(prompt, frames)]."""
    import numpy as np
    from repro.audio.features import audio_frames
    from repro.audio.stream import synth_waveform
    rng = np.random.default_rng(seed)
    lo, hi = seconds
    out = []
    for i in range(n):
        sec = float(rng.integers(lo, hi + 1))
        wave = synth_waveform(sec, SR, seed=seed + 1 + i)
        frames = np.asarray(audio_frames(wave, cfg.d_model), np.float32)
        prompt = list(PROMPT) + [int(t) for t in
                                 rng.integers(2, cfg.vocab, i % 3)]
        out.append((prompt, frames))
    return out


def phase_serve(model, params, requests, *, n_slots: int,
                decode_block: int, max_new: int, enc_len: int) -> dict:
    """``ServeEngine`` + ``BatchScheduler`` serving ``requests`` twice
    on one engine (compile, then warm); every request must complete
    with ``max_new`` tokens, identically in both rounds."""
    from repro.serving.engine import AudioRequest, ServeEngine
    from repro.serving.scheduler import BatchScheduler
    max_len = max(len(p) for p, _ in requests) + max_new + 2
    engine = ServeEngine(model, params, n_slots=n_slots, max_len=max_len,
                         enc_len=enc_len, decode_block=decode_block)

    def serve_round(base: int):
        sched = BatchScheduler(engine)
        for i, (prompt, frames) in enumerate(requests):
            sched.submit(AudioRequest(uid=base + i, tokens=prompt,
                                      max_new=max_new, eos_id=-1,
                                      enc_frames=frames))
        sched.run_until_drained(strict=True)
        sts = [sched.results[base + i] for i in range(len(requests))]
        bad = [(st.req.uid, st.error) for st in sts
               if st.error or not st.done or len(st.out) != max_new]
        expect(not bad and sched.metrics.completed == len(requests),
               f"requests not served: {bad}")
        return [list(st.out) for st in sts], sched.metrics

    (outs, m1), first = _timed(lambda: serve_round(0))
    (outs2, m2), warm = _timed(lambda: serve_round(len(requests)))
    expect(outs == outs2, "the warm round served different tokens")
    return dict(outputs=outs, first_s=first, warm_s=warm, engine=engine,
                ticks=m2.ticks, tokens=m2.tokens)


def phase_parity(model, params, prompt, frames, got) -> dict:
    """Served tokens vs the slot-free greedy forward, near-tie rule."""
    from repro.serving.reference import assert_greedy_matches, tie_margin
    (matched, secs) = _timed(lambda: assert_greedy_matches(
        model, params, prompt, got, tie_margin(model.cfg),
        enc_frames=frames))
    return dict(matched=matched, of=len(got), first_s=secs)


def phase_logits(model, params, tokens, frames) -> dict:
    """Prefill logits on the bound kernels vs the ``ref`` backends."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.api import DispatchContext, use_context
    batch = {"tokens": jnp.asarray([tokens], jnp.int32),
             "enc_frames": jnp.asarray(frames, jnp.float32)[None]}
    vocab = model.cfg.vocab

    def logits(ctx):
        fwd = jax.jit(lambda p, b: model.forward(p, b, mode="train")[0])
        with use_context(ctx):
            return np.asarray(fwd(params, batch)[0, :, :vocab], np.float32)

    got, first = _timed(lambda: logits(None))
    ref_ctx = DispatchContext(vmem_budget=0, force_backend="ref")
    want, ref_s = _timed(lambda: logits(ref_ctx))
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    return dict(max_abs=err, rel=err / scale, ref_max=scale, first_s=first,
                ref_s=ref_s, finite=bool(np.isfinite(got).all()))


def run_phases(cfg, *, seed: int = 0, audio_s: float = 30.0,
               serve_s: tuple = (5, 30), n_requests: int = 8,
               max_new: int = 32, n_slots: int = 4, decode_block: int = 8,
               stream_chunk: int = STREAM_CHUNK, log=print) -> dict:
    """Every phase at ``cfg``'s widths; raises on the first failure.
    Returns the per-phase records and the dispatch counters of the
    served path (taken before the ``ref`` comparison)."""
    import jax
    from repro.audio.stream import synth_waveform
    from repro.kernels.api import dispatch_counters, reset_dispatch_log
    from repro.models.model import build
    reset_dispatch_log()
    t0 = time.perf_counter()
    model = build(cfg)
    params = model.init_values(jax.random.key(seed))
    wave = synth_waveform(audio_s, SR, seed=seed)
    requests = serve_requests(cfg, seed, n_requests, serve_s, max_new)
    log(f"setup: weights, audio and request features "
        f"{time.perf_counter() - t0:.3f} s")
    rec = {}

    def done(name, r):
        rec[name] = r
        extra = {k: v for k, v in r.items()
                 if k not in ("engine", "outputs", "tokens", "first_s",
                              "warm_s")}
        warm = f", warm {r['warm_s']:.3f} s" if "warm_s" in r else ""
        log(f"phase {name}: first call {r['first_s']:.3f} s{warm} {extra}")

    for tier in ("bf16", "q8_0"):
        done(f"oneshot_{tier}", phase_oneshot(
            model, params, wave, tier, max_new=max_new,
            chunk_frames=stream_chunk))
    done("stream", phase_stream(
        rec["oneshot_bf16"]["engine"], wave, max_new=max_new,
        chunk_frames=stream_chunk,
        oneshot_tokens=rec["oneshot_bf16"]["tokens"]))
    enc_len = max(f.shape[0] for _, f in requests)
    done("serve", phase_serve(model, params, requests, n_slots=n_slots,
                              decode_block=decode_block, max_new=max_new,
                              enc_len=enc_len))
    counters = dispatch_counters()
    prompt, frames = requests[-1]
    got = rec["serve"]["outputs"][-1]
    done("parity", phase_parity(model, params, prompt, frames, got))
    done("logits", phase_logits(model, params, list(prompt) + got[:-1],
                                frames))
    expect(rec["logits"]["finite"], "non-finite logits")
    rec["counters"] = counters
    rec["engines"] = [rec["oneshot_bf16"]["engine"],
                      rec["oneshot_q8_0"]["engine"], rec["serve"]["engine"]]
    return rec


def check_binding(counters, contexts) -> list:
    """Problems with where the served path ran: an op bound to ``ref``,
    a Pallas context in interpret mode, or a main-path op that never
    bound to Pallas."""
    bad = [f"{op} bound to ref ({dec})" for (op, dec, be) in counters
           if be == "ref"]
    bad += [f"context {c} runs Pallas in interpret mode"
            for c in contexts if c.interpret]
    bound = {op for (op, _, be) in counters if be == "pallas"}
    bad += [f"{op} never bound to pallas" for op in PALLAS_OPS
            if op not in bound]
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
          f"count {len(devs)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; this run needs the chip",
              file=sys.stderr)
        return 2

    from repro import flags
    from repro.configs import get_config
    from repro.kernels.api import DispatchContext
    print(f"compilation cache: {flags.use_compile_cache()}", flush=True)
    cfg = get_config(ARCH)
    print(f"model: {cfg.name} n_layers={cfg.n_layers} "
          f"enc_layers={cfg.enc_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}x{cfg.head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} seed={args.seed}", flush=True)
    t0 = time.perf_counter()
    rec = run_phases(cfg, seed=args.seed,
                     log=lambda s: print(s, flush=True))
    by_backend = collections.defaultdict(int)
    for (op, dec, be), n in sorted(rec["counters"].items()):
        print(f"dispatch ({op}, {dec}, {be}): {n}")
        by_backend[be] += n
    contexts = [DispatchContext.from_env()] + [
        e.dispatch_ctx for e in rec["engines"] if e.dispatch_ctx]
    bad = check_binding(rec["counters"], contexts)
    lg = rec["logits"]
    print(f"logits vs ref: max abs err {lg['max_abs']:.6g}, relative "
          f"{lg['rel']:.6g} (max |ref| {lg['ref_max']:.6g}, tolerance "
          f"{LOGIT_REL_TOL})")
    if lg["rel"] > LOGIT_REL_TOL:
        bad.append(f"logits differ from ref by {lg['rel']:.4g} "
                   f"> {LOGIT_REL_TOL}")
    print(f"stream == one-shot: {rec['stream']['tokens'] == rec['oneshot_bf16']['tokens']}; "
          f"engine vs slot-free greedy: {rec['parity']['matched']}/"
          f"{rec['parity']['of']} tokens before any near-tie")
    print(f"total {time.perf_counter() - t0:.1f} s")
    if bad:
        for b in bad:
            print(f"FAIL: {b}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
