"""``repro.transcribe``: samples in, tokens out — the paper's full ASR
workload (log-mel frontend -> chunked encoder -> continuous-batching
decoder) in one call, with platform-aware dispatch and energy
accounting.

The repo serves *randomly-initialized* reproductions of the paper's
models (there are no trained checkpoints), so the emitted token ids are
not human text — what this API exercises end to end is the compute
pipeline the paper measures: every frontend GEMM, encoder chunk,
cross-K/V extension, and decode tick routes through the kernel-dispatch
control law, and ``TranscribeResult.energy`` carries the platform's
joules-per-audio-second.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import numpy as np

from repro.audio.features import (FrontendConfig, audio_frames,
                                  resample_linear)
from repro.audio.stream import chunk_list
from repro.configs import get_config
from repro.configs import reduced as reduced_cfg
from repro.models.model import build
from repro.serving.engine import (AudioRequest, ServeEngine,
                                  StreamingAudioRequest)
from repro.serving.scheduler import BatchScheduler

DEFAULT_PROMPT = (1,)        # stand-in for whisper's <|sot|> sequence
DEFAULT_CHUNK_FRAMES = 16    # encoder chunk (frame embeddings) for streaming


@dataclasses.dataclass
class TranscribeResult:
    """What one transcription produced and what it cost."""

    tokens: list                     # final transcript token ids
    partials: list                   # streaming: one hypothesis per chunk
    audio_s: float                   # seconds of input audio
    n_frames: int                    # encoder frame embeddings consumed
    ticks: int                       # fused decode ticks executed
    wall_s: float                    # serve wall time (incl. jit on first use)
    compute_ms_per_audio_s: float    # wall_s / audio_s * 1000
    platform: Optional[str]
    cache_dtype: str
    energy: Optional[dict]           # energy_report + joules_per_audio_s
    decode_block: int = 1            # decode steps fused per tick
    decode_steps: int = 0            # executed decode steps (ticks x block)
    host_syncs: int = 0              # device->host fetches on the decode path
    engine: Any = dataclasses.field(default=None, repr=False)

    @property
    def text(self) -> str:
        """Space-joined token ids (no trained tokenizer exists here)."""
        return " ".join(str(t) for t in self.tokens)


def _default_model(arch: str, reduced: bool, seed: int):
    cfg = get_config(arch)
    if reduced:
        cfg = reduced_cfg(cfg)
    model = build(cfg)
    return model, model.init_values(jax.random.key(seed))


def transcribe(samples, sr: int = 16_000, *,
               arch: str = "whisper-tiny-en", reduced: bool = True,
               model=None, params=None,
               platform: Optional[str] = None,
               cache_dtype: Optional[str] = None,
               decode_block: Optional[int] = None,
               chunk_frames: int = DEFAULT_CHUNK_FRAMES,
               prompt=DEFAULT_PROMPT, max_new: int = 16,
               eos_id: int = -1, stream: bool = False,
               frontend: Optional[FrontendConfig] = None,
               seed: int = 0, engine: Optional[ServeEngine] = None
               ) -> TranscribeResult:
    """Transcribe one waveform end to end.

    ``samples``: float waveform at ``sr`` Hz (resampled to the frontend
    rate if needed). ``platform`` (a ``repro.platforms`` name) derives
    the dispatch context and enables the energy report. ``stream=True``
    serves through the chunk-at-a-time streaming path (one chunk per
    scheduler tick, partial hypotheses in ``result.partials``); the
    final tokens are identical to ``stream=False`` on the same audio.
    ``decode_block`` fuses that many decode steps per engine tick (one
    host sync per tick — tokens are identical for any block size).
    Pass ``engine=`` (e.g. ``result.engine`` from a previous call with
    the same shapes) to reuse compiled prefill/decode functions; the
    reused engine's platform/cache policy apply (conflicting explicit
    ``platform``/``cache_dtype`` arguments raise; ``decode_block`` is a
    mutable knob and simply retunes the reused engine), and the serve
    stats are reset so ticks/energy in the result cover this call only.
    """
    if decode_block is not None and int(decode_block) < 1:
        raise ValueError(f"decode_block must be >= 1, got {decode_block}")
    fe = frontend or FrontendConfig()
    x = resample_linear(samples, sr, fe.sample_rate)
    audio_s = len(x) / fe.sample_rate
    if engine is not None:
        # the reused engine's model is the truth: frames must match its
        # width whatever arch/reduced say
        model, params = engine.model, engine.params
    elif model is None or params is None:
        model, params = _default_model(arch, reduced, seed)
    if not model.cfg.enc_dec:
        raise ValueError(f"transcribe needs an enc-dec (audio) model; "
                         f"{model.cfg.name} is {model.cfg.family}")
    frames = np.asarray(audio_frames(x, model.cfg.d_model, fe))
    if frames.shape[0] == 0:
        raise ValueError(
            f"audio too short: {len(x)} samples produce no frames "
            f"(need >= 1 hop = {fe.hop} samples)")
    chunks = chunk_list(frames, chunk_frames)
    n_frames = frames.shape[0]
    if engine is None:
        cache_dtype = cache_dtype or "bf16"
        engine = ServeEngine(
            model, params, n_slots=1,
            max_len=len(prompt) + max_new + 2, enc_len=n_frames,
            cache_dtype=cache_dtype, decode_block=decode_block or 1,
            platform=platform)
    else:
        # the reused engine's policies are the truth — refuse silent
        # mismatches with explicitly requested ones
        if cache_dtype is not None and cache_dtype != engine.cache_dtype:
            raise ValueError(
                f"cache_dtype={cache_dtype!r} conflicts with the reused "
                f"engine's {engine.cache_dtype!r}")
        if platform is not None:
            from repro.platforms import get_platform
            want = get_platform(platform).name
            have = engine.platform.name if engine.platform else None
            if want != have:
                raise ValueError(
                    f"platform={platform!r} conflicts with the reused "
                    f"engine's {have!r}")
        cache_dtype = engine.cache_dtype
        if decode_block is not None:
            engine.decode_block = int(decode_block)
    engine.reset_serve_stats()
    t0 = time.monotonic()
    if stream:
        sched = BatchScheduler(engine)
        req = StreamingAudioRequest(uid=0, tokens=list(prompt),
                                    max_new=max_new, eos_id=eos_id,
                                    chunks=chunks)
        sched.submit(req)
        sched.run_until_drained()
        st = sched.results[0]
        if st.error:
            raise ValueError(st.error)
    else:
        states = engine.encode_chunks(chunks)
        st = engine.admit(AudioRequest(uid=0, tokens=list(prompt),
                                       max_new=max_new, eos_id=eos_id,
                                       enc_states=states[0]))
        while engine.n_active:
            engine.step()
    wall = time.monotonic() - t0
    energy = None
    if engine.platform is not None:
        energy = engine.energy_report("fp16")
        energy["joules_per_audio_s"] = \
            energy["pdp_j"] / max(audio_s, 1e-9)
    return TranscribeResult(
        tokens=list(st.out), partials=[list(p) for p in st.partials],
        audio_s=audio_s, n_frames=n_frames, ticks=engine._ticks,
        wall_s=wall,
        compute_ms_per_audio_s=wall / max(audio_s, 1e-9) * 1e3,
        platform=engine.platform.name if engine.platform else None,
        cache_dtype=cache_dtype, energy=energy,
        decode_block=engine.decode_block,
        decode_steps=engine._decode_steps, host_syncs=engine._host_syncs,
        engine=engine)
