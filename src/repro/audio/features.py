"""Whisper-style log-mel frontend in pure JAX + a NumPy golden reference.

Pipeline (``audio_frames`` = the whole thing):

  samples (float32, 16 kHz) --frame/Hann/RFFT--> power spectrum
          --mel filterbank (dispatched matmul)--> mel energies
          --log10 + fixed-reference clamp + /4 norm--> log-mel (T, n_mels)
          --stride-2 pool + fixed cosine projection + GELU-->
          frame embeddings (T//2, d_model)  [the encoder's ``enc_frames``]

Two deliberate deviations from OpenAI Whisper, both forced by streaming:

* **no center padding** — frames start at ``t * hop`` and read
  ``n_fft`` samples forward, so a frame is final as soon as its window
  has arrived; the tail frame is zero-padded (flush);
* **fixed-reference normalization** — Whisper clamps at
  ``log_spec.max() - 8`` over the whole utterance, which needs the
  future; we clamp at the fixed floor ``-8`` (i.e. assume a 0 dBFS
  reference), so streaming and one-shot extraction are sample-exact.

The mel-filterbank application and the d_model projection are routed
through ``dispatch("fp16_matmul", ..., tag="frontend")`` so the
ACCEL/HOST control law and the energy/dispatch accounting see the
frontend GEMMs like every other kernel in the model.

The conv2 stem of real Whisper is replaced by a *deterministic* cosine
projection (this repo serves randomly-initialized reproductions — there
are no trained frontend weights to load); the stride-2 temporal pooling
keeps Whisper's 2x frame-rate reduction so ``enc_frames`` counts match
the paper's workload model (1500 frames per 30 s window).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.kernels.api import dispatch

SAMPLE_RATE = 16_000

LOG_FLOOR = -8.0       # fixed dynamic-range floor (log10 units)
MEL_EPS = 1e-10


@dataclasses.dataclass(frozen=True)
class FrontendConfig:
    """Whisper's frontend constants (25 ms window / 10 ms hop at 16 kHz)."""

    sample_rate: int = SAMPLE_RATE
    n_fft: int = 400
    hop: int = 160
    n_mels: int = 80
    fmin: float = 0.0
    fmax: Optional[float] = None   # None -> sample_rate / 2
    stride: int = 2                # temporal pooling of the conv-stem stand-in

    @property
    def n_freq(self) -> int:
        return self.n_fft // 2 + 1

    def n_frames(self, n_samples: int) -> int:
        """Mel frames for ``n_samples``: one per started hop (tail padded)."""
        return -(-n_samples // self.hop) if n_samples > 0 else 0

    def n_embed_frames(self, n_samples: int) -> int:
        """Frame embeddings after the stride-``stride`` pooling."""
        return -(-self.n_frames(n_samples) // self.stride)


def frame_starts(n_samples: int, cfg: FrontendConfig) -> np.ndarray:
    """Sample offset of each mel frame (frame t covers
    ``[t*hop, t*hop + n_fft)``; the tail is zero-padded)."""
    return np.arange(cfg.n_frames(n_samples)) * cfg.hop


def hann_window(n: int) -> np.ndarray:
    """Periodic Hann window (what torch.hann_window/Whisper uses)."""
    return (0.5 - 0.5 * np.cos(2.0 * np.pi * np.arange(n) / n)) \
        .astype(np.float32)


@functools.lru_cache(maxsize=8)
def _mel_filterbank_cached(n_mels: int, n_fft: int, sr: int, fmin: float,
                           fmax: float) -> np.ndarray:
    def hz_to_mel(f):
        return 2595.0 * np.log10(1.0 + np.asarray(f, np.float64) / 700.0)

    def mel_to_hz(m):
        return 700.0 * (10.0 ** (np.asarray(m, np.float64) / 2595.0) - 1.0)

    pts = mel_to_hz(np.linspace(hz_to_mel(fmin), hz_to_mel(fmax),
                                n_mels + 2))
    freqs = np.linspace(0.0, sr / 2.0, n_fft // 2 + 1)
    fb = np.zeros((n_fft // 2 + 1, n_mels), np.float64)
    for m in range(n_mels):
        lo, center, hi = pts[m], pts[m + 1], pts[m + 2]
        up = (freqs - lo) / max(center - lo, 1e-9)
        down = (hi - freqs) / max(hi - center, 1e-9)
        tri = np.maximum(0.0, np.minimum(up, down))
        fb[:, m] = tri * (2.0 / max(hi - lo, 1e-9))   # slaney area norm
    return fb.astype(np.float32)


def mel_filterbank(cfg: FrontendConfig) -> np.ndarray:
    """(n_freq, n_mels) triangular HTK-mel filterbank, slaney-normalized."""
    fmax = cfg.fmax if cfg.fmax is not None else cfg.sample_rate / 2.0
    return _mel_filterbank_cached(cfg.n_mels, cfg.n_fft, cfg.sample_rate,
                                  float(cfg.fmin), float(fmax))


def _frame_signal_np(samples: np.ndarray, cfg: FrontendConfig) -> np.ndarray:
    """(T, n_fft) frame matrix; the last frame is zero-padded. Input of
    any shape is flattened first ((1, N)/(N, 1) loader outputs frame
    identically to (N,))."""
    x = np.asarray(samples, np.float32).reshape(-1)
    t = cfg.n_frames(len(x))
    if t == 0:
        return np.zeros((0, cfg.n_fft), np.float32)
    need = (t - 1) * cfg.hop + cfg.n_fft
    if need > len(x):
        x = np.pad(x, (0, need - len(x)))
    idx = (np.arange(t) * cfg.hop)[:, None] + np.arange(cfg.n_fft)
    return x[idx]


def log_mel(samples, cfg: FrontendConfig = FrontendConfig()) -> jnp.ndarray:
    """Log-mel spectrogram (T, n_mels), float32 — the JAX frontend.

    Framing/window/RFFT run row-independent (each output frame depends
    only on its own sample window), so streaming extraction is exact.
    The mel matmul routes through the kernel-dispatch API.
    """
    frames = jnp.asarray(_frame_signal_np(samples, cfg))
    if frames.shape[0] == 0:
        return jnp.zeros((0, cfg.n_mels), jnp.float32)
    win = jnp.asarray(hann_window(cfg.n_fft))
    spec = jnp.fft.rfft(frames * win[None, :], axis=-1)
    power = (jnp.abs(spec) ** 2).astype(jnp.float32)
    mel = dispatch("fp16_matmul", power, jnp.asarray(mel_filterbank(cfg)),
                   out_dtype=jnp.float32, tag="frontend")
    log_spec = jnp.log10(jnp.maximum(mel, MEL_EPS))
    log_spec = jnp.maximum(log_spec, LOG_FLOOR)
    return ((log_spec + 4.0) / 4.0).astype(jnp.float32)


def log_mel_ref(samples, cfg: FrontendConfig = FrontendConfig()) -> np.ndarray:
    """NumPy golden reference for ``log_mel`` (same math, np.fft)."""
    frames = _frame_signal_np(samples, cfg)
    if frames.shape[0] == 0:
        return np.zeros((0, cfg.n_mels), np.float32)
    spec = np.fft.rfft(frames * hann_window(cfg.n_fft)[None, :], axis=-1)
    power = (np.abs(spec) ** 2).astype(np.float32)
    mel = power @ mel_filterbank(cfg)
    log_spec = np.log10(np.maximum(mel, MEL_EPS))
    log_spec = np.maximum(log_spec, LOG_FLOOR)
    return ((log_spec + 4.0) / 4.0).astype(np.float32)


@functools.lru_cache(maxsize=8)
def _cosine_projection(n_mels: int, d_model: int) -> np.ndarray:
    """Deterministic (n_mels, d_model) DCT-like projection — the
    conv-stem stand-in's mixing matrix (no trained weights exist)."""
    m = np.arange(n_mels, dtype=np.float64)[:, None]
    j = np.arange(d_model, dtype=np.float64)[None, :]
    p = np.cos(np.pi * (m + 0.5) * (j + 1.0) / n_mels)
    return (p * math.sqrt(2.0 / n_mels)).astype(np.float32)


def mel_to_frames(logmel, d_model: int,
                  cfg: FrontendConfig = FrontendConfig()) -> jnp.ndarray:
    """Log-mel (T, n_mels) -> encoder frame embeddings (ceil(T/stride),
    d_model): stride-mean temporal pooling (Whisper's conv2 stride-2
    frame-rate halving) then the fixed cosine projection + GELU. The
    projection GEMM is dispatched, tagged ``frontend``. Row-independent
    in pooled-frame units, so streaming emission is exact."""
    x = jnp.asarray(logmel, jnp.float32)
    t = x.shape[0]
    s = cfg.stride
    tp = -(-t // s) if t else 0
    if tp * s > t:
        x = jnp.pad(x, ((0, tp * s - t), (0, 0)))
    if tp == 0:
        return jnp.zeros((0, d_model), jnp.float32)
    pooled = x.reshape(tp, s, cfg.n_mels).mean(axis=1)
    proj = jnp.asarray(_cosine_projection(cfg.n_mels, d_model))
    y = dispatch("fp16_matmul", pooled, proj, out_dtype=jnp.float32,
                 tag="frontend")
    return jax.nn.gelu(y, approximate=False).astype(jnp.float32)


def audio_frames(samples, d_model: int,
                 cfg: FrontendConfig = FrontendConfig()) -> jnp.ndarray:
    """samples -> (n_embed_frames, d_model) encoder frame embeddings:
    the full frontend (``log_mel`` then ``mel_to_frames``). Its span,
    ``frontend.frames``, is the host time of the call: it dispatches the
    frontend's operations and does not wait for the device."""
    with tracing.span("frontend.frames"):
        return mel_to_frames(log_mel(samples, cfg), d_model, cfg)


def resample_linear(samples, sr_in: int, sr_out: int) -> np.ndarray:
    """Cheap linear-interpolation resampler (NumPy) so ``transcribe``
    accepts non-16 kHz input; use a real resampler for quality."""
    x = np.asarray(samples, np.float32).reshape(-1)
    if sr_in == sr_out or len(x) == 0:
        return x
    n_out = int(round(len(x) * sr_out / sr_in))
    t_out = np.arange(n_out, dtype=np.float64) * (sr_in / sr_out)
    return np.interp(t_out, np.arange(len(x), dtype=np.float64),
                     x).astype(np.float32)
