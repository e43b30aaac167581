"""One generator for every traffic mix, driven by the mix's parameters.

A mix file (``mixes/<name>.json``) names its ``kind``:

* ``closed`` — batch transcription: a pool of windows, in blocks of
  ``block`` that each hold the same work, that a client keeps
  ``outstanding_per_slot * n_slots`` of in flight, submitting the next
  as soon as one completes;
* ``open``   — one-shot utterances with Poisson arrivals at
  ``rate_per_s``;
* ``stream`` — live sessions with Poisson arrivals, each fed in
  ``chunk_s`` chunks at wall-clock pace.

The amount of work is fixed by the mix's ``work_seed``: the multiset of
audio lengths, prompt lengths, output budgets and inter-arrival gaps is
the same for every run seed. The run seed orders that work (unless the
mix sets ``fixed_order``) and draws the content (waveforms, token ids),
so two seeds give the same load in another order. The same seed always
gives the same requests.

Times are seconds relative to the start of the measured window; work
due before 0 is the lead-in that set-up runs.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np

SAMPLE_RATE = 16_000


@dataclasses.dataclass(frozen=True)
class Req:
    """One request (or one streaming session)."""

    idx: int
    audio_s: float              # seconds of audio
    prompt: tuple               # decoder prompt token ids
    max_new: int                # output budget (decoded exactly: no EOS)
    due: Optional[float]        # when it is due (None: closed loop)
    wave_seed: int              # seeds the waveform


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(w) & 0xFFFFFFFF for w in words]
        + [int(w) >> 32 for w in words]))


def waveform(audio_s: float, seed: int) -> np.ndarray:
    """A speech-like test signal at 16 kHz: voiced harmonics under a
    syllable-rate envelope, plus noise. Deterministic in ``seed``."""
    rng = _rng(seed, 7)
    n = int(round(audio_s * SAMPLE_RATE))
    t = np.arange(n, dtype=np.float64) / SAMPLE_RATE
    f0 = rng.uniform(90.0, 220.0) * (1.0 + 0.1 * np.sin(
        2 * np.pi * rng.uniform(0.2, 0.6) * t))
    phase = 2 * np.pi * np.cumsum(f0) / SAMPLE_RATE
    voiced = sum(np.sin(k * phase) / k for k in range(1, 8))
    env = 0.5 + 0.5 * np.sin(2 * np.pi * rng.uniform(3.0, 5.0) * t
                             + rng.uniform(0, 2 * np.pi))
    x = 0.08 * env * voiced + 0.01 * rng.standard_normal(n)
    return x.astype(np.float32)


def _prompt(cfg: dict, n_prev: int, rng: np.random.Generator) -> tuple:
    """Whisper's decoder prompt: ``<|startofprev|>`` + the previous
    window's text (when there is any) + the start-of-transcript
    sequence."""
    p = cfg["prompt"]
    prev = ()
    if n_prev:
        prev = (p["startofprev"],) + tuple(
            int(t) for t in rng.integers(0, p["text_tokens"], n_prev))
    return prev + tuple(p["sot_sequence"])


def _gaps(rate: float, n: int, span: float, work: np.random.Generator,
          order) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at ``rate``, scaled to sum to
    ``span``, in the order the run seed gives them (``order``; None
    keeps the work seed's order)."""
    g = work.exponential(1.0 / rate, size=n)
    g *= span / g.sum()
    return g if order is None else order.permutation(g)


def generate(mix: dict, cfg: dict, seed: int, seconds: float) -> list:
    """The requests of one run: ``mix`` parameters, configuration
    ``cfg`` (its ``prompt`` block), run ``seed``, window ``seconds``."""
    kind = mix["kind"]
    work = _rng(mix["work_seed"])
    order = _rng(seed, 1)
    content = _rng(seed, 2)
    out = []
    if kind == "closed":
        # the pool is blocks of ``block`` windows, each block the same
        # multiset in its own order: the work a run gets through in its
        # window hardly depends on the seed
        block = mix["block"]
        lo, hi = mix["prev_text_tokens"]
        n_prev = work.integers(lo, hi + 1, block)
        lo, hi = mix["new_tokens"]
        n_new = work.integers(lo, hi + 1, block)
        perm = np.concatenate([order.permutation(block)
                               for _ in range(mix["pool"] // block)])
        for i, j in enumerate(perm):
            out.append(Req(i, float(mix["window_s"]),
                           _prompt(cfg, int(n_prev[j]), content),
                           int(n_new[j]), None,
                           int(content.integers(0, 2**62))))
        return out
    lead = float(mix["lead_s"])
    # an open loop's tails turn on which long requests arrive close
    # together: with ``fixed_order`` the run seed draws the content only
    if mix.get("fixed_order"):
        order = None
    if kind == "open":
        span = lead + seconds
        n = max(1, round(mix["rate_per_s"] * span))
        grid = np.asarray(mix["seconds_grid"], np.float64)
        w = np.asarray(mix["seconds_weights"], np.float64)
        lengths = work.choice(grid, size=n, p=w / w.sum())
        if order is not None:
            lengths = order.permutation(lengths)
        due = np.cumsum(_gaps(mix["rate_per_s"], n, span, work, order)) \
            - lead
        per_s = mix["tokens_per_audio_s"]
        for i in range(n):
            a = float(lengths[i])
            out.append(Req(i, a, _prompt(cfg, 0, content),
                           int(math.ceil(per_s * a)), float(due[i]),
                           int(content.integers(0, 2**62))))
        return out
    if kind == "stream":
        sess = float(mix["session_s"])
        # sessions start from one session length before the lead-in, so
        # the window opens on sessions at every stage of progress
        span = sess + lead + seconds
        n = max(1, round(mix["rate_per_s"] * span))
        start = np.cumsum(_gaps(mix["rate_per_s"], n, span, work, order)) \
            - sess - lead
        for i in range(n):
            out.append(Req(i, sess, _prompt(cfg, 0, content),
                           int(mix["new_tokens"]), float(start[i]),
                           int(content.integers(0, 2**62))))
        return out
    raise ValueError(f"unknown traffic kind {kind!r}")


def chunks_of(req: Req, chunk_s: float) -> list:
    """``(due, t0_s, t1_s)`` of each chunk of a streaming session: chunk
    ``i`` covers audio ``[t0_s, t1_s)`` and is due when its last sample
    has been spoken."""
    n = int(round(req.audio_s / chunk_s))
    return [(req.due + (i + 1) * chunk_s, i * chunk_s, (i + 1) * chunk_s)
            for i in range(n)]
