"""One generator for every traffic mix, driven by the mix's parameters.

A mix file (``mixes/<name>.json``) names its ``kind``:

* ``closed`` — batch work: a pool of requests, in blocks of ``block``
  that each hold the same work, that a client keeps
  ``outstanding_per_slot * n_slots`` of in flight, submitting the next
  as soon as one completes;
* ``open``   — one-shot requests with Poisson arrivals at
  ``rate_per_s``; with ``burst_period_s`` and ``burst_duty`` they come
  in on/off bursts: Poisson at ``rate_per_s / burst_duty`` in the first
  ``burst_duty`` of each period (periods count from the start of the
  lead-in) and none in the rest, so the mean rate stays ``rate_per_s``
  (the count is ``rate_per_s`` times the span, whole periods or not);
* ``stream`` — live sessions with Poisson arrivals, each fed in
  ``chunk_s`` chunks at wall-clock pace.

Requests are audio (``window_s`` or ``seconds_grid``) unless the mix
gives ``prompt_tokens`` and ``new_tokens``, each a ``{"grid": [...],
"weights": [...]}`` of lengths: then they carry no audio, and their
prompts are that long. The family's ``prompt(cfg, n, rng)`` makes the
prompt ids.

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


@dataclasses.dataclass(frozen=True)
class Req:
    """One request (or one streaming session)."""

    idx: int
    audio_s: float              # seconds of audio (0: a text request)
    prompt: tuple               # prompt token ids
    max_new: int                # output budget (decoded exactly: no EOS)
    due: Optional[float]        # when it is due (None: closed loop)
    wave_seed: int              # seeds the waveform (0: a text request)


def seeded(*words: int) -> np.random.Generator:
    """A generator seeded by whole numbers of any size."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(w) & 0xFFFFFFFF for w in words]
        + [int(w) >> 32 for w in words]))


def _lengths(work: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths drawn from ``spec``'s ``grid`` by its ``weights``."""
    w = np.asarray(spec["weights"], np.float64)
    return work.choice(np.asarray(spec["grid"], np.int64), size=n,
                       p=w / w.sum())


def _gaps(rate: float, n: int, span: float, work: np.random.Generator,
          order) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at ``rate``, scaled to sum to
    ``span``, in the order the run seed gives them (``order``; None
    keeps the work seed's order)."""
    g = work.exponential(1.0 / rate, size=n)
    g *= span / g.sum()
    return g if order is None else order.permutation(g)


def _arrivals(mix: dict, n: int, span: float, work: np.random.Generator,
              order) -> np.ndarray:
    """Arrival times of ``n`` requests over ``span`` seconds from its
    start: Poisson, or Poisson inside the on-phases of ``mix``'s bursts
    (the gaps are drawn over the on-time the span holds, the last
    period's part included, and laid into the periods)."""
    rate = mix["rate_per_s"]
    if "burst_period_s" not in mix:
        return np.cumsum(_gaps(rate, n, span, work, order))
    period, duty = float(mix["burst_period_s"]), float(mix["burst_duty"])
    if not 0.0 < duty <= 1.0 or period <= 0.0:
        raise ValueError(f"bursts need 0 < burst_duty <= 1 and "
                         f"burst_period_s > 0, not {duty}, {period}")
    on = duty * period
    whole, part = divmod(span, period)
    on_time = whole * on + min(part, on)
    t = np.cumsum(_gaps(rate / duty, n, on_time, work, order))
    # on-time [k on, (k + 1) on) lies at [k period, k period + on); the
    # last on-phase, whole or part, also takes the span's end
    k = np.minimum(np.floor(t / on), whole + (part > 0) - 1)
    return k * period + (t - k * on)


def generate(mix: dict, cfg: dict, prompt, seed: int,
             seconds: float) -> list:
    """The requests of one run: ``mix`` parameters, configuration
    ``cfg``, the family's ``prompt(cfg, n, rng)``, run ``seed``, window
    ``seconds``."""
    kind = mix["kind"]
    text = "prompt_tokens" in mix
    if text and kind == "stream":
        raise ValueError("a stream mix carries audio: it takes no "
                         "prompt_tokens")
    work = seeded(mix["work_seed"])
    order = seeded(seed, 1)
    content = seeded(seed, 2)
    out = []
    if kind == "closed":
        # the pool is blocks of ``block`` requests, each block the same
        # multiset in its own order: the work a run gets through in its
        # window hardly depends on the seed
        block = mix["block"]
        if text:
            n_prompt = _lengths(work, mix["prompt_tokens"], block)
            n_new = _lengths(work, mix["new_tokens"], block)
        else:
            lo, hi = mix["prev_text_tokens"]
            n_prompt = work.integers(lo, hi + 1, block)
            lo, hi = mix["new_tokens"]
            n_new = work.integers(lo, hi + 1, block)
        perm = np.concatenate([order.permutation(block)
                               for _ in range(mix["pool"] // block)])
        for i, j in enumerate(perm):
            p = prompt(cfg, int(n_prompt[j]), content)
            if text:
                out.append(Req(i, 0.0, p, int(n_new[j]), None, 0))
            else:
                out.append(Req(i, float(mix["window_s"]), p, int(n_new[j]),
                               None, int(content.integers(0, 2**62))))
        return out
    lead = float(mix["lead_s"])
    # an open loop's tails turn on which long requests arrive close
    # together: with ``fixed_order`` the run seed draws the content only
    if mix.get("fixed_order"):
        order = None
    if kind == "open":
        span = lead + seconds
        n = max(1, round(mix["rate_per_s"] * span))
        if text:
            n_prompt = _lengths(work, mix["prompt_tokens"], n)
            n_new = _lengths(work, mix["new_tokens"], n)
            if order is not None:
                perm = order.permutation(n)
                n_prompt, n_new = n_prompt[perm], n_new[perm]
        else:
            grid = np.asarray(mix["seconds_grid"], np.float64)
            w = np.asarray(mix["seconds_weights"], np.float64)
            lengths = work.choice(grid, size=n, p=w / w.sum())
            if order is not None:
                lengths = order.permutation(lengths)
        due = _arrivals(mix, n, span, work, order) - lead
        for i in range(n):
            if text:
                out.append(Req(i, 0.0, prompt(cfg, int(n_prompt[i]),
                                              content),
                               int(n_new[i]), float(due[i]), 0))
                continue
            a = float(lengths[i])
            out.append(Req(i, a, prompt(cfg, 0, content),
                           int(math.ceil(mix["tokens_per_audio_s"] * a)),
                           float(due[i]), int(content.integers(0, 2**62))))
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
            out.append(Req(i, sess, prompt(cfg, 0, content),
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
