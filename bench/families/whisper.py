"""Whisper: encoder-decoder speech recognition, served one audio clip
per request.

What of the benchmark depends on the model family (``spec.family_module``
lists it): the configuration holds the published ``config.json`` keys,
the deployment and the tokenizer's ``prompt`` ids. A request's audio is
a slice of the run's waveform bank; it passes through the frontend
(``audio_frames``, the harness span ``frontend``) once it is due, inside
the client coroutine, so the frontend is on the timed path. The plain
reference is ``reference.py``; the model FLOPs are ``flops.py``'s.
"""

from __future__ import annotations

import asyncio
import time
from typing import Optional

import numpy as np

import model as bench_model

SAMPLE_RATE = 16_000
BANK = 8             # distinct base waveforms per run
BANK_S = 32.0        # seconds of each
FRAMES_PER_S = 50    # encoder positions per second of audio


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a Whisper ``config.json``."""
    from repro.configs import ArchConfig
    hf = cfg["config"]
    if hf["encoder_attention_heads"] != hf["decoder_attention_heads"] or \
            hf["encoder_ffn_dim"] != hf["decoder_ffn_dim"]:
        raise ValueError("the program shares one head count and one FFN "
                         "width between encoder and decoder")
    return ArchConfig(
        name=cfg["name"], family="audio", enc_dec=True,
        n_layers=hf["decoder_layers"], enc_layers=hf["encoder_layers"],
        d_model=hf["d_model"], n_heads=hf["decoder_attention_heads"],
        n_kv_heads=hf["decoder_attention_heads"],
        d_ff=hf["decoder_ffn_dim"], vocab=hf["vocab_size"],
        act=hf["activation_function"], tie_embeddings=True,
        source=cfg["source"])


def init_leaf(path: str, shape, key):
    """Draw one leaf. Matrices are normal with variance 1/fan_in; norm
    scales sit near 1 and biases near 0, so that a norm applied wrong
    changes the output."""
    import jax
    name = bench_model.leaf_name(path)
    if name == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape)
    if name in ("bias", "dec_pos"):
        return 0.02 * jax.random.normal(key, shape)
    if name == "wo":
        fan_in = shape[-3] * shape[-2]
    elif name in ("wq", "wk", "wv"):
        fan_in = shape[-3]
    elif name == "table":
        fan_in = shape[-1]
    else:
        fan_in = shape[-2]
    return jax.random.normal(key, shape) * fan_in ** -0.5


def engine_options(deployment: dict) -> dict:
    return {key: deployment[key]
            for key in ("n_slots", "max_len", "enc_len", "cache_dtype")}


def prompt(cfg: dict, n_prev: int, rng: np.random.Generator) -> tuple:
    """Whisper's decoder prompt: ``<|startofprev|>`` + the previous
    window's text (when there is any) + the start-of-transcript
    sequence."""
    p = cfg["prompt"]
    prev = ()
    if n_prev:
        prev = (p["startofprev"],) + tuple(
            int(t) for t in rng.integers(0, p["text_tokens"], n_prev))
    return prev + tuple(p["sot_sequence"])


# ------------------------------------------------------------------ audio
def waveform(audio_s: float, seed: int) -> np.ndarray:
    """A speech-like test signal at 16 kHz: voiced harmonics under a
    syllable-rate envelope, plus noise. Deterministic in ``seed``."""
    import traffic
    rng = traffic.seeded(seed, 7)
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


class Bank:
    """The run's waveforms: ``BANK`` base signals from the seed; a
    request reads a slice chosen by its ``wave_seed``."""

    def __init__(self, seed: int):
        self.waves = [waveform(BANK_S, seed * BANK + i) for i in range(BANK)]

    def audio(self, req, t0_s: float = 0.0,
              t1_s: Optional[float] = None) -> np.ndarray:
        w = self.waves[req.wave_seed % BANK]
        n = int(round(req.audio_s * SAMPLE_RATE))
        room = len(w) - n
        off = (req.wave_seed // BANK) % (room + 1)
        a = off + int(round(t0_s * SAMPLE_RATE))
        b = off + (n if t1_s is None else int(round(t1_s * SAMPLE_RATE)))
        return w[a:b]


def inputs(seed: int) -> Bank:
    return Bank(seed)


def _frames(runner, wave):
    """The frontend on the timed path, in the harness span ``frontend``,
    ended when the frames are ready."""
    from repro.audio.features import audio_frames
    with runner.spans.span("frontend"):
        fr = audio_frames(wave, runner.cfg["config"]["d_model"])
        fr.block_until_ready()
    return fr


# --------------------------------------------------------------- requests
async def submit(runner, req, max_new: int):
    fr = _frames(runner, runner.inputs.audio(req))
    # the frames stay alive while registered, so their id is the lane's
    with runner.in_flight(id(fr), req):
        return await runner.gw.submit_audio(
            fr, tokens=list(req.prompt), eos_id=-1, slo=runner.slo,
            max_new=max_new, audio_s=req.audio_s)


def lane_key(request):
    fr = getattr(request, "enc_frames", None)
    return None if fr is None else id(fr)


async def session(runner, req, t_base: Optional[float], *,
                  paced: bool = True, max_new=None, keep: bool = True):
    """One streaming session: open, feed each chunk once it is due (all
    at once when not ``paced``), finalize. A session cancelled at the
    end of the run keeps the record of what it was fed."""
    import serve
    import traffic
    d = serve.Done(req, 0.0 if t_base is None else t_base + req.due)
    if keep:
        runner.done.append(d)
    sess = None
    try:
        sess = await runner.gw.open_session(
            tokens=list(req.prompt), max_new=max_new or req.max_new,
            eos_id=-1, slo=runner.slo, audio_s=req.audio_s)
        for due, a, b in traffic.chunks_of(req, runner.mix["chunk_s"]):
            if paced:
                due_abs = t_base + due
                await runner.sleep_until(due_abs)
                if runner.t0 is not None:
                    runner.lateness.append(time.monotonic() - due_abs)
            else:
                due_abs = time.monotonic()
            fr = _frames(runner, runner.inputs.audio(req, a, b))
            fed = time.monotonic()
            await sess.feed(fr)
            d.feeds.append((due_abs, fed))
        d.result = await sess.finalize()
    except asyncio.CancelledError:
        if sess is not None:
            d.result = await sess.cancel()
        raise
    return d


# ------------------------------------------------------------- read-back
def lane_planes(cache, slot: int, n_self: int, n_cross: int) -> dict:
    """One lane's cached self- and cross-K/V, float32 ``(layer,
    position, head, dim)`` over the positions written."""
    import serve
    layers = cache["layers"]
    return serve.kv_planes({"self": (layers["self"], n_self),
                            "cross": (layers["cross"], n_cross)}, slot)


def read_lane(cache, slot: int, state) -> dict:
    return lane_planes(cache, slot, state.pos,
                       state.req.enc_frames.shape[0])


class Reference:
    """``reference.Reference``, fed the reference frontend's frames of
    each request's audio (chunk by chunk for a stream)."""

    def __init__(self, cfg: dict, params):
        import reference
        self._frontend = reference.frames
        self._ref = reference.Reference(cfg, params)
        self.d_model = cfg["config"]["d_model"]

    def judge(self, runner, req, tokens, planes=None):
        import traffic
        audio = runner.inputs.audio
        if runner.mix["kind"] == "stream":
            pieces = [(a, b) for _, a, b in
                      traffic.chunks_of(req, runner.mix["chunk_s"])]
            fr = np.concatenate([self._frontend(audio(req, a, b),
                                                self.d_model)
                                 for a, b in pieces])
            chunk = fr.shape[0] // len(pieces)
        else:
            fr = self._frontend(audio(req), self.d_model)
            chunk = None
        return self._ref.judge(fr, chunk, req.prompt, tokens, planes)


def request_flops(cfg: dict, req, n_out: int) -> float:
    """``flops.request`` over the clip's encoder positions, the prompt
    and ``n_out`` served tokens."""
    import flops
    return flops.request(cfg, int(req.audio_s * FRAMES_PER_S),
                         len(req.prompt), n_out)
