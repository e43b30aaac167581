"""The plain reference the served tokens and cached K/V are judged by.

A straightforward float32 implementation of what the program serves,
written from the model's description and importing nothing of the
program: the log-mel frontend in NumPy (float64), and the
encoder-decoder forward in ``jax.numpy`` at ``HIGHEST`` matmul precision,
with no kernels, no cache and no batching. It reads the weights the
benchmark made from the seed (``model.make_weights``), upcast to f32.

What it follows, and where that departs from published Whisper:

* frontend: 25 ms Hann frames every 10 ms with no centre padding, an
  80-band HTK-mel filterbank with Slaney area norm, ``log10`` clamped at
  the fixed floor -8 and scaled ``(x + 4) / 4`` (Whisper clamps at the
  clip's max - 8, which needs the future); the conv stem is a stand-in:
  stride-2 mean pooling, a fixed cosine projection to ``d_model`` and
  exact GELU;
* encoder: a learned ``d_model x d_model`` projection, sinusoidal
  positions, pre-norm bidirectional attention and a GELU (tanh form) MLP,
  final LayerNorm; a clip is encoded at its own length, not padded to
  30 s; streamed audio is encoded chunk by chunk (block-diagonal
  attention, positions restarting in each chunk);
* decoder: tied token embeddings, learned positions, pre-norm causal
  self-attention, cross-attention over the valid encoder positions and
  the MLP; no biases in the projections or the MLP; logits over the
  published vocabulary.
"""

from __future__ import annotations

import math

import numpy as np

SR, N_FFT, HOP, N_MELS, STRIDE = 16_000, 400, 160, 80, 2
LOG_FLOOR, MEL_EPS, LN_EPS = -8.0, 1e-10, 1e-5
# a cached K/V element is off when it misses the reference's by more
# than this share of its plane's RMS: four times the most that rounding
# to bfloat16 (8 significant bits) moves a value of that size
OFF = 2.0 ** -6


# ------------------------------------------------------------- frontend
def _mel_fb() -> np.ndarray:
    def mel(f):
        return 2595.0 * np.log10(1.0 + f / 700.0)

    def hz(m):
        return 700.0 * (10.0 ** (m / 2595.0) - 1.0)

    pts = hz(np.linspace(mel(0.0), mel(SR / 2.0), N_MELS + 2))
    freqs = np.linspace(0.0, SR / 2.0, N_FFT // 2 + 1)
    fb = np.zeros((N_FFT // 2 + 1, N_MELS))
    for m in range(N_MELS):
        lo, c, hi = pts[m], pts[m + 1], pts[m + 2]
        tri = np.maximum(0.0, np.minimum((freqs - lo) / (c - lo),
                                         (hi - freqs) / (hi - c)))
        fb[:, m] = tri * 2.0 / (hi - lo)
    return fb


def _erf_gelu(x):
    from scipy.special import erf
    return 0.5 * x * (1.0 + erf(x / math.sqrt(2.0)))


def frames(wave: np.ndarray, d_model: int) -> np.ndarray:
    """Encoder input frames ``(ceil(T / 2), d_model)`` of a waveform."""
    x = np.asarray(wave, np.float64).reshape(-1)
    t = -(-len(x) // HOP)
    need = (t - 1) * HOP + N_FFT
    x = np.pad(x, (0, max(0, need - len(x))))
    idx = np.arange(t)[:, None] * HOP + np.arange(N_FFT)[None, :]
    win = 0.5 - 0.5 * np.cos(2 * np.pi * np.arange(N_FFT) / N_FFT)
    power = np.abs(np.fft.rfft(x[idx] * win, axis=-1)) ** 2
    logm = np.maximum(np.log10(np.maximum(power @ _mel_fb(), MEL_EPS)),
                      LOG_FLOOR)
    logm = (logm + 4.0) / 4.0
    tp = -(-t // STRIDE)
    logm = np.pad(logm, ((0, tp * STRIDE - t), (0, 0)))
    pooled = logm.reshape(tp, STRIDE, N_MELS).mean(axis=1)
    m = np.arange(N_MELS)[:, None]
    j = np.arange(d_model)[None, :]
    proj = np.cos(np.pi * (m + 0.5) * (j + 1.0) / N_MELS) \
        * math.sqrt(2.0 / N_MELS)
    return _erf_gelu(pooled @ proj).astype(np.float32)


# -------------------------------------------------------- model forward
def _forward_fn(d_model: int, n_heads: int, vocab: int):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def ln(p, x):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]

    def attn(p, xq, xkv, mask):
        """(output, K, V): K and V are what a cache would hold."""
        q = jnp.einsum("sd,dhk->shk", xq, p["wq"], precision=hi)
        k = jnp.einsum("sd,dhk->shk", xkv, p["wk"], precision=hi)
        v = jnp.einsum("sd,dhk->shk", xkv, p["wv"], precision=hi)
        s = jnp.einsum("qhk,shk->hqs", q, k, precision=hi) \
            / math.sqrt(q.shape[-1])
        s = jnp.where(mask[None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqs,shk->qhk", w, v, precision=hi)
        return jnp.einsum("qhk,hkd->qd", o, p["wo"], precision=hi), k, v

    def mlp(p, x):
        h = jnp.einsum("sd,df->sf", x, p["up"], precision=hi)
        return jnp.einsum("sf,fd->sd", jax.nn.gelu(h, approximate=True),
                          p["down"], precision=hi)

    def layer(tree, i):
        return jax.tree.map(lambda a: a[i], tree)

    def fwd(params, x_frames, n_frames, chunk, tokens):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        s = x_frames.shape[0]
        pos = jnp.arange(s) % chunk
        half = d_model // 2
        freq = jnp.exp(-math.log(10000.0) * jnp.arange(half) / (half - 1))
        ang = pos[:, None] * freq[None, :]
        x = jnp.einsum("sd,de->se", x_frames, p["frontend"], precision=hi) \
            + jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=1)
        i = jnp.arange(s)
        valid = i < n_frames
        emask = valid[None, :] & (i[:, None] // chunk == i[None, :] // chunk)
        n_enc = p["enc_layers"]["ln1"]["scale"].shape[0]
        for li in range(n_enc):
            lp = layer(p["enc_layers"], li)
            h = ln(lp["ln1"], x)
            x = x + attn(lp["attn"], h, h, emask)[0]
            x = x + mlp(lp["mlp"], ln(lp["ln2"], x))
        enc = ln(p["enc_ln"], x)
        t = tokens.shape[0]
        table = p["embed"]["table"]
        y = table[tokens] + p["dec_pos"][:t]
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        xmask = jnp.broadcast_to(valid[None, :], (t, s))
        n_dec = p["dec_layers"]["ln1"]["scale"].shape[0]
        kv = {"self": [], "cross": []}
        for li in range(n_dec):
            lp = layer(p["dec_layers"], li)
            h = ln(lp["ln1"], y)
            o, k, v = attn(lp["self_attn"], h, h, causal)
            y = y + o
            kv["self"].append(jnp.stack([k, v]))
            o, k, v = attn(lp["cross_attn"], ln(lp["ln_x"], y), enc, xmask)
            y = y + o
            kv["cross"].append(jnp.stack([k, v]))
            y = y + mlp(lp["mlp"], ln(lp["ln2"], y))
        y = ln(p["dec_ln"], y)
        # K/V by (layer, k|v, position, head, head_dim)
        kv = {kind: jnp.stack(planes) for kind, planes in kv.items()}
        return jnp.einsum("td,vd->tv", y, table[:vocab], precision=hi), kv

    return jax.jit(fwd, static_argnames=("chunk",))


class Reference:
    """Teacher-forced reference logits over served tokens.

    Every call pads the encoder input to ``enc_len`` frames and the
    tokens to ``max_len``, so one compiled program serves every request
    of a cell."""

    def __init__(self, cfg: dict, params):
        hf = cfg["config"]
        self.d_model = hf["d_model"]
        self.vocab = hf["vocab_size"]
        dep = cfg["deployment"]
        self.enc_len, self.max_len = dep["enc_len"], dep["max_len"]
        self.params = params
        self._fwd = _forward_fn(self.d_model, hf["decoder_attention_heads"],
                                self.vocab)

    def judge(self, x_frames: np.ndarray, chunk, prompt, served,
              planes=None):
        """Teacher-force ``prompt`` and ``served`` and judge them.

        Returns per served token how far its reference logit lies below
        the reference's best at that position (0 where it is the best),
        and, where ``planes`` holds the K/V a served lane had cached
        (``{"self"|"cross": {"k"|"v": (layer, position, head, dim)}}``
        over the positions it had written), for each kind the share of
        elements off the reference's by more than ``OFF`` of their
        plane's RMS, in its worst (layer, k|v) plane."""
        import jax.numpy as jnp
        s = x_frames.shape[0]
        xf = np.zeros((self.enc_len, self.d_model), np.float32)
        xf[:s] = x_frames
        toks = list(prompt) + list(served[:-1])
        row = np.zeros(self.max_len, np.int32)
        row[:len(toks)] = toks
        logits, kv = self._fwd(self.params, jnp.asarray(xf), s,
                               chunk or self.enc_len, jnp.asarray(row))
        logits = np.asarray(logits)
        at = np.arange(len(prompt) - 1, len(toks))
        rows = logits[at]
        gaps = rows.max(axis=1) - rows[np.arange(len(at)),
                                       np.asarray(served)]
        if planes is None:
            return gaps, None
        off = {}
        for kind, got in planes.items():
            ref = np.asarray(kv[kind])
            worst = 0.0
            for i, name in enumerate(("k", "v")):
                n = got[name].shape[1]
                r = ref[:, i, :n].astype(np.float64)
                d = np.abs(got[name] - r)
                rms = np.sqrt((r * r).mean(axis=(1, 2, 3), keepdims=True))
                worst = max(worst, float((d > OFF * rms).mean(
                    axis=(1, 2, 3)).max()))
            off[kind] = worst
        return gaps, off
