"""Model FLOPs of Whisper work, from the configuration's shapes.

Matrix-multiply FLOPs only (2 per multiply-add), as the model needs
them: the encoder over a clip's frames, the decoder over its prompt
(with cross-attention over those frames) and one decoder step per
generated token. Nothing is counted for padding, for recomputation or
for work the serving path repeats.
"""

from __future__ import annotations


def _dims(cfg: dict):
    hf = cfg["config"]
    return (hf["d_model"], hf["encoder_ffn_dim"], hf["encoder_layers"],
            hf["decoder_layers"], hf["vocab_size"])


def encoder(cfg: dict, frames: int) -> float:
    d, ff, le, _, _ = _dims(cfg)
    proj = 2 * frames * d * d                     # conv-stem stand-in
    per_layer = (2 * frames * d * 4 * d           # q, k, v, o
                 + 2 * 2 * frames * frames * d    # scores and values
                 + 2 * 2 * frames * d * ff)       # MLP
    return proj + le * per_layer


def cross_kv(cfg: dict, frames: int) -> float:
    d, _, _, ld, _ = _dims(cfg)
    return ld * 2 * frames * d * 2 * d


def decoder_tokens(cfg: dict, ctx0: int, n: int, frames: int) -> float:
    """``n`` decoder positions starting at context length ``ctx0``:
    projections, causal self-attention over the context so far,
    cross-attention over ``frames`` (its K/V counted in ``cross_kv``),
    MLP and the vocabulary head."""
    d, ff, _, ld, v = _dims(cfg)
    self_ctx = sum(ctx0 + i + 1 for i in range(n))
    per_layer = (2 * n * d * 4 * d + 2 * 2 * self_ctx * d      # self
                 + 2 * n * d * 2 * d + 2 * 2 * n * frames * d  # cross q, o
                 + 2 * 2 * n * d * ff)
    return ld * per_layer + 2 * n * d * v


def request(cfg: dict, frames: int, prompt: int, new: int) -> float:
    """One one-shot request: encode, prefill the prompt (its last
    position yields the first token), decode ``new - 1`` more tokens."""
    return (encoder(cfg, frames) + cross_kv(cfg, frames)
            + decoder_tokens(cfg, 0, prompt, frames)
            + decoder_tokens(cfg, prompt, max(new - 1, 0), frames))
