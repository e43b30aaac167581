"""Slot-free greedy reference for serving parity.

The engine decodes through a slot pool, a fused tick and per-lane
masks; the reference re-runs the full forward (``mode="train"``: no
cache, no slots) over the whole token prefix for every new token and
takes the argmax. Engine tokens must equal the reference's, except at a
near-tie: where the two pick different tokens, the engine's pick must
sit within a small logit margin of the reference argmax (accumulation
order differs between the paths, and bf16 activations round ~1e-2-scale
logit differences). After such a flip the sequences legitimately part,
so comparison stops there.

Every forward is right-padded to the full length (prompt plus new
tokens), so one compiled program serves every step; a retrace per
prefix length would cost a compile per token on the chip. Padding sits
after the read position, which a causal decoder never attends. MoE
capacity routing is not causal: compare MoE models at a capacity that
drops no token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# logit margin under which a greedy pick may legitimately flip between
# the engine's decode path and the full-forward reference, keyed by the
# config's *compute* dtype (params are stored f32)
TIE_MARGIN = {"bf16": 0.15, "f16": 0.05}
TIE_MARGIN_DEFAULT = 1e-3


def tie_margin(cfg) -> float:
    return TIE_MARGIN.get(cfg.dtype, TIE_MARGIN_DEFAULT)


def _last_logits_fn(model, params, enc_frames, pad_to: int):
    """toks (list[int]) -> f32 logits (vocab,) at the last token, every
    forward right-padded to ``pad_to`` tokens."""
    fwd = jax.jit(lambda p, b: model.forward(p, b, mode="train")[0])
    extra = {}
    if enc_frames is not None:
        extra["enc_frames"] = jnp.asarray(enc_frames, jnp.float32)[None]

    def last(toks):
        n = len(toks)
        row = list(toks) + [0] * (pad_to - n)
        logits = fwd(params, {"tokens": jnp.asarray([row], jnp.int32),
                              **extra})
        return np.asarray(logits[0, n - 1], np.float32)
    return last


def greedy_reference(model, params, prompt, n_new: int, *,
                     enc_frames=None) -> list:
    """``n_new`` greedy tokens from the full forward re-run per token.
    ``enc_frames`` (S, d_model): the audio of an enc-dec model."""
    last = _last_logits_fn(model, params, enc_frames, len(prompt) + n_new)
    toks, out = list(prompt), []
    for _ in range(n_new):
        nxt = int(last(toks).argmax())
        out.append(nxt)
        toks.append(nxt)
    return out


def assert_greedy_matches(model, params, prompt, got, margin: float, *,
                          enc_frames=None) -> int:
    """Engine tokens ``got`` must equal the slot-free greedy reference,
    except that at the FIRST divergence the engine's pick must be a
    near-tie: its reference logit within ``margin`` of the reference
    argmax. Returns the number of leading tokens that matched exactly
    (``len(got)`` when the sequences are identical)."""
    last = _last_logits_fn(model, params, enc_frames, len(prompt) + len(got))
    toks = list(prompt)
    for i, tok in enumerate(got):
        lg = last(toks)
        want = int(lg.argmax())
        if tok == want:
            toks.append(tok)
            continue
        gap = float(lg[want] - lg[tok])
        if gap >= margin:
            raise AssertionError(
                f"engine diverged at step {i} ({tok} vs {want}) with a "
                f"non-tie logit gap {gap:.4f} >= {margin}")
        return i
    return len(got)
