"""Public wrapper: GQA-aware flash attention over (B, S, H, D) layouts."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import pad_dim
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas


def _choose_block(s: int, pref: int = 128) -> int:
    """The q/kv block: the whole sequence when it fits one block (a
    block equal to the array dim is always a legal TPU tile), else
    ``pref`` with S padded up to a multiple of it. Halving until the
    block divides S would reach 4-row blocks for S = 1500 (a 30 s
    Whisper window), below the TPU's 8-row sublane tile."""
    return min(s, pref)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    window: int | None = None,
                    softcap: float | None = None,
                    interpret: bool) -> jax.Array:
    """q: (B, S, H, D); k, v: (B, S, Hkv, D) with H % Hkv == 0 (GQA).
    Returns (B, S, H, D)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    assert h % hkv == 0, (h, hkv)
    rep = h // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    blk = _choose_block(s)
    # padded keys are masked in the kernel (kv_len); padded query rows
    # are sliced off
    qf, kf, vf = (pad_dim(t.transpose(0, 2, 1, 3).reshape(b * h, s, d), 1,
                          blk) for t in (q, k, v))
    out = flash_attention_pallas(qf, kf, vf, causal=causal, window=window,
                                 softcap=softcap, bq=blk, bk=blk, kv_len=s,
                                 interpret=interpret)
    return out[:, :s].reshape(b, h, s, d).transpose(0, 2, 1, 3)
