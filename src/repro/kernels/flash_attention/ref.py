"""Pure-jnp oracle for flash attention (dense softmax, same masks)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True,
                  window: int | None = None,
                  softcap: float | None = None) -> jax.Array:
    """q: (BH, Sq, D); k, v: (BH, Sk, D). Dense reference with identical
    masking (query i sits at position i; Sq != Sk is cross-attention).
    f32 throughout: HIGHEST precision keeps the TPU's dots at f32 too."""
    bh, s, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    hi = jax.lax.Precision.HIGHEST
    logits = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                        k.astype(jnp.float32), precision=hi) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((s, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    logits = jnp.where(mask[None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bqk,bkd->bqd", p / l, v.astype(jnp.float32),
                     precision=hi)
    return out.astype(q.dtype)
