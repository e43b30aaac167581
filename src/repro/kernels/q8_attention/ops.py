"""Public wrapper: Q8_0 KV-cache decode attention (+ its traffic model).

``quantize_kv`` builds the Q8 cache planes from bf16 K/V (per-token,
per-head 32-blocks along head_dim — the ggml layout transposed to the
cache's natural axes). ``q8_decode_attention`` pads S to the block
multiple and dispatches the kernel.

Traffic: the per-step cache stream drops from 2·S·D bf16 bytes to
2·S·D·(1 + 2/QBLOCK)/2 ≈ 1.06·S·D — the paper's Q8_0 LOAD saving applied
to the decode bottleneck (≈1.88x on the §Roofline decode memory terms'
cache component).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.footprint import UnsupportedCall
from repro.core.quantize import QBLOCK, quantize_q8_0
from repro.kernels.common import pad_dim
from repro.kernels.q8_attention.q8_attention import q8_decode_attention_pallas


def quantize_kv(k: jax.Array):
    """k: (..., S, D) float -> (int8 plane, (…, S, D//QBLOCK) scales)."""
    t = quantize_q8_0(k, axis=-1)
    return t.q, t.scale


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def q8_decode_attention(q, kq, ks, vq, vs, length, *, bk: int = 128,
                        interpret: bool) -> jax.Array:
    """q: (BH, 1, D); kq/vq: (BH, S, D) int8; ks/vs scales; attend
    [0, length). ``length`` is a scalar (lockstep decode) or a (BH,)
    vector (continuous batching: every serving lane at its own depth).
    Handles S not divisible by bk via zero padding (masked by
    ``length``). Single-query only: the speculative verify's (BH, Q)
    case raises ``UnsupportedCall`` so dispatch falls back to the XLA
    backend."""
    bh, _, d = q.shape
    length = jnp.asarray(length)
    if q.shape[1] != 1 or length.ndim > 1:
        raise UnsupportedCall(
            "q8_decode_attention (Pallas) is single-query: got "
            f"q {q.shape}, length {length.shape}; multi-query verify "
            "routes to the XLA backend via dispatch fallback")
    kq, vq, ks, vs = (pad_dim(t, 1, bk) for t in (kq, vq, ks, vs))
    # scalar-vs-(BH,) length normalization happens in the pallas wrapper
    return q8_decode_attention_pallas(q, kq, ks, vq, vs,
                                      jnp.asarray(length), bk=bk,
                                      interpret=interpret)


def cache_traffic_ratio() -> float:
    """Q8 cache bytes per element vs bf16 (paper C1 LOAD saving)."""
    q8 = 1.0 + 2.0 / QBLOCK
    return q8 / 2.0
