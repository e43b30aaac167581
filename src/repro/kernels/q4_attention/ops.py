"""Public wrapper: Q4_0 KV-cache decode attention (+ its traffic model).

``quantize_kv_q4`` builds the packed nibble planes from bf16 K/V
(per-token, per-head 32-blocks along head_dim). ``q4_decode_attention``
pads S to the block multiple and dispatches the Pallas kernel; it is
single-query only — the speculative verify's (BH, Q) case raises
``UnsupportedCall`` so the kernel registry's accel->host fallback routes it
to the XLA backend.

Traffic: the per-step cache stream drops from 2·S·D bf16 bytes to
2·S·D·(0.5 + 2/QBLOCK)/2 ≈ 0.56·S·D — 0.28125x of bf16 and 0.53x of the
Q8_0 tier, the int4 LOAD saving the CGLA follow-up headlines.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.footprint import UnsupportedCall
from repro.core.quantize import QBLOCK, quantize_q4_0
from repro.kernels.common import pad_dim
from repro.kernels.q4_attention.q4_attention import q4_decode_attention_pallas


def quantize_kv_q4(k: jax.Array):
    """k: (..., S, D) float -> (packed uint8 plane (…, S, D//2),
    (…, S, D//QBLOCK) scales)."""
    t = quantize_q4_0(k, axis=-1)
    return t.q, t.scale


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def q4_decode_attention(q, kp, ks, vp, vs, length, *, bk: int = 128,
                        interpret: bool) -> jax.Array:
    """q: (BH, 1, D); kp/vp: (BH, S, D//2) packed uint8; ks/vs scales;
    attend [0, length) with ``length`` a scalar or (BH,) vector. Handles
    S not divisible by bk via zero padding (masked by ``length``)."""
    length = jnp.asarray(length)
    if q.shape[1] != 1 or length.ndim > 1:
        raise UnsupportedCall(
            "q4_decode_attention (Pallas) is single-query: got "
            f"q {q.shape}, length {length.shape}; multi-query verify "
            "routes to the XLA backend via dispatch fallback")
    kp, vp, ks, vs = (pad_dim(t, 1, bk) for t in (kp, vp, ks, vs))
    return q4_decode_attention_pallas(q, kp, ks, vp, vs, length, bk=bk,
                                      interpret=interpret)


def cache_traffic_ratio_q4() -> float:
    """Q4 cache bytes per element vs bf16 (paper C1 LOAD saving,
    int4 tier): (0.5 + 2/QBLOCK) / 2 = 0.28125."""
    q4 = 0.5 + 2.0 / QBLOCK
    return q4 / 2.0
