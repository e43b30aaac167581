"""Pallas TPU kernel: decode attention over a Q4_0-quantized KV cache.

One tier below ``q8_attention``: the cache stream drops to
(0.5 + 2/QBLOCK)/2 = 0.28125x of bf16 — nibble codes plus one f16 scale
per 32-element block along head_dim. Nibbles are unpacked and scaled
**in VMEM right before the MXU dot** (paper C1); the cache never exists
in HBM above 4 bits/element.

Online-softmax over KV blocks, one grid step per (head, kv-block), with
a masked tail for cache positions beyond the current decode position.
Single-query only: the speculative multi-query verify path routes to the
XLA backend via the dispatch fallback.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import QBLOCK
from repro.kernels.common import expand_scales, scale_operand, widen_scales

NEG_INF = -1e30


def _q4_attn_kernel(len_ref, qe_ref, qo_ref, kp_ref, ks_ref, vp_ref, vs_ref,
                    oe_ref, oo_ref, m_ref, l_ref, acce_ref, acco_ref, *,
                    scale, n_k_blocks, bk):
    """Byte i of a packed row holds head_dim columns 2i (low nibble) and
    2i+1 (high): q and the output are split into their even and odd
    columns, so the nibbles are never interleaved in VMEM."""
    h = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acce_ref[...] = jnp.zeros_like(acce_ref)
        acco_ref[...] = jnp.zeros_like(acco_ref)

    def dequant(pref, sref):
        raw = pref[0].astype(jnp.int32)                  # (bk, D//2)
        # packed columns 16g..16g+15 are head_dim columns 32g..32g+31
        sc = expand_scales(widen_scales(sref[0]), raw.shape[1],
                           QBLOCK // 2)                  # C1: in-VMEM
        return (((raw & 0xF) - 8).astype(jnp.float32) * sc,
                ((raw >> 4) - 8).astype(jnp.float32) * sc)

    k_lo, k_hi = dequant(kp_ref, ks_ref)
    v_lo, v_hi = dequant(vp_ref, vs_ref)

    qk = (((1,), (1,)), ((), ()))
    s = (jax.lax.dot_general(qe_ref[0].astype(jnp.float32), k_lo, qk,
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(qo_ref[0].astype(jnp.float32), k_hi, qk,
                               preferred_element_type=jnp.float32))
    s = s * scale                                        # (1, bk)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    s = jnp.where(kpos < len_ref[h], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    pv = (((1,), (0,)), ((), ()))
    acce_ref[...] = acce_ref[...] * alpha + jax.lax.dot_general(
        p, v_lo, pv, preferred_element_type=jnp.float32)
    acco_ref[...] = acco_ref[...] * alpha + jax.lax.dot_general(
        p, v_hi, pv, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_k_blocks - 1)
    def _done():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        oe_ref[0] = (acce_ref[...] / safe).astype(oe_ref.dtype)
        oo_ref[0] = (acco_ref[...] / safe).astype(oo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def q4_decode_attention_pallas(q: jax.Array, kp: jax.Array, ks: jax.Array,
                               vp: jax.Array, vs: jax.Array,
                               length: jax.Array, *,
                               bk: int = 128,
                               interpret: bool = False) -> jax.Array:
    """q: (BH, 1, D); kp/vp: (BH, S, D//2) packed uint8; ks/vs:
    (BH, S, D//QBLOCK) scales; length: () or (BH,) int32 — lane h attends
    positions [0, length[h]). S % bk == 0. Returns (BH, 1, D) in q.dtype."""
    bh, one, d = q.shape
    s = kp.shape[1]
    assert one == 1 and kp.shape == (bh, s, d // 2) and s % bk == 0
    assert ks.shape == (bh, s, d // QBLOCK), ks.shape
    n_k_blocks = s // bk
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(_q4_attn_kernel, scale=scale,
                               n_k_blocks=n_k_blocks, bk=bk)
    lens = jnp.broadcast_to(
        jnp.asarray(length, jnp.int32).reshape(-1), (bh,))
    half = d // 2
    row = pl.BlockSpec((1, 1, half), lambda h, j, lens: (h, 0, 0))
    # per-lane lengths ride in SMEM as a scalar-prefetch operand
    oe, oo = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, n_k_blocks),
            in_specs=[
                row, row,
                pl.BlockSpec((1, bk, half), lambda h, j, lens: (h, j, 0)),
                pl.BlockSpec((1, bk, d // QBLOCK),
                             lambda h, j, lens: (h, j, 0)),
                pl.BlockSpec((1, bk, half), lambda h, j, lens: (h, j, 0)),
                pl.BlockSpec((1, bk, d // QBLOCK),
                             lambda h, j, lens: (h, j, 0)),
            ],
            out_specs=[row, row],
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, half), jnp.float32),
                pltpu.VMEM((1, half), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((bh, 1, half), q.dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens, q[..., 0::2], q[..., 1::2], kp, scale_operand(ks), vp,
      scale_operand(vs))
    return jnp.stack([oe, oo], axis=-1).reshape(bh, 1, d)
