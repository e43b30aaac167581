"""Pallas TPU kernel: decode attention over a Q8_0-quantized KV cache.

The paper's C1 (inline dequantization next to the compute unit) applied
to the *decode bottleneck*: every decode step streams the full KV cache,
so cache bytes — not weight bytes — dominate the serving memory term
(§Roofline decode rows). Quantizing the cache to Q8_0 (int8 + one f16
scale per 32-element block along head_dim) cuts the stream to ~0.53x of
bf16; this kernel dequantizes blocks **in VMEM right before the MXU dot**
— the cache never exists in HBM at bf16/f32.

Online-softmax over KV blocks (one grid step per (head, kv-block)), with
a masked tail for cache positions beyond the current decode position.
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


def _q8_attn_kernel(len_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                    o_ref, m_ref, l_ref, acc_ref, *,
                    scale, n_k_blocks, bk):
    h = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                     # (1, D)

    def dequant(qref, sref):
        raw = qref[0].astype(jnp.float32)                # (bk, D)
        return raw * expand_scales(widen_scales(sref[0]), raw.shape[1])

    k = dequant(kq_ref, ks_ref)
    v = dequant(vq_ref, vs_ref)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, bk)
    s = s * scale
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    s = jnp.where(kpos < len_ref[h], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_k_blocks - 1)
    def _done():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def q8_decode_attention_pallas(q: jax.Array, kq: jax.Array, ks: jax.Array,
                               vq: jax.Array, vs: jax.Array,
                               length: jax.Array, *,
                               bk: int = 128,
                               interpret: bool = False) -> jax.Array:
    """q: (BH, 1, D); kq/vq: (BH, S, D) int8; ks/vs: (BH, S, D//QBLOCK)
    scales; length: () or (BH,) int32 — lane h attends positions
    [0, length[h]) (per-lane depths under continuous batching).
    S % bk == 0. Returns (BH, 1, D) in q.dtype."""
    bh, one, d = q.shape
    s = kq.shape[1]
    assert one == 1 and kq.shape == (bh, s, d) and s % bk == 0
    assert ks.shape == (bh, s, d // QBLOCK), ks.shape
    n_k_blocks = s // bk
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(_q8_attn_kernel, scale=scale,
                               n_k_blocks=n_k_blocks, bk=bk)
    lens = jnp.broadcast_to(
        jnp.asarray(length, jnp.int32).reshape(-1), (bh,))
    # per-lane lengths ride in SMEM as a scalar-prefetch operand
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, n_k_blocks),
            in_specs=[
                pl.BlockSpec((1, 1, d), lambda h, j, lens: (h, 0, 0)),
                pl.BlockSpec((1, bk, d), lambda h, j, lens: (h, j, 0)),
                pl.BlockSpec((1, bk, d // QBLOCK),
                             lambda h, j, lens: (h, j, 0)),
                pl.BlockSpec((1, bk, d), lambda h, j, lens: (h, j, 0)),
                pl.BlockSpec((1, bk, d // QBLOCK),
                             lambda h, j, lens: (h, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, d), lambda h, j, lens: (h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((bh, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens, q, kq, scale_operand(ks), vq, scale_operand(vs))
