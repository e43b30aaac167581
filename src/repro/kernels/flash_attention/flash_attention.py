"""Pallas TPU kernel: online-softmax (flash) attention.

Needed by the long-context cells (32k prefill / 500k hybrid decode): the
scores matrix must never materialize in HBM. Online softmax over KV blocks
with running (m, l) statistics; causal, sliding-window (Mixtral), and
logit-softcap (Gemma-2) variants are folded into the mask/logits path so
one kernel serves every assigned architecture.

VMEM residency per grid step = q-block + k-block + v-block + accumulators —
chosen against the same VMEM budget machinery as the GEMM kernels (C4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, window, softcap, n_k_blocks, bq, bk,
                  kv_len):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # (bq, d)
    k = k_ref[0].astype(jnp.float32)                    # (bk, d)
    v = v_ref[0].astype(jnp.float32)                    # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < kv_len                               # padded keys
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                 # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                              # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                     # (bq, 1)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == n_k_blocks - 1)
    def _done():
        # fully-masked rows (can happen with sliding windows) get l == 0
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "bq", "bk", "kv_len", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True,
                           window: int | None = None,
                           softcap: float | None = None,
                           bq: int = 128, bk: int = 128,
                           kv_len: int | None = None,
                           interpret: bool = False) -> jax.Array:
    """q, k, v: (BH, S, D) — heads pre-flattened (GQA handled by ops.py).
    S must divide by bq and bk. Keys at positions >= ``kv_len`` (the
    wrapper's padding; default S) are masked out."""
    bh, s, d = q.shape
    assert k.shape == (bh, s, d) and v.shape == (bh, s, d)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    n_k_blocks = s // bk
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, n_k_blocks=n_k_blocks, bq=bq, bk=bk,
        kv_len=s if kv_len is None else kv_len)
    return pl.pallas_call(
        kernel,
        grid=(bh, s // bq, n_k_blocks),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
