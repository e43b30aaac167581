"""Public jit'd wrapper for the Q4_0 GEMM — mixed execution + budgets.

Same co-design stack as ``q8_matmul`` one tier lower:

* C1 inline conversion: nibbles are unpacked and scaled in VMEM right
  before the MXU dot — the HBM stream stays at 0.5625 bytes/element.
* C2 mixed execution: K split into a block-aligned main segment (Pallas)
  and a residual tail on the plain-XLA path, summed.
* C4 VMEM budget: block shapes from ``select_blocks(b_dtype="q4_0")``.

The XLA backend (``q4_matmul_xla``) deliberately widens the int4 codes to
**bf16, never f32**: unlike the q8 weight path, q4 planes are live inside
the traced draft-verify decode program, so a full-plane f32 dequant here
would be a real HBM regression (and an SC-DTYPE finding).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.footprint import select_blocks
from repro.core.quantize import QBLOCK, Q4Tensor, unpack_q4
from repro.kernels.common import pad_dim
from repro.kernels.q4_matmul.q4_matmul import q4_matmul_pallas
from repro.kernels.q4_matmul.ref import q4_matmul_ref


@functools.partial(jax.jit, static_argnames=("vmem_budget", "interpret",
                                             "out_dtype"))
def q4_matmul(x: jax.Array, w: Q4Tensor, *,
              vmem_budget: int = 4 * 1024 * 1024,
              out_dtype=jnp.float32,
              interpret: bool) -> jax.Array:
    """y = x @ dequant(w), w stored as Q4Tensor packed along K.

    ``w.q`` is (K//2, N) uint8 (two codes/byte), ``w.scale`` (K//QBLOCK, N).
    """
    if x.ndim != 2:
        lead = x.shape[:-1]
        y = q4_matmul(x.reshape(-1, x.shape[-1]), w,
                      vmem_budget=vmem_budget, out_dtype=out_dtype,
                      interpret=interpret)
        return y.reshape(*lead, y.shape[-1])

    m, k = x.shape
    k2, n = w.q.shape
    assert k == 2 * k2, (x.shape, w.q.shape)

    blocks = select_blocks(m, n, k, vmem_budget, a_dtype="bf16",
                           b_dtype="q4_0")
    bm, bn, bk = blocks.bm, blocks.bn, blocks.bk
    bk = max(QBLOCK, (bk // QBLOCK) * QBLOCK)

    # --- C2: burst/tile-aligned main segment vs residual tail ---
    k_main = (k // bk) * bk
    x_main, x_res = x[:, :k_main], x[:, k_main:]
    wp_main, wp_res = w.q[:k_main // 2], w.q[k_main // 2:]
    ws_main, ws_res = w.scale[:k_main // QBLOCK], w.scale[k_main // QBLOCK:]

    xp = pad_dim(x_main, 0, bm)
    wpp = pad_dim(wp_main, 1, bn)
    wsp = pad_dim(ws_main, 1, bn)

    if k_main > 0:
        y = q4_matmul_pallas(xp, wpp, wsp, bm=bm, bn=bn, bk=bk,
                             out_dtype=jnp.float32, interpret=interpret)
        y = y[:m, :n]
    else:
        y = jnp.zeros((m, n), jnp.float32)

    if k_main < k:  # residual on the XLA ("host") path, then summed
        y = y + q4_matmul_ref(x_res, wp_res, ws_res)
    return y.astype(out_dtype)


def q4_matmul_xla(x: jax.Array, w: Q4Tensor, out_dtype=jnp.float32) -> jax.Array:
    """XLA fallback (the HOST decision) with **bf16-widened** dequant.

    Codes go uint8 -> int8 -> bf16 (exact: |q| <= 8) and the dot runs
    blockwise so per-group scales fold in at f32 *after* the contraction —
    the int4 plane never materializes in f32 (SC-DTYPE clean even when the
    draft weights live inside the fused decode scan).
    """
    if x.ndim != 2:
        lead = x.shape[:-1]
        y = q4_matmul_xla(x.reshape(-1, x.shape[-1]), w, out_dtype)
        return y.reshape(*lead, y.shape[-1])
    m, k = x.shape
    assert k == 2 * w.q.shape[0], (x.shape, w.q.shape)
    n = w.q.shape[-1]
    codes = unpack_q4(w.q, axis=0).astype(jnp.bfloat16)       # (K, N)
    # the 32-group axis leads both operands: XLA's CPU runtime has no
    # bf16 x bf16 -> f32 kernel for a batch dim that trails in the lhs
    xb = x.astype(jnp.bfloat16).reshape(m, k // QBLOCK, QBLOCK)
    xb = xb.transpose(1, 0, 2)                                 # (K/32, M, 32)
    cb = codes.reshape(k // QBLOCK, QBLOCK, n)
    part = jnp.einsum("bmk,bkn->bmn", xb, cb,
                      preferred_element_type=jnp.float32)      # (K/32, M, N)
    y = (part * w.scale.astype(jnp.float32)[:, None, :]).sum(axis=0)
    return y.astype(out_dtype)
