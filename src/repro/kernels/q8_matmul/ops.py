"""Public jit'd wrapper for the Q8_0 GEMM — mixed execution + budgets.

Implements the paper's co-design stack on top of the raw kernel:

* C2 mixed execution: K is split into a block-aligned main segment (Pallas)
  and a residual tail computed on the plain-XLA path and summed.
* C3 dense packing: operands are the packed (q, scale) planes — no row
  padding is ever materialized.
* C4 VMEM budget: block shapes are selected by
  ``repro.core.footprint.select_blocks`` under a byte budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.footprint import select_blocks
from repro.core.quantize import QBLOCK, Q8Tensor
from repro.kernels.common import pad_dim
from repro.kernels.q8_matmul.q8_matmul import q8_matmul_pallas
from repro.kernels.q8_matmul.ref import q8_matmul_ref


@functools.partial(jax.jit, static_argnames=("vmem_budget", "interpret",
                                             "out_dtype"))
def q8_matmul(x: jax.Array, w: Q8Tensor, *,
              vmem_budget: int = 4 * 1024 * 1024,
              out_dtype=jnp.float32,
              interpret: bool) -> jax.Array:
    """y = x @ dequant(w), w stored as Q8Tensor with shape (K, N).

    ``interpret=True`` runs the kernel body on the CPU; on a TPU pass
    ``interpret=False``.
    """
    if x.ndim != 2:
        lead = x.shape[:-1]
        y = q8_matmul(x.reshape(-1, x.shape[-1]), w,
                      vmem_budget=vmem_budget, out_dtype=out_dtype,
                      interpret=interpret)
        return y.reshape(*lead, y.shape[-1])

    m, k = x.shape
    k2, n = w.q.shape
    assert k == k2, (x.shape, w.q.shape)

    blocks = select_blocks(m, n, k, vmem_budget, a_dtype="bf16",
                           b_dtype="q8_0")
    bm, bn, bk = blocks.bm, blocks.bn, blocks.bk
    bk = max(QBLOCK, (bk // QBLOCK) * QBLOCK)

    # --- C2: burst/tile-aligned main segment vs residual tail ---
    k_main = (k // bk) * bk
    x_main, x_res = x[:, :k_main], x[:, k_main:]
    wq_main, wq_res = w.q[:k_main], w.q[k_main:]
    ws_main, ws_res = w.scale[:k_main // QBLOCK], w.scale[k_main // QBLOCK:]

    # pad M/N up to block multiples (packed operands, C3 — padding exists
    # only transiently in VMEM-tile space, never in HBM layout)
    xp = pad_dim(x_main, 0, bm)
    wqp = pad_dim(wq_main, 1, bn)
    wsp = pad_dim(ws_main, 1, bn)

    if k_main > 0:
        y = q8_matmul_pallas(xp, wqp, wsp, bm=bm, bn=bn, bk=bk,
                             out_dtype=jnp.float32, interpret=interpret)
        y = y[:m, :n]
    else:
        y = jnp.zeros((m, n), jnp.float32)

    if k_main < k:  # residual on the XLA ("host") path, then summed
        y = y + q8_matmul_ref(x_res, wq_res, ws_res)
    return y.astype(out_dtype)


def q8_matmul_xla(x: jax.Array, w: Q8Tensor, out_dtype=jnp.float32) -> jax.Array:
    """XLA fallback path (the offload planner's HOST decision): dequant in
    HLO + dense dot. Also what the multi-pod dry-run lowers, since TPU
    Pallas cannot be lowered on the CPU backend."""
    if x.ndim != 2:
        lead = x.shape[:-1]
        y = q8_matmul_xla(x.reshape(-1, x.shape[-1]), w, out_dtype)
        return y.reshape(*lead, y.shape[-1])
    return q8_matmul_ref(x, w.q, w.scale, out_dtype=out_dtype)
