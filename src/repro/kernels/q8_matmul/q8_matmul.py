"""Pallas TPU kernel: Q8_0 dequant-in-kernel GEMM (paper C1, TPU binding).

``y[M, N] = x[M, K] @ dequant(wq[K, N], ws[K/32, N])``

The IMAX kernel converts Q8_0 blocks to f32 inline on the PE's bit-
manipulation units as data streams from the LMM; the TPU analogue is
dequantizing the int8 tile *in VMEM* immediately before the MXU dot, so
HBM→VMEM traffic stays at ~1.06 bytes/element (the paper's Q8_0 LOAD
saving) while the MXU still sees a dense f32/bf16 operand.

Block shapes come from ``repro.core.footprint.select_blocks`` under a VMEM
byte budget — the TPU binding of the paper's LMM-size knob (C4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import QBLOCK
from repro.kernels.common import scale_operand, widen_scales


def _q8_matmul_kernel(x_ref, wq_ref, ws_ref, o_ref, acc_ref, *, n_k_blocks):
    """One (bm, bn) output tile; grid dim 2 walks K in bk steps."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                  # (bm, bk)
    q = wq_ref[...].astype(jnp.float32)                 # (bk, bn)
    s = widen_scales(ws_ref[...])                       # (bk // 32, bn)
    bk, bn = q.shape
    # inline dequant: expand per-32-block scales along K (C1)
    scales = jnp.broadcast_to(s[:, None, :], (bk // QBLOCK, QBLOCK, bn))
    w = q * scales.reshape(bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k_blocks - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "out_dtype"))
def q8_matmul_pallas(x: jax.Array, wq: jax.Array, ws: jax.Array, *,
                     bm: int = 128, bn: int = 128, bk: int = 512,
                     out_dtype=jnp.float32,
                     interpret: bool = False) -> jax.Array:
    """x: (M, K) float; wq: (K, N) int8; ws: (K//QBLOCK, N) scales.

    M % bm == 0, N % bn == 0, K % bk == 0, bk % QBLOCK == 0 — the burst-
    aligned "main segment"; ragged shapes are handled by the mixed-execution
    wrapper in ops.py (paper C2).
    """
    m, k = x.shape
    k2, n = wq.shape
    assert k == k2 and ws.shape == (k // QBLOCK, n), (x.shape, wq.shape, ws.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % QBLOCK == 0, (
        (m, n, k), (bm, bn, bk))
    n_k_blocks = k // bk
    grid = (m // bm, n // bn, n_k_blocks)
    return pl.pallas_call(
        functools.partial(_q8_matmul_kernel, n_k_blocks=n_k_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // QBLOCK, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, wq, scale_operand(ws))

