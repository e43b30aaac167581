"""Pallas TPU kernel: FP16 GEMM with inline FP16→FP32 upconversion (C1).

IMAX performs FP16→FP32 conversion inline on PE bit-manipulation units to
avoid dedicated hardware; the TPU analogue is storing/streaming fp16 and
upcasting in VMEM right before the MXU dot (the MXU natively consumes
bf16/f32 — fp16 inputs would otherwise be upcast in HBM, doubling traffic).

The paper's SIMD pairing (two 32-bit ops on a 64-bit datapath) and 4-way
column multithreading map onto the MXU's native 8x128 lane structure and
the grid pipeline — reflected here by MXU-aligned block shapes and the
k-grid accumulation pipeline rather than emulated literally (DESIGN.md §2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fp16_matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k_blocks):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # inline fp16 -> fp32 conversion in VMEM (C1)
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k_blocks - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "out_dtype"))
def fp16_matmul_pallas(x: jax.Array, w: jax.Array, *,
                       bm: int = 128, bn: int = 128, bk: int = 512,
                       out_dtype=jnp.float32,
                       interpret: bool = False) -> jax.Array:
    """x: (M, K); w: (K, N) float16. Shapes must be block-aligned (the
    mixed-execution wrapper in ops.py handles ragged K/M/N)."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, ((m, n, k), (bm, bn, bk))
    n_k_blocks = k // bk
    return pl.pallas_call(
        functools.partial(_fp16_matmul_kernel, n_k_blocks=n_k_blocks),
        grid=(m // bm, n // bn, n_k_blocks),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)
