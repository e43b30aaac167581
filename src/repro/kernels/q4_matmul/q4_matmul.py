"""Pallas TPU kernel: Q4_0 dequant-in-kernel GEMM (paper C1, int4 tier).

``y[M, N] = x[M, K] @ dequant(wp[K/2, N], ws[K/32, N])``

The int4 tier below Q8_0: two 4-bit codes per byte along K plus one f16
scale per 32-element block — 0.5625 bytes/element streamed from HBM, the
CGLA follow-up's headline low-bit dot-product saving. The nibbles are
unpacked and scaled *in VMEM* immediately before the MXU dot, so the
weight plane never exists in HBM above 4 bits/elem.

Block shapes come from ``repro.core.footprint.select_blocks`` under a
VMEM byte budget (C4), with bk rounded to the QBLOCK multiple so scale
blocks never straddle a tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import QBLOCK
from repro.kernels.common import scale_operand, widen_scales


def _q4_matmul_kernel(xe_ref, xo_ref, wp_ref, ws_ref, o_ref, acc_ref, *,
                      n_k_blocks):
    """One (bm, bn) output tile; grid dim 2 walks K in bk steps.

    Packed row i holds logical rows 2i (low nibble) and 2i+1 (high), so
    the tile is ``xe @ lo + xo @ hi`` over the even/odd columns of x:
    the nibbles are never interleaved back into K order in VMEM."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p = wp_ref[...].astype(jnp.int32)                   # (bk//2, bn)
    half, bn = p.shape
    s = widen_scales(ws_ref[...])                       # (bk // 32, bn)
    # packed rows 16g..16g+15 are logical rows 32g..32g+31: scale group g
    scales = jnp.broadcast_to(s[:, None, :], (s.shape[0], QBLOCK // 2, bn))
    scales = scales.reshape(half, bn)
    lo = ((p & 0xF) - 8).astype(jnp.float32) * scales   # C1: in VMEM
    hi = ((p >> 4) - 8).astype(jnp.float32) * scales
    dims = (((1,), (0,)), ((), ()))
    acc_ref[...] += (
        jax.lax.dot_general(xe_ref[...].astype(jnp.float32), lo, dims,
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(xo_ref[...].astype(jnp.float32), hi, dims,
                              preferred_element_type=jnp.float32))

    @pl.when(k_idx == n_k_blocks - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "out_dtype"))
def q4_matmul_pallas(x: jax.Array, wp: jax.Array, ws: jax.Array, *,
                     bm: int = 128, bn: int = 128, bk: int = 512,
                     out_dtype=jnp.float32,
                     interpret: bool = False) -> jax.Array:
    """x: (M, K) float; wp: (K//2, N) packed uint8; ws: (K//QBLOCK, N).

    M % bm == 0, N % bn == 0, K % bk == 0, bk % QBLOCK == 0 — the burst-
    aligned "main segment"; ragged shapes are handled by the mixed-execution
    wrapper in ops.py (paper C2).
    """
    m, k = x.shape
    k2, n = wp.shape
    assert k == 2 * k2 and ws.shape == (k // QBLOCK, n), (
        x.shape, wp.shape, ws.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % QBLOCK == 0, (
        (m, n, k), (bm, bn, bk))
    n_k_blocks = k // bk
    grid = (m // bm, n // bn, n_k_blocks)
    xe, xo = x[:, 0::2], x[:, 1::2]      # the nibble order, split on K
    return pl.pallas_call(
        functools.partial(_q4_matmul_kernel, n_k_blocks=n_k_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk // 2), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, bk // 2), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk // QBLOCK, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xe, xo, wp, scale_operand(ws))
