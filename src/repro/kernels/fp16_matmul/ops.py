"""Public wrapper for the FP16 GEMM: the literal C2 mixed-execution split.

``K`` is partitioned into a burst-aligned main segment (Pallas kernel, the
"IMAX" path) and a residual tail (plain XLA, the "host" path), executed
concurrently under jit and summed — exactly Sec III-B's strategy. The
``burst`` parameter is the kernel's K-block; ``offload_info`` reports the
achieved offload rate (paper: ~95 % of MACs at burst=16 on Whisper).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.burst import split_burst
from repro.core.footprint import select_blocks
from repro.kernels.common import pad_dim
from repro.kernels.fp16_matmul.fp16_matmul import fp16_matmul_pallas
from repro.kernels.fp16_matmul.ref import fp16_matmul_ref


@functools.partial(jax.jit, static_argnames=("vmem_budget", "interpret",
                                             "out_dtype"))
def fp16_matmul(x: jax.Array, w: jax.Array, *,
                vmem_budget: int = 4 * 1024 * 1024,
                out_dtype=jnp.float32,
                interpret: bool) -> jax.Array:
    """y = x @ w for fp16/bf16 operands of any shape; C2 split on K."""
    if x.ndim != 2:
        lead = x.shape[:-1]
        y = fp16_matmul(x.reshape(-1, x.shape[-1]), w,
                        vmem_budget=vmem_budget, out_dtype=out_dtype,
                        interpret=interpret)
        return y.reshape(*lead, y.shape[-1])
    m, k = x.shape
    k2, n = w.shape
    assert k == k2

    blocks = select_blocks(m, n, k, vmem_budget, a_dtype="f16", b_dtype="f16")
    bm, bn, bk = blocks.bm, blocks.bn, blocks.bk

    split = split_burst(k, bk)
    x_main, x_res = x[:, :split.k_main], x[:, split.k_main:]
    w_main, w_res = w[:split.k_main], w[split.k_main:]

    xp = pad_dim(x_main, 0, bm)
    wp = pad_dim(w_main, 1, bn)

    if split.k_main > 0:
        y = fp16_matmul_pallas(xp, wp, bm=bm, bn=bn, bk=bk,
                               out_dtype=jnp.float32, interpret=interpret)
        y = y[:m, :n]
    else:
        y = jnp.zeros((m, n), jnp.float32)
    if split.k_residual > 0:
        y = y + fp16_matmul_ref(x_res, w_res)
    return y.astype(out_dtype)


def offload_info(m: int, n: int, k: int,
                 vmem_budget: int = 4 * 1024 * 1024) -> dict:
    """Report the C2 split this wrapper would use for a GEMM shape."""
    blocks = select_blocks(m, n, k, vmem_budget, a_dtype="f16", b_dtype="f16")
    s = split_burst(k, blocks.bk)
    return dict(bm=blocks.bm, bn=blocks.bn, bk=blocks.bk,
                k_main=s.k_main, k_residual=s.k_residual,
                offload_fraction=s.offload_fraction,
                vmem_bytes=blocks.vmem_bytes)
