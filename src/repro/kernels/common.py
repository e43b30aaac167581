"""Shared helpers for the kernel wrappers: padding (paper C3: padding is
a transient VMEM-tile artifact, never an HBM layout property), decode
length masks, and the f16 scale planes of the quantized kernels.

Mosaic cannot load float16 on the TPU, so an f16 scale plane enters a
Pallas kernel as its int16 bit pattern (``scale_operand``: a bitcast,
no copy) and is widened to f32 in VMEM (``widen_scales``). Scales stay
f16 in HBM, as the C1 byte counts assume.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantize import QBLOCK


def pad_dim(x: jax.Array, axis: int, mult: int) -> jax.Array:
    """Zero-pad ``axis`` of ``x`` up to the next multiple of ``mult``."""
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def lens_mask(length, bh: int, s_len: int) -> jax.Array:
    """Normalize a decode-attention ``length`` of shape (), (BH,), or
    (BH, Q) into a (BH, Q|1, S) bool attend mask. The (BH, Q) form gives
    every query row its own depth — how the speculative verify forward
    masks draft position j to [0, pos + j + 1)."""
    lens = jnp.asarray(length, jnp.int32)
    if lens.ndim <= 1:
        lens = jnp.broadcast_to(lens.reshape(-1), (bh,))[:, None]
    return jnp.arange(s_len)[None, None, :] < lens[:, :, None]


def scale_operand(s: jax.Array) -> jax.Array:
    """A quantized kernel's scale plane as a Pallas operand: f16 travels
    as its int16 bits; other float dtypes pass unchanged."""
    if s.dtype == jnp.float16:
        return jax.lax.bitcast_convert_type(s, jnp.int16)
    return s


def widen_scales(s: jax.Array) -> jax.Array:
    """In-kernel inverse of ``scale_operand``: a scale block to f32.
    int16 blocks hold f16 bit patterns, decoded with integer ops only
    (exact for every f16 value, subnormals and inf/nan included)."""
    if s.dtype != jnp.int16:
        return s.astype(jnp.float32)
    h = s.astype(jnp.int32) & 0xFFFF
    sign = (h >> 15) << 31
    e = (h >> 10) & 0x1F
    m = h & 0x3FF
    e32 = jnp.where(e == 0x1F, 0xFF, e + 112)
    normal = jax.lax.bitcast_convert_type(sign | (e32 << 23) | (m << 13),
                                          jnp.float32)
    sub = m.astype(jnp.float32) * (2.0 ** -24)
    return jnp.where(e == 0, jnp.where(sign != 0, -sub, sub), normal)


def expand_scales(s: jax.Array, width: int,
                  group_size: int = QBLOCK) -> jax.Array:
    """In-kernel: (rows, width // group_size) f32 scales to (rows,
    width), column c taking group c // group_size. A select per group,
    exact, with no lane-dim reshape (which Mosaic refuses)."""
    group = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], width), 1)
    group = group // group_size
    out = jnp.broadcast_to(s[:, :1], (s.shape[0], width))
    for g in range(1, s.shape[1]):
        out = jnp.where(group == g, s[:, g:g + 1], out)
    return out
