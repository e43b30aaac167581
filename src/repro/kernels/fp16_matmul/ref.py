"""Pure-jnp oracle for the FP16 GEMM kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def fp16_matmul_ref(x: jax.Array, w: jax.Array,
                    out_dtype=jnp.float32) -> jax.Array:
    """y = f32(x) @ f32(w) with f32 accumulation (IMAX computes f32 after
    inline conversion); HIGHEST precision keeps the TPU's dot at f32."""
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).astype(out_dtype)
