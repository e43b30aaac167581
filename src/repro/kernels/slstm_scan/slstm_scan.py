"""Pallas TPU kernel: time-chunked sLSTM scan (§Perf xlstm iteration 3).

The XLA formulation of the sLSTM recurrence round-trips the (4, B, H, hd)
state and every per-timestep intermediate through HBM 4096 times per
segment — the worst memory term of the whole 40-cell table. The TPU-native
fix keeps the recurrence resident:

* the stacked recurrent weights R (4, H, hd, hd) and the running state
  (c, n, h, m) live in VMEM for the whole sequence;
* the precomputed input pre-activations ``wx`` stream in T-step chunks
  (one grid step = T timesteps), and only the h outputs stream back;
* HBM traffic per chunk = wx-in + h-out (+ R and state once per
  sequence) — ~50x less than the per-step XLA loop.

Grid dim 0 walks the sequence chunks sequentially ("arbitrary"
semantics); VMEM scratch persists across grid steps, carrying the state.
Numerics match the model's stabilized formulation exactly (log-sigmoid
forget, m-state max-stabilizer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _slstm_chunk_kernel(wx_ref, r_ref, s0_ref, hs_ref, sout_ref, state_ref,
                        *, t_chunk, n_chunks):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        state_ref[...] = s0_ref[...].astype(jnp.float32)

    r = r_ref[...].astype(jnp.float32)            # (4, H, hd, hd)

    def step(t, _):
        st = state_ref[...]
        c, n, h, m = st[0], st[1], st[2], st[3]
        wx_t = wx_ref[t].astype(jnp.float32)      # (4, B, H, hd)
        rh = jax.lax.dot_general(                 # (B,H,e)x(4,H,e,f)
            h, r, (((2,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32)   # -> (H, B, 4, f)
        pre = wx_t + rh.transpose(2, 1, 0, 3)     # (4, B, H, hd)
        i_r, f_r, z_r, o_r = pre[0], pre[1], pre[2], pre[3]
        logf = jax.nn.log_sigmoid(f_r)
        m_new = jnp.maximum(logf + m, i_r)
        i_g = jnp.exp(i_r - m_new)
        f_g = jnp.exp(logf + m - m_new)
        c_new = f_g * c + i_g * jnp.tanh(z_r)
        n_new = f_g * n + i_g
        h_new = jax.nn.sigmoid(o_r) * c_new / jnp.maximum(n_new, 1e-6)
        state_ref[...] = jnp.stack([c_new, n_new, h_new, m_new])
        hs_ref[t] = h_new.astype(hs_ref.dtype)
        return 0

    jax.lax.fori_loop(0, t_chunk, step, 0)

    @pl.when(i == n_chunks - 1)
    def _done():
        sout_ref[...] = state_ref[...].astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("t_chunk", "interpret"))
def slstm_scan_pallas(wx: jax.Array, r_all: jax.Array, state0: jax.Array, *,
                      t_chunk: int = 64,
                      interpret: bool = False):
    """wx: (S, 4, B, H, hd) input pre-activations (Wx+b, precomputed);
    r_all: (4, H, hd, hd); state0: (4, B, H, hd) stacked (c, n, h, m).
    Returns (hs: (S, B, H, hd) f32, state_final: (4, B, H, hd)).
    S must divide by t_chunk (ops.py pads)."""
    s, four, b, h, hd = wx.shape
    assert four == 4 and r_all.shape == (4, h, hd, hd), (wx.shape,
                                                         r_all.shape)
    assert s % t_chunk == 0, (s, t_chunk)
    n_chunks = s // t_chunk
    kernel = functools.partial(_slstm_chunk_kernel, t_chunk=t_chunk,
                               n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((t_chunk, 4, b, h, hd), lambda i: (i, 0, 0, 0, 0)),
            pl.BlockSpec((4, h, hd, hd), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((4, b, h, hd), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((t_chunk, b, h, hd), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((4, b, h, hd), lambda i: (0, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, b, h, hd), jnp.float32),
            jax.ShapeDtypeStruct((4, b, h, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((4, b, h, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(wx, r_all, state0)
