"""Public wrapper for the sLSTM time-chunk kernel (+ its roofline model).

``slstm_scan`` pads S to the chunk multiple and dispatches the Pallas
kernel (interpret=True on CPU). ``kernel_traffic_model`` is the analytic
HBM-traffic model used by EXPERIMENTS.md §Perf (the kernel cannot be
lowered by the CPU backend, so its roofline term is derived, not parsed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.slstm_scan.slstm_scan import slstm_scan_pallas


@functools.partial(jax.jit, static_argnames=("t_chunk", "interpret"))
def slstm_scan(wx: jax.Array, r_all: jax.Array, state0: jax.Array, *,
               t_chunk: int = 64, interpret: bool):
    """wx: (S, 4, B, H, hd); returns (hs (S,B,H,hd), state (4,B,H,hd))."""
    s = wx.shape[0]
    pad = (-s) % t_chunk
    if pad:
        # state-preserving padding: i-gate -> -inf (add nothing),
        # f-gate -> +large (log-sigmoid ~ 0: keep everything); the padded
        # h outputs are sliced off below.
        _, four, b, h, hd = wx.shape
        pad_row = jnp.stack([
            jnp.full((b, h, hd), -1e30, wx.dtype),   # i
            jnp.full((b, h, hd), 40.0, wx.dtype),    # f
            jnp.zeros((b, h, hd), wx.dtype),         # z
            jnp.zeros((b, h, hd), wx.dtype),         # o
        ])
        wx = jnp.concatenate(
            [wx, jnp.broadcast_to(pad_row, (pad,) + pad_row.shape)], 0)
    hs, state = slstm_scan_pallas(wx, r_all, state0, t_chunk=t_chunk,
                                  interpret=interpret)
    if pad:
        # c/n/m are pad-invariant; h drifts on padded steps — restore the
        # last valid output
        state = jnp.concatenate([state[:2], hs[s - 1][None], state[3:]])
    return hs[:s], state


def kernel_traffic_model(s: int, b: int, h: int, hd: int,
                         n_segments: int, n_micro: int = 1,
                         bwd_factor: float = 3.0) -> dict:
    """Per-device HBM bytes for the kernelized sLSTM pass.

    Streams: wx in (4·S·B·H·hd f32 — written once by the projection GEMM,
    read once by the kernel), h out (S·B·H·hd f32), R + state resident in
    VMEM (R: 4·H·hd² ≈ 4 MB; state: 4·B·H·hd ≈ 256 KB — both fit v5e's
    128 MB VMEM with the wx chunk double-buffered). ``bwd_factor``
    models the backward kernel (re-reads wx + h, writes dwx, accumulates
    dR in VMEM) at ~2x forward plus the recompute read.
    """
    wx_bytes = 4 * s * b * h * hd * 4
    h_bytes = s * b * h * hd * 4
    r_bytes = 4 * h * hd * hd * 4
    fwd = 2 * wx_bytes + 2 * h_bytes + r_bytes   # write+read each stream
    total = fwd * (1 + bwd_factor) * n_segments * n_micro
    return {"fwd_bytes": fwd, "total_bytes": total,
            "vmem_resident": r_bytes + 4 * b * h * hd * 4}
