"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Sweeps shapes × dtypes, including non-multiple K (the C2 mixed-execution
split) and budget-driven block selection (the C4 VMEM knob).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize import quantize_q8_0
from repro.kernels.fp16_matmul.ops import fp16_matmul, offload_info
from repro.kernels.fp16_matmul.ref import fp16_matmul_ref
from repro.kernels.q8_matmul.ops import q8_matmul, q8_matmul_xla
from repro.kernels.q8_matmul.ref import q8_matmul_ref
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref

KEY = jax.random.key(42)


# ------------------------------------------------------------------ q8 gemm

@pytest.mark.parametrize("m,n,k", [
    (8, 128, 64), (16, 128, 128),
    pytest.param(128, 256, 512, marks=pytest.mark.slow),  # big-tile sweep
    (8, 128, 96),          # K not a multiple of default bk -> C2 residual
    (5, 130, 64),          # ragged M/N -> padding path
    (1, 128, 2048),        # matvec (decode shape)
])
def test_q8_matmul_matches_ref(m, n, k):
    x = jax.random.normal(jax.random.fold_in(KEY, m * n), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(KEY, k), (k, n), jnp.float32)
    wq = quantize_q8_0(w, axis=0)
    got = q8_matmul(x, wq, interpret=True)
    want = q8_matmul_ref(x, wq.q, wq.scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("budget", [256 * 1024, 1024 * 1024, 8 * 1024 * 1024])
def test_q8_matmul_budget_sweep(budget):
    """The C4 knob: result identical under any VMEM budget."""
    x = jax.random.normal(KEY, (32, 320), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(KEY, 7), (320, 256), jnp.float32)
    wq = quantize_q8_0(w, axis=0)
    got = q8_matmul(x, wq, vmem_budget=budget, interpret=True)
    want = q8_matmul_ref(x, wq.q, wq.scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_q8_matmul_approximates_dense():
    """Quantized GEMM ~= dense GEMM within the Q8 error envelope."""
    x = jax.random.normal(KEY, (16, 256), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(KEY, 3), (256, 128), jnp.float32)
    wq = quantize_q8_0(w, axis=0)
    got = q8_matmul(x, wq, interpret=True)
    dense = x @ w
    # relative error ~ 1/127 per element, sqrt(K) accumulation
    rel = float(jnp.linalg.norm(got - dense) / jnp.linalg.norm(dense))
    assert rel < 0.02, rel


def test_q8_matmul_batched_input():
    x = jax.random.normal(KEY, (2, 4, 64), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(KEY, 9), (64, 128), jnp.float32)
    wq = quantize_q8_0(w, axis=0)
    got = q8_matmul(x, wq, interpret=True)
    assert got.shape == (2, 4, 128)
    want = q8_matmul_xla(x, wq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


# ----------------------------------------------------------------- fp16 gemm

@pytest.mark.parametrize("m,n,k,dtype", [
    (8, 128, 64, jnp.float16), (64, 256, 512, jnp.float16),
    (16, 128, 100, jnp.float16),    # K=100: split 96+4 at burst 16
    (7, 99, 35, jnp.bfloat16),      # fully ragged
    (1, 512, 1024, jnp.bfloat16),   # matvec
])
def test_fp16_matmul_matches_ref(m, n, k, dtype):
    x = jax.random.normal(jax.random.fold_in(KEY, m + n), (m, k)).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(KEY, k + 1), (k, n)).astype(dtype)
    got = fp16_matmul(x, w, interpret=True)
    want = fp16_matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_fp16_offload_info_reports_split():
    info = offload_info(64, 128, 1000)
    assert info["k_main"] + info["k_residual"] == 1000
    assert info["k_main"] % info["bk"] == 0
    assert 0.85 < info["offload_fraction"] <= 1.0
    # hardware-aligned K (all assigned archs): full offload
    info = offload_info(64, 128, 4096)
    assert info["offload_fraction"] == 1.0


# ------------------------------------------------------------ flash attention

@pytest.mark.parametrize("causal,window,softcap", [
    (True, None, None), (True, 64, None), (True, None, 30.0),
    (False, None, None),
])
def test_flash_attention_matches_ref(causal, window, softcap):
    bh, s, d = 4, 256, 64
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (bh, s, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (bh, s, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (bh, s, d), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 softcap=softcap, bq=64, bk=64,
                                 interpret=True)
    want = attention_ref(q, k, v, causal=causal, window=window,
                         softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("s", [
    128,
    pytest.param(192, marks=pytest.mark.slow),
    pytest.param(384, marks=pytest.mark.slow),
])
def test_flash_attention_seq_sweep(s):
    bh, d = 2, 32
    q = jax.random.normal(jax.random.fold_in(KEY, s), (bh, s, d))
    k = jax.random.normal(jax.random.fold_in(KEY, s + 1), (bh, s, d))
    v = jax.random.normal(jax.random.fold_in(KEY, s + 2), (bh, s, d))
    got = flash_attention_pallas(q, k, v, causal=True, bq=64, bk=64,
                                 interpret=True)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_gqa_wrapper():
    """(B,S,H,D) GQA wrapper: kv heads repeat to q heads."""
    b, s, h, hkv, d = 2, 128, 4, 2, 32
    q = jax.random.normal(jax.random.fold_in(KEY, 21), (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 22), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 23), (b, s, hkv, d))
    got = flash_attention(q, k, v, causal=True, interpret=True)
    kr = jnp.repeat(k, 2, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vr = jnp.repeat(v, 2, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, d)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    want = attention_ref(qr, kr, vr, causal=True).reshape(
        b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# --------------------------------------------------- chunked-XLA attention

def test_chunked_attention_equals_dense():
    """The model's chunked online-softmax (XLA binding of the kernel)
    must equal dense attention — incl. local windows and softcaps.

    Tolerance: the production path streams Q/K/V/P into the dot in bf16
    with f32 accumulation (the C1-inline optimization, §Perf cell C), so
    agreement with the f32 dense oracle is at bf16 input precision
    (~8-bit mantissa -> ~1e-2 relative)."""
    from repro.models.attention import chunked_attention
    b, s, h, d = 2, 200, 4, 32
    q = jax.random.normal(jax.random.fold_in(KEY, 11), (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 12), (b, s, h, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 13), (b, s, h, d))
    for window, softcap in [(None, None), (37, None), (None, 25.0)]:
        got = chunked_attention(q, k, v, causal=True, window=window,
                                softcap=softcap, chunk=64)
        want = attention_ref(
            q.transpose(0, 2, 1, 3).reshape(b * h, s, d),
            k.transpose(0, 2, 1, 3).reshape(b * h, s, d),
            v.transpose(0, 2, 1, 3).reshape(b * h, s, d),
            causal=True, window=window, softcap=softcap,
        ).reshape(b, h, s, d).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)


# ------------------------------------------------------------- slstm kernel

@pytest.mark.parametrize("s,b,h,hd,t", [
    (64, 2, 4, 32, 64), (100, 2, 4, 32, 32),   # ragged S -> padded chunk
    pytest.param(128, 1, 2, 128, 32, marks=pytest.mark.slow),
])
def test_slstm_scan_kernel_matches_ref(s, b, h, hd, t):
    """Time-chunked Pallas sLSTM (state resident in VMEM) ≡ lax.scan
    oracle, including state-preserving chunk padding (§Perf cell A)."""
    from repro.kernels.slstm_scan.ops import slstm_scan
    from repro.kernels.slstm_scan.ref import slstm_scan_ref
    wx = jax.random.normal(jax.random.fold_in(KEY, s),
                           (s, 4, b, h, hd), jnp.float32) * 0.5
    r = jax.random.normal(jax.random.fold_in(KEY, s + 1),
                          (4, h, hd, hd), jnp.float32) * 0.1
    s0 = jnp.stack([jnp.zeros((b, h, hd))] * 3
                   + [jnp.full((b, h, hd), -1e30)])
    hs, st = slstm_scan(wx, r, s0, t_chunk=t, interpret=True)
    hs_ref, st_ref = slstm_scan_ref(wx, r, s0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=2e-5, atol=2e-5)


def test_slstm_kernel_vmem_budget():
    """Resident R + state fit VMEM with double-buffered wx chunks (C4)."""
    from repro.kernels.slstm_scan.ops import kernel_traffic_model
    m = kernel_traffic_model(4096, 16, 4, 256, n_segments=12)
    wx_chunk = 64 * 4 * 16 * 4 * 256 * 4          # (T,4,B,H,hd) f32
    assert m["vmem_resident"] + 2 * wx_chunk < 128 * 1024 * 1024


# ------------------------------------------------------- q8 decode attention

@pytest.mark.parametrize("bh,s,d,length,bk", [
    (4, 256, 64, 200, 128),       # masked tail
    (2, 300, 32, 300, 128),       # ragged S -> padded blocks
    (8, 128, 128, 1, 64),         # single valid position
])
def test_q8_decode_attention_matches_ref(bh, s, d, length, bk):
    """Dequant-in-kernel Q8_0 KV attention ≡ dequantized dense oracle
    (paper C1 applied to the decode cache — the §Roofline decode
    bottleneck; cache stream 0.53x of bf16)."""
    from repro.kernels.q8_attention.ops import (q8_decode_attention,
                                                quantize_kv)
    from repro.kernels.q8_attention.ref import q8_decode_attention_ref
    q = jax.random.normal(jax.random.fold_in(KEY, bh), (bh, 1, d))
    k = jax.random.normal(jax.random.fold_in(KEY, s), (bh, s, d))
    v = jax.random.normal(jax.random.fold_in(KEY, d), (bh, s, d))
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    got = q8_decode_attention(q, kq, ks, vq, vs, length, bk=bk,
                              interpret=True)
    want = q8_decode_attention_ref(q, kq, ks, vq, vs, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_q8_decode_attention_per_lane_lengths():
    """(BH,) length vector: each lane masks at its own depth — the
    serving engine's continuous-batching configuration — and must agree
    with per-lane scalar-length calls."""
    from repro.kernels.q8_attention.ops import (q8_decode_attention,
                                                quantize_kv)
    from repro.kernels.q8_attention.ref import q8_decode_attention_ref
    bh, s, d = 4, 128, 32
    q = jax.random.normal(jax.random.fold_in(KEY, 41), (bh, 1, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 42), (bh, s, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 43), (bh, s, d))
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    lens = jnp.asarray([1, 17, 64, 128], jnp.int32)
    got = q8_decode_attention(q, kq, ks, vq, vs, lens, bk=64,
                              interpret=True)
    want = q8_decode_attention_ref(q, kq, ks, vq, vs, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # per-lane == the same lane run alone at a scalar length
    for i, n in enumerate(lens):
        one = q8_decode_attention_ref(q[i:i + 1], kq[i:i + 1],
                                      ks[i:i + 1], vq[i:i + 1],
                                      vs[i:i + 1], int(n))
        np.testing.assert_allclose(np.asarray(want[i]), np.asarray(one[0]),
                                   rtol=1e-5, atol=1e-5)


def test_q8_decode_attention_close_to_exact():
    """Within the Q8 error envelope of exact bf16 attention."""
    from repro.kernels.q8_attention.ops import (q8_decode_attention,
                                                quantize_kv)
    bh, s, d = 4, 256, 64
    q = jax.random.normal(jax.random.fold_in(KEY, 31), (bh, 1, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 32), (bh, s, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 33), (bh, s, d))
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    got = q8_decode_attention(q, kq, ks, vq, vs, s, interpret=True)
    sd = jnp.einsum("bqd,bkd->bqk", q, k) * d ** -0.5
    dense = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(sd, -1), v)
    rel = float(jnp.linalg.norm(got - dense) / jnp.linalg.norm(dense))
    assert rel < 0.02, rel


# ------------------------------------------- padded flash / q4 kernels

@pytest.mark.parametrize("s,causal", [(200, False), (200, True), (12, False)])
def test_flash_attention_wrapper_pads_ragged_seq(s, causal):
    """S that no power-of-two block divides (a 30 s Whisper window is
    1500 frames) pads to 128-row blocks; padded keys never leak into
    the softmax and padded query rows are sliced off."""
    b, h, d = 1, 2, 64
    q = jax.random.normal(jax.random.fold_in(KEY, 61), (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 62), (b, s, h, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 63), (b, s, h, d))
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    flat = [t.transpose(0, 2, 1, 3).reshape(b * h, s, d) for t in (q, k, v)]
    want = attention_ref(*flat, causal=causal).reshape(
        b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_widen_scales_decodes_every_f16_bit_pattern():
    """f16 scales enter the Pallas kernels as int16 bits (Mosaic cannot
    load f16 on the TPU); the in-kernel decode is exact for all 65536
    patterns, subnormals, signed zeros and inf/nan included."""
    from repro.kernels.common import scale_operand, widen_scales
    bits = np.arange(65536, dtype=np.uint32).astype(np.uint16)
    f16 = bits.view(np.float16)
    got = np.asarray(widen_scales(scale_operand(jnp.asarray(f16))))
    want = f16.astype(np.float32)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.signbit(got), np.signbit(want))


@pytest.mark.parametrize("m,n,k", [(8, 128, 64), (5, 130, 96), (1, 256, 512)])
def test_q4_matmul_matches_ref(m, n, k):
    """Nibble-unpack-in-kernel Q4_0 GEMM (even/odd K split) == oracle,
    ragged M/N and the C2 residual included."""
    from repro.core.quantize import quantize_q4_0
    from repro.kernels.q4_matmul.ops import q4_matmul
    from repro.kernels.q4_matmul.ref import q4_matmul_ref
    x = jax.random.normal(jax.random.fold_in(KEY, m + 70), (m, k))
    w = jax.random.normal(jax.random.fold_in(KEY, n + 71), (k, n))
    wq = quantize_q4_0(w, axis=0)
    got = q4_matmul(x, wq, interpret=True)
    want = q4_matmul_ref(x, wq.q, wq.scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("bh,s,d,length,bk", [
    (4, 256, 64, 200, 128),       # masked tail
    (2, 300, 32, 300, 128),       # ragged S -> padded blocks
])
def test_q4_decode_attention_matches_ref(bh, s, d, length, bk):
    """Nibble-packed Q4_0 KV attention (even/odd head_dim split, per-lane
    lengths in SMEM) == the dequantized dense oracle."""
    from repro.kernels.q4_attention.ops import (q4_decode_attention,
                                                quantize_kv_q4)
    from repro.kernels.q4_attention.ref import q4_decode_attention_ref
    q = jax.random.normal(jax.random.fold_in(KEY, bh + 80), (bh, 1, d))
    k = jax.random.normal(jax.random.fold_in(KEY, s + 81), (bh, s, d))
    v = jax.random.normal(jax.random.fold_in(KEY, d + 82), (bh, s, d))
    kp, ks = quantize_kv_q4(k)
    vp, vs = quantize_kv_q4(v)
    lens = jnp.full((bh,), length, jnp.int32).at[0].set(1)
    got = q4_decode_attention(q, kp, ks, vp, vs, lens, bk=bk,
                              interpret=True)
    want = q4_decode_attention_ref(q, kp, ks, vp, vs, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
