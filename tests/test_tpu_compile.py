"""Compile the main-path Pallas kernels for a described TPU v5e.

Each case lowers a public kernel wrapper with ``interpret=False`` at the
shapes whisper-tiny.en gives it at full width (d_model 384, d_ff 1536,
vocab 51865, 6 heads of 64, a 30 s window of 1500 encoder frames), plus
the head_dim-128 GQA shapes of qwen3-4b (32 query / 8 KV heads, d_model
2560, d_ff 9728) and qwen3-moe-30b-a3b (32 / 4 heads), and compiles it
with the TPU compiler for a chip that is described, not attached. What
Mosaic refuses fails here, without a chip. Nothing runs: these say
nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quantize import Q4Tensor, Q8Tensor
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.fp16_matmul.ops import fp16_matmul
from repro.kernels.q4_attention.ops import q4_decode_attention
from repro.kernels.q4_matmul.ops import q4_matmul
from repro.kernels.q8_attention.ops import q8_decode_attention
from repro.kernels.q8_matmul.ops import q8_matmul

D, H, HD, FF, VOCAB = 384, 6, 64, 1536, 51865
ENC = 1500                 # encoder frames in a 30 s window
SLOTS = 4                  # serving lanes: decode attention BH = 4 * 6
BUDGET = 4 * 1024 * 1024   # the default dispatch VMEM budget


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, args, **kw):
    """Lower ``fn(*args, **kw)`` for the described chip and compile it;
    the Pallas kernel must be in the program as a Mosaic custom call."""
    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    args = jax.tree.map(sds, args)
    compiled = jax.jit(lambda *a: fn(*a, **kw)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


GEMMS = [(SLOTS, D, FF), (SLOTS, FF, D), (ENC, D, FF), (ENC, FF, D),
         (SLOTS, D, VOCAB)]
GEMM_IDS = [f"m{m}-{k}x{n}" for m, k, n in GEMMS]


@pytest.mark.parametrize("m,k,n", GEMMS, ids=GEMM_IDS)
def test_fp16_matmul_compiles(one_chip, m, k, n):
    _compile(one_chip, fp16_matmul,
             (_s((m, k), jnp.bfloat16), _s((k, n), jnp.bfloat16)),
             vmem_budget=BUDGET, out_dtype=jnp.bfloat16, interpret=False)


@pytest.mark.parametrize("m,k,n", GEMMS, ids=GEMM_IDS)
def test_q8_matmul_compiles(one_chip, m, k, n):
    w = Q8Tensor(_s((k, n), jnp.int8), _s((k // 32, n), jnp.float16))
    _compile(one_chip, q8_matmul, (_s((m, k), jnp.bfloat16), w),
             vmem_budget=BUDGET, out_dtype=jnp.bfloat16, interpret=False)


@pytest.mark.parametrize("m,k,n", [(SLOTS, D, FF), (SLOTS, FF, D)],
                         ids=["m4-384x1536", "m4-1536x384"])
def test_q4_matmul_compiles(one_chip, m, k, n):
    w = Q4Tensor(_s((k // 2, n), jnp.uint8), _s((k // 32, n), jnp.float16))
    _compile(one_chip, q4_matmul, (_s((m, k), jnp.bfloat16), w),
             vmem_budget=BUDGET, out_dtype=jnp.bfloat16, interpret=False)


# self-attention over a 64-position slot, cross-attention over 1500 frames
DECODE_S = [64, ENC]


@pytest.mark.parametrize("s", DECODE_S, ids=[f"S{s}" for s in DECODE_S])
def test_q8_decode_attention_compiles(one_chip, s):
    bh = SLOTS * H
    plane = _s((bh, s, HD), jnp.int8)
    scales = _s((bh, s, HD // 32), jnp.float16)
    _compile(one_chip, q8_decode_attention,
             (_s((bh, 1, HD), jnp.bfloat16), plane, scales, plane, scales,
              _s((bh,), jnp.int32)), interpret=False)


@pytest.mark.parametrize("s", DECODE_S, ids=[f"S{s}" for s in DECODE_S])
def test_q4_decode_attention_compiles(one_chip, s):
    bh = SLOTS * H
    plane = _s((bh, s, HD // 2), jnp.uint8)
    scales = _s((bh, s, HD // 32), jnp.float16)
    _compile(one_chip, q4_decode_attention,
             (_s((bh, 1, HD), jnp.bfloat16), plane, scales, plane, scales,
              _s((bh,), jnp.int32)), interpret=False)


@pytest.mark.parametrize("s,causal", [(ENC, False), (32, True)],
                         ids=["encoder-S1500", "decoder-bucket32-causal"])
def test_flash_attention_compiles(one_chip, s, causal):
    x = _s((1, s, H, HD), jnp.bfloat16)
    _compile(one_chip, flash_attention, (x, x, x), causal=causal,
             interpret=False)


# ---- head_dim 128 with grouped KV heads: qwen3-4b, qwen3-moe-30b-a3b

@pytest.mark.parametrize("m,k,n", [(SLOTS, 2560, 9728), (512, 9728, 2560)],
                         ids=["m4-2560x9728", "m512-9728x2560"])
def test_fp16_matmul_compiles_qwen3_widths(one_chip, m, k, n):
    _compile(one_chip, fp16_matmul,
             (_s((m, k), jnp.bfloat16), _s((k, n), jnp.bfloat16)),
             vmem_budget=BUDGET, out_dtype=jnp.bfloat16, interpret=False)


@pytest.mark.parametrize("kv_heads", [8, 4], ids=["kv8", "kv4"])
def test_flash_attention_compiles_gqa_head_dim_128(one_chip, kv_heads):
    q = _s((1, 512, 32, 128), jnp.bfloat16)
    kv = _s((1, 512, kv_heads, 128), jnp.bfloat16)
    _compile(one_chip, flash_attention, (q, kv, kv), causal=True,
             interpret=False)


def test_q8_decode_attention_compiles_head_dim_128(one_chip):
    bh, s = SLOTS * 32, 2048
    plane = _s((bh, s, 128), jnp.int8)
    scales = _s((bh, s, 128 // 32), jnp.float16)
    _compile(one_chip, q8_decode_attention,
             (_s((bh, 1, 128), jnp.bfloat16), plane, scales, plane, scales,
              _s((bh,), jnp.int32)), interpret=False)
