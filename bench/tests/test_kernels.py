"""The kernels' FLOP and byte counts, against hand counts, and the
model FLOPs behind ``mfu``."""

import pytest

import flops
import spec
import trace_reduce


def test_fp16_matmul_cost():
    km = spec.kernel_model("fp16_matmul")
    x, w, y = (("bf16", (128, 512), True), ("bf16", (512, 384), True),
               ("f32", (128, 384), True))
    f, b = km.cost_of_shapes(x, w, y)
    assert f == 2 * 128 * 512 * 384
    assert b == 128 * 512 * 2 + 512 * 384 * 2 + 128 * 384 * 4
    # an operand already in the core's memory costs no HBM bytes
    f2, b2 = km.cost_of_shapes(x, ("bf16", (512, 384), False), y)
    assert f2 == f and b2 == b - 512 * 384 * 2


def test_flash_attention_cost():
    km = spec.kernel_model("flash_attention")
    t = ("bf16", (6, 1536, 64), True)
    f, b = km.cost_of_shapes(t, t, t, t)
    assert f == 4 * 6 * 1536 * 1536 * 64
    assert b == 4 * 6 * 1536 * 64 * 2


def test_cost_reads_shapes_from_hlo_text():
    km = spec.kernel_model("fp16_matmul")
    text = ("%fp16_matmul_pallas.42 = f32[128,384]{1,0:T(8,128)S(1)} "
            "custom-call(bf16[128,512]{1,0:T(8,128)(2,1)S(1)} %pad.1, "
            "bf16[512,384]{1,0:T(8,128)(2,1)S(1)} %pad.2), "
            "custom_call_target=\"tpu_custom_call\"")
    ev = trace_reduce.Event(trace_reduce.short_name(text), 0, 1000,
                            {"hlo": text})
    assert trace_reduce.matches(ev, km.TRACE_NAMES)
    assert km.cost(ev) == km.cost_of_shapes(
        ("bf16", (128, 512), False), ("bf16", (512, 384), False),
        ("f32", (128, 384), False))


def test_model_flops_by_hand():
    cfg = {"config": {"d_model": 4, "encoder_ffn_dim": 8,
                      "encoder_layers": 1, "decoder_layers": 1,
                      "vocab_size": 10}}
    # encoder over 3 frames: stem 2*3*4*4, q/k/v/o 2*3*4*16,
    # scores+values 4*3*3*4, MLP 4*3*4*8
    assert flops.encoder(cfg, 3) == 96 + 384 + 144 + 384
    # one decoder position at context 0 over 3 frames
    one = (2 * 4 * 16 + 4 * 1 * 4 + 2 * 4 * 8 + 4 * 3 * 4 + 4 * 4 * 8
           + 2 * 4 * 10)
    assert flops.decoder_tokens(cfg, 0, 1, 3) == one
    assert flops.cross_kv(cfg, 3) == 2 * 3 * 4 * 8
    assert flops.request(cfg, 3, 1, 1) == pytest.approx(
        flops.encoder(cfg, 3) + flops.cross_kv(cfg, 3) + one)
