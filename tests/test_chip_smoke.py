"""``chip_smoke.py`` on the CPU: its phases at ``reduced()`` size, its
binding checks, and its refusal to report a result without a TPU."""

import importlib.util
import pathlib

import pytest

from repro.configs import get_config, reduced
from repro.kernels.api import DispatchContext

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def record(smoke):
    """Every phase once, at reduced width and short audio."""
    return smoke.run_phases(
        reduced(get_config("whisper-tiny-en")), audio_s=2.0,
        serve_s=(1, 2), n_requests=3, max_new=8, n_slots=2,
        decode_block=4, stream_chunk=40, log=lambda s: None)


def test_stream_equals_oneshot(record):
    assert record["stream"]["tokens"] == record["oneshot_bf16"]["tokens"]
    assert record["stream"]["n_partials"] > 1
    assert len(record["oneshot_q8_0"]["tokens"]) == 8


def test_engine_matches_slotfree_greedy(record):
    p = record["parity"]
    assert 0 < p["matched"] <= p["of"] == 8
    assert all(len(o) == 8 for o in record["serve"]["outputs"])


def test_logits_agree_with_ref_backends(smoke, record):
    lg = record["logits"]
    assert lg["finite"] and lg["rel"] <= smoke.LOGIT_REL_TOL, lg


def test_check_binding_flags_ref_interpret_and_missing_pallas(smoke):
    ok = {(op, "accel", "pallas"): 1 for op in smoke.PALLAS_OPS}
    ok[("flash_attention", "accel->host", "xla")] = 2
    chip = DispatchContext(vmem_budget=1, interpret=False)
    assert smoke.check_binding(ok, [chip]) == []
    bad = dict(ok)
    bad[("fp16_matmul", "host", "ref")] = 1
    del bad[("q8_decode_attention", "accel", "pallas")]
    got = smoke.check_binding(
        bad, [chip, DispatchContext(vmem_budget=1, interpret=True)])
    assert len(got) == 3, got


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out
