"""Serving engine + scheduler: correctness under continuous batching."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels.api import reset_dispatch_log
from repro.models.model import build
from repro.serving.engine import (AudioRequest, Request, ServeEngine,
                                  _bucket)
from repro.serving.reference import (assert_greedy_matches,
                                     greedy_reference, tie_margin)
from repro.serving.scheduler import BatchScheduler


def _engine(arch="qwen3-4b", n_slots=4, max_len=96, seed=0, **kw):
    cfg = reduced(get_config(arch))
    model = build(cfg)
    params = model.init_values(jax.random.key(seed))
    return cfg, model, params, ServeEngine(model, params, n_slots=n_slots,
                                           max_len=max_len, **kw)


def test_engine_matches_slotfree_reference():
    """Tokens from the batched continuous engine == full-forward greedy,
    up to near-ties at the bf16 rounding boundary (per-dtype margin)."""
    cfg, model, params, eng = _engine()
    margin = tie_margin(cfg)
    prompts = [[5, 6, 7, 8], [9, 10, 11], [3, 4, 5, 6, 7, 8, 9]]
    sts = [eng.admit(Request(uid=i, tokens=p, max_new=4, eos_id=-2))
           for i, p in enumerate(prompts)]
    while eng.n_active:
        eng.step()
    for st, p in zip(sts, prompts):
        assert_greedy_matches(model, params, p, st.out, margin)


def test_interleaved_admission_does_not_corrupt():
    """A request admitted mid-decode of others produces the same tokens
    as one decoded alone — the cache-isolation property."""
    cfg, model, params, eng = _engine()
    eng.admit(Request(uid=0, tokens=[5, 6, 7], max_new=6, eos_id=-2))
    eng.step()
    eng.step()
    st1 = eng.admit(Request(uid=1, tokens=[8, 9, 10, 11], max_new=4,
                            eos_id=-2))
    while eng.n_active:
        eng.step()

    _, model2, params2, eng2 = _engine()
    st1_alone = eng2.admit(Request(uid=9, tokens=[8, 9, 10, 11], max_new=4,
                                   eos_id=-2))
    while eng2.n_active:
        eng2.step()
    assert st1.out == st1_alone.out


def test_eos_stops_early():
    cfg, model, params, eng = _engine()
    eng.admit(Request(uid=0, tokens=[5, 6, 7], max_new=50, eos_id=-2))
    want = greedy_reference(model, params, [5, 6, 7], 3)
    eos = want[1]
    st2 = eng.admit(Request(uid=1, tokens=[5, 6, 7], max_new=50, eos_id=eos))
    while eng.n_active:
        eng.step()
    assert st2.out[-1] == eos and len(st2.out) == 2


def test_pool_exhaustion_returns_none():
    cfg, model, params, eng = _engine(n_slots=1)
    assert eng.admit(Request(uid=0, tokens=[3, 4], max_new=8,
                             eos_id=-2)) is not None
    assert eng.admit(Request(uid=1, tokens=[5, 6], max_new=8,
                             eos_id=-2)) is None


def test_request_too_long_raises():
    cfg, model, params, eng = _engine(max_len=32)
    with pytest.raises(ValueError):
        eng.admit(Request(uid=0, tokens=list(range(3, 30)), max_new=16))


def test_scheduler_drains_and_reuses_slots():
    cfg, model, params, eng = _engine(n_slots=2)
    sched = BatchScheduler(eng)
    for i in range(7):
        sched.submit(Request(uid=i, tokens=[3 + i, 4, 5], max_new=3,
                             eos_id=-2))
    sched.run_until_drained(max_ticks=200)
    assert sched.drained
    assert sched.metrics.completed == 7
    assert len(sched.results) == 7
    assert sched.metrics.mean_occupancy > 0.3


def test_bucket_rounding():
    assert _bucket(3) == 32
    assert _bucket(33) == 64
    assert _bucket(5000) == 6144


# --------------------------------------------------------------- enc-dec


WHISPER_PROMPTS = [[5, 6, 7, 8], [9, 10, 11], [3, 4, 5, 6, 7]]


def _whisper_frames(cfg, rng, lens=(8, 12, 8)):
    return [rng.standard_normal((n, cfg.d_model)).astype(np.float32) * 0.5
            for n in lens]


def _run_whisper_engine(cache_dtype, frames, n_new=4):
    cfg, model, params, eng = _engine("whisper-tiny-en", n_slots=4,
                                      max_len=64, enc_len=16,
                                      cache_dtype=cache_dtype)
    sts = [eng.admit(AudioRequest(uid=i, tokens=p, max_new=n_new,
                                  eos_id=-2, enc_frames=f))
           for i, (p, f) in enumerate(zip(WHISPER_PROMPTS, frames))]
    while eng.n_active:
        eng.step()
    return cfg, model, params, eng, sts


def test_whisper_engine_matches_slotfree_reference():
    """Enc-dec serving parity: the engine encodes frames at their exact
    length, caches per-slot encoder K/V (padded to the pool enc_len),
    and masks each lane's cross-attention — so batched continuous
    decoding must equal the slot-free full-forward greedy reference."""
    rng = np.random.default_rng(0)
    cfg0 = reduced(get_config("whisper-tiny-en"))
    frames = _whisper_frames(cfg0, rng)
    cfg, model, params, eng, sts = _run_whisper_engine("bf16", frames)
    for st, p, f in zip(sts, WHISPER_PROMPTS, frames):
        want = greedy_reference(model, params, p, 4, enc_frames=f)
        assert st.out == want, (st.out, want)


def test_whisper_missing_frames_rejected():
    cfg, model, params, eng = _engine("whisper-tiny-en", n_slots=2,
                                      max_len=32, enc_len=8)
    assert eng.validate(Request(uid=0, tokens=[1, 2], max_new=2))
    with pytest.raises(ValueError):
        eng.admit(Request(uid=0, tokens=[1, 2], max_new=2))
    # frames longer than the pool's enc_len are also unservable
    frames = np.zeros((9, model.cfg.d_model), np.float32)
    assert eng.validate(AudioRequest(uid=1, tokens=[1, 2], max_new=2,
                                     enc_frames=frames))


# --------------------------------------------------------- q8_0 KV cache


def test_q8_cache_engine_matches_bf16_and_routes_kernel():
    """The q8_0 cache-dtype policy: same whisper workload served through
    a quantized KV pool stays token-exact vs the bf16 engine (Q8_0 KV
    error ~0.4% — near-ties can flip in principle, but not on this
    pinned workload), and every decode tick's cache matvec routes
    through the q8_decode_attention op."""
    rng = np.random.default_rng(0)
    cfg0 = reduced(get_config("whisper-tiny-en"))
    frames = _whisper_frames(cfg0, rng)
    *_, sts_bf16 = _run_whisper_engine("bf16", frames)
    reset_dispatch_log()
    cfg, model, params, eng8, sts_q8 = _run_whisper_engine("q8_0", frames)

    agree = sum(a == b for a, b in
                zip((st.out for st in sts_q8),
                    (st.out for st in sts_bf16)))
    assert agree == len(sts_q8), [(a.out, b.out)
                                  for a, b in zip(sts_q8, sts_bf16)]

    rep = eng8.dispatch_report()
    q8_calls = sum(n for (op, _, _), n in rep["counters"].items()
                   if op == "q8_decode_attention")
    assert q8_calls > 0, rep["counters"]
    assert rep["cache"]["cache_dtype"] == "q8_0"
    assert rep["cache"]["traffic_ratio_vs_bf16"] == pytest.approx(0.53125)


def test_q8_cache_bytes_ratio():
    """Pool bytes: q8_0 stores 1.0625 bytes/elem vs 2 for bf16 — the
    paper's C1 LOAD saving on the decode-cache stream (~0.53x)."""
    rng = np.random.default_rng(0)
    cfg0 = reduced(get_config("whisper-tiny-en"))
    frames = _whisper_frames(cfg0, rng, lens=(8, 8, 8))
    *_, eng_bf, _ = _run_whisper_engine("bf16", frames, n_new=2)
    *_, eng_q8, _ = _run_whisper_engine("q8_0", frames, n_new=2)
    rb, rq = eng_bf.cache_report(), eng_q8.cache_report()
    assert rq["bytes_per_step"] / rb["bytes_per_step"] == \
        pytest.approx(0.53125)
    assert rq["self_kv_bytes_per_token"] / rb["self_kv_bytes_per_token"] \
        == pytest.approx(0.53125)


def test_q8_decode_attention_module_close_to_bf16():
    """One decode step through models.attention with a q8_0 cache is
    within the Q8 error envelope of the bf16 cache path (per-lane
    positions, stacked cache — the serving configuration)."""
    from repro.core.quantize import quantize_q8_0
    from repro.models.attention import attention, init_attention
    from repro.models.layers import KeyGen, split_params
    cfg = reduced(get_config("whisper-tiny-en"))
    p, _ = split_params(init_attention(KeyGen(jax.random.key(5)), cfg))
    b, s, hkv, d = 2, 32, cfg.n_kv_heads, cfg.head_dim
    key = jax.random.key(7)
    x = jax.random.normal(jax.random.fold_in(key, 0), (b, 1, cfg.d_model),
                          jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, b, s, hkv, d),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, b, s, hkv, d),
                          jnp.bfloat16)
    kt, vt = quantize_q8_0(k, axis=-1), quantize_q8_0(v, axis=-1)
    pos = jnp.asarray([5, 9], jnp.int32)
    y_bf, _ = attention(p, x, cfg, mode="decode", use_rope=False,
                        cache={"k": k, "v": v}, pos=pos, layer_idx=0)
    y_q8, c_q8 = attention(p, x, cfg, mode="decode", use_rope=False,
                           cache={"kq": kt.q, "ks": kt.scale,
                                  "vq": vt.q, "vs": vt.scale},
                           pos=pos, layer_idx=0)
    rel = float(jnp.linalg.norm((y_q8 - y_bf).astype(jnp.float32))
                / jnp.linalg.norm(y_bf.astype(jnp.float32)))
    assert rel < 0.05, rel
    # the write quantized the new token in place at each lane's pos
    got = np.asarray(c_q8["kq"])[0, np.arange(b), np.asarray(pos)]
    assert np.abs(got).sum() > 0


# ---------------------------------------------------- robustness bugfixes


def test_freed_slots_reset_parked_state():
    """Parked lanes must not attend their dead context: freeing a slot
    zeroes its pos/tokens, so a parked lane decodes exactly one
    position per tick (the comment in engine.py is now enforced)."""
    cfg, model, params, eng = _engine(n_slots=3, max_len=64)
    sts = [eng.admit(Request(uid=i, tokens=[5 + i, 6, 7], max_new=3,
                             eos_id=-2)) for i in range(3)]
    while eng.n_active:
        eng.step()
    assert all(st.done for st in sts)
    assert sorted(eng.free) == [0, 1, 2]
    assert (eng._pos == 0).all(), eng._pos
    assert (eng._tokens == 0).all(), eng._tokens
    assert (eng._enc_lens == 0).all()


def test_scheduler_survives_bad_requests():
    """One unservable request must not kill the serving loop: it is
    completed as a failed RequestState in results, everything else
    drains normally."""
    cfg, model, params, eng = _engine(n_slots=2, max_len=32)
    sched = BatchScheduler(eng)
    sched.submit(Request(uid=0, tokens=list(range(3, 30)), max_new=16,
                         eos_id=-2))                     # too long
    sched.submit(Request(uid=1, tokens=[4, 5, 6], max_new=3, eos_id=-2))
    sched.submit(Request(uid=2, tokens=[7, 8], max_new=3, eos_id=-2,
                         enc_frames=np.zeros((4, 8), np.float32)))
    sched.submit(Request(uid=3, tokens=[9, 10], max_new=3, eos_id=-2))
    sched.run_until_drained(max_ticks=100)
    assert sched.drained
    assert sched.metrics.rejected == 2
    assert sched.metrics.completed == 2
    assert sched.results[0].error and sched.results[0].slot == -1
    assert sched.results[2].error
    assert len(sched.results[1].out) == 3 and not sched.results[1].error
    assert len(sched.results[3].out) == 3
