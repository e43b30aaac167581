"""Cross-family engine conformance: the whole model zoo through one
``ServeEngine``.

Every family the lane-state spec (``Model.state_spec``) declares —
dense causal KV (qwen3), enc-dec self+cross KV (whisper), MoE KV +
expert-routing counters (qwen3-moe), hybrid KV + SSM state (zamba2),
pure recurrent mLSTM/sLSTM state (xlstm) — runs the same battery:

  admit -> (exact or bucketed) prefill -> fused decode ticks ->
  EOS mid-block -> abort -> drain

with the same invariants asserted for each: engine tokens equal the
slot-free full-forward greedy reference (up to documented near-tie
flips at the compute-dtype rounding boundary), the fused tick is
token-identical to sequential single steps, exactly one host sync per
tick, and the lane-state ledger (``engine.lanestate``) drains to zero
through every exit path. q8_0 rows run wherever the family's spec
supports the quantized KV tier; unsupported families reject the tier
with a spec-driven error.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models.model import build
from repro.serving.engine import AudioRequest, Request, ServeEngine
from repro.serving.reference import assert_greedy_matches, tie_margin
from repro.serving.scheduler import BatchScheduler

ARCHS = ("qwen3-4b", "whisper-tiny-en", "qwen3-moe-30b-a3b",
         "zamba2-7b", "xlstm-350m")
# families whose spec supports the q8_0 KV tier (asserted against the
# spec itself in test_q8_support_matrix)
Q8_ARCHS = ("qwen3-4b", "whisper-tiny-en", "qwen3-moe-30b-a3b",
            "zamba2-7b")
PAIRS = [(a, "bf16") for a in ARCHS] + [(a, "q8_0") for a in Q8_ARCHS]

PROMPTS = ([5, 6, 7], [9, 10, 11, 12])
MAX_NEW = 6

_SETUP_CACHE: dict = {}


def _setup(arch):
    if arch not in _SETUP_CACHE:
        cfg = reduced(get_config(arch))
        if cfg.is_moe:
            # raised so no token is capacity-dropped: the slot-free
            # reference recomputes the whole sequence each step and
            # would otherwise make *different* (correct-but-unequal)
            # capacity cuts than the engine's incremental path — same
            # idiom as test_prefill_decode_equals_forward; binding
            # capacity is covered by test_moe_prefill_padding_mask
            cfg = dataclasses.replace(
                cfg, capacity_factor=float(cfg.n_experts))
        model = build(cfg)
        params = model.init_values(jax.random.key(0))
        _SETUP_CACHE[arch] = (cfg, model, params)
    return _SETUP_CACHE[arch]


def _engine(arch, **kw):
    cfg, model, params = _setup(arch)
    kw.setdefault("n_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("enc_len", 16)
    kw.setdefault("decode_block", 4)
    return cfg, model, params, ServeEngine(model, params, **kw)


def _frames(cfg, uid):
    rng = np.random.default_rng(uid)
    return rng.standard_normal((8 + 2 * (uid % 4), cfg.d_model)).astype(
        np.float32) * 0.5


def _request(cfg, uid, tokens, max_new=MAX_NEW, eos=-2, fuid=None):
    """``fuid`` pins the (seeded) audio frames independently of the
    request uid, so a later request can replay an earlier workload."""
    if cfg.enc_dec:
        return AudioRequest(uid=uid, tokens=list(tokens),
                            max_new=max_new, eos_id=eos,
                            enc_frames=_frames(
                                cfg, uid if fuid is None else fuid))
    return Request(uid=uid, tokens=list(tokens), max_new=max_new,
                   eos_id=eos)


def _drain(eng):
    while eng.n_active:
        eng.step()


# ---------------------------------------------------------- the battery


@pytest.mark.parametrize("arch,cache_dtype", PAIRS,
                         ids=[f"{a}|{d}" for a, d in PAIRS])
def test_conformance_battery(arch, cache_dtype):
    cfg, model, params, eng = _engine(arch, cache_dtype=cache_dtype)
    margin = tie_margin(cfg)

    # --- admit -> prefill -> fused decode -> drain -------------------
    sts = [eng.admit(_request(cfg, i, p)) for i, p in enumerate(PROMPTS)]
    assert all(st is not None for st in sts)
    assert all(eng.lanestate.holds(st.slot) for st in sts)
    _drain(eng)
    assert eng.lanestate.drained and not eng.active
    assert eng._host_syncs == eng._ticks      # one host sync per tick
    full = [list(st.out) for st in sts]
    assert all(len(o) == MAX_NEW for o in full)

    # --- token parity vs the slot-free reference ---------------------
    # (the q8_0 rows too: Q8_0 KV error ~0.4% stays inside the greedy
    # near-tie envelope on these workloads)
    for st, p in zip(sts, PROMPTS):
        frames = _frames(cfg, st.req.uid) if cfg.enc_dec else None
        assert_greedy_matches(model, params, p, st.out, margin,
                              enc_frames=frames)

    # --- fused tick == sequential single steps -----------------------
    *_, eng_seq = _engine(arch, cache_dtype=cache_dtype)
    sts_seq = [eng_seq.admit(_request(cfg, i, p))
               for i, p in enumerate(PROMPTS)]
    while eng_seq.n_active:
        eng_seq.step(1)
    assert [st.out for st in sts_seq] == full
    assert eng._decode_steps == eng.decode_block * eng._ticks
    assert eng._ticks < eng_seq._ticks

    # --- EOS mid-block ----------------------------------------------
    # stop on the token this engine emits at step 2: it lands inside a
    # decode_block=4 tick, so the lane must freeze on device mid-block
    eos = full[0][2]
    want = full[0][:full[0].index(eos) + 1]
    st = eng.admit(_request(cfg, 7, PROMPTS[0], eos=eos, fuid=0))
    _drain(eng)
    assert st.out == want and st.out[-1] == eos
    assert eng.lanestate.drained

    # --- abort releases every reserved state kind --------------------
    sts = [eng.admit(_request(cfg, 10 + i, p, fuid=i))
           for i, p in enumerate(PROMPTS)]
    eng.step()
    victim, survivor = sts
    slot = victim.slot
    eng.abort(victim)
    assert not eng.lanestate.holds(slot) and slot in eng.free
    assert victim.done and not eng.lanestate.drained   # survivor lives
    # the freed slot is immediately reusable mid-decode
    st3 = eng.admit(_request(cfg, 12, PROMPTS[0], fuid=0))
    assert st3.slot == slot
    _drain(eng)
    assert st3.out == full[0]        # same workload, same tokens
    assert len(survivor.out) == MAX_NEW
    assert eng.lanestate.drained and eng._host_syncs == eng._ticks

    # --- spec-consistent accounting ----------------------------------
    spec = eng.spec
    rep = eng.cache_report()
    assert rep["family"] == spec.family
    assert rep["state_kinds"] == list(spec.state_kinds)
    assert rep["bytes_per_step"] > 0
    if spec.recurrent:
        assert rep["state_bytes_total"] > 0
    if not spec.self_kv:
        assert rep["kv_bytes_total"] == 0


# --------------------------------------------------- scheduler teardown


@pytest.mark.parametrize("arch", ARCHS)
def test_scheduler_serves_family(arch):
    """The continuous-batching scheduler drives every family with slot
    churn (5 requests through 2 slots), including a queued-request
    cancel — and the lane-state ledger is empty when drained."""
    cfg, model, params, eng = _engine(arch, n_slots=2)
    sched = BatchScheduler(eng)
    for i in range(5):
        sched.submit(_request(cfg, i, PROMPTS[i % 2], max_new=3))
    assert sched.abort(3) is not None       # still queued: cancelled
    sched.run_until_drained(max_ticks=200)
    assert sched.drained and eng.lanestate.drained
    assert sched.metrics.completed == 4
    assert sched.results[3].error_code is not None
    done = [sched.results[i].out for i in (0, 1, 2, 4)]
    assert all(len(o) == 3 for o in done)


@pytest.mark.parametrize("arch", ("xlstm-350m", "qwen3-moe-30b-a3b"))
def test_gateway_serves_family(arch):
    """The asyncio gateway fronts the spec-driven engine for the
    non-attention/MoE families too: one-shot token requests resolve
    with the same tokens the bare engine produced, and ``report()``
    carries the served family's lane-state spec."""
    import asyncio

    from repro.gateway import Gateway

    cfg, model, params, eng = _engine(arch, n_slots=2)
    sts = [eng.admit(_request(cfg, i, p)) for i, p in enumerate(PROMPTS)]
    _drain(eng)
    want = [list(st.out) for st in sts]

    *_, eng2 = _engine(arch, n_slots=2)

    async def go():
        async with Gateway(eng2, shed_on_submit=False) as gw:
            outs = await asyncio.gather(*[
                gw.submit_tokens(list(p), max_new=MAX_NEW, eos_id=-2)
                for p in PROMPTS])
            return outs, gw.report()

    outs, rep = asyncio.run(go())
    assert all(r.ok for r in outs)
    assert [list(r.tokens) for r in outs] == want
    assert rep["engine"]["family"] == eng2.spec.family
    assert rep["engine"]["state_kinds"] == list(eng2.spec.state_kinds)
    assert rep["engine"]["prefill_exact"] == eng2.spec.prefill_exact
    assert eng2.lanestate.drained


# ------------------------------------------------------- the q8 policy


def test_q8_support_matrix():
    """``LaneStateSpec.q8_supported`` is the single source of truth for
    the quantized-KV tier: families with q8-compatible KV planes accept
    it, pure-recurrent and windowed-attention families do not."""
    for arch in ARCHS:
        cfg = reduced(get_config(arch))
        spec = build(cfg).state_spec()
        assert spec.q8_supported == (arch in Q8_ARCHS), arch
    # mixtral declares KV but a sliding window — q8 tier unsupported
    mix = build(reduced(get_config("mixtral-8x7b"))).state_spec()
    assert mix.self_kv and not mix.q8_supported


def test_q8_rejected_for_pure_recurrent():
    cfg, model, params = _setup("xlstm-350m")
    with pytest.raises(ValueError, match="q8_0"):
        ServeEngine(model, params, n_slots=2, max_len=64, enc_len=16,
                    cache_dtype="q8_0")


def test_q8_shrinks_decode_stream():
    """Where the spec supports q8_0, the per-step cache stream shrinks;
    spec-declared recurrent/routing state is dtype-unaffected."""
    *_, eng_bf = _engine("qwen3-moe-30b-a3b", cache_dtype="bf16")
    *_, eng_q8 = _engine("qwen3-moe-30b-a3b", cache_dtype="q8_0")
    rb, rq = eng_bf.cache_report(), eng_q8.cache_report()
    assert rq["kv_bytes_total"] < rb["kv_bytes_total"]
    assert rq["bytes_per_step"] < rb["bytes_per_step"]
    assert rq["state_bytes_per_step"] == rb["state_bytes_per_step"]


def test_moe_prefill_padding_mask():
    """At *binding* capacity (the production capacity_factor), bucket
    padding must not evict live tokens from their experts: capacity
    routing is non-causal, so — unlike attention, where the causal mask
    hides the padded tail — an unmasked padded bucket changes live
    tokens' expert assignments. ``valid_len`` (threaded from the
    engine's prefill as ``batch[\"n_valid\"]``) zeroes padding gates
    before the per-expert top-C cut."""
    from repro.models import moe
    from repro.models.layers import KeyGen, split_params

    cfg = reduced(get_config("qwen3-moe-30b-a3b"))   # cf=1.25: binding
    p, _ = split_params(moe.init_moe(KeyGen(jax.random.key(0)), cfg))
    n, bucket = 4, 32
    # seed chosen so the exact-length pass is itself drop-free (its
    # per-expert top-C keeps every live token) — the oracle is clean
    xl = jax.random.normal(jax.random.key(20),
                           (1, n, cfg.d_model), jnp.float32) * 0.5
    # adversarial padding: amplified copies of a live token, routing
    # hard into its experts — exactly the crowding a padded bucket does
    pad = jnp.tile(xl[:, :1] * 6.0, (1, bucket - n, 1))
    x = jnp.concatenate([xl, pad], axis=1)

    exact = moe.moe_ffn(p, xl, cfg)
    masked = moe.moe_ffn(p, x, cfg, valid_len=n)[:, :n]
    unmasked = moe.moe_ffn(p, x, cfg)[:, :n]
    np.testing.assert_allclose(np.asarray(masked), np.asarray(exact),
                               atol=1e-5)
    assert not np.allclose(unmasked, exact, atol=5e-2), \
        "padding eviction did not occur: the mask is untested"
    # the baseline global dispatch honors the same mask (its different
    # gather order rounds differently in bf16 — routing-level drift
    # would be ~0.1+, cf. the unmasked assertion above)
    g = moe.moe_ffn(p, x, cfg, grouped=False, valid_len=n)[:, :n]
    np.testing.assert_allclose(np.asarray(g), np.asarray(exact),
                               atol=5e-3)


# -------------------------------------------------- routing diagnostics


def test_moe_routing_counters_reconcile():
    """The MoE lane's routing counters count executed top-k assignments
    exactly: prefill tokens + decode steps, per layer, per lane."""
    cfg, model, params, eng = _engine("qwen3-moe-30b-a3b")
    sts = [eng.admit(_request(cfg, i, p)) for i, p in enumerate(PROMPTS)]
    _drain(eng)
    rep = eng.routing_report()
    assert rep["n_experts"] == cfg.n_experts
    assert rep["top_k"] == cfg.top_k
    # the counters are a device-work diagnostic: prefill executes the
    # whole padded bucket through the experts, and the fused tick
    # executes every slot each step — parked/empty lanes included
    from repro.serving.engine import _bucket
    prefill_tokens = sum(min(_bucket(len(p)), eng.max_len)
                         for p in PROMPTS)
    decode_tokens = eng.n_slots * eng._decode_steps
    want = (prefill_tokens + decode_tokens) * rep["moe_layers"] \
        * cfg.top_k
    assert rep["executed_assignments"] == want
    # per-lane counts are nonnegative and sum to the total
    per_lane = np.asarray(rep["per_lane"])
    assert per_lane.sum() == want and (per_lane >= 0).all()
