"""Device-resident fused decode loop: parity, donation, sync counts.

The fused tick (``ServeEngine.step`` with ``decode_block=K``) must be
token-identical to K sequential single steps — including lanes that hit
EOS mid-block and parked streaming lanes — while donating the KV pool
and syncing to host exactly once per tick.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models.model import build
from repro.serving.engine import (AudioRequest, Request, ServeEngine,
                                  StreamingAudioRequest)
from repro.serving.scheduler import BatchScheduler

WHISPER_PROMPTS = [[5, 6, 7, 8], [9, 10, 11], [3, 4, 5, 6, 7]]


def _setup(arch="whisper-tiny-en", seed=0):
    cfg = reduced(get_config(arch))
    model = build(cfg)
    params = model.init_values(jax.random.key(seed))
    return cfg, model, params


def _engine(model, params, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("enc_len", 16)
    return ServeEngine(model, params, **kw)


def _frames(cfg, rng, lens=(8, 12, 8)):
    return [rng.standard_normal((n, cfg.d_model)).astype(np.float32) * 0.5
            for n in lens]


def _admit_all(eng, cfg, frames, max_new=8, eos=-2, prompts=None):
    prompts = prompts or WHISPER_PROMPTS
    return [eng.admit(AudioRequest(uid=i, tokens=list(p), max_new=max_new,
                                   eos_id=eos, enc_frames=f))
            for i, (p, f) in enumerate(zip(prompts, frames))]


def _drain(eng, k=None):
    while eng.n_active:
        eng.step(k)


# ------------------------------------------------------------- parity


@pytest.mark.parametrize("cache_dtype", ["bf16", "q8_0", "q4_0"])
def test_fused_tick_parity(cache_dtype):
    """K-step fused decode == K sequential step() calls, token for
    token, for bf16 and q8_0 cache pools."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(0)
    frames = _frames(cfg, rng)

    eng_seq = _engine(model, params, cache_dtype=cache_dtype)
    sts_seq = _admit_all(eng_seq, cfg, frames)
    _drain(eng_seq, k=1)

    eng_fus = _engine(model, params, cache_dtype=cache_dtype,
                      decode_block=4)
    sts_fus = _admit_all(eng_fus, cfg, frames)
    _drain(eng_fus)

    assert [st.out for st in sts_fus] == [st.out for st in sts_seq]
    # a fused tick buys decode_block steps per host sync
    assert eng_fus._host_syncs == eng_fus._ticks
    assert eng_fus._decode_steps == 4 * eng_fus._ticks
    assert eng_fus._ticks < eng_seq._ticks


@pytest.mark.parametrize("cache_dtype", ["bf16", "q8_0", "q4_0"])
def test_fused_tick_parity_eos_mid_block(cache_dtype):
    """A lane that hits EOS at a step that is NOT a block boundary must
    freeze mid-scan: its later in-block emits are masked, and every
    other lane is unaffected."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(0)
    frames = _frames(cfg, rng)

    # discover the greedy streams, then pick an eos that lane 0 emits at
    # step 2 of an 8-token run — inside a decode_block=4 tick
    probe = _engine(model, params, cache_dtype=cache_dtype)
    sts = _admit_all(probe, cfg, frames, max_new=8)
    _drain(probe, k=1)
    eos = sts[0].out[2]

    eng_seq = _engine(model, params, cache_dtype=cache_dtype)
    sts_seq = _admit_all(eng_seq, cfg, frames, max_new=8, eos=eos)
    _drain(eng_seq, k=1)

    eng_fus = _engine(model, params, cache_dtype=cache_dtype,
                      decode_block=4)
    sts_fus = _admit_all(eng_fus, cfg, frames, max_new=8, eos=eos)
    _drain(eng_fus)

    assert [st.out for st in sts_fus] == [st.out for st in sts_seq]
    assert sts_fus[0].out[-1] == eos and len(sts_fus[0].out) <= 4
    assert all(st.done for st in sts_fus)


def test_fused_tick_parity_with_parked_streaming_lane():
    """A streaming lane that exhausted max_new mid-stream parks (keeps
    its slot, stops decoding); fused ticks must keep it frozen while
    other lanes decode, and the finalized stream must match the
    sequential engine's transcript and partials."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(1)
    chunks = [rng.standard_normal((4, cfg.d_model)).astype(np.float32) * 0.5
              for _ in range(3)]
    frames = _frames(cfg, rng, lens=(8,))

    def serve(block):
        eng = _engine(model, params, decode_block=block)
        sched = BatchScheduler(eng)
        # max_new=2: the streaming lane finishes its mid-stream
        # hypothesis immediately and parks until the next chunk
        sched.submit(StreamingAudioRequest(uid=0, tokens=[5, 6], max_new=2,
                                           eos_id=-2, chunks=chunks))
        sched.submit(AudioRequest(uid=1, tokens=[7, 8, 9], max_new=9,
                                  eos_id=-2, enc_frames=frames[0]))
        sched.run_until_drained(max_ticks=100)
        assert sched.drained
        return sched.results

    seq, fus = serve(1), serve(4)
    assert fus[0].out == seq[0].out
    assert fus[0].partials == seq[0].partials
    assert fus[1].out == seq[1].out


def test_fused_decoder_only_parity():
    cfg, model, params = _setup("qwen3-4b")
    prompts = [[5, 6, 7, 8], [9, 10, 11]]

    def serve(block):
        eng = _engine(model, params, max_len=96, decode_block=block)
        sts = [eng.admit(Request(uid=i, tokens=p, max_new=9, eos_id=-2))
               for i, p in enumerate(prompts)]
        _drain(eng)
        return [st.out for st in sts]

    assert serve(1) == serve(4) == serve(16)


def test_step_k_overrides_block():
    """step(k) fuses k steps regardless of the engine default — the
    mutable-knob path transcribe(engine=...) uses."""
    cfg, model, params = _setup("qwen3-4b")
    eng = _engine(model, params, max_len=96)
    eng.admit(Request(uid=0, tokens=[5, 6, 7], max_new=9, eos_id=-2))
    eng.step(4)
    assert eng._decode_steps == 4 and eng._ticks == 1


def test_decode_block_validation():
    cfg, model, params = _setup("qwen3-4b")
    with pytest.raises(ValueError, match="decode_block"):
        _engine(model, params, decode_block=0)
    # mutable-knob path: a 0-block step would be a 0-length scan that
    # emits nothing and never drains — step() must refuse it too
    eng = _engine(model, params, max_len=96)
    eng.admit(Request(uid=0, tokens=[5, 6, 7], max_new=4, eos_id=-2))
    eng.decode_block = 0
    with pytest.raises(ValueError, match="block"):
        eng.step()


def test_transcribe_decode_block_validation():
    from repro.audio.transcribe import transcribe
    with pytest.raises(ValueError, match="decode_block"):
        transcribe(np.zeros(1600, np.float32), 16_000, decode_block=0)


# ------------------------------------------- donation & device residency


def test_decode_jit_donates_cache_and_state():
    """The fused decode jit must donate the KV pool and the lane-state
    buffers — the lowering carries input/output aliasing, so on
    donation-capable backends the pool is updated in place instead of
    copied every tick."""
    cfg, model, params = _setup()
    eng = _engine(model, params)
    fn = eng._build_decode(2)
    lowered = fn.lower(params, eng.cache, eng._tokens, eng._pos,
                       eng._lane_active, eng._lane_out, eng._enc_lens,
                       eng._lane_eos, eng._lane_max)
    txt = lowered.as_text()
    # cache leaves + tokens/pos/active/n_out: at least 5 donated inputs
    assert txt.count("tf.aliasing_output") >= 5, \
        txt.count("tf.aliasing_output")


def test_prefill_jit_donates_pool_and_returns_scalar_argmax():
    """Prefill takes the pool (donated: the slot scatter is an in-place
    lane write) and returns the first token as a device scalar — the
    [1, bucket, vocab] logits never reach the host."""
    cfg, model, params = _setup("qwen3-4b")
    eng = _engine(model, params, max_len=96)
    fn = eng._prefill_fn(32)
    toks = jnp.zeros((1, 32), jnp.int32)
    lowered = fn.lower(params, eng.cache, toks, 3, 0)
    txt = lowered.as_text()
    assert "tf.aliasing_output" in txt
    first, pool = jax.eval_shape(fn, params, eng.cache, toks, 3, 0)
    assert first.shape == () and first.dtype == jnp.int32


def test_decode_state_is_device_resident():
    """The per-lane decode state lives in jax arrays owned by the
    engine — nothing is re-uploaded from host NumPy per tick."""
    cfg, model, params = _setup("qwen3-4b")
    eng = _engine(model, params, max_len=96)
    for name in ("_tokens", "_pos", "_enc_lens", "_lane_active",
                 "_lane_eos", "_lane_max", "_lane_out"):
        assert isinstance(getattr(eng, name), jax.Array), name
    st = eng.admit(Request(uid=0, tokens=[5, 6, 7], max_new=4, eos_id=-2))
    assert int(eng._lane_active.sum()) == 1
    assert int(eng._lane_max[st.slot]) == 4
    assert int(eng._lane_out[st.slot]) == 1
    _drain(eng)
    assert int(eng._lane_active.sum()) == 0
    assert (np.asarray(eng._pos) == 0).all()


def test_one_host_sync_per_tick():
    cfg, model, params = _setup()
    rng = np.random.default_rng(0)
    eng = _engine(model, params, decode_block=4)
    _admit_all(eng, cfg, _frames(cfg, rng), max_new=8)
    syncs0 = eng._host_syncs
    n = 0
    while eng.n_active:
        eng.step()
        n += 1
    assert eng._host_syncs - syncs0 == n == eng._ticks


# ------------------------------------------------- lane-state writes

LANES = ("_tokens", "_pos", "_enc_lens", "_lane_eos", "_lane_max",
         "_lane_out", "_lane_active")


def _lanes(eng):
    return {name: np.asarray(getattr(eng, name)) for name in LANES}


def _np_write(lanes, slot, *, token, pos, enc_len, eos, max_new, n_out,
              active):
    """The seven per-lane writes ``_set_lane`` stands for, in NumPy."""
    out = {name: a.copy() for name, a in lanes.items()}
    out["_tokens"][slot, 0] = token
    for name, v in (("_pos", pos), ("_enc_lens", enc_len),
                    ("_lane_eos", eos), ("_lane_max", max_new),
                    ("_lane_out", n_out), ("_lane_active", active)):
        out[name][slot] = v
    return out


def _assert_lanes(eng, want):
    got = _lanes(eng)
    for name in LANES:
        assert got[name].dtype == want[name].dtype, name
        assert got[name].shape == want[name].shape, name
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def _free(eng, lanes, slot):
    return _np_write(lanes, slot, token=0, pos=0, enc_len=0, eos=0,
                     max_new=0, n_out=0, active=False)


@pytest.mark.parametrize("path", ["admit", "anchor", "free"])
def test_set_lane_writes_what_the_eager_updates_wrote(path):
    """Admission, a stream's anchor and a freed lane leave all seven
    lane vectors, dtypes and shapes as the seven ``.at[slot].set``
    updates would: element for element against a NumPy model, the
    other lanes untouched."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(3)
    eng = _engine(model, params, decode_block=2)
    want = _lanes(eng)
    assert want["_tokens"].shape == (4, 1)
    assert want["_lane_active"].dtype == np.bool_
    frames = _frames(cfg, rng, lens=(8, 12))
    for uid, (f, max_new) in enumerate(zip(frames, (3, 9))):
        st = eng.admit(AudioRequest(uid=uid, tokens=[5, 6, 7],
                                    max_new=max_new, eos_id=-2,
                                    enc_frames=f))
        want = _np_write(want, st.slot, token=st.out[0], pos=3,
                         enc_len=f.shape[0], eos=-2, max_new=max_new,
                         n_out=1, active=True)
        if path == "admit":
            _assert_lanes(eng, want)
    if path == "anchor":
        chunks = [rng.standard_normal((4, cfg.d_model)).astype(np.float32)
                  * 0.5 for _ in range(2)]
        st = eng.open_stream(StreamingAudioRequest(
            uid=7, tokens=[5, 6], max_new=4, eos_id=-2, chunks=chunks))
        _assert_lanes(eng, want)        # opening writes no lane state
        eng.stream_feed(st, chunks[0])
        want = _np_write(want, st.slot, token=st.out[0], pos=2, enc_len=4,
                         eos=-2, max_new=4, n_out=1, active=True)
        _assert_lanes(eng, want)
    if path == "free":
        # the first lane (max_new 3) finishes in the first tick of two
        # steps; replay frees it
        pending = eng.step_begin()
        tok_blk, emit_blk = eng.step_fetch(pending)
        want = _lanes(eng)               # as the decode program left it
        freed = eng.step_replay(pending, tok_blk, emit_blk)
        assert [st.req.uid for st in freed] == [0]
        want = _free(eng, want, freed[0].slot)
        _assert_lanes(eng, want)
        st = eng.active[next(iter(eng.active))]
        eng.abort(st)
        _assert_lanes(eng, _free(eng, want, st.slot))


def test_lane_write_donates_the_lane_vectors():
    """The lane-update program aliases each of the seven vectors to its
    output, so a donation-capable backend writes one lane in place."""
    cfg, model, params = _setup()
    eng = _engine(model, params)
    lanes = tuple(getattr(eng, name) for name in LANES)
    row = np.zeros(8, np.int32)
    txt = eng._write_lane.lower(lanes, row).as_text()
    assert txt.count("tf.aliasing_output") == len(LANES), \
        txt.count("tf.aliasing_output")


def test_lane_write_compiles_once_for_every_slot():
    """Slot and values are traced: writes at every slot, with admission
    and freeing values, leave one entry in the program's jit cache."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(4)
    eng = _engine(model, params)
    for uid, f in enumerate(_frames(cfg, rng, lens=(8, 12, 8, 10))):
        eng.admit(AudioRequest(uid=uid, tokens=[5, 6, 7], max_new=4,
                               eos_id=-2, enc_frames=f))
    assert sorted(eng.active) == [0, 1, 2, 3]
    for _ in range(3):                  # max_new 4: three decode steps
        eng.step()
    assert sorted(eng.free) == [0, 1, 2, 3]
    assert eng._write_lane._cache_size() == 1


# -------------------------------------------------- energy accounting


def test_energy_report_multi_token_ticks():
    """joules/token must not change when ticks advance once per K
    tokens: the stream is priced per decode step, and with a workload
    that has no in-block waste the fused and sequential reports are
    identical (bar tick counts)."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(0)
    frames = _frames(cfg, rng, lens=(8, 8))

    def serve(block):
        eng = _engine(model, params, n_slots=2, decode_block=block,
                      platform="imax3-28nm/32k")
        for i, f in enumerate(frames):
            # 1 prefill + 8 decode tokens; 8 % 4 == 0 -> no waste
            eng.admit(AudioRequest(uid=i, tokens=[5 + i, 6, 7], max_new=9,
                                   eos_id=-1, enc_frames=f))
        _drain(eng)
        return eng.energy_report()

    seq, fus = serve(1), serve(4)
    assert fus["decode_block"] == 4
    assert fus["ticks"] == seq["ticks"] / 4
    assert fus["decode_steps"] == seq["decode_steps"] == 8
    assert fus["tokens"] == seq["tokens"] == 18
    assert fus["stream_bytes_total"] == seq["stream_bytes_total"]
    assert fus["joules_per_token"] == pytest.approx(
        seq["joules_per_token"])
    assert fus["host_syncs"] == seq["host_syncs"] / 4
