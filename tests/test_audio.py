"""Audio pipeline: golden log-mel frontend, streaming exactness, and the
end-to-end transcribe API."""

import dataclasses
import functools

import jax
import numpy as np
import pytest

from repro.audio.features import (FrontendConfig, audio_frames, log_mel,
                                  log_mel_ref, mel_filterbank,
                                  mel_to_frames, resample_linear)
from repro.audio.stream import (StreamingFrontend, chunk_list,
                                synth_waveform)
from repro.audio.transcribe import transcribe
from repro.configs import get_config, reduced
from repro.models import encdec
from repro.models.model import build
from repro.serving.engine import (AudioRequest, ServeEngine,
                                  StreamingAudioRequest)
from repro.serving.scheduler import BatchScheduler

CFG = FrontendConfig()


@functools.lru_cache(maxsize=1)
def _whisper():
    cfg = reduced(get_config("whisper-tiny-en"))
    model = build(cfg)
    params = model.init_values(jax.random.key(0))
    return cfg, model, params


# ------------------------------------------------------------- frontend


def test_log_mel_matches_numpy_reference():
    """The JAX frontend is golden against the NumPy reference, including
    an input whose last frame is partial (zero-padded tail)."""
    for n in (400, 1000, 8000):   # exact window / partial tail / long
        x = synth_waveform(1.0)[:n]
        got = np.asarray(log_mel(x, CFG))
        ref = log_mel_ref(x, CFG)
        assert got.shape == ref.shape == (CFG.n_frames(n), CFG.n_mels)
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def test_log_mel_silence_hits_fixed_floor():
    """Silence maps every bin to the fixed-reference floor: mel=0 ->
    log10 clamp at -8 -> (-8+4)/4 = -1 (no utterance-global max — the
    streaming-causal normalization)."""
    lm = np.asarray(log_mel(np.zeros(1600, np.float32), CFG))
    assert lm.shape == (10, CFG.n_mels)
    np.testing.assert_allclose(lm, -1.0)
    np.testing.assert_allclose(log_mel_ref(np.zeros(1600, np.float32),
                                           CFG), -1.0)


def test_log_mel_edge_lengths():
    assert np.asarray(log_mel(np.zeros(0, np.float32), CFG)).shape \
        == (0, CFG.n_mels)
    # shorter than one hop: still one (padded) frame
    one = np.asarray(log_mel(0.1 * np.ones(50, np.float32), CFG))
    assert one.shape == (1, CFG.n_mels)
    assert np.isfinite(one).all()


def test_mel_filterbank_covers_spectrum():
    fb = mel_filterbank(CFG)
    assert fb.shape == (CFG.n_freq, CFG.n_mels)
    assert (fb >= 0).all()
    # every filter has support; interior frequency bins are covered
    assert (fb.sum(axis=0) > 0).all()
    assert (fb[1:-1].sum(axis=1) >= 0).any()


def test_mel_to_frames_pools_odd_tail():
    lm = np.linspace(0, 1, 5 * CFG.n_mels, dtype=np.float32) \
        .reshape(5, CFG.n_mels)
    out = np.asarray(mel_to_frames(lm, 64, CFG))
    assert out.shape == (3, 64)      # ceil(5/2) with zero-padded tail


def test_streaming_frontend_bit_exact():
    """Incremental push/flush equals one-shot audio_frames exactly,
    whatever the push granularity."""
    x = synth_waveform(0.7)
    one = np.asarray(audio_frames(x, 128, CFG))
    for step in (173, 1777, len(x)):
        sf = StreamingFrontend(128, CFG)
        outs = [sf.push(x[i:i + step]) for i in range(0, len(x), step)]
        outs.append(sf.flush())
        got = np.concatenate(outs)
        assert got.shape == one.shape
        assert np.array_equal(got, one)
        assert sf.frames_emitted == one.shape[0]
    with pytest.raises(ValueError):
        sf.push(x[:10])              # push after flush


def test_log_mel_accepts_2d_loader_shapes():
    """(1, N)/(N, 1) loader outputs are flattened, not truncated."""
    x = synth_waveform(0.2)
    want = log_mel_ref(x, CFG)
    assert want.shape[0] == CFG.n_frames(len(x))
    np.testing.assert_array_equal(log_mel_ref(x.reshape(1, -1), CFG), want)
    np.testing.assert_array_equal(log_mel_ref(x.reshape(-1, 1), CFG), want)
    np.testing.assert_array_equal(np.asarray(log_mel(x.reshape(1, -1),
                                                     CFG)), np.asarray(
                                                         log_mel(x, CFG)))


def test_resample_linear_identity_and_rate():
    x = synth_waveform(0.1)
    assert resample_linear(x, 16_000, 16_000) is x or \
        np.array_equal(resample_linear(x, 16_000, 16_000), x)
    y = resample_linear(x, 8_000, 16_000)
    assert abs(len(y) - 2 * len(x)) <= 1


# ------------------------------------------------- chunked encode (model)


def test_chunked_encode_is_block_diagonal():
    """A chunk's states depend only on its own frames: prefix states are
    unchanged when more audio is appended (the streaming invariant)."""
    cfg, model, params = _whisper()
    rng = np.random.default_rng(3)
    frames = jax.numpy.asarray(
        rng.standard_normal((1, 12, cfg.d_model)).astype(np.float32) * 0.5)
    full = encdec.encode_chunked(params, cfg, frames, chunk=4)
    prefix = encdec.encode_chunked(params, cfg, frames[:, :8], chunk=4)
    assert full.shape == (1, 12, cfg.d_model)
    np.testing.assert_array_equal(np.asarray(full[:, :8], np.float32),
                                  np.asarray(prefix, np.float32))
    # and each chunk equals its independent encode
    alone = encdec.encode(params, cfg, frames[:, 4:8])
    np.testing.assert_array_equal(np.asarray(full[:, 4:8], np.float32),
                                  np.asarray(alone, np.float32))


def test_cross_attn_kv_matches_prefill_planes():
    """Incremental cross-K/V extension writes the same planes the
    prompt prefill writes: feed two chunks (the second lands via the
    donated ``_extend_cross_cache`` jit), then finalize (which
    re-writes the whole slot
    from one prefill over the same chunked states) — the extended
    region must already hold the prefill's values."""
    cfg, model, params = _whisper()
    rng = np.random.default_rng(5)
    c1 = rng.standard_normal((6, cfg.d_model)).astype(np.float32) * 0.5
    c2 = rng.standard_normal((5, cfg.d_model)).astype(np.float32) * 0.5

    eng = ServeEngine(model, params, n_slots=1, max_len=32, enc_len=16)
    st = eng.open_stream(StreamingAudioRequest(
        uid=0, tokens=[1, 2], max_new=4, eos_id=-2, chunks=[c1, c2]))
    eng.stream_feed(st, c1)                   # anchor (prefill over c1)
    eng.stream_feed(st, c2)                   # incremental extension
    k_inc = np.asarray(
        eng.cache["layers"]["cross"]["k"][:, 0, 6:11], np.float32)
    v_inc = np.asarray(
        eng.cache["layers"]["cross"]["v"][:, 0, 6:11], np.float32)
    assert eng._enc_lens[0] == 11
    eng.stream_finalize(st)                   # prefill over c1+c2 states
    k_fin = np.asarray(
        eng.cache["layers"]["cross"]["k"][:, 0, 6:11], np.float32)
    v_fin = np.asarray(
        eng.cache["layers"]["cross"]["v"][:, 0, 6:11], np.float32)
    assert np.abs(k_inc).max() > 0
    np.testing.assert_allclose(k_inc, k_fin, atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(v_inc, v_fin, atol=2e-2, rtol=2e-2)


# ------------------------------------------------- streaming == one-shot


def test_streaming_serve_matches_one_shot_tokens():
    """The acceptance property: chunk-at-a-time streaming serving emits
    the same final transcript as one-shot serving of the same audio,
    token for token, and records partial hypotheses along the way."""
    cfg, model, params = _whisper()
    wave = synth_waveform(0.4)
    one = transcribe(wave, 16_000, model=model, params=params,
                     chunk_frames=6, max_new=5)
    streamed = transcribe(wave, 16_000, model=model, params=params,
                          chunk_frames=6, max_new=5, stream=True,
                          engine=one.engine)
    assert streamed.tokens == one.tokens
    assert len(streamed.partials) >= 2       # emitted while audio arrived
    assert one.partials == []
    assert streamed.n_frames == one.n_frames


def test_streaming_scheduler_mixed_with_audio_requests():
    """Streams and plain audio requests share the pool: both complete,
    slots are recycled, stream bookkeeping drains."""
    cfg, model, params = _whisper()
    eng = ServeEngine(model, params, n_slots=2, max_len=32, enc_len=16)
    sched = BatchScheduler(eng)
    rng = np.random.default_rng(0)
    frames = rng.standard_normal((10, cfg.d_model)).astype(np.float32) * 0.5
    sched.submit(StreamingAudioRequest(
        uid=0, tokens=[1, 2], max_new=4, eos_id=-2,
        chunks=chunk_list(frames, 4)))
    sched.submit(AudioRequest(uid=1, tokens=[3, 4, 5], max_new=3,
                              eos_id=-2, enc_frames=frames))
    sched.run_until_drained(max_ticks=100)
    assert sched.drained and eng.n_streams == 0
    assert len(sched.results[0].out) == 4
    assert len(sched.results[0].partials) >= 3   # one per chunk + final
    assert len(sched.results[1].out) == 3
    assert not sched.results[0].error and not sched.results[1].error
    assert sorted(eng.free) == [0, 1]


def test_streaming_validate_and_rejection():
    cfg, model, params = _whisper()
    eng = ServeEngine(model, params, n_slots=1, max_len=32, enc_len=8)
    d = cfg.d_model
    big = [np.zeros((6, d), np.float32), np.zeros((6, d), np.float32)]
    assert eng.validate(StreamingAudioRequest(
        uid=0, tokens=[1], max_new=2, chunks=big))   # 12 > enc_len 8
    with pytest.raises(ValueError):
        eng.admit(StreamingAudioRequest(uid=1, tokens=[1], max_new=2,
                                        chunks=[np.zeros((2, d))]))
    with pytest.raises(ValueError):
        StreamingAudioRequest(uid=2, tokens=[1], max_new=2, chunks=[])
    # both encoder inputs on a plain request is unservable
    assert eng.validate(AudioRequest(
        uid=3, tokens=[1], max_new=2,
        enc_frames=np.zeros((4, d), np.float32),
        enc_states=np.zeros((4, d), np.float32)))
    # scheduler completes an unservable stream as a failed state
    sched = BatchScheduler(eng)
    st = sched.submit(StreamingAudioRequest(uid=4, tokens=[1], max_new=2,
                                            chunks=big))
    assert st is not None and st.error and st.slot == -1


# -------------------------------------------------------- transcribe API


def test_transcribe_smoke_whisper_tiny():
    cfg, model, params = _whisper()
    wave = synth_waveform(0.3)
    r = transcribe(wave, 16_000, model=model, params=params,
                   chunk_frames=8, max_new=4)
    assert len(r.tokens) == 4
    assert all(0 <= t < cfg.vocab for t in r.tokens)
    assert r.n_frames == CFG.n_embed_frames(len(wave))
    assert r.audio_s == pytest.approx(0.3, abs=1e-3)
    assert r.energy is None and r.platform is None
    assert r.text == " ".join(str(t) for t in r.tokens)


def test_transcribe_platform_energy_and_q8():
    cfg, model, params = _whisper()
    wave = synth_waveform(0.3)
    r = transcribe(wave, 16_000, model=model, params=params,
                   chunk_frames=8, max_new=4, platform="imax3-28nm",
                   cache_dtype="q8_0")
    assert r.platform == "imax3-28nm/32k"
    assert r.cache_dtype == "q8_0"
    e = r.energy
    assert e["joules_per_audio_s"] > 0 and np.isfinite(
        e["joules_per_audio_s"])
    assert e["joules_per_audio_s"] == pytest.approx(
        e["pdp_j"] / r.audio_s, rel=1e-6)


def test_transcribe_engine_reuse_reports_per_call_stats():
    """A reused engine must not leak the previous call's ticks/energy
    into the next result, and conflicting explicit policies raise."""
    cfg, model, params = _whisper()
    wave = synth_waveform(0.3)
    a = transcribe(wave, 16_000, model=model, params=params,
                   chunk_frames=8, max_new=4, platform="imax3-28nm")
    b = transcribe(wave, 16_000, model=model, params=params,
                   chunk_frames=8, max_new=4, engine=a.engine)
    assert b.ticks == a.ticks
    assert b.energy["joules_per_audio_s"] == pytest.approx(
        a.energy["joules_per_audio_s"], rel=1e-6)
    assert b.platform == a.platform and b.cache_dtype == a.cache_dtype
    with pytest.raises(ValueError):
        transcribe(wave, 16_000, model=model, params=params,
                   chunk_frames=8, max_new=4, engine=a.engine,
                   cache_dtype="q8_0")
    with pytest.raises(ValueError):
        transcribe(wave, 16_000, model=model, params=params,
                   chunk_frames=8, max_new=4, engine=a.engine,
                   platform="rtx-4090")


def test_transcribe_reused_engine_brings_its_model():
    """engine= alone serves with the engine's own model: the frames are
    built at its width, not at the default (reduced) arch's."""
    cfg = dataclasses.replace(reduced(get_config("whisper-tiny-en")),
                              d_model=64)
    model = build(cfg)
    params = model.init_values(jax.random.key(0))
    wave = synth_waveform(0.3)
    a = transcribe(wave, 16_000, model=model, params=params,
                   chunk_frames=8, max_new=4)
    for stream in (False, True):
        b = transcribe(wave, 16_000, chunk_frames=8, max_new=4,
                       engine=a.engine, stream=stream)
        assert b.tokens == a.tokens


def test_transcribe_rejects_non_enc_dec_and_empty_audio():
    with pytest.raises(ValueError):
        transcribe(synth_waveform(0.2), 16_000, arch="qwen3-4b")
    cfg, model, params = _whisper()
    with pytest.raises(ValueError):
        transcribe(np.zeros(0, np.float32), 16_000, model=model,
                   params=params)
