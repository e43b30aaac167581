"""The traffic generator: the same seed gives the same requests, and
every seed gives the same amount of work in another order."""

import collections

import numpy as np
import pytest

import spec
import tiny
import traffic

MIXES = ("backlog", "poisson", "stream")
SEEDS = (0, 2**31 + 7, 2**33 + 1)


def _cfg():
    return spec.load_config(spec.load_benchmark(), "whisper-tiny.en")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = spec.load_mix(name)
    a = traffic.generate(mix, _cfg(), 2**31 + 5, 10)
    b = traffic.generate(mix, _cfg(), 2**31 + 5, 10)
    assert a == b and a


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work(name):
    """Lengths, budgets and prompt sizes form the same multiset under
    every seed; arrivals span the same time."""
    mix = spec.load_mix(name)

    def work(seed):
        reqs = traffic.generate(mix, _cfg(), seed, 10)
        return collections.Counter((r.audio_s, r.max_new, len(r.prompt))
                                   for r in reqs)
    w = [work(s) for s in SEEDS]
    assert w[0] == w[1] == w[2]
    runs = [traffic.generate(mix, _cfg(), s, 10) for s in SEEDS]
    if name != "backlog":
        last = [max(r.due for r in reqs) for reqs in runs]
        assert np.allclose(last, last[0], atol=0.5)
    assert runs[0] != runs[1]


def test_backlog_blocks_share_the_work():
    """Every block of the closed-loop pool holds the same work under
    every seed, so a window gets through the same work whatever the
    seed orders."""
    mix = spec.load_mix("backlog")
    b = mix["block"]
    blocks = set()
    for s in SEEDS:
        reqs = traffic.generate(mix, _cfg(), s, 10)
        assert len(reqs) == mix["pool"] and mix["pool"] % b == 0
        for i in range(0, len(reqs), b):
            blocks.add(tuple(sorted((r.max_new, len(r.prompt))
                                    for r in reqs[i:i + b])))
    assert len(blocks) == 1


def test_fixed_order_keeps_arrivals_and_sizes():
    """With ``fixed_order`` every seed gets the same lengths and arrival
    times in the same order; only the content differs."""
    mix = spec.load_mix("poisson")
    assert mix["fixed_order"]
    runs = [traffic.generate(mix, _cfg(), s, 10) for s in SEEDS]
    shape = [[(r.audio_s, r.max_new, r.due) for r in reqs] for reqs in runs]
    assert shape[0] == shape[1] == shape[2]
    assert [r.wave_seed for r in runs[0]] != [r.wave_seed for r in runs[1]]


def test_open_loop_rate_and_grid():
    mix = spec.load_mix("poisson")
    reqs = traffic.generate(mix, _cfg(), 9, 20)
    span = mix["lead_s"] + 20
    assert len(reqs) == round(mix["rate_per_s"] * span)
    assert {r.audio_s for r in reqs} <= set(mix["seconds_grid"])
    assert all(r.max_new == int(np.ceil(3 * r.audio_s)) for r in reqs)
    due = sorted(r.due for r in reqs)
    assert -mix["lead_s"] < due[0] and due[-1] <= 20 + 1e-9


def test_backlog_prompts_follow_whisper():
    cfg = _cfg()
    mix = spec.load_mix("backlog")
    for r in traffic.generate(mix, cfg, 4, 10)[:200]:
        sot = tuple(cfg["prompt"]["sot_sequence"])
        assert r.prompt[-len(sot):] == sot
        n_prev = len(r.prompt) - len(sot)
        if n_prev:
            assert r.prompt[0] == cfg["prompt"]["startofprev"]
            assert n_prev - 1 <= mix["prev_text_tokens"][1]
        assert mix["new_tokens"][0] <= r.max_new <= mix["new_tokens"][1]
        assert len(r.prompt) + r.max_new < cfg["deployment"]["max_len"]


def test_stream_chunks_are_due_when_spoken():
    mix = tiny.mix("stream")
    r = traffic.generate(mix, tiny.config(), 3, 5)[0]
    ch = traffic.chunks_of(r, mix["chunk_s"])
    assert len(ch) == mix["session_s"]
    assert ch[0] == (r.due + 1.0, 0.0, 1.0)


def test_waveform_is_seeded():
    a = traffic.waveform(1.0, 5)
    assert a.shape == (16000,) and a.dtype == np.float32
    assert np.array_equal(a, traffic.waveform(1.0, 5))
    assert not np.array_equal(a, traffic.waveform(1.0, 6))
