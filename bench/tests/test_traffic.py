"""The traffic generator: the same seed gives the same requests, and
every seed gives the same amount of work in another order; text mixes
and bursts."""

import collections
import dataclasses
import hashlib

import numpy as np
import pytest

import spec
import tiny
import traffic

MIXES = ("backlog", "poisson", "stream")
SEEDS = (0, 2**31 + 7, 2**33 + 1)
WHISPER = spec.family_module("whisper")
# sha256 of the requests (``dataclasses.astuple`` of each, ``repr`` of
# the list) that the generator made for whisper-tiny.en over a 20 s
# window before it took families and text mixes: (count, hash prefix)
AT_PARENT = {
    ("backlog", 7): (4096, "58742813b9acbec8"),
    ("backlog", 2**33 + 3141500043): (4096, "413a0861d2bcc1fc"),
    ("poisson", 7): (308, "2534eab98e51e5be"),
    ("poisson", 2**33 + 3141500043): (308, "535117e021347f39"),
    ("stream", 7): (28, "5b81b301c6b01e41"),
    ("stream", 2**33 + 3141500043): (28, "9b81ef95af3b6ff4"),
}


def _cfg():
    return spec.load_config(spec.load_benchmark(), "whisper-tiny.en")


def _gen(mix, seed, seconds=10, cfg=None, family=WHISPER):
    return traffic.generate(mix, cfg or _cfg(), family.prompt, seed,
                            seconds)


@pytest.mark.parametrize("name,seed", sorted(AT_PARENT))
def test_requests_as_before_families(name, seed):
    """The audio mixes make exactly the requests they made before the
    generator took text mixes and bursts."""
    reqs = _gen(spec.load_mix(name), seed, 20)
    digest = hashlib.sha256(repr([dataclasses.astuple(r) for r in reqs])
                            .encode()).hexdigest()
    assert (len(reqs), digest[:16]) == AT_PARENT[name, seed]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = spec.load_mix(name)
    a = _gen(mix, 2**31 + 5)
    b = _gen(mix, 2**31 + 5)
    assert a == b and a


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work(name):
    """Lengths, budgets and prompt sizes form the same multiset under
    every seed; arrivals span the same time."""
    mix = spec.load_mix(name)

    def work(seed):
        reqs = _gen(mix, seed)
        return collections.Counter((r.audio_s, r.max_new, len(r.prompt))
                                   for r in reqs)
    w = [work(s) for s in SEEDS]
    assert w[0] == w[1] == w[2]
    runs = [_gen(mix, s) for s in SEEDS]
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
        reqs = _gen(mix, s)
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
    runs = [_gen(mix, s) for s in SEEDS]
    shape = [[(r.audio_s, r.max_new, r.due) for r in reqs] for reqs in runs]
    assert shape[0] == shape[1] == shape[2]
    assert [r.wave_seed for r in runs[0]] != [r.wave_seed for r in runs[1]]


def test_open_loop_rate_and_grid():
    mix = spec.load_mix("poisson")
    reqs = _gen(mix, 9, 20)
    span = mix["lead_s"] + 20
    assert len(reqs) == round(mix["rate_per_s"] * span)
    assert {r.audio_s for r in reqs} <= set(mix["seconds_grid"])
    assert all(r.max_new == int(np.ceil(3 * r.audio_s)) for r in reqs)
    due = sorted(r.due for r in reqs)
    assert -mix["lead_s"] < due[0] and due[-1] <= 20 + 1e-9


def test_backlog_prompts_follow_whisper():
    cfg = _cfg()
    mix = spec.load_mix("backlog")
    for r in _gen(mix, 4)[:200]:
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
    r = _gen(mix, 3, 5, tiny.config())[0]
    ch = traffic.chunks_of(r, mix["chunk_s"])
    assert len(ch) == mix["session_s"]
    assert ch[0] == (r.due + 1.0, 0.0, 1.0)


def test_waveform_is_seeded():
    a = WHISPER.waveform(1.0, 5)
    assert a.shape == (16000,) and a.dtype == np.float32
    assert np.array_equal(a, WHISPER.waveform(1.0, 5))
    assert not np.array_equal(a, WHISPER.waveform(1.0, 6))


def _text(mix, seed, seconds=10):
    return _gen(mix, seed, seconds, tiny.DENSE, tiny.dense_family())


@pytest.mark.parametrize("kind", ["open", "closed"])
def test_text_mix_draws_from_its_grids(kind):
    """A mix with ``prompt_tokens`` and ``new_tokens`` makes text
    requests: no audio, prompts of the grid's lengths from the family's
    ``prompt``, budgets from the grid; every seed the same work."""
    mix = dict(tiny.CHAT)
    if kind == "closed":
        mix.update(kind="closed", block=8, pool=64, outstanding_per_slot=2)
    work = set()
    for s in SEEDS:
        reqs = _text(mix, s)
        assert reqs == _text(mix, s)
        assert all(r.audio_s == 0.0 and r.wave_seed == 0 for r in reqs)
        assert {len(r.prompt) for r in reqs} <= \
            set(mix["prompt_tokens"]["grid"])
        assert {r.max_new for r in reqs} <= set(mix["new_tokens"]["grid"])
        assert all(0 <= t < tiny.DENSE["prompt"]["text_tokens"]
                   for r in reqs for t in r.prompt)
        work.add(tuple(sorted(collections.Counter(
            (len(r.prompt), r.max_new) for r in reqs).items())))
    assert len(work) == 1


def test_bursts_keep_the_mean_rate():
    """On/off bursts: as many arrivals as the mean rate gives over the
    span, none in an off-phase, and about ``rate / duty`` in each
    on-phase; the seed orders the same gaps."""
    mix = dict(tiny.CHAT, rate_per_s=20.0, burst_period_s=2.0,
               burst_duty=0.25, lead_s=1.0, fixed_order=False)
    seconds = 60
    lead, period = mix["lead_s"], mix["burst_period_s"]
    on = mix["burst_duty"] * period
    runs = [_text(mix, s, seconds) for s in SEEDS]
    for reqs in runs:
        due = np.array(sorted(r.due for r in reqs))
        assert len(due) == round(mix["rate_per_s"] * (lead + seconds))
        assert -lead <= due[0] and due[-1] <= seconds + 1e-9
        phase = np.mod(due + lead, period)
        assert np.all((phase <= on + 1e-9) | (phase < 1e-9))
        in_window = np.sum((due >= 0) & (due < seconds))
        assert in_window == pytest.approx(mix["rate_per_s"] * seconds,
                                          rel=0.05)
    assert runs[0] != runs[1]


def test_bursts_must_have_a_duty():
    mix = dict(tiny.CHAT, burst_duty=0.0)
    with pytest.raises(ValueError):
        _text(mix, 1)
