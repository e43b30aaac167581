"""``correct`` at a size a CPU test can hold: a sound run passes, and a
run whose timed path is broken underneath fails.

The harness runs as ``bench/run.py`` runs it, minus the look for a chip:
the tiny configurations (``tiny.py``) through the gateway and engine on
the CPU, checked against their family's plain reference with the cell's
own limits: Whisper on the benchmark's mixes, and the test-only dense
family (``families/dense.py``) on a bursty text mix, ``chat``."""

import copy

import numpy as np
import pytest

import run
import spec
import tiny

# the stream's and the chat's cells are not in BENCHMARK.json
CELLS = {"backlog": "tiny_en.backlog", "poisson": "tiny_en.poisson",
         "stream": "tiny_en.stream", "chat": "dense.chat"}


def _limits(mixname):
    """The cell's limits; the stream, with no cell yet, is judged by the
    backlog's, less the one-shot lanes' K/V (a stream has none to
    read)."""
    if mixname != "stream":
        return spec.load_limits(CELLS[mixname])
    lim = dict(spec.load_limits(CELLS["backlog"]))
    del lim["lanes"]
    return lim


def _run(mixname, cfg=None, seconds=2.0, seed=2**32 + 3):
    bench = spec.load_benchmark()
    if mixname == "chat":
        cfg, mix, limits = cfg or tiny.DENSE, tiny.CHAT, tiny.CHAT_LIMITS
        family = tiny.dense_family()
    else:
        cfg, mix, limits = cfg or tiny.config(), tiny.mix(mixname), \
            _limits(mixname)
        family = None
    cell = {"name": CELLS[mixname], "config": cfg["name"],
            "traffic": mixname, "chips": 1}
    return run.run_cell(bench, cell, cfg, mix, limits, seed=seed,
                        seconds=seconds, trace=False,
                        peaks=spec.load_peaks("TPU v5 lite"), family=family,
                        log=lambda m: None)


@pytest.mark.parametrize("mixname", sorted(CELLS))
def test_sound_run_is_correct(mixname):
    out = _run(mixname)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "compared"


@pytest.fixture
def altered_tokens(monkeypatch):
    """Every emitted decode token replaced by its successor where the
    engine hands it from the device to the host."""
    from repro.serving.engine import ServeEngine
    fetch = ServeEngine.step_fetch

    def bad(self, pending):
        tok, emit = fetch(self, pending)
        tok = np.where(emit, (np.asarray(tok) + 1) % 500, tok)
        return tok, emit
    monkeypatch.setattr(ServeEngine, "step_fetch", bad)


@pytest.mark.parametrize("mixname", sorted(CELLS))
def test_altered_token_is_caught(mixname, altered_tokens):
    out = _run(mixname)
    assert not out["correct"]
    gap = out["compared"]["token_gap"]
    assert gap["value"] > gap["limit"]


def test_control_is_not_correct():
    """The control (``control.py --tier q8_0``): the same harness with the
    program's own q8_0 weights and KV cache switched on, checked against
    the same reference, comes out not correct: its lanes' cached K/V
    lies off the reference's in more elements than the limits allow."""
    import jax
    import model
    import serve
    from repro.core.quantize import Q8Tensor
    cfg = copy.deepcopy(tiny.config())
    cfg["deployment"].update(weights="q8_0", cache_dtype="q8_0")
    family = spec.family_module(cfg["family"])
    params = model.make_weights(serve._build(family.arch_config(cfg)), 3,
                                family.init_leaf)
    leaves = jax.tree.leaves(model.served(params, cfg["deployment"]),
                             is_leaf=lambda x: isinstance(x, Q8Tensor))
    assert any(isinstance(leaf, Q8Tensor) for leaf in leaves)
    out = _run("backlog", cfg, seconds=2.0)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert not out["correct"]
    for name in ("self_kv_off", "cross_kv_off"):
        c = out["compared"][name]
        assert c["value"] > c["limit"], (name, c)


@pytest.mark.parametrize("tier", ["bf16", "q8_0"])
def test_lane_planes_reads_the_pool(tier):
    """Whisper's ``lane_planes`` (``serve.kv_planes``) reads one lane's
    K/V back as float32: bf16 planes as stored, q8_0 planes decoded as
    the program decodes them."""
    import jax
    import jax.numpy as jnp
    from repro.core.quantize import Q8Tensor, dequantize_q8_0
    from repro.models.attention import quantize_kv_cache
    rng = np.random.default_rng(0)
    layer = {kind: {key: jnp.asarray(rng.standard_normal((2, 3, n, 2, 64)),
                                     jnp.bfloat16)
                    for key in ("k", "v")}
             for kind, n in (("self", 16), ("cross", 24))}
    cache = {"layers": layer if tier == "bf16"
             else quantize_kv_cache(layer, tier)}
    got = spec.family_module("whisper").lane_planes(cache, 1, 5, 7)
    for kind, n in (("self", 5), ("cross", 7)):
        for key in ("k", "v"):
            if tier == "bf16":
                want = layer[kind][key][:, 1, :n].astype(jnp.float32)
            else:
                p = cache["layers"][kind]
                want = dequantize_q8_0(Q8Tensor(p[key + "q"], p[key + "s"]))
                want = want[:, 1, :n]
            np.testing.assert_array_equal(got[kind][key],
                                          np.asarray(jax.device_get(want)))
