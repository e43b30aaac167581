"""A test-only family: the program's dense decoder-only transformer
(``repro.configs`` qwen3-4b through ``reduced()``: RMSNorm, q/k norm,
rotary positions, grouped-query attention, SwiGLU MLP, untied head),
served one text prompt per request through ``Gateway.submit_tokens``.

It shows that a family other than Whisper joins the benchmark through
a file of its own (``spec.family_module`` lists what it gives); it is
kept under ``bench/tests/`` and runs at the sizes a CPU test holds.
The reference below imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

import model as bench_model

# a cached K/V element is off when it misses the reference's by more
# than this share of its plane's RMS (as Whisper's reference has it)
OFF = 2.0 ** -6
NORMS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")


def arch_config(cfg: dict):
    from repro.configs import get_config, reduced
    hf = cfg["config"]
    return dataclasses.replace(
        reduced(get_config("qwen3-4b")), name=cfg["name"],
        n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_head=hf["head_dim"],
        d_ff=hf["intermediate_size"], vocab=hf["vocab_size"],
        rope_theta=hf["rope_theta"], norm_eps=hf["rms_norm_eps"],
        source=cfg["source"])


def init_leaf(path: str, shape, key):
    import jax
    name = bench_model.leaf_name(path)
    if name in NORMS:
        return 1.0 + 0.1 * jax.random.normal(key, shape)
    if name == "wo":
        fan_in = shape[-3] * shape[-2]
    elif name in ("wq", "wk", "wv"):
        fan_in = shape[-3]
    elif name == "table":
        fan_in = shape[-1]
    else:
        fan_in = shape[-2]
    return jax.random.normal(key, shape) * fan_in ** -0.5


def engine_options(deployment: dict) -> dict:
    return {key: deployment[key]
            for key in ("n_slots", "max_len", "cache_dtype")}


def prompt(cfg: dict, n: int, rng: np.random.Generator) -> tuple:
    return tuple(int(t) for t in
                 rng.integers(0, cfg["prompt"]["text_tokens"], n))


def inputs(seed: int):
    return None


async def submit(runner, req, max_new: int):
    # two requests in flight with one prompt share a key: either lane
    # then stands for both, whose reference is the same
    with runner.in_flight(tuple(req.prompt), req):
        return await runner.gw.submit_tokens(
            list(req.prompt), max_new=max_new, eos_id=-1, slo=runner.slo)


def lane_key(request):
    return tuple(request.tokens)


def read_lane(cache, slot: int, state) -> dict:
    import serve
    return serve.kv_planes(
        {"self": (cache["segments"]["block0"]["kv"], state.pos)}, slot)


def _forward(hf: dict):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    group = hf["num_attention_heads"] // hf["num_key_value_heads"]

    def norm(w, x):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w

    def rope(x):
        t, half = x.shape[0], x.shape[-1] // 2
        ang = jnp.arange(t)[:, None] * theta ** (-jnp.arange(half) / half)
        c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * c - b * s, b * c + a * s], -1)

    def fwd(params, tokens):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        t = tokens.shape[0]
        x = p["embed"]["table"][tokens]
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        seg = p["segments"]["block0"]
        kv = []
        for li in range(seg["ln1"].shape[0]):
            lp = jax.tree.map(lambda a: a[li], seg)
            at = lp["attn"]
            h = norm(lp["ln1"], x)
            q = rope(norm(at["q_norm"], jnp.einsum(
                "sd,dhk->shk", h, at["wq"], precision=hi)))
            k = rope(norm(at["k_norm"], jnp.einsum(
                "sd,dhk->shk", h, at["wk"], precision=hi)))
            v = jnp.einsum("sd,dhk->shk", h, at["wv"], precision=hi)
            kv.append(jnp.stack([k, v]))
            kr, vr = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
            s = jnp.einsum("qhk,shk->hqs", q, kr, precision=hi) \
                / math.sqrt(q.shape[-1])
            w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
            o = jnp.einsum("hqs,shk->qhk", w, vr, precision=hi)
            x = x + jnp.einsum("qhk,hkd->qd", o, at["wo"], precision=hi)
            h = norm(lp["ln2"], x)
            m = lp["mlp"]
            g = jax.nn.silu(jnp.einsum("sd,df->sf", h, m["gate"],
                                       precision=hi))
            u = jnp.einsum("sd,df->sf", h, m["up"], precision=hi)
            x = x + jnp.einsum("sf,fd->sd", g * u, m["down"], precision=hi)
        y = norm(p["final_norm"], x)
        logits = jnp.einsum("td,dv->tv", y, p["lm_head"][:, :hf["vocab_size"]],
                            precision=hi)
        # K/V by (layer, k|v, position, kv head, head_dim)
        return logits, jnp.stack(kv)

    return jax.jit(fwd)


class Reference:
    """Teacher-forced float32 logits at ``HIGHEST``; every call pads the
    tokens to ``max_len``, so one program serves every request."""

    def __init__(self, cfg: dict, params):
        self.params = params
        self.max_len = cfg["deployment"]["max_len"]
        self._fwd = _forward(cfg["config"])

    def judge(self, runner, req, tokens, planes=None):
        toks = list(req.prompt) + list(tokens[:-1])
        row = np.zeros(self.max_len, np.int32)
        row[:len(toks)] = toks
        logits, kv = self._fwd(self.params, row)
        at = np.arange(len(req.prompt) - 1, len(toks))
        rows = np.asarray(logits)[at]
        gaps = rows.max(axis=1) - rows[np.arange(len(at)),
                                       np.asarray(tokens)]
        if planes is None:
            return gaps, None
        ref, worst = np.asarray(kv), 0.0
        for i, name in enumerate(("k", "v")):
            got = planes["self"][name]
            r = ref[:, i, :got.shape[1]].astype(np.float64)
            rms = np.sqrt((r * r).mean(axis=(1, 2, 3), keepdims=True))
            off = np.abs(got - r) > OFF * rms
            worst = max(worst, float(off.mean(axis=(1, 2, 3)).max()))
        return gaps, {"self": worst}


def request_flops(cfg: dict, req, n_out: int) -> float:
    """Matrix-multiply FLOPs of the prompt and ``n_out - 1`` decode
    steps: projections, causal attention over the context, MLP, head."""
    hf = cfg["config"]
    d, f = hf["hidden_size"], hf["intermediate_size"]
    h, hk, dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                 hf["head_dim"])
    n = len(req.prompt) + max(n_out - 1, 0)
    ctx = n * (n + 1) // 2
    per_layer = 2 * n * d * (2 * h + 2 * hk) * dh + 4 * ctx * h * dh \
        + 6 * n * d * f
    return hf["num_hidden_layers"] * per_layer + 2 * n * d * hf["vocab_size"]
