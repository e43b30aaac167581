"""Build the system under test from a configuration file, and make its
weights from the seed.

The configuration file holds the published ``config.json`` keys of the
model; ``arch_config`` maps them onto the program's ``ArchConfig``. The
weights are the benchmark's own: one jitted call draws every leaf of
the program's parameter tree from the seed, on the device, in the type
the configuration serves them in. The reference reads the same arrays.
"""

from __future__ import annotations

import numpy as np


def seed_key(seed: int) -> int:
    """A 32-bit key for ``jax.random.key`` from any whole-number seed."""
    ss = np.random.SeedSequence([seed & 0xFFFFFFFF, seed >> 32])
    return int(ss.generate_state(1, np.uint32)[0])


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a Whisper ``config.json``."""
    from repro.configs import ArchConfig
    hf = cfg["config"]
    if hf["encoder_attention_heads"] != hf["decoder_attention_heads"] or \
            hf["encoder_ffn_dim"] != hf["decoder_ffn_dim"]:
        raise ValueError("the program shares one head count and one FFN "
                         "width between encoder and decoder")
    return ArchConfig(
        name=cfg["name"], family="audio", enc_dec=True,
        n_layers=hf["decoder_layers"], enc_layers=hf["encoder_layers"],
        d_model=hf["d_model"], n_heads=hf["decoder_attention_heads"],
        n_kv_heads=hf["decoder_attention_heads"],
        d_ff=hf["decoder_ffn_dim"], vocab=hf["vocab_size"],
        act=hf["activation_function"], tie_embeddings=True,
        source=cfg["source"])


_DTYPES = {"bf16": "bfloat16", "f32": "float32"}
QUANT_TIERS = ("q8_0", "q4_0")


def _init_leaf(jax, jnp, path: str, shape, key, dtype):
    """Draw one leaf. Matrices are normal with variance 1/fan_in; norm
    scales sit near 1 and biases near 0, so that a norm applied wrong
    changes the output."""
    name = path.rsplit("'", 2)[-2] if "'" in path else path
    if name == "scale":
        v = 1.0 + 0.1 * jax.random.normal(key, shape)
    elif name == "bias":
        v = 0.02 * jax.random.normal(key, shape)
    elif name == "dec_pos":
        v = 0.02 * jax.random.normal(key, shape)
    else:
        if name == "wo":
            fan_in = shape[-3] * shape[-2]
        elif name in ("wq", "wk", "wv"):
            fan_in = shape[-3]
        elif name == "table":
            fan_in = shape[-1]
        else:
            fan_in = shape[-2]
        v = jax.random.normal(key, shape) * fan_in ** -0.5
    return v.astype(dtype)


def make_weights(model, seed: int, dtype: str = "bf16"):
    """Every leaf of ``model``'s parameter tree from ``seed``, in one
    jitted call on the default device."""
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(model.init_values, jax.random.key(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    dt = jnp.dtype(_DTYPES[dtype])

    @jax.jit
    def build(key):
        leaves = [_init_leaf(jax, jnp, path, sd.shape,
                             jax.random.fold_in(key, i), dt)
                  for i, (path, (_, sd)) in enumerate(zip(paths, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(jax.random.key(seed_key(seed)))


def served(params, deployment: dict):
    """The weights as the deployment serves them: as made, or quantized
    by the program's own ``quantize_tree`` for a quantized weight tier.
    The reference always reads the weights as made."""
    tier = deployment["weights"]
    if tier in _DTYPES:
        return params
    if tier not in QUANT_TIERS:
        raise ValueError(f"unknown weight tier {tier!r}")
    from repro.core.quantize import quantize_tree
    return quantize_tree(params, tier=tier)
