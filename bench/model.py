"""Build the system under test's weights from the seed.

The configuration's family module (``families/<family>.py``) maps the
configuration onto the program's ``ArchConfig`` and says how each leaf
is drawn. The weights are the benchmark's own: one jitted call draws
every leaf of the program's parameter tree from the seed, on the
device, in the type the configuration serves them in. The reference
reads the same arrays.
"""

from __future__ import annotations

import numpy as np


def seed_key(seed: int) -> int:
    """A 32-bit key for ``jax.random.key`` from any whole-number seed."""
    ss = np.random.SeedSequence([seed & 0xFFFFFFFF, seed >> 32])
    return int(ss.generate_state(1, np.uint32)[0])


_DTYPES = {"bf16": "bfloat16", "f32": "float32"}
QUANT_TIERS = ("q8_0", "q4_0")


def make_weights(model, seed: int, init_leaf, dtype: str = "bf16"):
    """Every leaf of ``model``'s parameter tree from ``seed``, in one
    jitted call on the default device. ``init_leaf(path, shape, key)``
    (the family's) draws one leaf in float32 by its key path."""
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(model.init_values, jax.random.key(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    dt = jnp.dtype(_DTYPES[dtype])

    @jax.jit
    def build(key):
        leaves = [init_leaf(path, sd.shape,
                            jax.random.fold_in(key, i)).astype(dt)
                  for i, (path, (_, sd)) in enumerate(zip(paths, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build(jax.random.key(seed_key(seed)))


def leaf_name(path: str) -> str:
    """The last key of a ``keystr`` path: ``wq`` of
    ``['dec_layers']['self_attn']['wq']``."""
    return path.rsplit("'", 2)[-2] if "'" in path else path


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
