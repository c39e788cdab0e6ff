"""Seeded weights for the program's dense decoder, made on the device.

The tree is the one the program's `Model.init` returns for a dense config
(`embed`, `final_norm`, `lm_head`, and `layers` stacked over depth), which
is the program's weight-loading interface.  The values are the benchmark's
own: one jitted call draws every leaf from the seed in the served dtype.
Matrices are normal with standard deviation 0.02 (the scale of the
published models' `initializer_range`); norm scales are 1 plus a 0.1-wide
normal and QKV biases a 0.02-wide normal, so that neither path is a no-op
that a fault could hide behind.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, including ones past 32 bits."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def shapes(m: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes of the dense tree for the config's `model` block."""
    d, h, kvh = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd, f, v, n = m["d_head"], m["d_ff"], m["vocab_size"], m["n_layers"]
    attn = {
        "wq": (n, d, h, hd), "wk": (n, d, kvh, hd), "wv": (n, d, kvh, hd),
        "wo": (n, h, hd, d),
    }
    if m["qkv_bias"]:
        attn.update(bq=(n, h, hd), bk=(n, kvh, hd), bv=(n, kvh, hd))
    tree = {
        "embed": (v, d),
        "final_norm": {"scale": (d,)},
        "layers": {
            "attn": attn,
            "norm1": {"scale": (n, d)},
            "norm2": {"scale": (n, d)},
            "ffn": {"w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d)},
        },
    }
    if not m["tie_embeddings"]:
        tree["lm_head"] = (d, v)
    return tree


def make(m: Dict[str, Any], seed: int):
    """The whole tree on the default device, in ``m["dtype"]``, from one
    jitted call."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(m["dtype"])
    tree = shapes(m)
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 tree, is_leaf=lambda x: isinstance(x, tuple))[0]]

    def draw(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, shape, path in zip(keys, leaves, paths):
            z = jax.random.normal(k, shape, jnp.float32)
            if "'scale'" in path:
                x = 1.0 + 0.1 * z
            else:
                x = 0.02 * z
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)(seed_key(seed))
