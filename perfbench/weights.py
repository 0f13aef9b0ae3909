"""Backbone weights from the seed, on the device, in the type they are
served in, in one jitted call.

The tree has the layout the program's decoder reads
(``repro.models.transformer``: ``embed.table``, ``final_norm.scale`` and
``blocks.p0.*`` stacked over layers); the plain reference reads the same
tree. Matrices are normal with std 1/sqrt(fan_in), the embedding has std
0.02 and norm scales are 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def llama_shapes(cfg: dict) -> dict:
    """name -> (shape, fan_in or None for ones, or -1 for the embedding)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // nq
    n = cfg["num_hidden_layers"]
    vpad = (cfg["vocab_size"] + 255) // 256 * 256
    return {
        "embed/table": ((vpad, d), -1),
        "final_norm/scale": ((d,), None),
        "blocks/p0/norm1/scale": ((n, d), None),
        "blocks/p0/norm2/scale": ((n, d), None),
        "blocks/p0/attn/wq": ((n, d, nq, hd), d),
        "blocks/p0/attn/wk": ((n, d, nkv, hd), d),
        "blocks/p0/attn/wv": ((n, d, nkv, hd), d),
        "blocks/p0/attn/wo": ((n, nq, hd, d), nq * hd),
        "blocks/p0/mlp/wi": ((n, d, f), d),
        "blocks/p0/mlp/wg": ((n, d, f), d),
        "blocks/p0/mlp/wo": ((n, f, d), f),
    }


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def llama_params(cfg: dict, key, dtype=jnp.bfloat16, out_sharding=None) -> dict:
    shapes = llama_shapes(cfg)

    def make(key):
        flat = {}
        for i, (name, (shape, fan_in)) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if fan_in is None:
                x = jnp.ones(shape, jnp.float32)
            elif fan_in == -1:
                x = 0.02 * jax.random.normal(k, shape, jnp.float32)
            else:
                x = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(
                    jnp.float32(fan_in))
            flat[name] = x.astype(dtype)
        return _nest(flat)

    return jax.jit(make, out_shardings=out_sharding)(key)
