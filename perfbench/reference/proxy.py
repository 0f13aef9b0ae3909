"""The proxy's decision score, written from the paper's description.

A 3-layer perceptron E maps a document embedding and the query embedding
into one latent space (GELU, tanh form, between layers); the score is
(1 + cos(E(q), E(d))) / 2. ``precision="bfloat16"`` computes every step in
bfloat16 instead: the control, one step below the float32 the
configuration states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _mlp(layers, x, dt):
    for i, (w, b) in enumerate(layers):
        x = jnp.dot(x, w.astype(dt), precision="highest") + b.astype(dt)
        if i < len(layers) - 1:
            x = jax.nn.gelu(x, approximate=True)
    return x


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                           jnp.asarray(1e-8, x.dtype))


@partial(jax.jit, static_argnames=("dt",))
def _scores(layers, e_q, docs, dt):
    zq = _unit(_mlp(layers, e_q.astype(dt), dt))
    zd = _unit(_mlp(layers, docs.astype(dt), dt))
    return ((1 + jnp.dot(zd, zq, precision="highest")) / 2).astype(jnp.float32)


def layers_of(params) -> list:
    """[(w, b), ...] in order from the program's proxy tree
    (``layers.l<i>.{w,b}``); the projector head is not part of a score."""
    ls = params["layers"]
    return [(jnp.asarray(ls[f"l{i}"]["w"], jnp.float32),
             jnp.asarray(ls[f"l{i}"]["b"], jnp.float32)) for i in range(len(ls))]


def scores(layers, e_q, docs_device, precision: str = "float32",
           block: int = 16384) -> np.ndarray:
    """Scores of every row of ``docs_device`` (an (N, D) device array)."""
    dt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]
    e_q = jnp.asarray(e_q, jnp.float32)
    out = [np.asarray(_scores(layers, e_q, docs_device[i:i + block], dt))
           for i in range(0, docs_device.shape[0], block)]
    return np.concatenate(out)
