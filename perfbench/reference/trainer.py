"""The proxy's training, written from the paper's description (section
3.2 and 5) and the schedule the program documents for it.

One leaf's proxy is trained on a labelled sample of the collection:
``train_fraction`` of the documents, drawn without replacement by
``numpy.random.default_rng((seed, fp))`` where ``fp`` is the first 12 hex
digits of the SHA-1 of the query embedding's bytes; the labels are the
oracle's (here the truth). The jax key is ``fold_in(PRNGKey(seed), fp &
0x7FFFFFFF)``, split into (init, rebalance, train) keys.

- Init: each layer's weight a normal truncated to [-2, 2], over
  sqrt(fan_in); biases zero. Three layers D -> H -> H -> L with GELU (tanh
  form) between, then a linear projector head L -> P for training only.
- Rebalance: when the minority class is under ``rebalance_min_frac`` of
  the sample, Gaussian-noised copies of minority rows make it up.
- Step ``t`` (of ``phase1_steps + phase2_steps``) draws its batch of
  ``batch_size`` rows uniformly with replacement from the key
  ``fold_in(train_key, t)``, adds ``aug_noise`` Gaussian noise to it, and
  takes one AdamW step (b1 0.9, b2 0.95, eps 1e-8, weight decay on
  matrices, global-norm clip 1.0, 5 warm-up steps, cosine decay).
- Phase 1 minimises InfoNCE with the query as anchor, averaged over the
  batch's positives; phase 2 ``lam * SupCon + (1 - lam) * polar``, where
  polar pulls each class toward its bellwether (the positive least like
  the query, the negative most like it). Every loss works on the
  projected, L2-normalised latents at temperature ``tau``.

``dtype="bfloat16"`` computes every loss and gradient in bfloat16 (the
weights and the optimizer stay float32): the control, one step below the
float32 the configuration states. ``batch_size`` and the step counts come
from the caller, so a planted fault (half of each batch, fewer steps)
runs the same code.
"""
from __future__ import annotations

import functools
import hashlib
import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
B1, B2, EPS, CLIP, WARMUP = 0.9, 0.95, 1e-8, 1.0, 5


class Trained(NamedTuple):
    init: Dict            # {"layers": {"l<i>": {"w", "b"}}, "proj": {"w", "b"}}
    final: Dict
    losses: np.ndarray    # (steps,) each step's loss before its update
    grad0: Dict           # the first step's gradient


def fingerprint(e_q: np.ndarray) -> int:
    return int(hashlib.sha1(np.asarray(e_q, np.float32).tobytes()).hexdigest()[:12], 16)


def sample(seed: int, e_q: np.ndarray, n_docs: int, train_fraction: float):
    """The labelled sample's document ids and the leaf's training key."""
    fp = fingerprint(e_q)
    n_train = min(max(int(train_fraction * n_docs), 16), n_docs)
    idx = np.random.default_rng((seed, fp)).choice(n_docs, size=n_train, replace=False)
    return idx, jax.random.fold_in(jax.random.PRNGKey(seed), fp & 0x7FFFFFFF)


def init(key, dims, proj_dim) -> Dict:
    keys = jax.random.split(key, len(dims))

    def dense(k, a, b):
        return jax.random.truncated_normal(k, -2.0, 2.0, (a, b)) * (1.0 / math.sqrt(a))

    layers = {f"l{i}": {"w": dense(keys[i], a, b), "b": jnp.zeros((b,), jnp.float32)}
              for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))}
    return {"layers": layers,
            "proj": {"w": dense(keys[-1], dims[-1], proj_dim),
                     "b": jnp.zeros((proj_dim,), jnp.float32)}}


def rebalance(kbal, embeds: np.ndarray, labels: np.ndarray, min_frac: float,
              noise: float):
    labels = labels.astype(np.int32)
    n, n_pos = len(labels), int(labels.sum())
    n_neg = n - n_pos
    if min(n_pos, n_neg) >= min_frac * n or n_pos == 0 or n_neg == 0:
        return embeds, labels
    minority = 1 if n_pos < n_neg else 0
    src = embeds[labels == minority]
    need = int(min_frac * n) - len(src)
    if need <= 0:
        return embeds, labels
    rng = np.random.default_rng(int(np.asarray(kbal).ravel()[-1]))
    pick = rng.integers(0, len(src), size=need)
    extra = src[pick] + rng.normal(0.0, noise, size=(need, embeds.shape[1])).astype(embeds.dtype)
    return (np.concatenate([embeds, extra]),
            np.concatenate([labels, np.full(need, minority, labels.dtype)]))


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                           jnp.asarray(1e-8, x.dtype))


def _project(p, x, dt):
    n = len(p["layers"])
    for i in range(n):
        layer = p["layers"][f"l{i}"]
        x = jnp.dot(x, layer["w"].astype(dt), precision=HI) + layer["b"].astype(dt)
        if i < n - 1:
            x = jax.nn.gelu(x, approximate=True)
    return jnp.dot(x, p["proj"]["w"].astype(dt), precision=HI) + p["proj"]["b"].astype(dt)


def _lse(x, mask, axis=-1):
    return jax.nn.logsumexp(jnp.where(mask, x, jnp.asarray(-1e30, x.dtype)), axis=axis)


def _qsim(zq, zd, pos, tau):
    sims = jnp.dot(zd, zq, precision=HI) / tau
    per = -(sims - jax.nn.logsumexp(sims))
    loss = jnp.sum(jnp.where(pos, per, 0)) / jnp.maximum(jnp.sum(pos), 1)
    return jnp.where(jnp.any(pos), loss, 0)


def _supcon(zd, pos, tau):
    n = zd.shape[0]
    sims = jnp.dot(zd, zd.T, precision=HI) / tau
    other = ~jnp.eye(n, dtype=bool)
    same = (pos[:, None] == pos[None, :]) & other
    count = jnp.sum(same, axis=1)
    per = -(_lse(sims, same, 1) - _lse(sims, other, 1)) / jnp.maximum(count, 1)
    valid = count > 0
    return jnp.sum(jnp.where(valid, per, 0)) / jnp.maximum(jnp.sum(valid), 1)


def _polar(zq, zd, pos, tau):
    sim_q = jnp.dot(zd, zq, precision=HI)
    bp = zd[jnp.argmin(jnp.where(pos, sim_q, jnp.inf))]
    bn = zd[jnp.argmax(jnp.where(~pos, sim_q, -jnp.inf))]
    sp = jnp.dot(zd, bp, precision=HI) / tau
    sn = jnp.dot(zd, bn, precision=HI) / tau
    lp = -(_lse(sp, pos) - jax.nn.logsumexp(sp))
    ln = -(_lse(sn, ~pos) - jax.nn.logsumexp(sn))
    return jnp.where(jnp.any(pos), lp, 0) + jnp.where(jnp.any(~pos), ln, 0)


def _loss(p, e_q, xb, yb, phase2, tau, lam, dt):
    zq = _unit(_project(p, e_q.astype(dt), dt))
    zd = _unit(_project(p, xb.astype(dt), dt))
    pos = yb > 0.5
    tau = jnp.asarray(tau, dt)
    if phase2:
        return lam * _supcon(zd, pos, tau) + (1 - lam) * _polar(zq, zd, pos, tau)
    return _qsim(zq, zd, pos, tau)


@functools.partial(jax.jit, static_argnames=("steps1", "steps2", "bs", "hp", "dt"))
def _train(params, ktrain, e_q, embeds, labels, *, steps1, steps2, bs, hp, dt):
    lr, wd, aug, tau, lam = hp
    total = steps1 + steps2
    n_valid = jnp.asarray(embeds.shape[0], jnp.int32)

    def draws(t):
        kb, kn = jax.random.split(jax.random.fold_in(ktrain, t))
        idx = jax.random.randint(kb, (bs,), 0, n_valid)
        xb = jnp.take(embeds, idx, axis=0)
        return xb + aug * jax.random.normal(kn, xb.shape, xb.dtype), jnp.take(labels, idx)

    xs, ys = jax.vmap(draws)(jnp.arange(total))
    zeros = jax.tree.map(jnp.zeros_like, params)

    def step(carry, batch, phase2):
        p, m, v, k = carry
        xb, yb = batch
        loss, g = jax.value_and_grad(
            lambda q: _loss(q, e_q, xb, yb, phase2, tau, lam, dt).astype(jnp.float32))(p)
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, CLIP / jnp.maximum(norm, 1e-9)), g)
        k = k + 1
        kf = k.astype(jnp.float32)
        rate = lr * jnp.minimum(kf / WARMUP, 1.0) * 0.5 * (
            1 + jnp.cos(jnp.pi * jnp.clip((kf - WARMUP) / max(total - WARMUP, 1), 0.0, 1.0)))
        m = jax.tree.map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
        v = jax.tree.map(lambda a, b: B2 * a + (1 - B2) * b * b, v, g)

        def upd(x, a, b):
            d = (a / (1 - B1 ** kf)) / (jnp.sqrt(b / (1 - B2 ** kf)) + EPS)
            return x - rate * (d + wd * x if x.ndim >= 2 else d)

        p = jax.tree.map(upd, p, m, v)
        return (p, m, v, k), loss

    carry = (params, zeros, zeros, jnp.zeros((), jnp.int32))
    carry, l1 = jax.lax.scan(functools.partial(step, phase2=False), carry,
                             (xs[:steps1], ys[:steps1]))
    carry, l2 = jax.lax.scan(functools.partial(step, phase2=True), carry,
                             (xs[steps1:], ys[steps1:]))
    g0 = jax.grad(lambda q: _loss(q, e_q, xs[0], ys[0], steps1 == 0, tau, lam, dt)
                  .astype(jnp.float32))(params)
    return carry[0], jnp.concatenate([l1, l2]), g0


def train(key, e_q, embeds: np.ndarray, labels: np.ndarray, proxy: Dict, *,
          dtype: str = "float32", batch_size: int = None,
          steps=None) -> Trained:
    """Train one proxy from the leaf's key on its labelled sample.
    ``proxy`` holds the configuration's proxy settings; ``batch_size``
    and ``steps`` (phase 1, phase 2) override them for a planted fault."""
    kinit, kbal, ktrain = jax.random.split(key, 3)
    dims = [proxy["embed_dim"]] + [proxy["hidden_dim"]] * (proxy["num_layers"] - 1) \
        + [proxy["latent_dim"]]
    p0 = init(kinit, dims, proxy["proj_dim"])
    x, y = rebalance(kbal, np.asarray(embeds, np.float32), np.asarray(labels),
                     proxy["rebalance_min_frac"], proxy["rebalance_noise"])
    s1, s2 = steps or (proxy["phase1_steps"], proxy["phase2_steps"])
    hp = (proxy["lr"], proxy["weight_decay"], proxy["aug_noise"],
          proxy["temperature"], proxy["lambda_supcon"])
    dt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype]
    final, losses, g0 = _train(p0, ktrain, jnp.asarray(e_q, jnp.float32), jnp.asarray(x),
                               jnp.asarray(y.astype(np.float32)), steps1=s1, steps2=s2,
                               bs=batch_size or proxy["batch_size"], hp=hp, dt=dt)
    return Trained(p0, final, np.asarray(losses, np.float64), g0)
