"""Two-phase contrastive training of the query-aware proxy (paper §3.2, §5).

Given a small oracle-labeled sample of document embeddings, trains the
lightweight encoder:
  Phase 1: L_qsim only              -> semantic monotonicity
  Phase 2: lam*L_supcon + (1-lam)*L_polar -> bipolarity

Implementation details from paper §5:
  * fallback-style rebalancing: if the labeled sample is heavily skewed,
    augment the minority class with Gaussian-noised copies of its
    embeddings;
  * mini-batches contain the query embedding + documents; the projector
    head exists only during training;
  * losses are computed on projector outputs, scores on encoder outputs.

Execution model (the online-latency hot path, ScaleDoc §5): the whole
two-phase run is ONE compiled device program — ``lax.scan`` over training
steps with on-device batch sampling (`jax.random` keys folded per step),
params/opt-state buffers donated to the jit, and the full loss trace
returned as a single array, so a run costs one dispatch and one
device->host sync instead of one of each per step. Phase-2 losses route
through ``repro.kernels.contrastive`` (Pallas forward on TPU, reference
VJP backward). ``train_proxy_multi`` vmaps the same scanned core over Q
stacked (e_q, sample, labels) sets so a compound predicate's leaves all
train in one program; ragged samples are zero-padded to a shared bucket
and a per-leaf ``n_valid`` bounds the batch sampler, which makes padding
invisible to the math — multi results are identical to Q single calls.
The padded batch is built on the device: the host copies only the rows
that exist, in fixed-size blocks, into a device buffer of zeros.

Batch indices are drawn per step as ``randint(fold_in(key, t), (bs,), 0,
n_valid)`` (uniform with replacement). The pre-scan per-step host loop
survives as ``method="steps"`` — same key schedule, same batches, same
math — as the parity oracle and the dispatch-overhead baseline that
benchmarks/bench_training.py measures against.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import OptimizerConfig, ProxyConfig
from repro.core import losses
from repro.core.encoder import encoder_apply, encoder_init, projector_apply
from repro.kernels.contrastive import ops as contrastive_ops
from repro.optimizer import adamw
from repro.runtime import trace


class ProxyTrainResult(NamedTuple):
    params: Dict
    phase1_losses: np.ndarray
    phase2_losses: np.ndarray


class ProxyTrainResultMulti(NamedTuple):
    """Q proxies trained in one compiled program. ``params`` leaves carry
    a leading (Q,) axis; use :func:`unstack_params` for per-proxy trees."""
    params: Dict
    phase1_losses: np.ndarray   # (Q, phase1_steps)
    phase2_losses: np.ndarray   # (Q, phase2_steps)


def _key_seed(key) -> int:
    """Host uint32 seed from a PRNG key — handles both typed PRNG key
    arrays (where np.asarray raises) and legacy uint32 vector keys (kept
    byte-compatible with the pre-typed-key seeding)."""
    data = key
    dtype = getattr(key, "dtype", None)
    if dtype is not None and jnp.issubdtype(dtype, jax.dtypes.prng_key):
        data = jax.random.key_data(key)
    return int(np.asarray(data).ravel()[-1])


def rebalance(key, embeds: np.ndarray, labels: np.ndarray,
              cfg: ProxyConfig) -> Tuple[np.ndarray, np.ndarray]:
    """Fallback rebalancing: Gaussian-noise augmentation of the minority."""
    labels = labels.astype(np.int32)
    n = len(labels)
    n_pos = int(labels.sum())
    n_neg = n - n_pos
    if n == 0 or min(n_pos, n_neg) >= cfg.rebalance_min_frac * n:
        return embeds, labels
    if n_pos == 0 or n_neg == 0:
        # degenerate sample: nothing to mirror — caller handles
        return embeds, labels
    minority = 1 if n_pos < n_neg else 0
    need = int(cfg.rebalance_min_frac * n) - min(n_pos, n_neg)
    if need <= 0:
        return embeds, labels
    # the sample is read on the host only when it is augmented
    embeds = np.asarray(embeds)
    src = embeds[labels == minority]
    rng = np.random.default_rng(_key_seed(key))
    idx = rng.integers(0, len(src), size=need)
    noise = rng.normal(0.0, cfg.rebalance_noise, size=(need, embeds.shape[1]))
    aug = src[idx] + noise.astype(embeds.dtype)
    embeds = np.concatenate([embeds, aug], axis=0)
    labels = np.concatenate([labels, np.full(need, minority, labels.dtype)])
    return embeds, labels


# ---------------------------------------------------------------------------
# loss selection (static at trace time)
# ---------------------------------------------------------------------------

def _project(params, x):
    return projector_apply(params, encoder_apply(params, x))


def _loss_phase1(params, e_q, xb, yb, cfg: ProxyConfig):
    return losses.phase1_loss(_project(params, e_q), _project(params, xb),
                              yb, cfg.temperature, cfg.qsim_variant)


def _loss_phase2(params, e_q, xb, yb, cfg: ProxyConfig):
    return contrastive_ops.phase2_loss(
        _project(params, e_q), _project(params, xb), yb,
        cfg.temperature, cfg.lambda_supcon, cfg.contrastive_impl)


def _loss_mlp(params, e_q, xb, yb, cfg: ProxyConfig):
    del e_q
    h = jax.nn.gelu(xb @ params["w1"] + params["b1"])
    h = jax.nn.gelu(h @ params["w2"] + params["b2"])
    logit = (h @ params["w3"] + params["b3"])[:, 0]
    return jnp.mean(jnp.maximum(logit, 0) - logit * yb
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))


# kind -> (phase-1 loss, phase-2 loss, apply Gaussian batch augmentation)
_KINDS = {
    "two_phase": (_loss_phase1, _loss_phase2, True),
    "mlp": (_loss_mlp, _loss_mlp, False),
}


@jax.named_scope("proxy_train_step")
def _train_core(params, ktrain, e_q, embeds, labels, n_valid, *,
                cfg: ProxyConfig, opt_cfg: OptimizerConfig, kind: str,
                bs: int):
    """The whole two-phase run as one traced program: two back-to-back
    scans (one per phase) over a shared global step counter ``t`` whose
    fold_in defines the batch/noise key schedule.

    All per-step RNG (batch indices, augmentation noise) is drawn in one
    vmapped pass over the step counter before the scans — bitwise the
    same values the scanned body would draw (vmap of threefry is exact),
    but as a handful of wide kernels instead of T small sequential
    threefry chains; on CPU this is a large share of the per-step time
    for small proxies. The gather rides along in the same pass, so the
    scan body is left with just loss + update over precomputed batches.
    """
    loss1, loss2, use_aug = _KINDS[kind]
    total = cfg.phase1_steps + cfg.phase2_steps
    aug = use_aug and cfg.aug_noise > 0

    def draws(t):
        kstep = jax.random.fold_in(ktrain, t)
        kb, kn = jax.random.split(kstep)
        idx = jax.random.randint(kb, (bs,), 0, n_valid)
        xb = jnp.take(embeds, idx, axis=0)
        if aug:
            xb = xb + cfg.aug_noise * jax.random.normal(kn, xb.shape,
                                                        xb.dtype)
        return xb, jnp.take(labels, idx, axis=0)

    xs_all, ys_all = jax.vmap(draws)(jnp.arange(total))   # (T, bs, D), (T, bs)

    opt_state = adamw.init(opt_cfg, params)

    def phase_scan(params, opt_state, t0, steps, loss_fn):
        def body(carry, batch):
            params, opt_state = carry
            xb, yb = batch
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, e_q, xb, yb, cfg))(params)
            params, opt_state = adamw.update(opt_cfg, params, grads,
                                             opt_state)
            return (params, opt_state), loss
        (params, opt_state), trace = jax.lax.scan(
            body, (params, opt_state),
            (xs_all[t0:t0 + steps], ys_all[t0:t0 + steps]))
        return params, opt_state, trace

    params, opt_state, l1 = phase_scan(params, opt_state, 0,
                                       cfg.phase1_steps, loss1)
    params, opt_state, l2 = phase_scan(params, opt_state, cfg.phase1_steps,
                                       cfg.phase2_steps, loss2)
    return params, l1, l2


@functools.lru_cache(maxsize=None)
def _compiled_trainer(cfg: ProxyConfig, opt_cfg: OptimizerConfig, kind: str,
                      bs: int, multi: bool, donate: bool):
    """jit (optionally vmapped over a leading Q axis) of ``_train_core``.

    ``donate=False`` on backends without donation support (CPU) avoids a
    warning; elsewhere the params/opt-state buffers alias in place."""
    fn = functools.partial(_train_core, cfg=cfg, opt_cfg=opt_cfg, kind=kind,
                           bs=bs)
    if multi:
        fn = jax.vmap(fn)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def _donate() -> bool:
    return jax.default_backend() not in ("cpu",)


def _bucket(n: int) -> int:
    """Pad target for the labeled sample: next power of two (>= 64).

    The compiled trainer specializes on the padded shape, so bucketing
    bounds recompilation at one program per octave of sample size; the
    traced ``n_valid`` keeps the batch sampler exact, so padding never
    changes results."""
    m = 64
    while m < n:
        m *= 2
    return m


# Rows one host->device block carries (32 MB at 4096-d f32). Blocks are
# one shape per padded batch, so the batch costs one writer program per
# (Q, pad_to, D), whatever the lanes' row counts.
ROW_BLOCK = 2048


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(batch, block, lane, row):
    """Write one (B, D) block of a lane's rows into the (Q, pad_to, D)
    device batch at ``[lane, row:row + B]``; the donated batch is
    updated in place."""
    return jax.lax.dynamic_update_slice(batch, block[None], (lane, row, 0))


def _row_blocks(samples: Sequence, pad_to: int
                ) -> List[Tuple[int, int, np.ndarray]]:
    """``(lane, row, block)`` f32 host blocks that cover each sample's
    rows.

    A block is ``min(ROW_BLOCK, pad_to)`` rows, which divides ``pad_to``
    (both are powers of two). A lane's last block ends at its last row,
    so it rewrites rows the block before it wrote, with the same values,
    instead of being padded; only a lane shorter than one block is
    zero-padded, here. A ``None`` sample has no rows and no blocks."""
    blk = min(ROW_BLOCK, pad_to)
    out = []
    for lane, e in enumerate(samples):
        if e is None:
            continue
        e = np.asarray(e, np.float32)
        n = e.shape[0]
        if n < blk:
            block = np.zeros((blk, e.shape[1]), np.float32)
            block[:n] = e
            out.append((lane, 0, block))
            continue
        for row in range(0, n, blk):
            row = min(row, n - blk)
            out.append((lane, row, e[row:row + blk]))
    return out


def _device_batch(blocks, shape: Tuple[int, int, int]
                  ) -> Tuple[jax.Array, int]:
    """The zero-padded f32 ``shape`` batch, made on the device: zeros,
    with each host block written in. Returns it and the bytes the blocks
    copied to the device.

    Each write is waited for before the next block is sent, so the
    device holds the batch and one block in flight, not every block of
    the sample; the host has nothing else to do before the program
    runs, so the waits cost only a dispatch each."""
    batch = jnp.zeros(shape, jnp.float32)
    for lane, row, block in blocks:
        batch = _write_rows(batch, block, np.int32(lane), np.int32(row))
        batch.block_until_ready()
    return batch, sum(block.nbytes for _, _, block in blocks)


def _pad_sample(embeds: np.ndarray, labels: np.ndarray,
                pad_to: int) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    n, d = embeds.shape
    batch, _ = _device_batch(_row_blocks([embeds], pad_to), (1, pad_to, d))
    if n < pad_to:
        labels = np.concatenate([labels, np.zeros(pad_to - n, labels.dtype)])
    return (batch.reshape(pad_to, d), jnp.asarray(labels.astype(np.float32)),
            n)


def _proxy_opt_cfg(cfg: ProxyConfig, weight_decay: float = None
                   ) -> OptimizerConfig:
    wd = cfg.weight_decay if weight_decay is None else weight_decay
    return OptimizerConfig(lr=cfg.lr, warmup_steps=5,
                           total_steps=cfg.phase1_steps + cfg.phase2_steps,
                           schedule="cosine", weight_decay=wd,
                           grad_clip=1.0)


def _prepare(kbal, embeds, labels, cfg: ProxyConfig, pad_to: int = 0):
    embeds_np, labels_np = np.asarray(embeds), np.asarray(labels)
    if cfg.rebalance:
        embeds_np, labels_np = rebalance(kbal, embeds_np, labels_np, cfg)
    return _pad_sample(embeds_np, labels_np,
                       pad_to or _bucket(embeds_np.shape[0]))


def train_proxy(key, e_q: jnp.ndarray, embeds: jnp.ndarray,
                labels: jnp.ndarray, cfg: ProxyConfig, *,
                method: str = "scan") -> ProxyTrainResult:
    """Train the proxy on an oracle-labeled sample.

    e_q: (D,) query embedding; embeds: (n, D); labels: (n,) {0,1}.

    ``method="scan"`` (default) runs the whole two-phase schedule as one
    compiled device program; ``method="steps"`` dispatches one jitted
    step at a time from the host (the pre-scan trainer — kept as the
    parity/benchmark baseline; same keys, same batches, same math).
    """
    kinit, kbal, ktrain = jax.random.split(key, 3)
    embeds_d, labels_d, n_valid = _prepare(kbal, embeds, labels, cfg)
    params = encoder_init(kinit, cfg)
    opt_cfg = _proxy_opt_cfg(cfg)
    e_q = jnp.asarray(e_q)
    bs = cfg.batch_size
    nv = jnp.asarray(n_valid, jnp.int32)
    kind = "two_phase"

    if method == "scan":
        fn = _compiled_trainer(cfg, opt_cfg, kind, bs, multi=False,
                               donate=_donate())
        params, l1, l2 = fn(params, ktrain, e_q, embeds_d, labels_d, nv)
        return ProxyTrainResult(params, np.asarray(l1), np.asarray(l2))

    if method != "steps":
        raise ValueError(f"unknown method {method!r}")
    opt_state = adamw.init(opt_cfg, params)
    p1_losses, p2_losses = [], []
    for t in range(cfg.phase1_steps + cfg.phase2_steps):
        phase2 = t >= cfg.phase1_steps
        # the PR-2 host-loop structure: batch sampling and the gather are
        # separate dispatches outside the step jit, and every step ends
        # in a device->host float(loss) sync — the overhead the scanned
        # path collapses into one program
        kstep = jax.random.fold_in(ktrain, t)
        kb, kn = jax.random.split(kstep)
        idx = jax.random.randint(kb, (bs,), 0, nv)
        params, opt_state, loss = _train_step(
            params, opt_state, kn, e_q, embeds_d[idx], labels_d[idx],
            cfg=cfg, opt_cfg=opt_cfg, kind=kind, phase2=phase2)
        (p2_losses if phase2 else p1_losses).append(float(loss))
    return ProxyTrainResult(params, np.asarray(p1_losses),
                            np.asarray(p2_losses))


@functools.partial(jax.jit,
                   static_argnames=("cfg", "opt_cfg", "kind", "phase2"))
@jax.named_scope("proxy_train_step")
def _train_step(params, opt_state, knoise, e_q, xb, yb, *,
                cfg: ProxyConfig, opt_cfg: OptimizerConfig, kind: str,
                phase2: bool):
    """One step of the ``method="steps"`` baseline: identical math to one
    iteration of the scanned body, dispatched (and synced) per step."""
    loss1, loss2, use_aug = _KINDS[kind]
    loss_fn = loss2 if phase2 else loss1
    if use_aug and cfg.aug_noise > 0:
        xb = xb + cfg.aug_noise * jax.random.normal(knoise, xb.shape,
                                                    xb.dtype)
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(p, e_q, xb, yb, cfg))(params)
    params, opt_state = adamw.update(opt_cfg, params, grads, opt_state)
    return params, opt_state, loss


@functools.lru_cache(maxsize=None)
def _compiled_multi_init(cfg: ProxyConfig):
    """One jitted program that splits Q keys and initializes Q encoders
    (vmapped — bitwise the values per-leaf ``split`` + ``encoder_init``
    would produce). Eagerly re-tracing this per call costs milliseconds
    of small dispatches, which is real money next to a ~100ms train."""
    def init(keys):
        def one(k):
            kinit, kbal, ktrain = jax.random.split(k, 3)
            return encoder_init(kinit, cfg), kbal, ktrain
        return jax.vmap(one)(keys)
    return jax.jit(init)


def unstack_params(stacked: Dict) -> List[Dict]:
    """Split a ``train_proxy_multi`` stacked param tree into Q trees."""
    q = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(q)]


def train_proxy_multi(keys, e_qs, samples: Sequence, labels: Sequence,
                      cfg: ProxyConfig) -> ProxyTrainResultMulti:
    """Train Q independent proxies in ONE compiled program.

    keys: Q PRNG keys; e_qs: (Q, D) query embeddings; samples[i]:
    (n_i, D) labeled embeddings, or None for a lane with no rows (an
    inert filler lane: it trains on len(labels[i]) zero rows, and is not
    rebalanced); labels[i]: (n_i,) {0,1}. Ragged sample sizes are
    zero-padded to a shared bucket; a per-proxy ``n_valid`` bounds the
    on-device batch sampler, so each lane draws exactly the batches a
    standalone ``train_proxy(keys[i], ...)`` call would — the vmapped
    run returns identical params, just without Q separate
    dispatch/compile round-trips. The padded batch is made on the
    device (``_device_batch``): only the lanes' rows cross from the
    host, and their bytes are added to the ambient span's
    ``h2d_bytes``.

    Each step is a phase of the caller's ambient span: ``rebalance``
    (key split, encoder init, minority augmentation), ``pad`` (the host
    blocks and padded labels), ``put`` (the blocks' copies and the
    device batch, as dispatched) and ``run`` (the program, until the
    losses are on the host).
    """
    q = len(samples)
    assert q == len(labels) and q == len(keys)
    with trace.phase("rebalance"):
        params0, kbals, ktrain = _compiled_multi_init(cfg)(
            jnp.stack([jnp.asarray(k) for k in keys]))
        lanes = []
        for i, (s, y) in enumerate(zip(samples, labels)):
            y = np.asarray(y)
            if cfg.rebalance and s is not None:
                s, y = rebalance(kbals[i], s, y, cfg)
            lanes.append((s, y))
    with trace.phase("pad"):
        n_valid = [len(y) if s is None else len(s) for s, y in lanes]
        pad_to = _bucket(max(n_valid))
        blocks = _row_blocks([s for s, _ in lanes], pad_to)
        labels_np = np.zeros((q, pad_to), np.float32)
        for i, (_, y) in enumerate(lanes):
            labels_np[i, :len(y)] = y
    with trace.phase("put"):
        e_qs = jnp.asarray(e_qs)
        embeds_d, sent = _device_batch(blocks, (q, pad_to, e_qs.shape[1]))
        trace.tally(h2d_bytes=sent)
        labels_d = jnp.asarray(labels_np)
        n_valid = jnp.asarray(n_valid, jnp.int32)
    opt_cfg = _proxy_opt_cfg(cfg)

    fn = _compiled_trainer(cfg, opt_cfg, "two_phase", cfg.batch_size,
                           multi=True, donate=_donate())
    with trace.phase("run"):
        params, l1, l2 = fn(params0, ktrain, e_qs, embeds_d, labels_d,
                            n_valid)
        l1, l2 = np.asarray(l1), np.asarray(l2)
    return ProxyTrainResultMulti(params, l1, l2)


def train_proxy_variant(key, e_q, embeds, labels, cfg: ProxyConfig,
                        variant: str, *, method: str = "scan") -> Dict:
    """Ablation variants for the paper's Fig. 9/11: 'qsim' (phase 1 only),
    'qsim+supcon', 'qsim+polar', 'full', or 'mlp' (binary classifier).

    All variants ride the scanned trainer: they are expressed as config
    rewrites of the same compiled two-phase core (rebalancing stays off
    for the partial objectives, matching the original ablation setup).
    """
    import dataclasses as _dc
    if variant == "full":
        return train_proxy(key, e_q, embeds, labels, cfg,
                           method=method).params
    if variant == "mlp":
        return _train_mlp_classifier(key, embeds, labels, cfg,
                                     method=method)
    rewrites = {
        "qsim": dict(phase1_steps=cfg.phase1_steps + cfg.phase2_steps,
                     phase2_steps=0),
        "qsim+supcon": dict(lambda_supcon=1.0),
        "qsim+polar": dict(lambda_supcon=0.0),
    }
    cfg_v = _dc.replace(cfg, rebalance=False, **rewrites[variant])
    return train_proxy(key, e_q, embeds, labels, cfg_v,
                       method=method).params


def _train_mlp_classifier(key, embeds, labels, cfg: ProxyConfig, *,
                          method: str = "scan") -> Dict:
    """Baseline: plain MLP binary classifier on embeddings (paper Fig. 9
    'MLP'). Returns params usable with mlp_classifier_scores. Runs on the
    same scanned core as the proxy, with the BCE loss swapped in."""
    from repro.models.common import dense_init
    import dataclasses as _dc
    k1, k2, k3, ktrain = jax.random.split(key, 4)
    params = {"w1": dense_init(k1, cfg.embed_dim, (cfg.hidden_dim,),
                               jnp.float32),
              "b1": jnp.zeros((cfg.hidden_dim,)),
              "w2": dense_init(k2, cfg.hidden_dim, (cfg.hidden_dim,),
                               jnp.float32),
              "b2": jnp.zeros((cfg.hidden_dim,)),
              "w3": dense_init(k3, cfg.hidden_dim, (1,), jnp.float32),
              "b3": jnp.zeros((1,))}
    opt_cfg = _proxy_opt_cfg(cfg, weight_decay=0.0)
    cfg_m = _dc.replace(cfg, rebalance=False)
    e_q = jnp.zeros((np.asarray(embeds).shape[1],), jnp.float32)
    # reuse train_proxy's driver with the classifier loss; the ktrain-only
    # key split there would diverge from this function's historical
    # 4-way split, so drive the compiled core directly
    embeds_d, labels_d, n_valid = _prepare(None, embeds, labels, cfg_m)
    if method == "scan":
        fn = _compiled_trainer(cfg_m, opt_cfg, "mlp", cfg.batch_size,
                               multi=False, donate=_donate())
        params, _, _ = fn(params, ktrain, e_q, embeds_d, labels_d,
                          jnp.asarray(n_valid, jnp.int32))
        return params
    opt_state = adamw.init(opt_cfg, params)
    nv = jnp.asarray(n_valid, jnp.int32)
    for t in range(cfg.phase1_steps + cfg.phase2_steps):
        kstep = jax.random.fold_in(ktrain, t)
        kb, kn = jax.random.split(kstep)
        idx = jax.random.randint(kb, (cfg.batch_size,), 0, nv)
        params, opt_state, _ = _train_step(
            params, opt_state, kn, e_q, embeds_d[idx], labels_d[idx],
            cfg=cfg_m, opt_cfg=opt_cfg, kind="mlp",
            phase2=t >= cfg.phase1_steps)
    return params


def mlp_classifier_scores(params, embeds) -> jnp.ndarray:
    h = jax.nn.gelu(embeds @ params["w1"] + params["b1"])
    h = jax.nn.gelu(h @ params["w2"] + params["b2"])
    return jax.nn.sigmoid((h @ params["w3"] + params["b3"])[:, 0])
