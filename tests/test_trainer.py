"""Compiled proxy trainer: scan-vs-step-loop parity, vmapped multi-leaf
training vs per-leaf training, the device-built padded batch against the
host-built one, typed-key rebalancing, and variant dedup onto the
scanned core."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import ProxyConfig
from repro.core import trainer
from repro.core.trainer import (ProxyTrainResult, mlp_classifier_scores,
                                rebalance, train_proxy, train_proxy_multi,
                                train_proxy_variant, unstack_params)

DIM = 32


@pytest.fixture(scope="module")
def cfg():
    return ProxyConfig(embed_dim=DIM, hidden_dim=32, latent_dim=16,
                       proj_dim=8, phase1_steps=10, phase2_steps=10,
                       batch_size=32)


@pytest.fixture(scope="module")
def sample():
    rng = np.random.default_rng(0)
    n = 150
    embeds = rng.normal(size=(n, DIM)).astype(np.float32)
    labels = (rng.random(n) < 0.3).astype(np.float32)
    e_q = rng.normal(size=DIM).astype(np.float32)
    return e_q, embeds, labels


def _tree_allclose(a, b, **tol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **tol)


def test_scan_matches_step_loop(cfg, sample):
    """The compiled scan trainer and the per-step host loop share the key
    schedule, hence the batches — params and loss traces must agree."""
    e_q, embeds, labels = sample
    key = jax.random.PRNGKey(0)
    r_scan = train_proxy(key, e_q, embeds, labels, cfg)
    r_step = train_proxy(key, e_q, embeds, labels, cfg, method="steps")
    assert isinstance(r_scan, ProxyTrainResult)
    assert r_scan.phase1_losses.shape == (cfg.phase1_steps,)
    assert r_scan.phase2_losses.shape == (cfg.phase2_steps,)
    _tree_allclose(r_scan.params, r_step.params, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(r_scan.phase1_losses, r_step.phase1_losses,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(r_scan.phase2_losses, r_step.phase2_losses,
                               rtol=1e-5, atol=1e-6)


def test_padding_is_invisible(cfg, sample):
    """Bucketed zero-padding must not change results: the same sample at
    two different pad targets (via n just below / above a bucket edge)
    trains identically because the sampler only sees n_valid."""
    e_q, embeds, labels = sample
    key = jax.random.PRNGKey(3)
    from repro.core import trainer
    r_small = train_proxy(key, e_q, embeds, labels, cfg)
    orig = trainer._bucket
    try:
        trainer._bucket = lambda n: orig(n) * 2   # force a larger pad
        r_big = train_proxy(key, e_q, embeds, labels, cfg)
    finally:
        trainer._bucket = orig
    _tree_allclose(r_small.params, r_big.params, rtol=0, atol=0)


def test_multi_matches_single(cfg):
    """Q proxies trained in one vmapped program == Q standalone calls
    (ragged sample sizes, shared zero-pad bucket)."""
    rng = np.random.default_rng(1)
    sizes = [150, 90, 40]
    keys = [jax.random.fold_in(jax.random.PRNGKey(7), i)
            for i in range(len(sizes))]
    e_qs = rng.normal(size=(len(sizes), DIM)).astype(np.float32)
    samples = [rng.normal(size=(n, DIM)).astype(np.float32) for n in sizes]
    labels = [(rng.random(n) < 0.4).astype(np.float32) for n in sizes]

    multi = train_proxy_multi(keys, e_qs, samples, labels, cfg)
    assert multi.phase1_losses.shape == (len(sizes), cfg.phase1_steps)
    assert multi.phase2_losses.shape == (len(sizes), cfg.phase2_steps)
    for i, params in enumerate(unstack_params(multi.params)):
        single = train_proxy(keys[i], e_qs[i], samples[i], labels[i], cfg)
        _tree_allclose(params, single.params, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(multi.phase1_losses[i],
                                   single.phase1_losses,
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(multi.phase2_losses[i],
                                   single.phase2_losses,
                                   rtol=1e-4, atol=1e-5)


def _engine_lanes(rng, sizes, pos_rates):
    """Real lanes of the given sizes, then filler lanes up to four as the
    engine passes them: no sample, mixed labels of the first lane's size."""
    keys = [jax.random.fold_in(jax.random.PRNGKey(17), i) for i in range(4)]
    e_qs = np.zeros((4, DIM), np.float32)
    e_qs[:len(sizes)] = rng.normal(size=(len(sizes), DIM))
    samples = [rng.normal(size=(n, DIM)).astype(np.float32) for n in sizes]
    labels = [(rng.random(n) < p).astype(np.float32)
              for n, p in zip(sizes, pos_rates)]
    dummies = 4 - len(sizes)
    return (keys, e_qs, samples + [None] * dummies,
            labels + [np.arange(sizes[0]) % 2 == 0] * dummies)


def _host_stack_multi(keys, e_qs, samples, labels, cfg):
    """``train_proxy_multi`` as it ran on a zero-padded stack built on
    the host, filler lanes as zero rows: the reference for the device
    batch."""
    params0, kbals, ktrain = trainer._compiled_multi_init(cfg)(
        jnp.stack(keys))
    lanes = []
    for i, (s, y) in enumerate(zip(samples, labels)):
        s = np.zeros((len(y), DIM), np.float32) if s is None else s
        lanes.append(rebalance(kbals[i], s, np.asarray(y), cfg))
    pad_to = trainer._bucket(max(len(e) for e, _ in lanes))
    embeds = np.zeros((len(lanes), pad_to, DIM), np.float32)
    labs = np.zeros((len(lanes), pad_to), np.float32)
    for i, (e, y) in enumerate(lanes):
        embeds[i, :len(e)] = e
        labs[i, :len(y)] = y
    fn = trainer._compiled_trainer(cfg, trainer._proxy_opt_cfg(cfg),
                                   "two_phase", cfg.batch_size, multi=True,
                                   donate=trainer._donate())
    params, l1, l2 = fn(params0, ktrain, jnp.asarray(e_qs),
                        jnp.asarray(embeds), jnp.asarray(labs),
                        jnp.asarray([len(e) for e, _ in lanes], jnp.int32))
    return params, np.asarray(l1), np.asarray(l2), lanes


def test_device_batch_is_bitwise_the_host_stack(cfg):
    """A plain lane, a rebalanced lane whose rows are no multiple of the
    block, and two filler lanes: the device-built batch trains bitwise
    what the host-built zero-padded stack trained."""
    rng = np.random.default_rng(4)
    keys, e_qs, samples, labels = _engine_lanes(rng, [2500, 2300],
                                                [0.4, 0.05])
    params, l1, l2, lanes = _host_stack_multi(keys, e_qs, samples, labels,
                                              cfg)
    rows = [len(e) for e, _ in lanes]
    assert rows[1] > 2300 and rows[1] % trainer.ROW_BLOCK  # augmented
    assert rows[0] % trainer.ROW_BLOCK
    res = train_proxy_multi(keys, e_qs, samples, labels, cfg)
    _tree_allclose(res.params, params, rtol=0, atol=0)
    np.testing.assert_array_equal(res.phase1_losses, l1)
    np.testing.assert_array_equal(res.phase2_losses, l2)
    assert res.phase1_losses.shape == (4, cfg.phase1_steps)


def test_row_counts_in_one_bucket_compile_nothing_new(cfg):
    """Lanes of other sizes in the same bucket, one of them rebalanced,
    reuse every program of the first call: nothing compiles or lowers."""
    from repro.runtime.trace import Tracer
    rng = np.random.default_rng(5)
    tracer = Tracer()
    first = _engine_lanes(rng, [2500, 3000], [0.4, 0.5])
    second = _engine_lanes(rng, [2300, 3900], [0.05, 0.4])
    with tracer.span("first"):
        train_proxy_multi(*first, cfg)
    outside = tracer.snapshot()["compiles_outside_spans"]
    with tracer.span("second"):
        res = train_proxy_multi(*second, cfg)
    span = tracer.spans()[-1]
    builds = [p for p in span["attrs"]["phases"]
              if p[0] in ("compile", "lower")]
    assert builds == []
    assert tracer.snapshot()["compiles_outside_spans"] == outside
    # two blocks for each real lane (2760 rebalanced rows, 3900), none
    # for the filler lanes
    assert span["attrs"]["h2d_bytes"] == 2 * 2 * trainer.ROW_BLOCK * DIM * 4
    assert np.isfinite(res.phase2_losses[:2]).all()


def test_rebalance_accepts_typed_and_legacy_keys(cfg, sample):
    _, embeds, _ = sample
    skewed = np.zeros(len(embeds), np.int32)
    skewed[:5] = 1                                  # 5/150 positives
    for key in (jax.random.PRNGKey(1), jax.random.key(1)):
        e1, y1 = rebalance(key, embeds, skewed, cfg)
        e2, y2 = rebalance(key, embeds, skewed, cfg)
        assert len(e1) > len(embeds)                # minority augmented
        np.testing.assert_array_equal(e1, e2)       # deterministic
        np.testing.assert_array_equal(y1, y2)
        n_min = min(y1.sum(), len(y1) - y1.sum())
        assert n_min == int(cfg.rebalance_min_frac * len(embeds))


def test_rebalance_legacy_seed_unchanged(cfg, sample):
    """The typed-key fix must not move the legacy-key seed: it is still
    the last uint32 word of the key."""
    _, embeds, _ = sample
    skewed = np.zeros(len(embeds), np.int32)
    skewed[:5] = 1
    key = jax.random.PRNGKey(42)
    e1, _ = rebalance(key, embeds, skewed, cfg)
    rng = np.random.default_rng(int(np.asarray(key)[-1]))
    src = embeds[skewed == 1]
    need = int(cfg.rebalance_min_frac * len(skewed)) - len(src)
    idx = rng.integers(0, len(src), size=need)
    noise = rng.normal(0.0, cfg.rebalance_noise, size=(need, DIM))
    np.testing.assert_array_equal(e1[len(embeds):],
                                  src[idx] + noise.astype(np.float32))


def test_variants_ride_the_scanned_core(cfg, sample):
    e_q, embeds, labels = sample
    key = jax.random.PRNGKey(2)
    for variant in ("qsim", "qsim+supcon", "qsim+polar", "full"):
        params = train_proxy_variant(key, e_q, embeds, labels, cfg, variant)
        assert set(params) == {"layers", "proj"}
        steps = train_proxy_variant(key, e_q, embeds, labels, cfg, variant,
                                    method="steps")
        _tree_allclose(params, steps, rtol=1e-5, atol=1e-6)
    # 'qsim' == two-phase run with every step on the phase-1 objective
    qsim = train_proxy_variant(key, e_q, embeds, labels, cfg, "qsim")
    cfg_q = dataclasses.replace(cfg, rebalance=False,
                                phase1_steps=cfg.phase1_steps
                                + cfg.phase2_steps, phase2_steps=0)
    _tree_allclose(qsim,
                   train_proxy(key, e_q, embeds, labels, cfg_q).params,
                   rtol=0, atol=0)


def test_mlp_variant_trains_classifier(cfg, sample):
    e_q, embeds, labels = sample
    key = jax.random.PRNGKey(5)
    params = train_proxy_variant(key, e_q, embeds, labels, cfg, "mlp")
    assert set(params) == {"w1", "b1", "w2", "b2", "w3", "b3"}
    scores = np.asarray(mlp_classifier_scores(params, embeds))
    assert scores.shape == (len(embeds),)
    assert (scores >= 0).all() and (scores <= 1).all()
    steps = train_proxy_variant(key, e_q, embeds, labels, cfg, "mlp",
                                method="steps")
    _tree_allclose(params, steps, rtol=1e-5, atol=1e-6)


def test_kernel_phase2_path_in_trainer(cfg, sample):
    """contrastive_impl='interpret' runs the Pallas forward inside the
    scanned trainer; gradients come from the reference VJP, so results
    match the default path."""
    e_q, embeds, labels = sample
    key = jax.random.PRNGKey(11)
    small = dataclasses.replace(cfg, phase1_steps=2, phase2_steps=3)
    r_ref = train_proxy(key, e_q, embeds, labels, small)
    r_pallas = train_proxy(
        key, e_q, embeds, labels,
        dataclasses.replace(small, contrastive_impl="interpret"))
    _tree_allclose(r_pallas.params, r_ref.params, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(r_pallas.phase2_losses, r_ref.phase2_losses,
                               rtol=1e-4, atol=1e-5)
