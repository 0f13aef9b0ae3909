"""The comparison that decides ``correct``: each number against its
limit, and the limit failing what it has to fail."""
import jax
import numpy as np
import pytest

from perfbench import checks as chk
from perfbench.checks import F, T, U
from perfbench.reference import llama
from perfbench.tests import tiny
from perfbench.weights import llama_params


def _leaf(n=6, seed=0):
    rng = np.random.default_rng(seed)
    scores = rng.random(n).astype(np.float32)
    truth = rng.random(n) < 0.5
    l, r = 0.3, 0.7
    labels = np.where(scores > r, True, np.where(scores < l, False, truth))
    return chk.LeafAnswer(pending=np.arange(n), scores=scores, labels=labels,
                          l=l, r=r, truth=truth, ref_scores=scores.copy())


def test_a_sound_query_reads_zero_everywhere():
    a, b = _leaf(seed=1), _leaf(seed=2)
    mask = a.labels & ~b.labels
    got = chk.query_readings(chk.QueryAnswer("and_not", mask, [a, b]))
    assert got["score_gap"] == 0 and got["decision_mismatch"] == 0
    assert got["answer_mismatch"] == 0


def test_altered_answers_are_caught():
    a, b = _leaf(seed=1), _leaf(seed=2)
    mask = a.labels & ~b.labels
    flipped = mask.copy()
    flipped[0] = ~flipped[0]
    assert chk.query_readings(chk.QueryAnswer("and_not", flipped, [a, b]))[
        "answer_mismatch"] == 1
    a.labels = a.labels.copy()
    a.labels[2] = ~a.labels[2]
    got = chk.query_readings(chk.QueryAnswer("and_not", mask, [a, b]))
    assert got["decision_mismatch"] == 1
    a.ref_scores = a.ref_scores + 0.01
    assert chk.query_readings(chk.QueryAnswer("and_not", mask, [a, b]))[
        "score_gap"] == pytest.approx(0.01, abs=1e-6)


def test_short_circuited_leaves_compose_in_kleene_logic():
    a = np.array([F, F, T, T, U], np.int8)
    b = np.array([U, T, F, T, F], np.int8)
    assert chk.kleene("and", a, b).tolist() == [F, F, F, T, F]
    assert chk.kleene("and_not", a, b).tolist() == [F, F, T, F, U]
    assert chk.kleene("or", a, b).tolist() == [U, T, T, T, U]


def test_fold_keeps_the_worst_reading_beside_its_limit():
    got = chk.fold([{"x": 1.0, "y": 0.9}, {"x": 3.0, "y": 0.5}],
                   {"x": "max", "y": "min"}, {"x": 2.0, "y": 0.6})
    assert [(c.name, c.value, c.ok) for c in got] == [("x", 3.0, False),
                                                       ("y", 0.5, False)]


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = tiny.config("smollm-360m")
    params = llama_params(cfg, jax.random.PRNGKey(0), dtype=np.float32)
    tokens = np.random.default_rng(0).integers(1, cfg["vocab_size"], (6, 32))
    return cfg, params, tokens, llama.pooled(params, tokens, cfg)


def test_ingest_check_fails_a_lower_precision_backbone(tiny_llama):
    """The fp8 control in the program's place fails the row check at the
    limit the chip's readings set."""
    from perfbench.tests.tiny import BENCH
    import json
    limits = json.loads((BENCH / "configs" / "smollm-360m.json").read_text())[
        "check_limits"]
    cfg, params, tokens, want = tiny_llama
    low = llama.pooled(params, tokens, cfg, precision="fp8")
    got = chk.ingest_checks(low, len(low), low, want, limits)
    assert {c.name: c.ok for c in got} == {"rows_missing": True, "bad_rows": True,
                                          "row_rel_err": False}
    assert {c.name: c.ok for c in chk.ingest_checks(
        want, len(want), want, want, limits)} == {
            "rows_missing": True, "bad_rows": True, "row_rel_err": True}


def test_ingest_check_counts_rows_lost_and_broken(tiny_llama):
    _, _, _, want = tiny_llama
    rows = want.copy()
    rows[1] = 0.0
    got = {c.name: c.value for c in chk.ingest_checks(
        rows, len(rows) + 2, rows, want, {"rows_missing": 0, "bad_rows": 0,
                                          "row_rel_err": 1.0})}
    assert got["rows_missing"] == 2 and got["bad_rows"] == 1


def test_leaf_f1_holds_each_leaf_to_the_target():
    """One leaf decided exactly and one wrong everywhere: the worst leaf's
    F1 is read, not a pool that the exact leaf would lift."""
    a, b = _leaf(seed=1), _leaf(seed=2)
    a.l, a.r, a.labels = -1.0, 2.0, a.truth.copy()   # all band: a is exact
    b.l, b.r, b.labels = -1.0, 2.0, ~b.truth         # every decision of b wrong
    mask = np.where(a.labels, ~b.labels, False)
    limits = {"score_gap": 1, "decision_mismatch": 99, "answer_mismatch": 0,
              "leaf_f1_min": 0.9}
    got = {c.name: c for c in chk.query_checks(
        [chk.QueryAnswer("and_not", mask, [a, b])], limits)}
    assert got["leaf_f1_min"].value == 0.0 and not got["leaf_f1_min"].ok
    assert got["answer_mismatch"].ok and got["decision_mismatch"].value == len(b.truth)
    assert chk.f1(a.truth, a.truth) == 1.0


def _trained(seed=0):
    rng = np.random.default_rng(seed)
    shapes = {"layers.l0.w": (6, 4), "layers.l0.b": (4,), "proj.w": (4, 2), "proj.b": (2,)}
    init = {k: rng.normal(size=s) for k, s in shapes.items()}
    final = {k: v + 0.1 * rng.normal(size=v.shape) for k, v in init.items()}
    grad0 = {k: rng.normal(size=s) for k, s in shapes.items()}
    losses = rng.random(8) + 1.0
    return chk.TrainAnswer(losses=losses, final=dict(final), ref_losses=losses.copy(),
                           ref_init=init, ref_final=final, ref_grad0=grad0)


def test_a_trainer_that_follows_the_reference_reads_zero():
    got = chk.train_readings(_trained())
    assert got == {"train_loss_gap": 0.0, "train_change_gap": 0.0, "train_param_gap": 0.0}


def test_trainer_faults_are_read():
    """A state returned unchanged reads a change gap of 1; a first loss 5%
    off reads 0.05; a leaf whose first gradient is nought to rounding is
    left out of the change."""
    t = _trained()
    t.final = dict(t.ref_init)
    assert chk.train_readings(t)["train_change_gap"] == pytest.approx(1.0)
    t = _trained()
    t.losses = t.losses.copy()
    t.losses[0] *= 1.05
    assert chk.train_readings(t)["train_loss_gap"] == pytest.approx(0.05)
    t = _trained()
    t.ref_grad0["proj.b"] = np.full(2, 1e-9)
    t.final = dict(t.final, **{"proj.b": t.ref_init["proj.b"]})
    assert chk.train_readings(t)["train_change_gap"] == 0.0
    t.losses = t.losses[:1]
    assert chk.train_readings(t)["train_loss_gap"] == float("inf")


def test_reference_trainer_follows_the_program_on_the_cpu():
    """The reference, written apart from the program, trains the same
    proxy from the same leaf and seed: same initial state bit for bit,
    the same losses and parameters to float32 rounding."""
    import dataclasses
    from repro.config.base import ProxyConfig
    from repro.core.encoder import encoder_init
    from repro.core.trainer import train_proxy_multi, unstack_params
    from perfbench.reference import trainer as ref
    from perfbench.runners.common import flat
    proxy = tiny.config("scaledoc-paper-4096")["proxy"]
    cfg = ProxyConfig(**{k: v for k, v in proxy.items()
                         if k in {f.name for f in dataclasses.fields(ProxyConfig)}})
    rng = np.random.default_rng(0)
    embeds = rng.normal(size=(1500, proxy["embed_dim"])).astype(np.float32)
    e_q = rng.normal(size=proxy["embed_dim"]).astype(np.float32)
    truth = rng.random(1500) < 0.15                 # the sample is rebalanced
    idx, key = ref.sample(2**31 - 5, e_q, 1500, proxy["train_fraction"])
    prog = train_proxy_multi([key], e_q[None], [embeds[idx]], [truth[idx]], cfg)
    want = ref.train(key, e_q, embeds[idx], truth[idx], proxy)
    init = flat(encoder_init(jax.random.split(key, 3)[0], cfg))
    assert all(np.array_equal(init[k], v) for k, v in flat(want.init).items())
    losses = np.concatenate([prog.phase1_losses[0], prog.phase2_losses[0]])
    np.testing.assert_allclose(losses, want.losses, rtol=1e-5)
    got, ref_final = flat(unstack_params(prog.params)[0]), flat(want.final)
    for k, v in ref_final.items():
        np.testing.assert_allclose(got[k], v, atol=1e-5)
