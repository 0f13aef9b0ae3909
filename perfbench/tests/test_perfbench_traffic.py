"""Traffic and data are functions of the seed alone, for seeds of any
size."""
import numpy as np
import pytest

from perfbench import data

BIG = [2**31 + 7, 2**40 + 3]


@pytest.fixture(scope="module")
def stores():
    return {s: data.topic_store(s, 300, 32, 16, 0.03) for s in (BIG[0], BIG[1])}


def test_same_seed_same_store_and_leaves(stores):
    again = data.topic_store(BIG[0], 300, 32, 16, 0.03)
    assert np.array_equal(again.embeds, stores[BIG[0]].embeds)
    a = data.planted_leaf(stores[BIG[0]], BIG[0], (3, 1), 0.3)
    b = data.planted_leaf(again, BIG[0], (3, 1), 0.3)
    assert np.array_equal(a.embed, b.embed) and np.array_equal(a.truth, b.truth)
    assert np.allclose(np.linalg.norm(again.embeds, axis=1), 1.0, atol=1e-5)


def test_other_seed_or_tag_other_inputs(stores):
    s0, s1 = stores[BIG[0]], stores[BIG[1]]
    assert not np.allclose(s0.embeds, s1.embeds)
    a = data.planted_leaf(s0, BIG[0], (3, 1), 0.3)
    b = data.planted_leaf(s0, BIG[0], (4, 1), 0.3)
    assert not np.array_equal(a.embed, b.embed)


def test_selectivity_is_the_planted_share(stores):
    leaf = data.planted_leaf(stores[BIG[0]], BIG[0], (0, 0), 0.3)
    assert leaf.truth.mean() == pytest.approx(0.3, abs=1 / 300)


def test_token_docs_have_no_pad_and_repeat():
    a = data.token_docs(BIG[1], 16, 32, 256)
    assert a.shape == (16, 32) and a.dtype == np.int32
    assert a.min() >= 1 and a.max() < 256
    assert np.array_equal(a, data.token_docs(BIG[1], 16, 32, 256))
    assert not np.array_equal(a, data.token_docs(BIG[0], 16, 32, 256))


def test_program_seed_fits_31_bits():
    for s in BIG + [0, 1, 2**63 - 1]:
        assert 0 <= data.program_seed(s) < 2**31
    assert data.program_seed(BIG[0]) != data.program_seed(BIG[1])


def test_truth_oracle_counts_every_document():
    o = data.TruthOracle(np.array([True, False, True]))
    assert o.label([0, 1]).tolist() == [True, False]
    assert o.label(np.array([2, 2])).tolist() == [True, True]
    assert o.calls == 4
