"""The exact-boundary window: a rate stops its numerator and its
denominator at the same completed unit."""
import pytest

from perfbench.window import close_window

GROUP = 256          # docs per commit group
PERIOD = 1.2         # seconds per group at a steady 213.33 docs/s


def commit_timeline(phase: float, groups: int = 40):
    """Commits of a steady ingest whose first commit lands at ``phase``."""
    return [(phase + k * PERIOD, (k + 1) * GROUP) for k in range(groups)]


@pytest.mark.parametrize("phase", [0.0, 0.3, 0.61, 1.19])
def test_rate_is_exact_whatever_the_phase(phase):
    marks = commit_timeline(phase)
    w = close_window(marks, marks[0], seconds=29.0)
    assert w.rate == pytest.approx(GROUP / PERIOD, rel=1e-12)
    assert w.units == GROUP * w.completions
    assert w.end <= w.start + 29.0


@pytest.mark.parametrize("phase", [0.0, 0.3, 0.61, 1.19])
def test_fixed_window_count_would_be_off_by_a_partial_group(phase):
    """Counting durable rows over the nominal window mixes in a random
    part of a group: the error this module exists to remove."""
    marks = commit_timeline(phase)
    t0 = marks[0][0]
    durable = max(r for t, r in marks if t <= t0 + 29.0) - marks[0][1]
    fixed = durable / 29.0
    exact = close_window(marks, marks[0], 29.0).rate
    assert exact == pytest.approx(GROUP / PERIOD)
    assert abs(fixed - exact) / exact > 0.005


def test_units_that_straddle_a_boundary_count_on_neither_side():
    marks = [(1.0, 1), (2.0, 2), (3.5, 3), (4.9, 4), (6.2, 5)]
    w = close_window(marks, (1.0, 1), seconds=4.5)
    assert (w.start, w.end, w.units, w.completions) == (1.0, 4.9, 3, 3)
    assert w.seconds_per_completion == pytest.approx(3.9 / 3)


def test_a_window_shorter_than_one_unit_is_an_error():
    with pytest.raises(RuntimeError):
        close_window([(0.0, 0), (5.0, 1)], (0.0, 0), seconds=2.0)
