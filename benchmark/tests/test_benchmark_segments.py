"""The run's reading: all the window's tokens over all its seconds;
the segments beside it say what a slow run met."""

import statistics

import pytest

from benchmark import segments


def test_a_stall_in_one_segment_moves_the_reading():
    """A later PR that adds a stall inside the window (a save, a
    collection, a sync every few steps) has to show in the end-to-end
    rate, whichever segment it lands in."""
    tokens = [100_000] * 5
    base = segments.total_rate(tokens, [6.0] * 5, chips=1)
    stalled = [6.0, 6.0, 6.4, 6.0, 6.0]       # a 0.4 s stall in one
    assert segments.total_rate(tokens, stalled, chips=1) == \
        pytest.approx(base * 30.0 / 30.4)


def test_the_median_segment_beside_it_tells_a_stall_from_a_slowdown():
    tokens = [100_000] * 5
    base = statistics.median(segments.rates(tokens, [6.0] * 5, 1))
    stalled = segments.rates(tokens, [6.0, 6.0, 6.4, 6.0, 6.0], 1)
    slower = segments.rates(tokens, [6.06] * 5, 1)
    assert statistics.median(stalled) == base      # one stall: unmoved
    assert statistics.median(slower) == pytest.approx(base / 1.01)
    assert segments.total_rate(tokens, [6.06] * 5, 1) == \
        pytest.approx(base / 1.01)


def test_all_the_tokens_over_all_the_seconds_and_chips():
    assert segments.total_rate([8000, 8000], [1.0, 3.0], chips=4) == 1000.0
    assert segments.rates([8000, 8000], [1.0, 2.0], chips=4) == \
        [2000.0, 1000.0]


@pytest.mark.parametrize("seconds,step_s,want", [
    (12, 0.2522, (5, 9)),       # gpt1b3-s2k-1chip
    (12, 1.0141, (5, 2)),       # gpt590m-s16k-1chip: ten steps a window
    (12, 0.3414, (5, 7)),       # gpt1b3-s2k-dp4
    (1, 5.0, (5, 1)),           # never less than one step
])
def test_plan(seconds, step_s, want):
    assert segments.plan(seconds, step_s) == want


def test_plan_refuses_a_step_time_that_is_not_positive():
    with pytest.raises(ValueError):
        segments.plan(12, 0.0)
