"""The estimators of the serving driver on hand-made stamps, each answer
worked out by hand. Outside tier-1: `pytest benchmark/tests`."""

import pytest

from harness import stats


def test_request_mean_gaps_is_per_request_and_inside_the_window():
    # window [10, 20]. A: tokens at 11, 11.1, 11.3 -> gaps 0.1, 0.2, mean
    # 150 ms, finished 11.3. B: first gap starts before the window (9.9 ->
    # 10.1: left out), then 10.1 -> 10.2 -> 10.6: gaps 0.1, 0.4, mean 250
    # ms. C finished after the window: left out. D finished inside with a
    # single token: no gap, gives nothing. E never finished.
    reqs = [(11.3, [(11.0, 11.1), (11.1, 11.3)]),
            (10.6, [(9.9, 10.1), (10.1, 10.2), (10.2, 10.6)]),
            (20.5, [(19.0, 19.5), (19.5, 20.5)]),
            (12.0, []),
            (None, [(15.0, 15.1)])]
    got = stats.request_mean_gaps_ms(reqs, 10.0, 20.0)
    assert got == pytest.approx([150.0, 250.0])
    # the tail over REQUESTS is not the tail over gaps: one slow request
    # of many tokens weighs as one
    assert stats.pct(got, 90) == pytest.approx(240.0)


def test_binned_rates_and_means():
    ev = [(0.5, 64), (1.5, 64), (4.9, 1), (5.0, 10), (11.0, 7), (12.0, 99)]
    # [0, 5): 129 / 5 s; [5, 10): 10 / 5 s; [10, 12): 7 / 2 s (the last
    # bin is short; the event at the window's end is outside)
    assert stats.binned(ev, 0.0, 12.0, 5.0) == pytest.approx(
        [25.8, 2.0, 3.5])
    assert stats.binned(ev, 0.0, 12.0, 5.0, mean=True) == pytest.approx(
        [43.0, 10.0, 7.0])
    assert stats.binned([], 0.0, 10.0, 5.0, mean=True) == [None, None]

