"""The train cells' verdict on the loss asks what a run can show: somewhere
in the window the loss on the measured batch lies below where it began; the
first step alone and the window's last loss are numbers in the output, not
verdicts (seeds 2147483659 and 1449468193: PERF.md section 6, PR 56).
Outside tier-1: `pytest benchmark/tests`."""

import os

import pytest

from harness import cells

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def train():
    return cells.load_module(os.path.join(BENCH, "drivers", "train.py"),
                             "bench_driver_train_for_test")


@pytest.mark.parametrize("losses, verdict", [
    # falls at once and keeps falling
    ([11.07, 10.9, 10.5, 10.1], True),
    # seed 1449468193's kind: falls for nineteen steps, ends on a spike
    ([11.067, 10.849, 10.729, 10.611, 10.491, 10.376, 9.606, 13.104], True),
    # seed 2147483659's kind: the first step overshoots, the second is below
    ([11.069, 11.154, 10.840, 10.761], True),
    # rises first and falls below where it began later in the window
    ([11.07, 11.20, 11.31, 11.25, 11.18, 11.09, 10.8, 10.1], True),
    # never below where it began
    ([11.07, 11.20, 11.31, 11.25, 11.18, 11.09, 11.08], False),
    # a state returned unchanged: the loss does not move
    ([11.07, 11.07, 11.07, 11.07, 11.07, 11.07], False),
    ([11.07, float("nan"), float("nan")], False),
    # no step on the measured batch inside the window: nothing was shown
    ([11.07], False),
])
def test_the_loss_has_to_fall_below_where_it_began(train, losses, verdict):
    ok, fall = train.loss_descends(losses)
    assert ok is verdict
    if verdict:
        assert fall < 0
