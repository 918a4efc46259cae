"""What a run's last line carries beside the contract's keys: a serving
run's longest single dispatch (so that a reader can tell a stalled run from
a slow program: the stall stays in every end-to-end metric), and every
number compared beside its limit under a key of its own that comes last.
Outside tier-1: `pytest benchmark/tests`."""

import pytest

from test_rehearsal import ROOT, rehearsed, run_cell


@pytest.fixture(scope="module")
def chat_line():
    proc = run_cell(ROOT, "serve-gpt2xl-chat", "--trace", "0", "--rehearse")
    assert proc.returncode == 3, proc.stderr[-2000:]
    return rehearsed(proc), proc


def test_a_serving_line_names_its_longest_dispatch(chat_line):
    out, proc = chat_line
    assert 0.0 < out["longest_dispatch_s"] < 3.0
    window = next(ln for ln in proc.stdout.splitlines()
                  if '"info": "window"' in ln)
    assert '"longest_dispatch_s"' in window and '"dispatches"' in window


def test_the_compared_numbers_come_last_each_beside_its_limit(chat_line):
    out, _ = chat_line
    assert list(out)[-1] == "compared"
    assert {"warmup_max_abs_logit_error", "compiles_inside_window",
            "served_gap_max"} <= set(out["compared"])
    assert all(len(pair) == 2 for pair in out["compared"].values())
    # the contract's keys are all there, before it
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)


def test_a_training_line_reports_the_later_steps_and_judges_the_first():
    proc = run_cell(ROOT, "train-gpt2xl-1chip", "--trace", "0", "--rehearse")
    assert proc.returncode == 3, proc.stderr[-2000:]
    out = rehearsed(proc)
    held = out["compared"]
    assert held["lowest_loss_in_window_minus_before"][1] == "<0"
    assert held["lowest_loss_in_window_minus_before"][0] < 0
    assert held["loss_after_first_step_minus_before"][1] == "not held"
    assert held["last_loss_minus_first"][1] == "not held"
    assert "longest_dispatch_s" not in out
