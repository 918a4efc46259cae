"""The generator's steadiness: the seed permutes, it does not resize."""

import json
import os

import numpy as np

from harness import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix(name):
    return json.load(open(os.path.join(BENCH, "traffic", name + ".json")))


def test_open_loop_same_multiset_and_counts_for_every_seed():
    m = mix("chat-open-0p8")
    plans = [traffic.open_loop_plan(m, 45.0, np.random.default_rng(s))
             for s in (1, 2147483659, 77)]
    ramp = plans[0][0]
    assert ramp == m["ramp_requests"] / m["rate_rps"]
    n_win = round(m["rate_rps"] * 45.0)
    for ramp_s, plan in plans:
        assert ramp_s == ramp
        due = np.array([d for d, _, _ in plan])
        assert (np.diff(due[:m["ramp_requests"]]) >= 0).all()
        assert (due < ramp).sum() == m["ramp_requests"]
        assert ((due >= ramp) & (due < ramp + 45.0)).sum() == n_win
        assert all(p + a <= m["max_total"] for _, p, a in plan)
    prompts = [sorted(p for _, p, _ in plan) for _, plan in plans]
    answers = [sorted(a for _, _, a in plan) for _, plan in plans]
    assert prompts[0] == prompts[1] == prompts[2]
    assert answers[0] == answers[1] == answers[2]
    # and the order does change with the seed
    assert [p for _, p, _ in plans[0][1]] != [p for _, p, _ in plans[1][1]]
    lo, hi = m["prompt"]["min"], m["prompt"]["max"]
    assert lo <= prompts[0][0] and prompts[0][-1] <= hi
    med = float(np.median(traffic.stratified_lengths(
        m["prompt"], 1001, np.random.default_rng(0))))
    assert abs(med - m["prompt"]["median"]) <= 1


def test_chat_cell_rate_ramp_and_window_counts():
    """The cell as PR 56 re-anchored it: 0.8 of the knee of 7.0 req/s swept
    on that tree's program, a ramp of 84 requests (15 s, ten residence times
    of about 1.4 s), 252 requests due inside a 45 s window, and one fixed
    schedule."""
    m = mix("chat-open-0p8")
    assert m["rate_rps"] == 5.6 == round(0.8 * 7.0, 6)
    assert m["ramp_requests"] == 84
    plans = [traffic.open_loop_plan(m, 45.0, np.random.default_rng(
        m["schedule_seed"])) for _ in range(2)]
    assert plans[0] == plans[1]               # the schedule is the mix's
    ramp_s, plan = plans[0]
    assert abs(ramp_s - 15.0) < 1e-9 and ramp_s >= 10 * 1.4
    due = np.array([d for d, _, _ in plan])
    assert (due < ramp_s).sum() == 84
    assert ((due >= ramp_s) & (due < ramp_s + 45.0)).sum() == 252 == len(
        plan) - 84
    # the neighbouring rates of the steadiness test keep the multiset's
    # shape: the same quantile grid at another count
    for scale, n_win in ((0.9, 227), (1.1, 277)):
        _, p2 = traffic.open_loop_plan(dict(m, rate_rps=5.6 * scale), 45.0,
                                       np.random.default_rng(23))
        assert len(p2) == 84 + n_win
        assert abs(np.median([p for _, p, _ in p2]) - 192) <= 4


def test_docs_backlog_outlasts_a_window_eight_times():
    """A 45 s window spends about 126 documents (my chip runs, PR 27): the
    backlog holds eight times that, and building the plan with every
    prompt's tokens takes well under a second."""
    import time
    m = mix("docs-backlog")
    assert m["backlog"] == 1024 >= 8 * 126
    t = time.perf_counter()
    plan = traffic.closed_loop_plan(m, 17, np.random.default_rng(
        m["schedule_seed"]))
    rng = np.random.default_rng([2147483659, 1])
    toks = [traffic.prompt_tokens(p, 50257, rng) for p, _ in plan]
    assert time.perf_counter() - t < 1.0
    assert len(toks) == 1024 and all(p + a <= 1024 for p, a in plan)


def test_closed_loop_first_generation_is_staggered_and_fixed():
    m = mix("docs-backlog")
    plans = [traffic.closed_loop_plan(m, 17, np.random.default_rng(s))
             for s in (5, 6)]
    for plan in plans:
        assert len(plan) == m["backlog"]
        first = sorted(p for p, _ in plan[:17])
        assert first[0] < m["prompt"]["min"] < first[-1]
        assert all(m["prompt"]["min"] <= p <= m["prompt"]["max"]
                   for p, _ in plan[17:])
    assert sorted(p for p, _ in plans[0][:17]) == sorted(
        p for p, _ in plans[1][:17])
    assert sorted(p for p, _ in plans[0][17:]) == sorted(
        p for p, _ in plans[1][17:])
