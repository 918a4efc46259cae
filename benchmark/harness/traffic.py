"""The one general traffic generator. A traffic mix is a JSON file of
parameters; this file turns it and a seed into work.

Steadiness by construction: every seed gets the SAME multiset of lengths
(stratified sampling on a fixed quantile grid of the stated distribution;
the seed only shuffles the order) and, in an open loop, the same NUMBER of
arrivals in the ramp and in the window (a Poisson process conditioned on its
count is a set of sorted uniforms). The seed permutes; it does not resize.

A mix may go further and fix its whole schedule with ``schedule_seed``: the
order of the lengths and the arrival times then come from that number, the
same for every run, and ``--seed`` draws only token ids and weights. Runs
with different seeds then do the same work, and differ as two runs of one
seed do.

Fields of a ``requests`` mix:
  loop            "open" (arrivals on a schedule) | "closed" (a backlog, a
                  fixed number outstanding)
  rate_rps        open loop: arrivals per second
  ramp_requests   requests that arrive (open) or finish (closed) before the
                  measured window opens; the ramp is set-up
  outstanding     closed loop: requests in flight; "num_slots" takes the
                  configuration's
  backlog         closed loop: documents present at time zero
  first_generation_prompt
                  closed loop, optional: the prompt distribution of the first
                  ``outstanding`` documents (see ``closed_loop_plan``)
  prompt, answer  {"dist": "lognormal", "median", "sigma", "min", "max"} or
                  {"dist": "uniform", "min", "max"}, in tokens
  max_total       prompt plus answer never exceeds it (the answer is cut)
  schedule_seed   optional: fixes the order of lengths and the arrival times
Fields of a ``batches`` mix: global_batch, seq.
"""

from statistics import NormalDist

import numpy as np


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified_lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``spec``'s
    distribution, clipped to [min, max], in an order drawn from ``rng``."""
    q = _quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        vals = np.exp(np.log(float(spec["median"]))
                      + float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        vals = lo + q * (hi - lo)
    else:
        raise SystemExit(f"traffic: unknown dist {spec['dist']!r}")
    vals = np.clip(np.rint(vals), lo, hi).astype(np.int64)
    return vals[rng.permutation(n)]


def request_lengths(mix: dict, n: int, rng):
    """[(prompt_len, answer_len)] x n."""
    prompts = stratified_lengths(mix["prompt"], n, rng)
    answers = stratified_lengths(mix["answer"], n, rng)
    cap = int(mix["max_total"])
    return [(int(p), int(min(a, cap - p))) for p, a in zip(prompts, answers)]


def conditioned_arrivals(n: int, start: float, length: float, rng):
    """``n`` arrival times of a Poisson process on [start, start + length)
    given that it had exactly ``n`` arrivals: sorted uniforms."""
    return np.sort(start + rng.random(n) * length)


def open_loop_plan(mix: dict, seconds: float, rng):
    """(ramp_s, [(due_s, prompt_len, answer_len)]) with due times relative
    to the start of the ramp; the window is [ramp_s, ramp_s + seconds)."""
    rate = float(mix["rate_rps"])
    n_ramp = int(mix["ramp_requests"])
    ramp_s = n_ramp / rate
    n_win = int(round(rate * seconds))
    due = np.concatenate([conditioned_arrivals(n_ramp, 0.0, ramp_s, rng),
                          conditioned_arrivals(n_win, ramp_s, seconds, rng)])
    lens = request_lengths(mix, n_ramp + n_win, rng)
    return ramp_s, [(float(t), p, a) for t, (p, a) in zip(due, lens)]


def closed_loop_plan(mix: dict, outstanding: int, rng):
    """[(prompt_len, answer_len)] x backlog, in the order they are taken.
    With ``first_generation_prompt`` the first ``outstanding`` documents draw
    their prompt lengths from that distribution instead: a system that has
    been running holds requests at every stage of their prefill, so the run
    starts with prompts of staggered lengths and not with ``outstanding``
    documents in lockstep. Both groups are fixed multisets."""
    n = int(mix["backlog"])
    head = mix.get("first_generation_prompt")
    if not head:
        return request_lengths(mix, n, rng)
    first = request_lengths(dict(mix, prompt=head), outstanding, rng)
    return first + request_lengths(mix, n - outstanding, rng)


def prompt_tokens(length: int, vocab: int, rng) -> np.ndarray:
    return rng.integers(1, vocab, int(length)).astype(np.int32)
