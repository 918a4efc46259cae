"""The reader of the telemetry plane's own host time
(harness/readers_selfcost.py, PR 52): on a hand-made ring it takes the
population that `host_gap_ms` takes and the medians it says; a ring from
before PR 52 (no `self_us`) and a run that is not serving give None and no
line; both new entries of BENCHMARK.json are found by name, each with its
reader; a CPU rehearsal of the chat cell reports the metric and says the
`telemetry_self` line. Outside tier-1: `pytest benchmark/tests`."""

import json
import os

from harness import readers_selfcost as sc
from test_rehearsal import MANIFEST, ROOT, rehearsed, run_cell


class Ring:
    """What a reader asks of the program's tracer: ``spans(name)``."""

    def __init__(self, records):
        self.records = records

    def spans(self, name=None):
        return [r for r in self.records if name is None or r[1] == name]


def dispatch(t0, gap_us, self_us=None, parts=None, **counts):
    """A serve.dispatch ring record that starts at ``t0`` s and lasts 1 ms:
    (ts, name, rid, step, slot, counts, end, sid, parent)."""
    c = dict(counts, gap_us=gap_us)
    if self_us is not None:
        c.update(self_us=self_us, self_parts=parts)
    return (t0, "serve.dispatch", None, 0, -1, c, t0 + 1e-3, 1, 0)


def run_of(records, said):
    return {"kind": "serve", "tracer": Ring(records),
            "host_window": (10.0, 20.0), "trace_host_window": (20.0, 26.0),
            "say": lambda **row: said.append(row)}


def test_median_over_the_untraced_window_and_the_line():
    parts = lambda s: (s - 6.0, 2.0, 2.0, 1.5, 0.5, 9.0)    # noqa: E731
    records = [
        dispatch(9.9995, 800, 40, parts(40)),       # its gap began before
        dispatch(11.0, 1000, 30, parts(30)),
        dispatch(12.0, 1200, 50, parts(50)),
        dispatch(13.0, 90000, 70, parts(70), after_empty=1),   # a pause
        dispatch(14.0, 1400, 90, parts(90)),
        dispatch(21.0, 1000, 130, parts(130)),      # the traced tail
        (15.0, "serve.step", None, 0, -1, None, 15.1, 2, 0)]
    said = []
    run = run_of(records, said)
    assert sc.telemetry_self_ms(run) == 0.05
    assert sc.telemetry_self_ms(run) == 0.05         # made once
    (line,) = said
    assert line["info"] == "telemetry_self"
    assert line["parts"] == list(sc.PARTS)
    un, tail = line["untraced"], line["traced_tail"]
    assert un["dispatches"] == 3
    assert (un["self_us_p50"], un["self_us_mean"]) == (50.0, 170 / 3)
    assert un["gap_us_p50"] == 1200.0
    assert un["self_over_gap"] == 50.0 / 1200.0
    assert un["parts_us_p50"] == {"spans": 44.0, "accountant": 2.0,
                                  "histograms": 2.0, "counts": 1.5,
                                  "gauges": 0.5, "hidden": 9.0}
    assert tail["dispatches"] == 1 and tail["self_us_p50"] == 130.0
    json.dumps(line)


def test_a_ring_without_the_count_says_nothing():
    said = []
    old = run_of([dispatch(11.0, 1000), dispatch(12.0, 1100)], said)
    assert sc.telemetry_self_ms(old) is None
    assert sc.telemetry_self_ms({"kind": "train", "say": said.append}) is None
    off = dict(run_of([], said), tracer=None)
    assert sc.telemetry_self_ms(off) is None
    assert said == []


NEW = {m["name"]: m for m in MANIFEST["per_layer"]
       if m["name"].startswith("telemetry_self_ms")}


def test_the_two_entries_and_their_readers():
    # found by name, not by place: a later PR appends after them
    assert sorted(NEW) == ["telemetry_self_ms", "telemetry_self_ms_tput"]
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, m in NEW.items():
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
        twin = by_name[name.replace("telemetry_self_ms", "host_gap_ms")]
        # the cells, the layer and the end-to-end metric of host_gap_ms
        for key in ("unit", "better", "source", "layer", "moves",
                    "workloads"):
            assert m[key] == twin[key], key
        assert set(m) == set(twin)


def test_rehearsal_reports_the_count_and_says_the_line(monkeypatch):
    monkeypatch.setenv("DS_TELEMETRY", "on")
    proc = run_cell(ROOT, "serve-gpt2xl-chat", "--trace", "1", "--rehearse")
    assert proc.returncode == 3, proc.stderr[-2000:]
    out = rehearsed(proc)
    assert out["correct"] is True
    mine = out["metrics"]["telemetry_self_ms"]
    assert mine["unit"] == "ms"
    assert 0 < mine["value"] <= out["metrics"]["host_gap_ms"]["value"]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{"info": "telemetry_self"')]
    assert len(lines) == 1
    assert lines[0]["untraced"]["dispatches"] > 0
    assert set(lines[0]["untraced"]["parts_us_p50"]) == set(
        lines[0]["parts"])
