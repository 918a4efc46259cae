"""The trace reduction, on hand-made event lists and on a small trace
recorded on the chip (`record_trace.py`; `data/small_*chip.xplane.pb`)."""

import glob
import json
import os

import pytest

from harness import tracereduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_merge_and_union():
    assert tr.merge([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [[0, 2], [3, 4]]
    assert tr.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)


def test_self_time_takes_children_out_of_the_container():
    ev = [("while.1", 0.0, 10.0), ("fusion.2", 1.0, 4.0),
          ("all-gather.3", 4.0, 6.0), ("copy.4", 6.5, 9.5)]
    got = {n: s for n, _, _, s in tr.self_times(ev)}
    assert got == pytest.approx({"while.1": 2.0, "fusion.2": 3.0,
                                 "all-gather.3": 2.0, "copy.4": 3.0})


def test_device_trace_busy_kernels_and_exposed_collectives():
    ev = [("while.1", 0.0, 10.0), ("paged_decode.9", 1.0, 4.0),
          ("all-gather-done.3", 4.0, 6.0), ("paged_decode.9", 6.5, 9.5),
          ("fusion.7", 12.0, 13.0), ("all-reduce.2", 20.0, 30.0)]
    d = tr.DeviceTrace("/device:TPU:0", ev, 0.0, 16.0)
    assert d.busy_s == pytest.approx(11.0)          # [0,10) and [12,13)
    assert d.kernel_seconds("paged_decode") == pytest.approx(6.0)
    assert len(d.kernel_events("paged_decode")) == 2
    assert d.collective_self_seconds() == pytest.approx(2.0)
    t = tr.Trace([d], [("decode_dispatch", 9.0, 12.5)], 0.0, 16.0)
    assert t.top_ops(2)[0] == ["paged_decode.9", pytest.approx(6.0)]
    gaps = dict((k, v) for k, v in t.idle_gaps(10) if k.startswith("sum:"))
    assert gaps == pytest.approx({"sum:decode_dispatch": 2.0,
                                  "sum:no_span": 3.0})
    assert tr.base_name("copy.46") == "copy"
    assert tr.base_name("flash_fwd") == "flash_fwd"


RECORDED = sorted(glob.glob(os.path.join(DATA, "small_*chip.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED or [None])
def test_recorded_trace(path):
    if path is None:
        pytest.skip("no recorded trace under benchmark/tests/data")
    facts = json.load(open(path.replace(".xplane.pb", ".json")))
    t = tr.load(path, ("step", "decode_dispatch"), "bench_traced_window")
    assert len(t.devices) == facts["chips"]
    # the window is the annotated one and holds every call's span
    assert len([s for s in t.host_spans if s[0] == "step"]) == facts["calls"]
    assert 0.0 < t.busy_s <= t.window_s
    for d in t.devices:
        assert d.busy_s == pytest.approx(
            tr.union_seconds([(s, e) for _, s, e, _ in d.events]))
        # self times add up to the busy time: nothing is counted twice
        assert sum(x for *_, x in d.events) == pytest.approx(d.busy_s,
                                                             rel=1e-6)
    assert facts["pinned"]["busy_s"] == pytest.approx(t.busy_s, rel=1e-9)
    for name, want in facts["pinned"]["kernel_seconds"].items():
        assert t.kernel_seconds(name) == pytest.approx(want, rel=1e-9)
    exposed = t.collective_exposed_s()
    assert exposed == pytest.approx(facts["pinned"]["collective_exposed_s"],
                                    rel=1e-9, abs=1e-12)
    if facts["chips"] > 1:
        assert 0.0 < exposed < t.busy_s
    else:
        assert exposed == 0.0
    # every idle gap lies inside a span of the loop that made the calls
    sums = {k: v for k, v in t.idle_gaps(10) if k.startswith("sum:")}
    assert sum(sums.values()) == pytest.approx(
        t.window_s - t.devices[0].busy_s, rel=1e-6)
