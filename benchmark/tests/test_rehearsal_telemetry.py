"""Both drivers at the tiny size with `--trace 1` and the program's
telemetry ON (`DS_TELEMETRY=on`, the switch the program already has: the
drivers construct their engines as always): the run goes through, the
program's spans and provenance cost no correctness, and the readers that
need a device trace leave their metrics out on the CPU without raising.
Outside tier-1: `pytest benchmark/tests`."""

import json
import os

import pytest

from test_rehearsal import MANIFEST, ROOT, no_result_on_stdout, rehearsed, \
    run_cell

ONE_PER_DRIVER = ["serve-gpt2xl-chat", "train-gpt2xl-1chip"]
NEW = {"kv_relayout_share", "kv_relayout_share_tput", "remat_time_share"}


@pytest.mark.parametrize("workload", ONE_PER_DRIVER)
def test_rehearsal_with_telemetry_on(workload, monkeypatch):
    monkeypatch.setenv("DS_TELEMETRY", "on")
    proc = run_cell(ROOT, workload, "--trace", "1", "--rehearse")
    assert proc.returncode == 3, proc.stderr[-2000:]
    no_result_on_stdout(proc)
    out = rehearsed(proc)
    assert out["correct"] is True and out["failed"] == 0
    # no device plane on the CPU: the device-trace metrics are left out
    assert not NEW & set(out["metrics"])
    want = [m["name"] for m in MANIFEST["per_layer"]
            if ("workloads" not in m or workload in m["workloads"])
            and m["source"] != "device_trace"
            and m["name"] != "train_peak_hbm_share"]
    assert sorted(out["metrics"]) == sorted(want)


HOST = {"dispatch_enqueue_ms", "dispatch_enqueue_ms_tput",
        "dispatch_idle_ms", "dispatch_idle_ms_tput"}


def test_new_entries_only_append():
    """The metrics later PRs added (PR 24: three read from the device
    trace; PR 27: the host's four, read from the program's own spans) sit
    at the end of `per_layer` in the order they came, each with a reader
    file, on cells that report the metric they move."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert set(names[-7:-4]) == NEW and set(names[-4:]) == HOST
    for m in MANIFEST["per_layer"][-4:]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
        moved = next(e for e in MANIFEST["end_to_end"]
                     if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved["workloads"])
        assert m["layer"] == "model step inference/engine.py"
    for m in MANIFEST["per_layer"][-7:-4]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
        moved = next(e for e in MANIFEST["end_to_end"]
                     if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved["workloads"])
        assert m["source"] == "device_trace" and m["better"] == "lower"
    json.dumps(MANIFEST)
