"""A later PR adds a cell by adding files and entries, and edits no file
that is there: a traffic file, a configuration file and a per-layer metric
file go into a temporary copy, and the new cell runs by name."""

import json
import os
import shutil

from test_rehearsal import ROOT, rehearsed, run_cell


def test_new_cell_from_new_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "deepspeed_tpu"),
               os.path.join(root, "deepspeed_tpu"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()

    bench = os.path.join(root, "benchmark")
    cfg = json.load(open(os.path.join(bench, "configs",
                                      "gpt2-xl-serve.json")))
    cfg["rehearsal"]["serving"]["num_slots"] = 3
    json.dump(cfg, open(os.path.join(bench, "configs", "new-serve.json"),
                        "w"))
    mix = json.load(open(os.path.join(bench, "traffic",
                                      "chat-open-0p8.json")))
    mix["rehearsal"]["rate_rps"] = 12.0
    json.dump(mix, open(os.path.join(bench, "traffic", "new-mix.json"), "w"))
    with open(os.path.join(bench, "layer_metrics", "new_steps.py"),
              "w") as f:
        f.write('def read(run):\n'
                '    return float(len(run["log"].named("step", '
                '*run["host_window"])))\n')

    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    man["configs"].append({
        "name": "new-serve", "source": "test",
        "file": "benchmark/configs/new-serve.json", "reduced": [],
        "why": "test"})
    man["workloads"].append({
        "name": "new-cell", "config": "new-serve", "traffic": "new-mix",
        "chips": 1, "why": "test"})
    for m in man["end_to_end"]:
        if m["name"] == "tpot_p90_ms":
            m["workloads"].append("new-cell")
    # and an end-to-end metric the accepted benchmark does not list yet
    man["end_to_end"].append({
        "name": "ttft_p50_ms", "unit": "ms", "better": "lower",
        "bound": 0.1, "source": "host_clock", "workloads": ["new-cell"]})
    man["per_layer"].append({
        "name": "new_steps", "unit": "steps", "better": "higher",
        "source": "program_span", "layer": "test", "moves": "tpot_p90_ms",
        "workloads": ["new-cell"]})
    json.dump(man, open(os.path.join(root, "BENCHMARK.json"), "w"))

    proc = run_cell(root, "new-cell", "--trace", "1", "--rehearse")
    assert proc.returncode == 3, proc.stderr[-2000:]
    out = rehearsed(proc)
    assert out["correct"] is True
    assert out["metrics"]["new_steps"]["value"] > 0
    proc = run_cell(root, "new-cell", "--trace", "0", "--rehearse")
    assert sorted(rehearsed(proc)["metrics"]) == [
        "setup_s", "tpot_p90_ms", "ttft_p50_ms"]
    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"
