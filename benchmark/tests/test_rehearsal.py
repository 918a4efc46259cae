"""Runs every cell's command at a tiny size on the CPU and checks that it
goes through its whole flow, computes its metrics, and prints no result:
none without a TPU, and none from a rehearsal on any platform. Outside
tier-1: `pytest benchmark/tests`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def run_cell(root, workload, *extra, chips=1, seconds="3"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache_t"))
    cmd = [sys.executable] + MANIFEST["command"][1:] + [
        "--workload", workload, "--seed", "2147483659",
        "--seconds", seconds, *extra]
    return subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=600)


def rehearsed(proc):
    """The object a rehearsal writes to stderr in place of a result."""
    lines = [ln for ln in proc.stderr.splitlines()
             if ln.startswith("REHEARSAL on cpu")]
    assert lines, proc.stderr[-2000:]
    return json.loads(lines[-1].split("): ", 1)[1])


def no_result_on_stdout(proc):
    for ln in proc.stdout.splitlines():
        if ln.startswith("{"):
            assert "metrics" not in json.loads(ln), ln


@pytest.mark.parametrize("workload", CELLS)
def test_refuses_without_tpu(workload):
    proc = run_cell(ROOT, workload, "--trace", "0")
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_computes_and_refuses_to_print(workload, trace):
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == workload)
    proc = run_cell(ROOT, workload, "--trace", trace, "--rehearse",
                    chips=cell["chips"])
    assert proc.returncode == 3, proc.stderr[-2000:]
    no_result_on_stdout(proc)
    out = rehearsed(proc)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    key = "end_to_end" if trace == "0" else "per_layer"
    mine = [m["name"] for m in MANIFEST[key]
            if "workloads" not in m or workload in m["workloads"]]
    if trace == "0":
        assert sorted(out["metrics"]) == sorted(mine)
    else:
        # host-span and counter metrics are there; device-trace ones need
        # a chip and are left out
        want = [m["name"] for m in MANIFEST[key] if m["name"] in mine
                and m["source"] != "device_trace"
                and m["name"] != "train_peak_hbm_share"]
        assert sorted(out["metrics"]) == sorted(want)
        assert "busy_s" not in out["device"]
