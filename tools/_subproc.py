"""Shared helper for the on-chip bench tools: run one measurement config
in a subprocess with a timeout and print exactly one JSON line.

A chip belongs to one process at a time, so the parent must stay off
JAX: each child builds its own model and needs the whole device."""

import json
import subprocess

from deepspeed_tpu.utils import holds_chip


def run_json(cmd, timeout, tag) -> bool:
    """Run cmd; print its last JSON stdout line, or a {**tag, ...} error
    line on failure/timeout. Returns whether the child succeeded, so the
    caller can exit non-zero when a configuration failed."""
    if holds_chip():
        raise RuntimeError(
            "this process has initialised the TPU backend and holds the "
            "chip: a child process could not reach it")
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout)
    except subprocess.TimeoutExpired:
        print(json.dumps({**tag, "timeout_s": timeout}), flush=True)
        return False
    line = next((ln for ln in reversed(r.stdout.splitlines())
                 if ln.startswith("{")), None)
    print(line or json.dumps({**tag, "rc": r.returncode,
                              "err": r.stderr[-300:]}), flush=True)
    return r.returncode == 0 and line is not None
