"""Experiment scheduler.

Capability match for the reference's ``ResourceManager``
(ref: deepspeed/autotuning/scheduler.py:35): owns the experiment queue,
dispatches experiments, records results. Two dispatch modes:

- an in-process callable (fresh engine + timed steps) for cheap local
  sweeps, and
- ``SubprocessRunner`` — each experiment in its own OS process with a
  wall-clock timeout and OOM/compile-failure classification, the analog
  of the reference launching every experiment as a separate job
  (ref: scheduler.py:35 run_job + :183 parse_results). Process
  isolation is what makes unattended tuning safe here: a diverging
  candidate or a borderline-HBM compile costs its own timeout, never
  the tuning loop. A chip belongs to one process at a time, so the
  process that runs ``SubprocessRunner`` must itself stay off the TPU.
"""

import json
import os
import re
import subprocess
import sys
import tempfile
from typing import Any, Callable, Dict, List, Optional

from deepspeed_tpu.utils.logging import logger


class Experiment:
    def __init__(self, name: str, ds_config: Dict):
        self.name = name
        self.ds_config = ds_config
        self.done = False
        self.metric_val: Optional[float] = None
        self.error: Optional[str] = None

    def as_record(self) -> Dict[str, Any]:
        return {"name": self.name, "ds_config": self.ds_config,
                "metric_val": self.metric_val, "error": self.error}


class ExperimentError(RuntimeError):
    """A failed experiment with a classified kind: 'timeout' (hung or
    over-budget), 'oom' (device/host memory exhaustion), or 'error'
    (everything else). The tuning loop treats all three as a lost
    experiment, but the kind is recorded so an unattended sweep's log
    shows WHY configs were rejected (ref: the reference's per-job
    error capture in scheduler.py:128 run_job)."""

    def __init__(self, kind: str, detail: str):
        super().__init__(f"{kind}: {detail}")
        self.kind = kind
        self.detail = detail


_OOM_MARKERS = ("resource_exhausted", "out of memory", "memoryerror",
                "failed to allocate", "hbm limit")
# the bare marker needs word boundaries: "bloom"/"zoom" in a model name
# or log line must not classify an ordinary failure as out-of-memory
_OOM_RE = re.compile(r"\boom\b")


def _is_oom(blob: str) -> bool:
    return any(m in blob for m in _OOM_MARKERS) or bool(_OOM_RE.search(blob))


class SubprocessRunner:
    """Run each experiment in its own OS process with a timeout.

    Exactly one of ``cmd`` / ``cmd_builder``:
    - ``cmd``: argv prefix; the experiment's ds_config is written to a
      temp JSON file whose path is appended (the reference's pattern of
      materializing exp_dir/ds_config.json per job, scheduler.py:35).
    - ``cmd_builder(ds_config) -> argv``: full control (e.g. embedding
      the spec in a ``python -c`` template).

    The child must print a JSON line ``{"metric": <float>}`` (override
    with ``parse(stdout) -> float`` for other formats). Non-zero exit,
    hang, or unparsable output raise ``ExperimentError`` with a
    classified kind.
    """

    def __init__(self, cmd: Optional[List[str]] = None, *,
                 cmd_builder: Optional[Callable[[Dict], List[str]]] = None,
                 parse: Optional[Callable[[str], float]] = None,
                 timeout_s: float = 1800.0, env: Optional[Dict] = None,
                 cwd: Optional[str] = None):
        assert (cmd is None) != (cmd_builder is None), \
            "exactly one of cmd / cmd_builder"
        self.cmd = cmd
        self.cmd_builder = cmd_builder
        self.parse = parse or self._parse_metric_line
        self.timeout_s = timeout_s
        self.env = env
        self.cwd = cwd
        self.last_stdout: str = ""

    @staticmethod
    def _parse_metric_line(stdout: str) -> float:
        for line in reversed(stdout.splitlines()):
            line = line.strip()
            if line.startswith("{") and '"metric"' in line:
                try:
                    return float(json.loads(line)["metric"])
                except (ValueError, KeyError, TypeError):
                    continue
        raise ExperimentError("error", "no {\"metric\": ...} line in output")

    def __call__(self, ds_config: Dict) -> float:
        from deepspeed_tpu.utils import holds_chip
        if holds_chip():
            raise RuntimeError(
                "this process has initialised the TPU backend and holds "
                "the chip: an experiment subprocess could not reach it. "
                "Run experiments in-process (Autotuner.run_ds_config) or "
                "keep the scheduling process off JAX")
        tmp = None
        if self.cmd_builder is not None:
            argv = self.cmd_builder(ds_config)
        else:
            fd, tmp = tempfile.mkstemp(suffix=".json", prefix="ds_exp_")
            with os.fdopen(fd, "w") as f:
                json.dump(ds_config, f)
            argv = list(self.cmd) + [tmp]
        env = dict(os.environ) if self.env is None else dict(self.env)
        try:
            try:
                r = subprocess.run(argv, capture_output=True, text=True,
                                   timeout=self.timeout_s, env=env,
                                   cwd=self.cwd)
            except subprocess.TimeoutExpired:
                raise ExperimentError(
                    "timeout", f"exceeded {self.timeout_s:.0f}s wall clock")
            self.last_stdout = r.stdout or ""
            if r.returncode != 0:
                blob = ((r.stderr or "") + (r.stdout or "")).lower()
                kind = "oom" if _is_oom(blob) else "error"
                raise ExperimentError(
                    kind, f"rc={r.returncode}: {(r.stderr or '')[-400:]}")
            return float(self.parse(self.last_stdout))
        finally:
            if tmp is not None:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass


class ResourceManager:
    """Runs experiments through ``runner(ds_config) -> float`` and keeps
    records (ref: scheduler.py:35; `parse_results` :183)."""

    def __init__(self, runner: Callable[[Dict], float],
                 results_dir: Optional[str] = None):
        self.runner = runner
        self.results_dir = results_dir
        self.experiment_queue: List[Experiment] = []
        self.finished_experiments: List[Experiment] = []
        if results_dir:
            os.makedirs(results_dir, exist_ok=True)

    def schedule_experiments(self, exps) -> None:
        for e in exps:
            self.experiment_queue.append(e)

    def run(self) -> None:
        while self.experiment_queue:
            exp = self.experiment_queue.pop(0)
            try:
                exp.metric_val = float(self.runner(exp.ds_config))
            except Exception as err:  # OOM/compile failure = experiment loss
                exp.error = f"{type(err).__name__}: {err}"
                exp.metric_val = None
                logger.warning(f"experiment {exp.name} failed: {exp.error}")
            exp.done = True
            self.finished_experiments.append(exp)
            if self.results_dir:
                path = os.path.join(self.results_dir, f"{exp.name}.json")
                with open(path, "w") as f:
                    json.dump(exp.as_record(), f, indent=2)

    def clear(self) -> None:
        self.experiment_queue.clear()

    def best(self) -> Optional[Experiment]:
        done = [e for e in self.finished_experiments
                if e.metric_val is not None]
        return max(done, key=lambda e: e.metric_val) if done else None
