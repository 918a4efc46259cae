"""Finds a cell and everything that belongs to it by the names in
``BENCHMARK.json``: the configuration's file, the traffic mix's file
(``benchmark/traffic/<traffic>.json``), the driver for the configuration's
``kind`` (``benchmark/drivers/<kind>.py``) and each per-layer metric's reader
(``benchmark/layer_metrics/<metric>.py``). Adding a cell is adding files and
one entry; no file that is there needs an edit."""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


class Cell:
    """One entry of ``workloads`` with its configuration, traffic and the
    metrics it has to report."""

    def __init__(self, workload: str, root: str = ROOT):
        self.root = root
        self.manifest = _load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(f"benchmark: no workload {workload!r} in "
                             f"BENCHMARK.json (has: {sorted(cells)})")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.bench_dir = os.path.join(root, self.manifest["paths"][0])
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = _load_json(os.path.join(root, self.config_entry["file"]))
        self.traffic = _load_json(os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json"))

    def use_rehearsal_size(self):
        """Lay the configuration's and the traffic's ``rehearsal`` overrides
        (a tiny size for the CPU tests) over the real ones."""
        self.config = _merge(self.config, self.config.get("rehearsal", {}))
        self.traffic = _merge(self.traffic, self.traffic.get("rehearsal", {}))

    def reference(self):
        """The configuration's plain reference, named in its file."""
        return load_module(os.path.join(self.root, self.config["reference"]),
                           "bench_reference")

    def _mine(self, metrics):
        return [m for m in metrics
                if "workloads" not in m or self.name in m["workloads"]]

    @property
    def end_to_end(self):
        return self._mine(self.manifest["end_to_end"])

    @property
    def per_layer(self):
        return self._mine(self.manifest["per_layer"])

    def driver(self):
        kind = self.config["kind"]
        return load_module(os.path.join(self.bench_dir, "drivers",
                                        kind + ".py"), "bench_driver_" + kind)

    def layer_reader(self, metric_name: str):
        """The reader module of one per-layer metric, or None when the file
        is not there (the metric is then left out of the line)."""
        path = os.path.join(self.bench_dir, "layer_metrics",
                            metric_name + ".py")
        if not os.path.exists(path):
            return None
        return load_module(path, "bench_metric_" + metric_name.replace(
            ".", "_").replace("-", "_"))
