"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

- ``BENCHMARK.json``                    cells, metrics, configurations
- ``bench/configs/<config>.json``       the configuration's sizes, as run
- ``bench/configs/<config>.py``         its model, data and work counts
- ``bench/configs/<config>_ref.py``     its plain reference
- ``bench/traffic/<traffic>.json``      the fit each cell repeats
- ``bench/workloads/<cell>.json``       the cell's correctness limits
- ``bench/metrics/<metric>.py``         the reader of one per-layer metric

A cell, configuration, traffic mix or per-layer metric is added by adding
its files and its entry in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root=ROOT):
    return _json(root, "BENCHMARK.json")


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, name, bench=None, root=ROOT):
        bench = benchmark(root) if bench is None else bench
        entries = {w["name"]: w for w in bench["workloads"]}
        if name not in entries:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(entries)})")
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        bench_dir = os.path.join(root, "bench")
        cfg = self.entry["config"]
        self.config = _json(bench_dir, "configs", f"{cfg}.json")
        self.model = _module(os.path.join(bench_dir, "configs", f"{cfg}.py"),
                             f"bench_config_{cfg}")
        self.reference = _module(
            os.path.join(bench_dir, "configs", f"{cfg}_ref.py"),
            f"bench_reference_{cfg}")
        self.traffic = _json(bench_dir, "traffic",
                             f"{self.entry['traffic']}.json")
        self.limits = _json(bench_dir, "workloads", f"{name}.json")["limits"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]
        self._bench_dir = bench_dir

    def reader(self, metric):
        """The ``read(run)`` function of a per-layer metric."""
        path = os.path.join(self._bench_dir, "metrics", f"{metric}.py")
        return _module(path, f"bench_metric_{metric}").read


def peaks(device_kind, root=ROOT):
    """Peak rates of ``device_kind``; a kind not in the table is an error."""
    table = _json(root, "bench", "peaks.json")
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json; add its published peaks there")
    return table[device_kind]
