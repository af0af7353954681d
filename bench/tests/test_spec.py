"""Cells, configurations, traffic mixes and per-layer metrics are found by
the names BENCHMARK.json gives them: adding one takes new files and entries
only."""
import json
import os
import shutil

import pytest

from bench import checks, spec


def test_every_cell_loads():
    bench = spec.benchmark()
    for entry in bench["workloads"]:
        cell = spec.Cell(entry["name"], bench)
        assert cell.end_to_end and cell.per_layer
        assert set(checks.NAMES) <= set(cell.limits)
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))


def test_config_files_hold_what_is_run():
    bench = spec.benchmark()
    for c in bench["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]


def test_new_cell_and_metric_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = spec.benchmark()
    bench["workloads"].append(
        {"name": "covtype_nuts8", "config": "covtype_logreg",
         "traffic": "nuts8_d10", "chips": 1, "why": "more chains"})
    bench["per_layer"].append(
        {"name": "chains_seen", "unit": "chains", "better": "higher",
         "source": "program_counter", "layer": "NUTS transition",
         "moves": "grad_evals_per_s", "workloads": ["covtype_nuts8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    traffic = json.loads((root / "bench/traffic/nuts4_d10.json").read_text())
    traffic["num_chains"] = 8
    (root / "bench/traffic/nuts8_d10.json").write_text(json.dumps(traffic))
    (root / "bench/workloads/covtype_nuts8.json").write_text(
        json.dumps({"limits": {"pe_gap": 2.0, "grad_gap": 0.001,
                               "virial_z": 3.0}}))
    (root / "bench/metrics/chains_seen.py").write_text(
        "def read(run):\n    return run.cell.traffic['num_chains']\n")

    cell = spec.Cell("covtype_nuts8", root=str(root))
    assert cell.traffic["num_chains"] == 8
    assert cell.limits["pe_gap"] == 2.0
    assert [m["name"] for m in cell.per_layer][-1] == "chains_seen"
    assert "chains_seen" not in [m["name"] for m in
                                 spec.Cell("covtype_nuts4",
                                           root=str(root)).per_layer]

    class Run:
        pass

    run = Run()
    run.cell = cell
    assert cell.reader("chains_seen")(run) == 8


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        spec.peaks("TPU v9 imaginary")
