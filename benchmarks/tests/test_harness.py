"""The harness's own tests: ``pytest benchmarks/tests -q`` (CPU, by hand —
not part of the repository's tier-1 run).

What they pin: every file the benchmark names exists and agrees with
``BENCHMARK.json``; the copied generator still equals the program's; the
trace reduction gives the hand-computed share; a rehearsal ends in the one
line the contract fixes; each cell's control comes out not correct; and a
run with the timed path broken underneath (``faults/sitecustomize.py``)
reports ``correct`` false.
"""

from __future__ import annotations

import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

import run as harness  # noqa: E402
from common import RunFailed, load_json  # noqa: E402
from readers import xplane_idle  # noqa: E402
from traffic import vcf as traffic_vcf  # noqa: E402

BENCHMARK = load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench_file(*parts) -> dict:
    return load_json(os.path.join(BENCH, *parts))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_names_files_that_exist(cell):
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    spec = bench_file("workloads", f"{cell}.json")
    for key in ("config", "traffic", "chips", "why"):
        assert spec[key] == entry[key], key
    config = bench_file("configs", f"{spec['config']}.json")
    listed = next(c for c in BENCHMARK["configs"]
                  if c["name"] == spec["config"])
    assert listed["file"] == f"benchmarks/configs/{spec['config']}.json"
    assert listed["source"] == config["source"]
    assert listed["reduced"] == config["reduced"]
    assert config["guarantees"], "a deployment states its guarantees"
    assert hasattr(importlib.import_module(f"drivers.{spec['driver']}"),
                   "run")
    reported = [m["name"] for m in BENCHMARK["end_to_end"]
                if harness.applies(m, cell)]
    assert "setup_s" in reported and len(reported) >= 2
    assert any(harness.applies(m, cell) for m in BENCHMARK["per_layer"])


@pytest.mark.parametrize("metric", [m["name"]
                                    for m in BENCHMARK["per_layer"]])
def test_layer_metric_names_a_reader_and_what_it_moves(metric):
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == metric)
    spec = bench_file("metrics", f"{metric}.json")
    for key, value in entry.items():
        assert spec[key] == value, key
    assert callable(importlib.import_module(
        f"readers.{spec['reader']}").read)
    moved = next(m for m in BENCHMARK["end_to_end"]
                 if m["name"] == entry["moves"])
    for cell in entry["workloads"]:
        assert cell in CELLS and harness.applies(moved, cell)
    assert "mfu" not in metric and "roofline" not in metric


def test_names_and_units_are_allowed():
    names = [BENCHMARK[k] for k in ("configs", "workloads", "end_to_end",
                                    "per_layer")]
    for entry in (e for group in names for e in group):
        assert NAME.match(entry["name"]), entry["name"]
        if "unit" in entry:
            assert UNIT.match(entry["unit"]), entry["unit"]
            assert entry["better"] in ("lower", "higher")
        for key in ("config", "traffic"):
            if key in entry:
                assert NAME.match(entry[key])
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
    for metric in BENCHMARK["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.25
    assert len({e["name"] for e in BENCHMARK["end_to_end"]
                + BENCHMARK["per_layer"]}) == len(
        BENCHMARK["end_to_end"]) + len(BENCHMARK["per_layer"])


def test_copied_generator_equals_the_programs(tmp_path):
    synth = pytest.importorskip("annotatedvdb_tpu.io.synth")
    ours, theirs = tmp_path / "ours.vcf", tmp_path / "theirs.vcf"
    chromosomes = ("1", "2", "22")
    traffic_vcf.write_synth_vcf(str(ours), 2000, 2147483659, chromosomes)
    synth.write_synth_vcf(str(theirs), 2000, 2147483659, chromosomes)
    assert ours.read_bytes() == theirs.read_bytes()


def test_idle_share_of_a_hand_made_trace():
    # two overlapping operations (1.0-2.0, 1.5-3.0), a gap (3.0-4.0), one
    # more (4.0-4.4); window 0.5-5.0: busy 2.4 of 4.5 s
    device = {"/device:TPU:0": [("a", 1.0, 2.0), ("b", 1.5, 3.0),
                                ("a", 4.0, 4.4)]}
    host = [("outer", 3.0, 4.0), ("inner", 3.2, 3.7), ("early", 0.5, 0.9)]
    reduced = xplane_idle.reduce_events(device, host, window=(0.5, 5.0))
    assert reduced["busy_s"] == pytest.approx(2.4)
    assert reduced["window_s"] == pytest.approx(4.5)
    assert reduced["idle_pct"] == pytest.approx(100 * 2.1 / 4.5)
    assert reduced["device_ops"][0] == ["b", pytest.approx(1.5)]
    gaps = dict(reduced["idle_gaps"])
    assert gaps["host:inner"] == pytest.approx(0.5)   # innermost span wins
    assert gaps["host:outer"] == pytest.approx(0.5)
    assert gaps["host:early"] == pytest.approx(0.4)
    assert gaps["host:untraced"] == pytest.approx(0.7)  # 0.9-1.0, 4.4-5.0
    assert xplane_idle.reduce_events({"/device:TPU:0": []}, host) is None
    assert xplane_idle.read({"xplane": None}) is None


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes"] == 16e9
    with pytest.raises(RunFailed):
        harness.peaks_for("TPU v9 imaginary")


def run_cell(cell: str, trace: int, seed: int, env: dict | None = None):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(seed), "--seconds", "2", "--trace", str(trace),
         "--rehearse"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("cell,trace", [(c, t) for c in CELLS
                                        for t in (0, 1)])
def test_rehearsal_ends_in_the_contracts_line(cell, trace):
    line, stderr = run_cell(cell, trace, 2147483777 + trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks" and line["correct"] is True
    assert line["device"]["platform"] == "cpu"  # never a measurement
    assert line["attempted"] > 0 and line["failed"] == 0
    wanted = BENCHMARK["per_layer"] if trace else BENCHMARK["end_to_end"]
    names = {m["name"] for m in wanted if harness.applies(m, cell)}
    device_only = {m["name"] for m in BENCHMARK["per_layer"]
                   if m["source"] == "device_trace"}
    assert names - device_only <= set(line["metrics"]) <= names
    counters = {m["name"] for m in BENCHMARK["per_layer"]
                if m["source"] == "program_counter"}  # 0 off the device
    for name, metric in line["metrics"].items():
        assert metric["value"] > 0 or name in counters, name
    for name, c in line["checks"].items():
        assert f"check {name}: {c['value']} (limit {c['limit']})" in stderr


def driver_of(cell: str):
    spec = bench_file("workloads", f"{cell}.json")
    return spec, importlib.import_module(f"drivers.{spec['driver']}")


@pytest.mark.parametrize("seed", [7, 2147483659, 3000000019])
@pytest.mark.parametrize("cell", CELLS)
def test_control_comes_out_not_correct(cell, seed):
    spec, driver = driver_of(cell)
    config = bench_file("configs", f"{spec['config']}.json")
    params = dict(spec["parameters"], **spec["rehearse"])
    results = driver.controls(config, params, seed, seconds=49)
    assert len(results) >= 3
    for name, numbers in results.items():
        over = [k for k, limit in driver.LIMITS.items()
                if numbers.get(k, 0) > limit]
        assert over, f"control {name} passed every limit: {numbers}"


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in CELLS for f in driver_of(c)[1].FAULTS])
def test_a_broken_timed_path_reads_not_correct(cell, fault):
    faults_dir = os.path.join(BENCH, "tests", "faults")
    line, stderr = run_cell(cell, 0, 99, env={
        "PYTHONPATH": faults_dir, "AVDB_BENCH_TEST_FAULT": fault})
    assert line["correct"] is False, stderr[-1500:]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_no_result_without_the_program_around(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
    )
    assert proc.returncode != 0 and proc.stdout == ""
