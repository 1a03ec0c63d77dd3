"""A whole new cell is new files only: its configuration, traffic mix,
entry, faults, rehearsal sizes and per-layer metrics.

The files are written to a temporary directory laid out as ``bench/`` is
and searched before it, and the cell and its metrics are entries added to
a copy of ``BENCHMARK.json``.  The cell goes through the checks that
``test_cells.py`` puts every cell through, and no file of the benchmark
is edited.
"""
import json

import pytest

from bench.lib import harness
from bench.tests import test_cells as cells
from bench.tests.conftest import ROOT, bench_file, write_sizes

CELL = "probe_copy.one_at_a_time"

DEVICE_METRIC = '''\
def read(run):
    if run.trace is None or not run.window.completed:
        return None
    seconds = run.trace.scope_s("spd_solve")
    return 1e3 * seconds / run.window.completed if seconds > 0 else None
'''


def bench_files():
    return {p: p.read_bytes() for p in (ROOT / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def write(path, text):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def add_cell(new):
    """The cell's files under ``new``; returns ``BENCHMARK.json`` with the
    cell's entries appended."""
    probe = json.loads(
        (ROOT / "bench/configs/resnet18_in1k_probe.json").read_text())
    probe["name"] = "probe_copy"
    write(new / "configs/probe_copy.json", json.dumps(probe))
    mix = json.loads((ROOT / "bench/traffic/batched_spd.json").read_text())
    mix.update(entry="ridge_copy", inflight=1, checked_steps=3)
    write(new / "traffic/one_at_a_time.json", json.dumps(mix))
    write(new / "entries/ridge_copy.py",
          (ROOT / "bench/entries/spd_hypergrad.py").read_text())
    write(new / "faults/ridge_copy.py",
          "from bench.lib.faults import altered, half, wrap_solution\n"
          "FAULTS = {'altered': lambda s: wrap_solution(s, altered),\n"
          "          'half_batch': lambda s: wrap_solution(s, half)}\n")
    write(new / "tests/sizes/configs/probe_copy.json", json.dumps({
        "tiny": {"classes": 3, "dim": 24, "train_rows": 48, "val_rows": 24},
        "control": {"classes": 8, "dim": 512, "train_rows": 64000,
                    "val_rows": 4000, "theta_range": [500.0, 50000.0]}}))
    write(new / "tests/sizes/traffic/one_at_a_time.json",
          json.dumps({"tiny": {}, "control": {}}))
    write(new / "metrics/steps_done.py",
          "def read(run):\n    return run.records['steps']\n")
    write(new / "metrics/solve_ms.py", DEVICE_METRIC)

    bench = bench_file()
    bench["configs"].append({"name": "probe_copy", "source": "test",
                             "file": "bench/configs/probe_copy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "probe_copy",
                               "traffic": "one_at_a_time", "chips": 1,
                               "why": "test"})
    assert bench["end_to_end"][0]["name"] == "step_s"
    bench["end_to_end"][0].setdefault("workloads", []).append(CELL)
    layer = {"better": "higher", "layer": "test", "moves": "step_s",
             "workloads": [CELL]}
    bench["per_layer"] += [
        dict(layer, name="steps_done", unit="steps",
             source="program_counter"),
        dict(layer, name="solve_ms", unit="ms", better="lower",
             source="device_trace")]
    return bench


def test_cell_added_from_files(tmp_path):
    before = bench_files()
    new = tmp_path / "new"
    bench = add_cell(new)
    tiny = write_sizes(tmp_path / "tiny", "tiny", bench, (new,))
    control = write_sizes(tmp_path / "control", "control", bench, (new,))
    assert harness.load_json("configs", "probe_copy", (tiny, new))["dim"] == 24

    cells.check_has_rehearsal_sizes(bench, (new, harness.BENCH_DIR), CELL)
    cells.check_entry_has_faults(bench, (new, harness.BENCH_DIR), CELL)
    dirs = (tiny, new, harness.BENCH_DIR)
    cells.check_runs_and_is_correct(bench, dirs, CELL)
    out = cells.check_traced_run_reads_host_metrics(bench, dirs, CELL)
    assert out["metrics"]["steps_done"]["value"] == out["attempted"] > 0
    cells.check_control_fails_the_limit(
        bench, (control, new, harness.BENCH_DIR), CELL)
    assert cells.fault_names(bench, CELL, dirs) == ["altered", "half_batch"]
    for fault in cells.fault_names(bench, CELL, dirs):
        with pytest.MonkeyPatch.context() as mp:
            cells.check_fault_is_caught(bench, dirs, CELL, fault, mp.setattr)

    assert bench_files() == before
