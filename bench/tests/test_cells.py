"""Each cell end to end at a tiny size on the CPU, its control, and the
faults its comparison has to catch.

The harness's look for a chip is skipped (``require_tpu=False``); the rest
of a run is the one the chip runs: set-up, the window, the metrics and the
comparison with the plain reference.
"""
import time

import jax
import pytest

from bench.lib import faults, harness
from bench.tests.conftest import bench_file

CELLS = [w["name"] for w in bench_file()["workloads"]]


def entry_of(cell):
    w = {w["name"]: w for w in bench_file()["workloads"]}[cell]
    return harness.load_json("traffic", w["traffic"])["entry"]


def run(tiny, cell, traced=False, seconds=1.0, seed=2**33 + 5):
    return harness.run_cell(bench_file(), cell, seed, seconds, traced,
                            t_process=time.perf_counter(), require_tpu=False,
                            dirs=(tiny, harness.BENCH_DIR))


def readings(tiny, cell, control, seed=2**31 + 11):
    return harness.readings(bench_file(), cell, seed, 0.5, control=control,
                            require_tpu=False, dirs=(tiny, harness.BENCH_DIR))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(tiny, cell):
    bench = bench_file()
    out = run(tiny, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    wanted = {m["name"] for m in harness.cell_metrics(bench, cell, False)}
    assert set(out["metrics"]) == wanted
    assert "setup_s" in wanted and len(wanted) >= 2
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reads_host_metrics(tiny, cell):
    out = run(tiny, cell, traced=True)
    assert out["correct"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # device metrics need a chip: on the CPU they read nothing and are left
    # out; what the host counts is there
    device_metrics = {"idle_share.step", "spd_solve_roofline"}
    per_layer = {m["name"] for m in harness.cell_metrics(bench_file(), cell, True)}
    assert set(out["metrics"]) == per_layer - device_metrics


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(control_sized, cell):
    """The control, the reference a precision lower put in the program's
    place, goes through the same comparison as a run and comes out not
    correct, while the program on the same seed comes out correct."""
    program = readings(control_sized, cell, control=False)
    control = readings(control_sized, cell, control=True)
    assert program["correct"], program["checks"]
    assert control["correct"] is False, control["checks"]
    assert control["checks"].keys() == program["checks"].keys()
    assert any(c["value"] > c["limit"] for c in control["checks"].values())


# -- faults planted under the timed path ------------------------------------

FAULT_CASES = [(cell, fault) for cell in CELLS
               for fault in faults.FAULTS[entry_of(cell)]]


@pytest.mark.parametrize("cell,fault", FAULT_CASES)
def test_fault_is_caught(tiny, monkeypatch, cell, fault):
    faults.plant(entry_of(cell), fault, monkeypatch.setattr)
    assert run(tiny, cell)["correct"] is False


def test_no_chip_no_result(capsys):
    from bench import run as cli
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a chip is attached")
    cell = CELLS[0]
    assert cli.main(["--workload", cell, "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""
