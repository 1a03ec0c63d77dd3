"""Each cell end to end at a tiny size on the CPU, its control, and the
faults its comparison has to catch.

The harness's look for a chip is skipped (``require_tpu=False``); the rest
of a run is the one the chip runs: set-up, the window, the metrics and the
comparison with the plain reference.  Each ``check_*`` takes the benchmark
file and the directories searched before ``bench/``, so that
``test_add_cell.py`` puts a cell made of new files through the same
checks.
"""
import time

import jax
import pytest

from bench.lib import faults, harness
from bench.tests.conftest import SIZED, bench_file, sizes

BENCH_DIR = harness.BENCH_DIR
CELLS = [w["name"] for w in bench_file()["workloads"]]


def workload(bench, cell):
    return {w["name"]: w for w in bench["workloads"]}[cell]


def entry_of(bench, cell, dirs=(BENCH_DIR,)):
    return harness.load_json("traffic", workload(bench, cell)["traffic"],
                             dirs)["entry"]


def fault_names(bench, cell, dirs=(BENCH_DIR,)):
    """The faults of the cell's entry; none where it has no table, which
    ``check_entry_has_faults`` fails, and not the collection of every
    other test."""
    try:
        return list(faults.table(entry_of(bench, cell, dirs), dirs))
    except FileNotFoundError:
        return []


def run(bench, dirs, cell, traced=False, seconds=1.0, seed=2**33 + 5):
    return harness.run_cell(bench, cell, seed, seconds, traced,
                            t_process=time.perf_counter(), require_tpu=False,
                            dirs=dirs)


def readings(bench, dirs, cell, control, seed=2**31 + 11):
    return harness.readings(bench, cell, seed, 0.5, control=control,
                            require_tpu=False, dirs=dirs)


def check_runs_and_is_correct(bench, dirs, cell):
    out = run(bench, dirs, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    wanted = {m["name"] for m in harness.cell_metrics(bench, cell, False)}
    assert set(out["metrics"]) == wanted
    assert "setup_s" in wanted and len(wanted) >= 2
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


def check_traced_run_reads_host_metrics(bench, dirs, cell):
    out = run(bench, dirs, cell, traced=True)
    assert out["correct"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # device metrics need a chip: on the CPU they read nothing and are left
    # out; what the host counts is there
    per_layer = harness.cell_metrics(bench, cell, True)
    device = {m["name"] for m in per_layer if m["source"] == "device_trace"}
    assert set(out["metrics"]) == {m["name"] for m in per_layer} - device
    return out


def check_control_fails_the_limit(bench, dirs, cell):
    """The control, the reference a precision lower put in the program's
    place, goes through the same comparison as a run and comes out not
    correct, while the program on the same seed comes out correct."""
    program = readings(bench, dirs, cell, control=False)
    control = readings(bench, dirs, cell, control=True)
    assert program["correct"], program["checks"]
    assert control["correct"] is False, control["checks"]
    assert control["checks"].keys() == program["checks"].keys()
    assert any(c["value"] > c["limit"] for c in control["checks"].values())


def check_fault_is_caught(bench, dirs, cell, fault, setattr):
    faults.plant(entry_of(bench, cell, dirs), fault, setattr, dirs)
    assert run(bench, dirs, cell)["correct"] is False


def check_entry_has_faults(bench, dirs, cell):
    entry = entry_of(bench, cell, dirs)
    assert faults.table(entry, dirs), f"{entry}'s FAULTS is empty"


def check_has_rehearsal_sizes(bench, dirs, cell):
    """The cell's configuration and traffic mix each have a sizes file,
    whose sizes change only keys the file has."""
    w = workload(bench, cell)
    for kind, key in SIZED.items():
        full = harness.load_json(kind, w[key], dirs)
        changes = sizes(kind, w[key], dirs)
        for which in ("tiny", "control"):
            assert set(changes[which]) <= set(full), (kind, w[key], which)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_rehearsal_sizes(cell):
    check_has_rehearsal_sizes(bench_file(), (BENCH_DIR,), cell)


@pytest.mark.parametrize("cell", CELLS)
def test_every_entry_has_faults(cell):
    check_entry_has_faults(bench_file(), (BENCH_DIR,), cell)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(tiny, cell):
    check_runs_and_is_correct(bench_file(), (tiny, BENCH_DIR), cell)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reads_host_metrics(tiny, cell):
    check_traced_run_reads_host_metrics(bench_file(), (tiny, BENCH_DIR), cell)


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(control_sized, cell):
    check_control_fails_the_limit(bench_file(), (control_sized, BENCH_DIR),
                                  cell)


# -- faults planted under the timed path ------------------------------------

FAULT_CASES = [(cell, fault) for cell in CELLS
               for fault in fault_names(bench_file(), cell)]


@pytest.mark.parametrize("cell,fault", FAULT_CASES)
def test_fault_is_caught(tiny, monkeypatch, cell, fault):
    check_fault_is_caught(bench_file(), (tiny, BENCH_DIR), cell, fault,
                          monkeypatch.setattr)


@pytest.mark.parametrize("cell", CELLS)
def test_readings_rejects_an_unknown_fault(capsys, cell):
    from bench import readings as cli
    with pytest.raises(SystemExit) as e:
        cli.main(["--workload", cell, "--seeds", "1", "--fault", "no_such"])
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "no_such" in err
    assert str(sorted(fault_names(bench_file(), cell))) in err


def test_no_chip_no_result(capsys):
    from bench import run as cli
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a chip is attached")
    cell = CELLS[0]
    assert cli.main(["--workload", cell, "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""
