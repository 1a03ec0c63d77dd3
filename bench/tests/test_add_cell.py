"""A new configuration, traffic mix and per-layer metric are new files only.

The files are written to a temporary directory searched before ``bench/``,
and the cell and metric are entries added to a copy of ``BENCHMARK.json``;
no file of the benchmark is edited.
"""
import json
import time

from bench.lib import harness
from bench.tests.conftest import ROOT, bench_file


def test_cell_added_from_files(tmp_path):
    before = {p: p.read_bytes() for p in (ROOT / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    for kind in ("configs", "traffic", "metrics"):
        (tmp_path / kind).mkdir()
    probe = json.loads(
        (ROOT / "bench/configs/resnet18_in1k_probe.json").read_text())
    probe.update(name="probe_d24", classes=3, dim=24, train_rows=48,
                 val_rows=24)
    (tmp_path / "configs/probe_d24.json").write_text(json.dumps(probe))
    mix = json.loads((ROOT / "bench/traffic/batched_spd.json").read_text())
    mix.update(inflight=1, checked_steps=3, limits={"hypergrad_rms": 1e-3})
    (tmp_path / "traffic/one_at_a_time.json").write_text(json.dumps(mix))
    (tmp_path / "metrics/steps_done.py").write_text(
        "def read(run):\n    return run.records['steps']\n")

    bench = bench_file()
    bench["workloads"].append({"name": "probe_d24.one_at_a_time",
                               "config": "probe_d24",
                               "traffic": "one_at_a_time", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0].setdefault("workloads", []).append(
        "probe_d24.one_at_a_time")
    bench["per_layer"].append({"name": "steps_done", "unit": "steps",
                               "better": "higher", "source": "program_counter",
                               "layer": "test", "moves": "step_s",
                               "workloads": ["probe_d24.one_at_a_time"]})
    assert bench["end_to_end"][0]["name"] == "step_s"

    dirs = (tmp_path, harness.BENCH_DIR)
    for traced in (False, True):
        out = harness.run_cell(bench, "probe_d24.one_at_a_time", 3, 0.5,
                               traced, t_process=time.perf_counter(),
                               require_tpu=False, dirs=dirs)
        assert out["correct"], out["checks"]
        assert set(out["checks"]) == {"hypergrad_rms"}
    assert out["metrics"]["steps_done"]["value"] == out["attempted"] > 0
    after = {p: p.read_bytes() for p in (ROOT / "bench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert after == before
