"""One run of one cell: set-up, the measured window, metrics and the check.

Everything that belongs to one configuration, traffic mix or metric is
found by name in files of its own (see ``bench/run.py``); this module
holds what every cell shares.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import pathlib
import shutil
import time
from typing import Any, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
#: fixed paths inside the checkout (listed in .gitignore)
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def judge(checks: list, failed: int) -> dict:
    """``correct`` and the numbers compared, each beside its limit: the
    one comparison that every run, and every reading, goes through."""
    return {"correct": all(c.ok for c in checks) and failed == 0,
            "checks": {c.name: {"value": c.value, "limit": c.limit}
                       for c in checks}}


@dataclasses.dataclass
class Window:
    """What the measured window produced, read by the metric readers."""
    seconds: float                 # wall time from first dispatch to close
    completed: int                 # steps or requests finished in it
    attempted: int
    failed: int


@dataclasses.dataclass
class RunData:
    """Everything a metric reader may read."""
    workload: dict
    config: dict
    traffic: dict
    setup_s: float
    window: Window
    records: dict                  # host numbers the entry returned
    trace: Any = None              # bench.lib.trace.Trace, --trace 1 only
    peak: Optional[dict] = None    # the chip's row of peaks.json


def seed_key(seed: int):
    """A JAX key from any whole seed, the bits above 32 folded in."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def sample(seed: int, n: int, k: int, *salt: int) -> list:
    """``k`` of the indices ``0..n-1`` drawn from the seed (and ``salt``),
    with the last."""
    import numpy as np
    rng = np.random.default_rng([abs(seed), *salt])
    picked = set(rng.choice(n, size=min(k, n), replace=False).tolist())
    picked.discard(n - 1)
    return sorted(picked)[:max(k - 1, 0)] + [n - 1]


def find(kind: str, name: str, suffix: str, dirs=(BENCH_DIR,)) -> pathlib.Path:
    """``<dir>/<kind>/<name><suffix>`` from the first of ``dirs`` that
    holds it."""
    for d in dirs:
        path = pathlib.Path(d) / kind / f"{name}{suffix}"
        if path.exists():
            return path
    raise FileNotFoundError(f"no {kind} file {name}{suffix} under "
                            f"{[str(d) for d in dirs]}")


def load_json(kind: str, name: str, dirs=(BENCH_DIR,)) -> dict:
    return json.loads(find(kind, name, ".json", dirs).read_text())


def load_module(kind: str, name: str, dirs=(BENCH_DIR,)):
    """``<kind>/<name>.py`` as a module (names may hold dots).  The
    benchmark's own are imported as ``bench.<kind>.<name>``, one module
    object however often they are looked up."""
    path = find(kind, name, ".py", dirs)
    if path.parent == BENCH_DIR / kind and "." not in name:
        return importlib.import_module(f"bench.{kind}.{name}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, workload: str, traced: bool) -> list:
    """The cell's end-to-end metrics, or with ``traced`` its per-layer
    metrics: those whose ``workloads`` name it, or that have no such list
    and move an end-to-end metric the cell reports."""
    def applies(m, names=None):
        if "workloads" in m:
            return workload in m["workloads"]
        return names is None or m["moves"] in names
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"] if applies(m, names)]


def require_chips(chips: int) -> list:
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no accelerator: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r}); "
                     "the benchmark runs on the chip only")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        CACHE_DIR.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # every program of a cell, however quick to compile, is found again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_info(devices) -> dict:
    stats = [d.memory_stats() or {} for d in devices]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats)}


def prepare(bench: dict, workload_name: str, require_tpu: bool, dirs):
    """The cell's entry, files and devices, with the compile cache on."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload_name not in cells:
        raise SystemExit(f"unknown workload {workload_name!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    workload = cells[workload_name]
    config = load_json("configs", workload["config"], dirs)
    traffic = load_json("traffic", workload["traffic"], dirs)
    import jax
    if require_tpu:
        devices = require_chips(int(workload["chips"]))
    else:
        devices = jax.devices()[:int(workload["chips"])]
    enable_compile_cache()
    from repro.analysis import autotune
    # routing and tiles from the library's cold rules: no tuning file that
    # the environment names is read
    autotune.set_default_cache(autotune.TuningCache())
    return workload, config, traffic, devices


def readings(bench: dict, workload_name: str, seed: int, seconds: float, *,
             control: bool = False, require_tpu: bool = True,
             dirs=(BENCH_DIR,)) -> dict:
    """One short run of the cell and its comparison, or with ``control``
    the comparison of the reference a precision lower put in the
    program's place, judged by the same limits as a run."""
    from bench.lib import loops
    workload, config, traffic, _ = prepare(bench, workload_name,
                                           require_tpu, dirs)
    entry = load_module("entries", traffic["entry"], dirs).build(
        config, traffic, seed)
    entry.warmup()
    window = loops.run(traffic["loop"], entry, traffic, seed, seconds)
    failed = window.failed + int(entry.close().get("failed", 0))
    return {"attempted": window.attempted, "failed": failed,
            **judge(entry.check(control=control), failed)}


def run_cell(bench: dict, workload_name: str, seed: int, seconds: float,
             traced: bool, *, t_process: float,
             require_tpu: bool = True, dirs=(BENCH_DIR,)) -> dict:
    """Run the cell once; returns the result line as a dict.

    ``require_tpu=False`` lets the tests drive the rest of a run on the
    CPU at a tiny size, and ``dirs`` lets them put files of their own
    before the benchmark's; the command line always requires the chip and
    reads only ``bench/``.
    """
    workload, config, traffic, devices = prepare(bench, workload_name,
                                                 require_tpu, dirs)
    metrics = cell_metrics(bench, workload_name, traced)
    import jax
    from bench.lib import loops
    entry = load_module("entries", traffic["entry"], dirs).build(
        config, traffic, seed)
    entry.traced = traced
    entry.warmup()
    setup_s = time.perf_counter() - t_process

    trace = None
    if traced:
        # a trace of a few seconds holds thousands of steps; the per-layer
        # metrics are read from it, the end-to-end ones from untraced runs
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
        from bench.lib import trace as trace_lib
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        with jax.profiler.trace(str(TRACE_DIR)):
            window = loops.run(traffic["loop"], entry, traffic, seed,
                               seconds)
        trace = trace_lib.Trace.from_dir(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        window = loops.run(traffic["loop"], entry, traffic, seed, seconds)
    device = device_info(devices)
    records = entry.close()
    window.failed += int(records.pop("failed", 0))
    checks = entry.check()

    peak = None
    if device["platform"] == "tpu":
        from bench.lib import roofline
        peak = roofline.peaks(device["kind"])
    run = RunData(workload=workload, config=config, traffic=traffic,
                  setup_s=setup_s, window=window, records=records,
                  trace=trace, peak=peak)
    values = {}
    for m in metrics:
        v = load_module("metrics", m["name"], dirs).read(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    verdict = judge(checks, window.failed)
    result = {"correct": verdict["correct"],
              "attempted": window.attempted, "failed": window.failed,
              "metrics": values, "device": device}
    if trace is not None:
        device.update(busy_s=trace.busy_s(), window_s=trace.window_s())
        result["breakdown"] = {"device_ops": trace.top_ops(10),
                               "idle_gaps": trace.idle_gaps(10)}
    result["checks"] = verdict["checks"]
    return result
