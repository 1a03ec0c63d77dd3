"""The benchmark's own tests: rehearsals on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

The sizes a rehearsal runs at are files found by name, like every other
per-cell file: ``sizes/configs/<config>.json`` and
``sizes/traffic/<traffic>.json`` beside this file, each with a ``tiny``
and a ``control`` set of the keys it changes.
"""
import json
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from bench.lib import harness  # noqa: E402

#: a cell's files whose sizes a rehearsal changes: kind -> workload key
SIZED = {"configs": "config", "traffic": "traffic"}


def bench_file():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture
def tiny(tmp_path):
    """A directory of tiny copies of every configuration and traffic mix
    a cell names, searched before ``bench/``."""
    return write_sizes(tmp_path, "tiny")


@pytest.fixture
def control_sized(tmp_path):
    """As ``tiny``, at the sizes at which the control's rounding shows on
    the CPU."""
    return write_sizes(tmp_path, "control")


def sizes(kind, name, dirs=(harness.BENCH_DIR,)):
    """The sizes file of configuration or traffic mix ``name``, from
    ``tests/sizes/`` under the first of ``dirs`` that holds it."""
    return harness.load_json(kind, name, [pathlib.Path(d) / "tests" / "sizes"
                                          for d in dirs])


def write_sizes(out, which, bench=None, dirs=()):
    """Copies of every configuration and traffic mix that a cell of
    ``bench`` (``BENCHMARK.json`` by default) names, with the ``which``
    sizes of their sizes files, written to ``out/<kind>/``.  ``dirs`` are
    searched before ``bench/``, for the files and their sizes alike.  A
    file with no sizes raises: no cell is rehearsed at its full size."""
    dirs = (*dirs, harness.BENCH_DIR)
    for w in (bench or bench_file())["workloads"]:
        for kind, key in SIZED.items():
            name = w[key]
            data = dict(harness.load_json(kind, name, dirs),
                        **sizes(kind, name, dirs)[which])
            (out / kind).mkdir(parents=True, exist_ok=True)
            (out / kind / f"{name}.json").write_text(json.dumps(data))
    return out
