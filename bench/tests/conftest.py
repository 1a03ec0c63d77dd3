"""The benchmark's own tests: rehearsals on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import json
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

#: per configuration and traffic mix, the keys a tiny rehearsal changes
TINY = {
    "configs": {
        "resnet18_in1k_probe": {"classes": 8, "dim": 256,
                                "train_rows": 4000, "val_rows": 1000},
    },
    "traffic": {},
}


def bench_file():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


#: the sizes at which the control's rounding shows on the CPU: the full
#: width, rows enough that the Gram matrices' sums carry it, and theta
#: scaled with the rows, so the systems keep the configuration's condition
CONTROL = {"configs": {"resnet18_in1k_probe": {
    "classes": 8, "dim": 512, "train_rows": 64000, "val_rows": 4000,
    "theta_range": [500.0, 50000.0]}}}


@pytest.fixture
def tiny(tmp_path):
    """A directory of tiny copies of every configuration and traffic mix
    that ``TINY`` shrinks, searched before ``bench/``."""
    return write_sizes(tmp_path, TINY)


@pytest.fixture
def control_sized(tmp_path):
    """As ``tiny``, at the sizes of ``CONTROL``."""
    return write_sizes(tmp_path, CONTROL)


def write_sizes(tmp_path, sizes):
    for kind, entries in sizes.items():
        (tmp_path / kind).mkdir()
        for name, changes in entries.items():
            src = ROOT / "bench" / kind / f"{name}.json"
            data = dict(json.loads(src.read_text()), **changes)
            (tmp_path / kind / f"{name}.json").write_text(json.dumps(data))
    return tmp_path
