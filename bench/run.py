"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  It names a
configuration (``bench/configs/<config>.json``, whose ``problem`` is the
module of ``bench/problems/`` that makes its data and holds its plain
reference) and a traffic mix (``bench/traffic/<traffic>.json``, whose
``entry`` names the code in ``bench/entries/`` that drives the system under
test and whose ``loop`` names the generator in ``bench/lib/loops.py``).  Each metric is read by
``bench/metrics/<metric>.py``.  A new cell, mix or metric is new files.

Set-up makes the data on the device from ``--seed``, compiles and warms
every program the window runs, then the window runs for ``--seconds``.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  Once the window
has closed, the outputs it produced are compared with the plain reference;
the numbers compared and their limits are the last lines on standard
error and the last key of the result line, the last line on standard
output.  With no TPU, or fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    try:
        result = harness.run_cell(bench, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
