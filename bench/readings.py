"""Read a cell's compared numbers over many seeds, and its control's.

    python3 bench/readings.py --workload <cell> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--seconds 1] [--fault <name>]

For each seed, one process-local run of the cell's set-up, a short window
at the cell's own load, and its comparison with the reference: the lower
readings that a limit is set from.  For each control seed, the same
comparison with the reference computed a precision lower in the program's
place: the upper readings.  Both are judged by the limits a run is judged
by, so a control reading comes out ``"correct": false``.  With
``--fault``, the program's readings with that fault of the cell's entry
planted under the timed path (``bench/faults/<entry>.py``); a name the
entry lacks is refused, with the names it has.  One JSON line per reading
on standard output.
The benchmark's own runs never run this; it runs on the chip only.
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

from bench.lib import faults, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--fault", help="plant this fault of the cell's entry "
                    "(bench/faults/<entry>.py) under the timed path first")
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if args.fault:
        w = {w["name"]: w for w in bench["workloads"]}[args.workload]
        try:
            faults.plant(harness.load_json("traffic", w["traffic"])["entry"],
                         args.fault)
        except ValueError as e:
            ap.error(str(e))
    for kind, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        for seed in seeds:
            out = harness.readings(bench, args.workload, seed, args.seconds,
                                   control=kind == "control")
            print(json.dumps({"workload": args.workload,
                              "kind": args.fault or kind,
                              "seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
