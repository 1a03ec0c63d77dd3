"""Percent of its roofline that the dense SPD solve reaches per step.

The floor is the least time the chip needs for the problem's own work,
whatever solves it (``bench/lib/roofline.py``): each system's matrix read
once and four d-vectors moved, against the HBM bandwidth, or one pass of
arithmetic over the matrices against the peak rate, whichever is longer.
The divisor is the device time of the ops under the entry's
``spd_solve`` scope, forward and backward, per step of the traced window.
"""
from bench.lib import roofline

SCOPE = "spd_solve"


def read(run):
    if run.trace is None or run.peak is None or not run.window.completed:
        return None
    seconds = run.trace.scope_s(SCOPE) / run.window.completed
    if seconds <= 0:
        return None
    B, d = run.config["classes"], run.config["dim"]
    floor, _ = roofline.floor_seconds(roofline.spd_solve_flops(B, d),
                                      roofline.spd_solve_bytes(B, d),
                                      run.peak)
    return 100.0 * floor / seconds
