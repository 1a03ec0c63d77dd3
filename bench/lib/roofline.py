"""The chip's peaks and the work of the problems the cells solve.

The work is that of the problem, whatever code solves it, so that a later
change of solver or kernel is judged on the same yardstick.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str, table: dict | None = None) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    if table is None:
        table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; add the chip with its source")
    return table[device_kind]


def spd_solve_bytes(B: int, d: int, itemsize: int = 4) -> int:
    """HBM bytes a hypergradient of B dense SPD systems needs at least.

    Each system's matrix is read once, and four d-vectors move: the
    right-hand side and solution of the forward solve, the cotangent and
    the adjoint solution of the backward solve.
    """
    return itemsize * B * (d * d + 4 * d)


def spd_solve_flops(B: int, d: int) -> int:
    """One pass over each matrix: the least arithmetic such a solve does."""
    return 2 * B * d * d


def floor_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """``(seconds, bound)``: the larger of the compute and memory times."""
    t_flop = flops / peak["bf16_flop_per_s"]
    t_mem = nbytes / peak["hbm_byte_per_s"]
    return (t_mem, "hbm") if t_mem >= t_flop else (t_flop, "flops")
