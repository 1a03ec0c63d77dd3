"""Faults planted under a cell's timed path, which its comparison must catch.

Each entry's faults live in ``bench/faults/<entry>.py``, found by name
like every other per-cell file: its ``FAULTS`` maps a fault's name to a
planter, ``planter(setattr)``.  A fault is planted with a
``setattr(obj, name, value)`` the caller gives (pytest's
``monkeypatch.setattr`` in the tests; the plain builtin in
``bench/readings.py --fault``, whose process ends after the reading).
This module holds what the entries' tables share: wrapping the solution
where ``linear_solve`` produces it, and two ways of changing it.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.lib import harness


def wrap_solution(setattr, change):
    """``change`` applied to every solution ``linear_solve.solve`` and
    ``route_solve`` produce."""
    from repro.core import linear_solve as ls
    for name in ("solve", "route_solve"):
        real = getattr(ls, name)

        def wrapped(*args, _real=real, **kw):
            out = _real(*args, **kw)
            if isinstance(out, tuple):
                return (change(out[0]), *out[1:])
            return change(out)
        setattr(ls, name, wrapped)


def altered(x):
    """One number changed: the last system's, which every comparison's
    sample holds."""
    return x.at[(-1,) * x.ndim].multiply(1.5)


def half(x):
    """Half the batch left out: its solutions zeroed."""
    keep = jnp.arange(x.shape[0]) < x.shape[0] // 2
    return jnp.where(keep.reshape((-1,) + (1,) * (x.ndim - 1)), x, 0.0)


def table(entry: str, dirs=(harness.BENCH_DIR,)) -> dict:
    """The entry's ``FAULTS``: fault name -> planter(setattr)."""
    return harness.load_module("faults", entry, dirs).FAULTS


def plant(entry: str, fault: str, setattr=setattr,
          dirs=(harness.BENCH_DIR,)) -> None:
    faults = table(entry, dirs)
    if fault not in faults:
        raise ValueError(f"entry {entry!r} has no fault {fault!r}; "
                         f"it has {sorted(faults)}")
    faults[fault](setattr)
