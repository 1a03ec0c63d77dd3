"""Faults planted under a cell's timed path, which its comparison must catch.

Each fault is planted with a ``setattr(obj, name, value)`` the caller
gives (pytest's ``monkeypatch.setattr`` in the tests; the plain builtin in
``bench/readings.py --fault``, whose process ends after the reading):

* ``altered``: one number of the produced solution (of the last system)
  changed where the solve produces it;
* ``half_batch``: half the batch left out (its solutions zeroed);
* ``state_unchanged``: the step returns the state it was given, its step
  index: every step repeats the first.
"""
from __future__ import annotations

import jax.numpy as jnp


def _wrap_solution(setattr, change):
    from repro.core import linear_solve as ls
    for name in ("solve", "route_solve"):
        real = getattr(ls, name)

        def wrapped(*args, _real=real, **kw):
            out = _real(*args, **kw)
            if isinstance(out, tuple):
                return (change(out[0]), *out[1:])
            return change(out)
        setattr(ls, name, wrapped)


def _altered(x):
    # the last system's: every comparison's sample holds it
    return x.at[(-1,) * x.ndim].multiply(1.5)


def _half(x):
    keep = jnp.arange(x.shape[0]) < x.shape[0] // 2
    return jnp.where(keep.reshape((-1,) + (1,) * (x.ndim - 1)), x, 0.0)


def _stale_spd(setattr):
    from bench.entries import spd_hypergrad
    real = spd_hypergrad.make_step

    def stale(config):
        step = real(config)
        return lambda k, *a: (k, *step(k, *a)[1:])
    setattr(spd_hypergrad, "make_step", stale)


#: entry -> fault -> planter(setattr)
FAULTS = {
    "spd_hypergrad": {
        "altered": lambda s: _wrap_solution(s, _altered),
        "half_batch": lambda s: _wrap_solution(s, _half),
        "state_unchanged": _stale_spd,
    },
}


def plant(entry: str, fault: str, setattr=setattr) -> None:
    FAULTS[entry][fault](setattr)
