"""Faults under ``spd_hypergrad``'s timed path:

* ``altered``: one number of the produced solution (of the last system)
  changed where the solve produces it;
* ``half_batch``: half the batch left out (its solutions zeroed);
* ``state_unchanged``: the step returns the state it was given, its step
  index: every step repeats the first.
"""
from __future__ import annotations

from bench.lib.faults import altered, half, wrap_solution


def _stale(setattr):
    from bench.entries import spd_hypergrad
    real = spd_hypergrad.make_step

    def stale(config):
        step = real(config)
        return lambda k, *a: (k, *step(k, *a)[1:])
    setattr(spd_hypergrad, "make_step", stale)


FAULTS = {
    "altered": lambda s: wrap_solution(s, altered),
    "half_batch": lambda s: wrap_solution(s, half),
    "state_unchanged": _stale,
}
