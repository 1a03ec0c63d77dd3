"""Mesh builders.

Every builder is a FUNCTION (importing this module never touches jax
device state) and goes through ``make_mesh``, which gives every axis the
``Auto`` type: XLA propagates shardings through the program, as
``shard_map`` bodies and ``NamedSharding`` placements in this repository
expect.  (``jax.make_mesh`` defaults to ``Explicit`` axes, under which a
matmul contracting a sharded dimension is a type error.)

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).  Multi-pod:
(pod=2, data=16, model=16) = 512 chips, with the ``pod`` axis mapped to
the slowest (DCN/ICI-bridge) links — pure data parallelism crosses it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes over ``devices`` (default: the
    first ``prod(shape)`` local devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests)."""
    return make_mesh((data, model), ("data", "model"))


def auto_mesh_size(B: int, d: int, *, spd: bool = True,
                   dtype: str = "float32", max_devices: int = None) -> int:
    """The cost-model-selected 1-D solve-mesh extent for a (B, d) regime.

    Thin front end over ``analysis.autotune.auto_mesh_size``: candidates
    are power-of-two extents dividing ``B`` up to the local device count,
    ranked by measured tuning-cache entries when any exist and by the
    roofline solve model otherwise.  Pair with ``make_solve_mesh``::

        n = auto_mesh_size(B, d)
        mesh = make_solve_mesh(devices=n)

    so examples and benchmarks pick their extent empirically instead of
    hardcoding "all devices" (which BENCH showed oversharding at mesh=8
    for B=64, d=16).
    """
    from repro.analysis import autotune
    return autotune.auto_mesh_size(B, d, spd=spd, dtype=dtype,
                                   max_devices=max_devices)


def make_solve_mesh(devices: int = None, axis: str = "data"):
    """1-D mesh for sharded linear solves (``ShardedOperator`` and the
    ``sharded_*`` registry solvers).

    Uses the first ``devices`` local devices (all by default, so the same
    call serves a laptop, a CI lane with forced host devices, and a real
    slice).  Batched hypergradient workloads shard the instance batch over
    this axis; ``devices`` must then divide the batch size.
    """
    devs = jax.devices()
    if devices is not None:
        if devices > len(devs):
            raise ValueError(f"requested {devices} devices, have "
                             f"{len(devs)}")
        devs = devs[:devices]
    return make_mesh((len(devs),), (axis,), devices=devs)
