"""Measured cost model + persistent tuning cache behind every dispatch.

The dispatch layer (``core/linear_solve._resolve_auto`` and
``_upgrade_for_sharded``, ``launch/mesh.auto_mesh_size``, the Pallas
``batched_cg(block_b="auto")`` schedule) used to choose on structure
alone; BENCH_smoke.json showed that leaving large factors on the table
(sharded 1.44x SLOWER than single-device at mesh=8, B=64, d=16).  This
module makes every such decision empirical:

  * ``TuningCache`` — a persistent map from a dispatch regime
    ``TuningKey(backend, solver, B, d, dtype, mesh_size, precond,
    variant)`` to a measured (or modeled) solve time.  Versioned JSON
    ``save``/``load`` mirrors the ``WarmStartCache._SAVE_VERSION``
    pattern; ``REPRO_AUTOTUNE_CACHE`` pre-loads the process default, so a
    deployment ships a pre-tuned cache as a file.
  * measurement — ``measure_solver`` / ``measure_block_schedule`` run
    timed candidate micro-benchmarks (median-of-k, jit-warmup excluded)
    and record them; ``benchmarks/autotune_sweep.py`` drives them
    offline.  Measurement NEVER happens inside dispatch: decisions are
    made at trace time from the cache, populated on demand from host
    code or offline sweeps.
  * prediction — ``predict_solve_seconds`` returns the measured entry
    when one exists and otherwise falls back to the roofline solve model
    (``analysis/roofline.analyze_solve``).  Costs are only ever compared
    LIKE-FOR-LIKE: measured against measured, roofline against roofline
    (a TPU-model estimate and a wall-clock median are different units).
  * decisions — ``should_shard`` (gates the sharded-solver upgrade at
    the operand's mesh size), ``auto_mesh_size`` (picks the mesh extent
    instead of blindly using all devices) and ``choose_block_b`` (the
    tuned Pallas tile height behind ``block_b="auto"``).

Cold-cache semantics: with no measurements the roofline fallback
predicts a win for pure batch sharding at any extent (per-chip work
divides by the mesh, no collectives), so structural behavior is
unchanged until measurements say otherwise — host-side dispatch
overhead, the cause of the mesh=8 regression, is exactly what measured
entries capture and the hardware model deliberately omits.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import threading
import time
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional, Tuple

from repro.observability import metrics as obs_metrics

# NOTE: repro.core / repro.distributed / repro.launch are imported lazily
# inside functions — linear_solve consults this module at dispatch time,
# so a top-level import either way would cycle.  repro.observability is
# bottom-adjacent (imports nothing from repro), so it is safe up here;
# the decision counters below are always-on host-side bookkeeping, not
# gated telemetry — recording WHY dispatch chose a path costs one dict
# lookup and never touches the device.

_SHARD_ACCEPT_SLACK = 1.05   # shard when predicted <= single * slack


class TuningKey(NamedTuple):
    """One dispatch regime: everything a timing is conditioned on.

    ``backend`` is the jax backend the measurement ran on (timings never
    transfer across backends), ``solver`` a registry name (or
    ``"batched_cg"`` for kernel-schedule entries), ``B``/``d``/``dtype``
    the batched-system shape, ``mesh_size`` the 1-D solve-mesh extent
    (1 = single device), ``precond`` the normalized preconditioner tag
    ("" for none) and ``variant`` a free-form schedule qualifier (e.g.
    ``"block_b=16"``).
    """
    backend: str
    solver: str
    B: int
    d: int
    dtype: str = "float32"
    mesh_size: int = 1
    precond: str = ""
    variant: str = ""


@dataclasses.dataclass(frozen=True)
class TuningRecord:
    """A cached cost: ``seconds`` per solve, its ``source`` (``"measured"``
    or ``"roofline"``) and how many timed ``samples`` produced it."""
    seconds: float
    source: str = "measured"
    samples: int = 0


def normalize_precond(precond) -> str:
    """Fold a ``precond`` argument to its cache-key tag ("" for none)."""
    if precond is None:
        return ""
    if isinstance(precond, str):
        return precond
    return "callable"


def current_backend() -> str:
    """The jax backend dispatch decisions are conditioned on."""
    import jax
    return jax.default_backend()


class TuningCache:
    """Thread-safe store of ``TuningKey -> TuningRecord`` with versioned
    persistence (the ``WarmStartCache`` save/load pattern, JSON-encoded
    since entries are scalars, not arrays)."""

    _SAVE_VERSION = 1

    def __init__(self):
        self._mutex = threading.Lock()
        self._store: Dict[TuningKey, TuningRecord] = {}

    def put(self, key: TuningKey, seconds: float, *,
            source: str = "measured", samples: int = 1) -> TuningRecord:
        """Insert/overwrite the cost record for ``key``."""
        rec = TuningRecord(seconds=float(seconds), source=str(source),
                           samples=int(samples))
        with self._mutex:
            self._store[TuningKey(*key)] = rec
        obs_metrics.global_registry().counter(
            "repro_autotune_cache_puts_total",
            help="tuning-cache inserts by record source",
            source=rec.source).inc()
        return rec

    def get(self, key: TuningKey) -> Optional[TuningRecord]:
        """The record for ``key``, or None when never tuned."""
        with self._mutex:
            return self._store.get(TuningKey(*key))

    def lookup(self, **fields) -> Optional[TuningRecord]:
        """Keyword-style ``get`` (defaults fill unspecified key fields)."""
        return self.get(TuningKey(**fields))

    def __len__(self) -> int:
        with self._mutex:
            return len(self._store)

    def __contains__(self, key: TuningKey) -> bool:
        return self.get(key) is not None

    def items(self) -> List[Tuple[TuningKey, TuningRecord]]:
        """A stable snapshot of all entries (sorted by key)."""
        with self._mutex:
            return sorted(self._store.items())

    def save(self, path) -> str:
        """Persist all entries to ``path`` as version-stamped JSON.

        Layout: ``{"format_version": 1, "entries": [{<key fields>,
        "seconds", "source", "samples"}, ...]}``.  Returns the path
        written (``.json`` appended when missing).
        """
        path = str(path)
        if not path.endswith(".json"):
            path += ".json"
        entries = [{**k._asdict(), **dataclasses.asdict(r)}
                   for k, r in self.items()]
        with open(path, "w") as f:
            json.dump({"format_version": self._SAVE_VERSION,
                       "entries": entries}, f, indent=1, sort_keys=True)
        return path

    @classmethod
    def load(cls, path) -> "TuningCache":
        """Restore a cache written by ``save``; rejects unknown versions."""
        with open(str(path)) as f:
            blob = json.load(f)
        version = int(blob.get("format_version", -1))
        if version != cls._SAVE_VERSION:
            raise ValueError(
                f"tuning cache file {str(path)!r} has format version "
                f"{version}; this build reads version {cls._SAVE_VERSION}")
        cache = cls()
        for e in blob["entries"]:
            key = TuningKey(**{f: e[f] for f in TuningKey._fields})
            cache.put(key, e["seconds"], source=e["source"],
                      samples=e["samples"])
        return cache


# ---------------------------------------------------------------------------
# the process-default cache
# ---------------------------------------------------------------------------

_DEFAULT_CACHE: Optional[TuningCache] = None
_DEFAULT_MUTEX = threading.Lock()

#: environment variable naming a ``TuningCache.save`` file to pre-load as
#: the process default — how a deployment ships a pre-tuned cache.
CACHE_ENV_VAR = "REPRO_AUTOTUNE_CACHE"


def default_cache() -> TuningCache:
    """The process-wide cache every dispatch decision consults.

    Created empty on first use — unless ``REPRO_AUTOTUNE_CACHE`` names a
    readable ``TuningCache.save`` file, which is loaded instead.
    """
    global _DEFAULT_CACHE
    with _DEFAULT_MUTEX:
        if _DEFAULT_CACHE is None:
            path = os.environ.get(CACHE_ENV_VAR, "")
            if path and os.path.exists(path):
                _DEFAULT_CACHE = TuningCache.load(path)
            else:
                _DEFAULT_CACHE = TuningCache()
        return _DEFAULT_CACHE


def set_default_cache(cache: Optional[TuningCache]) -> Optional[TuningCache]:
    """Replace the process-default cache; returns the previous one.

    ``None`` resets to lazy re-initialization (re-reading the env var).
    """
    global _DEFAULT_CACHE
    with _DEFAULT_MUTEX:
        prev, _DEFAULT_CACHE = _DEFAULT_CACHE, cache
    return prev


@contextlib.contextmanager
def use_cache(cache: TuningCache):
    """Scope ``cache`` as the process default (tests seed decisions so)."""
    prev = set_default_cache(cache)
    try:
        yield cache
    finally:
        set_default_cache(prev)


# ---------------------------------------------------------------------------
# measurement (median-of-k, warmup excluded)
# ---------------------------------------------------------------------------

def measure(fn: Callable[[], object], *, warmup: int = 1,
            iters: int = 5) -> float:
    """Median wall-clock seconds of ``fn()`` over ``iters`` timed runs.

    ``warmup`` untimed calls run first, so jit compilation never counts;
    results with a ``block_until_ready`` method are synchronized inside
    the timed region (async dispatch would otherwise hide the work).
    """
    import statistics

    def _run():
        out = fn()
        block = getattr(out, "block_until_ready", None)
        if block is not None:
            block()
        return out

    for _ in range(max(warmup, 0)):
        _run()
    samples = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        _run()
        samples.append(time.perf_counter() - t0)
    return float(statistics.median(samples))


def _synthetic_spd(B: int, d: int, dtype: str, seed: int = 0):
    """A well-conditioned random SPD batch (B, d, d) + rhs (B, d)."""
    import numpy as np
    rng = np.random.RandomState(seed)
    C = rng.randn(B, d, d) / np.sqrt(d)
    A = np.einsum("bji,bjk->bik", C, C) + 0.5 * np.eye(d)
    b = rng.randn(B, d)
    # cast LAST: NumPy-2 scalar promotion would float64 the intermediate
    return A.astype(dtype), b.astype(dtype)


def measure_solver(solver: str, B: int, d: int, *, dtype: str = "float32",
                   mesh_size: int = 1, precond=None,
                   cache: Optional[TuningCache] = None, tol: float = 1e-6,
                   maxiter: int = 200, warmup: int = 1, iters: int = 5,
                   seed: int = 0) -> TuningRecord:
    """Micro-benchmark one registry solver on a synthetic SPD regime and
    record the median into the cache.

    ``sharded_*`` solvers run on a fresh 1-D mesh of ``mesh_size`` local
    devices with the batch axis sharded (the production hypergradient
    layout); everything else runs single-device on a ``DenseOperator``.
    The timed call is jitted, so the median captures steady-state
    execution (shard_map dispatch overhead included — the quantity the
    mesh cost model exists to observe) while compilation lands in the
    warmup.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import linear_solve as ls
    from repro.core import operators as ops

    cache = cache if cache is not None else default_cache()
    A_np, b_np = _synthetic_spd(B, d, dtype, seed)
    A = jnp.asarray(A_np)
    b = jnp.asarray(b_np)
    base = ops.DenseOperator(A, positive_definite=True)
    if solver.startswith("sharded_"):
        from repro.distributed.sharded_operators import ShardedOperator
        from repro.launch.mesh import make_solve_mesh
        mesh = make_solve_mesh(devices=int(mesh_size))
        op = ShardedOperator(base, mesh, P("data", None))
    else:
        if mesh_size != 1:
            raise ValueError(f"single-device solver {solver!r} cannot be "
                             f"measured at mesh_size={mesh_size}")
        op = base

    fn = jax.jit(lambda rhs: ls.solve(op, rhs, method=solver, tol=tol,
                                      maxiter=maxiter))
    seconds = measure(lambda: fn(b), warmup=warmup, iters=iters)
    key = TuningKey(current_backend(), solver, int(B), int(d), dtype,
                    int(mesh_size), normalize_precond(precond))
    return cache.put(key, seconds, source="measured", samples=iters)


def block_b_candidates(B: int, d: int, dtype: str = "float32") -> List[int]:
    """The tile heights the compiled kernel accepts at ``(B, d, dtype)``
    (``kernel.tile_heights``) — the sweep grid."""
    from repro.kernels.batched_cg.kernel import tile_heights
    return tile_heights(int(B), int(d), _dtype_bytes(dtype)) or [int(B)]


def measure_block_schedule(B: int, d: int, *, dtype: str = "float32",
                           candidates: Optional[Iterable[int]] = None,
                           interpret: bool = True,
                           cache: Optional[TuningCache] = None,
                           tol: float = 1e-6, warmup: int = 1,
                           iters: int = 3, seed: int = 0) \
        -> Dict[int, TuningRecord]:
    """Sweep the Pallas batched-CG ``(block_b, lanes-padded d')`` schedule
    at one ``(B, d)`` point and record each candidate.

    Entries are keyed ``solver="batched_cg"``, ``variant="block_b=<k>"``.
    On non-TPU backends the sweep runs the kernel in interpret mode
    (``interpret=True``), where ``block_b`` controls the emulated grid's
    program count — the same schedule trade-off the compiled kernel has,
    observable without hardware; on TPU pass ``interpret=False`` to time
    the real kernel.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.batched_cg.ops import batched_cg

    cache = cache if cache is not None else default_cache()
    A_np, b_np = _synthetic_spd(B, d, dtype, seed)
    A = jnp.asarray(A_np)
    b = jnp.asarray(b_np)
    out: Dict[int, TuningRecord] = {}
    for bb in (candidates if candidates is not None
               else block_b_candidates(B, d, dtype)):
        fn = jax.jit(lambda rhs, bb=bb: batched_cg(
            A, rhs, tol=tol, block_b=bb, interpret=interpret))
        seconds = measure(lambda: fn(b), warmup=warmup, iters=iters)
        key = TuningKey(current_backend(), "batched_cg", int(B), int(d),
                        dtype, 1, "", f"block_b={int(bb)}")
        out[int(bb)] = cache.put(key, seconds, source="measured",
                                 samples=iters)
    return out


# ---------------------------------------------------------------------------
# prediction (measured first, roofline fallback)
# ---------------------------------------------------------------------------

def _dtype_bytes(dtype: str) -> int:
    import numpy as np
    return int(np.dtype(dtype).itemsize)


def roofline_solve_seconds(B: int, d: int, *, dtype: str = "float32",
                           mesh_size: int = 1,
                           instance_sharded: bool = False) -> float:
    """The cold-cache estimate: ``roofline.analyze_solve`` step time."""
    from repro.analysis import roofline
    terms = roofline.analyze_solve(int(B), int(d),
                                   dtype_bytes=_dtype_bytes(dtype),
                                   mesh_size=int(mesh_size),
                                   instance_sharded=bool(instance_sharded))
    return terms.step_time_s


def predict_solve_seconds(solver: str, B: int, d: int, *,
                          dtype: str = "float32", mesh_size: int = 1,
                          precond=None, instance_sharded: bool = False,
                          cache: Optional[TuningCache] = None,
                          backend: Optional[str] = None) \
        -> Tuple[float, str]:
    """Predicted seconds for one solve and the prediction's source.

    Returns ``(seconds, "measured")`` when the cache holds a measurement
    for this exact regime, else ``(seconds, "roofline")`` from the
    hardware model.  Callers comparing candidates must compare like
    sources only — see ``should_shard``.
    """
    cache = cache if cache is not None else default_cache()
    key = TuningKey(backend or current_backend(), solver, int(B), int(d),
                    dtype, int(mesh_size), normalize_precond(precond))
    rec = cache.get(key)
    counter = obs_metrics.global_registry().counter
    if rec is not None and rec.source == "measured":
        counter("repro_autotune_predictions_total",
                help="cost predictions by source", source="measured").inc()
        return rec.seconds, "measured"
    counter("repro_autotune_predictions_total",
            help="cost predictions by source", source="roofline").inc()
    return roofline_solve_seconds(
        B, d, dtype=dtype, mesh_size=mesh_size,
        instance_sharded=instance_sharded), "roofline"


# ---------------------------------------------------------------------------
# decisions
# ---------------------------------------------------------------------------

def single_device_solver(spd: bool, d: int, plain: bool = True,
                         dtype: str = "float32") -> str:
    """The single-device registry solver a regime would route to — the
    comparison point for every sharding decision (the dense / matrix-free
    split of ``linear_solve._resolve_auto``, which calls this)."""
    from repro.core import linear_solve as ls
    if d <= ls.MAX_DENSE_DIM:
        kernel = spd and plain and _dtype_bytes(dtype) <= 4
        return "pallas_cg" if kernel else "dense_gmres"
    return "cg" if spd else "normal_cg"


def should_shard(B: int, d: int, *, mesh_size: int,
                 instance_sharded: bool = False, spd: bool = True,
                 dtype: str = "float32", precond=None, plain: bool = True,
                 cache: Optional[TuningCache] = None,
                 backend: Optional[str] = None) -> bool:
    """True when the cost model predicts the sharded solver wins (within
    5% slack) over the single-device path at this operand's mesh size.

    ``mesh_size <= 1`` always shards (a 1-device mesh is the
    single-device path under shard_map, and refusing it would make local
    runs diverge from their own placement declarations).  Otherwise the
    sharded candidate (``sharded_cg`` for SPD, ``sharded_normal_cg``
    else) is compared against ``single_device_solver``'s pick —
    measured-vs-measured when the cache holds BOTH sides, otherwise
    roofline-vs-roofline.  A cold cache therefore keeps structural
    behavior (the hardware model has batch sharding dividing per-chip
    work with zero communication) until measurements prove a regime
    loses — which is how the B=64/d=16 mesh=8 oversharding gets refused.
    """
    counter = obs_metrics.global_registry().counter

    def _decide(shard: bool, basis: str) -> bool:
        counter("repro_autotune_shard_decisions_total",
                help="sharding decisions by outcome and evidence basis",
                decision="shard" if shard else "single",
                basis=basis).inc()
        return shard

    if mesh_size <= 1:
        return _decide(True, "trivial")
    cache = cache if cache is not None else default_cache()
    backend = backend or current_backend()
    sharded = "sharded_cg" if spd else "sharded_normal_cg"
    single = single_device_solver(spd, d, plain, dtype)
    pc = normalize_precond(precond)
    rec_sh = cache.get(TuningKey(backend, sharded, int(B), int(d), dtype,
                                 int(mesh_size), pc))
    rec_si = cache.get(TuningKey(backend, single, int(B), int(d), dtype,
                                 1, pc))
    if rec_sh is not None and rec_si is not None:
        t_sh, t_si = rec_sh.seconds, rec_si.seconds
        basis = "measured"
    else:
        t_sh = roofline_solve_seconds(B, d, dtype=dtype,
                                      mesh_size=mesh_size,
                                      instance_sharded=instance_sharded)
        t_si = roofline_solve_seconds(B, d, dtype=dtype, mesh_size=1)
        basis = "roofline"
    return _decide(t_sh <= t_si * _SHARD_ACCEPT_SLACK, basis)


def mesh_candidates(B: int, max_devices: Optional[int] = None) -> List[int]:
    """Power-of-two mesh extents that divide ``B`` and fit the device
    count (1 is always a candidate)."""
    import jax
    cap = len(jax.devices()) if max_devices is None else int(max_devices)
    out = [m for m in (1, 2, 4, 8, 16, 32, 64, 128)
           if m <= cap and m <= B and B % m == 0]
    return out or [1]


def auto_mesh_size(B: int, d: int, *, max_devices: Optional[int] = None,
                   spd: bool = True, dtype: str = "float32",
                   instance_sharded: bool = False, precond=None,
                   cache: Optional[TuningCache] = None,
                   backend: Optional[str] = None) -> int:
    """The mesh extent the cost model picks for a (B, d) solve regime.

    Candidates are power-of-two extents dividing ``B`` up to the local
    device count (or ``max_devices``).  When ANY candidate has a
    measured cache entry the argmin runs over measured candidates only
    (a measurement always outranks a model); a fully cold cache falls
    back to the roofline argmin, which for batch sharding selects the
    largest extent — exactly the old all-devices behavior until
    measurements exist.  Ties break toward the smaller mesh.
    """
    cache = cache if cache is not None else default_cache()
    backend = backend or current_backend()
    solver = "sharded_cg" if spd else "sharded_normal_cg"
    pc = normalize_precond(precond)
    measured: Dict[int, float] = {}
    modeled: Dict[int, float] = {}
    for m in mesh_candidates(B, max_devices):
        rec = cache.get(TuningKey(backend, solver, int(B), int(d), dtype,
                                  int(m), pc))
        if rec is not None and rec.source == "measured":
            measured[m] = rec.seconds
        modeled[m] = roofline_solve_seconds(
            B, d, dtype=dtype, mesh_size=m,
            instance_sharded=instance_sharded)
    pool = measured if measured else modeled
    return min(sorted(pool), key=lambda m: (pool[m], m))


def default_block_b(B: int, d: int, *, dtype: str = "float32",
                    pad_lanes: bool = False) -> int:
    """The untuned tile height: the kernel's tile rule
    (``kernel.block_rows``) at its default height 8 over the (lane-padded)
    ``d'`` the kernel will see."""
    from repro.kernels.batched_cg.kernel import LANES, block_rows
    dp = -(-d // LANES) * LANES if pad_lanes else d
    return block_rows(int(B), int(dp), _dtype_bytes(dtype))[0]


def choose_block_b(B: int, d: int, *, dtype: str = "float32",
                   pad_lanes: bool = False,
                   cache: Optional[TuningCache] = None,
                   backend: Optional[str] = None) -> int:
    """The tuned Pallas batched-CG tile height for ``block_b="auto"``.

    Picks the fastest measured ``variant="block_b=<k>"`` entry for this
    ``(backend, B, d, dtype)`` regime (populated by
    ``measure_block_schedule`` / the offline sweep) among the heights
    the kernel accepts; with no measurements, falls back to
    ``default_block_b``.
    """
    cache = cache if cache is not None else default_cache()
    backend = backend or current_backend()
    measured: Dict[int, float] = {}
    for bb in block_b_candidates(B, d, dtype):
        rec = cache.get(TuningKey(backend, "batched_cg", int(B), int(d),
                                  dtype, 1, "", f"block_b={bb}"))
        if rec is not None and rec.source == "measured":
            measured[bb] = rec.seconds
    if measured:
        return min(sorted(measured), key=lambda bb: (measured[bb], bb))
    return default_block_b(B, d, dtype=dtype, pad_lanes=pad_lanes)


def operator_regime(A) -> Tuple[int, int, str]:
    """(B, d, dtype) of a ``LinearOperator``'s example — the dispatch
    regime key.  Batch-aware operators (``batch_ndim == 1``) read B off
    the leading axis; unbatched operators are B=1 with d the full raveled
    size."""
    import jax
    leaves = jax.tree_util.tree_leaves(A.example)
    if not leaves:
        return 1, 1, "float32"
    dtype = str(leaves[0].dtype)
    n = int(sum(leaf.size for leaf in leaves))
    if getattr(A, "batch_ndim", 0) == 1:
        Bn = int(leaves[0].shape[0])
        return Bn, max(n // max(Bn, 1), 1), dtype
    return 1, n, dtype
