"""Continuous-batching implicit-diff solve service with a warm-start cache.

The batched masked-solve engine (``repro.core.linear_solve``) is 20–80x
faster than looped solves — but only if a single caller hands it a
pre-batched problem.  This module is the missing front end for serving that
capability to *independent* concurrent callers: requests for linear solves
and implicit hypergradients are aggregated into **shape buckets** and each
bucket is dispatched as ONE batched masked solve through the
``route_solve`` + ``LinearOperator`` path.

Design (mirrors the ``ContinuousBatchingEngine`` slot discipline in
``repro.runtime.serving``, and the bucket-by-size batching idiom of
tensor2tensor's ``data_reader``):

  * **Bucketing** — requests are keyed by
    ``(d, solver, precond, symmetric/PD flags, dtype, tol, maxiter, ridge)``
    (``BucketKey``); everything in one bucket is mathematically one batched
    block-diagonal system, so one masked ``lax.while_loop`` serves all of it
    with per-instance convergence.
  * **Fixed compiled shapes** — buckets are padded to power-of-two
    capacities (``bucket_capacity``) with identity systems and zero
    right-hand sides; padded slots converge at loop entry, so their cost is
    ~zero and the compiled batch shape never changes during serving (no
    recompilation under traffic — the property that matters on TPU).  The
    set of compiled ``(key, capacity)`` programs is tracked in
    ``metrics["compiled"]``.
  * **Warm-start cache** — a ``WarmStartCache`` keyed by a problem
    fingerprint (operator sketch + rhs sketch, quantized so repeat/nearby
    problems collide on purpose) with LRU eviction and hit-rate counters.
    A hit seeds the request's slot with the cached solution (``init``), so
    repeat traffic — the common case under load — starts near the answer.
  * **Per-request diagnostics** — every request resolves to a
    ``ServiceResult`` carrying the solution, its own ``SolveInfo`` slice
    (exact per-instance iteration counts: masked batching preserves each
    instance's solo trajectory), queue/dispatch latency, bucket occupancy
    and cache provenance.

Hypergradient requests (``submit_hypergrad``) batch the *linear-solve* step
of implicit differentiation — the dominant, amortizable cost (cf.
"Efficient Automatic Differentiation of Implicit Functions"): the implicit
system ``Aᵀ u = v`` (``A = -∂₁F`` at ``x*``) joins a bucket like any other
solve, and the cheap per-request θ-VJP ``θ̄ = Bᵀu`` runs at completion.

Quickstart::

    from repro.runtime import SolveService

    svc = SolveService()                      # warm-start cache on
    futs = [svc.submit(A_i, b_i) for i in range(64)]   # e.g. (d, d) SPD
    svc.flush()                               # ONE batched masked solve
    results = [f.result() for f in futs]      # ServiceResult each
    results[0].info.iterations, svc.metrics["cache_hits"]

``docs/serving.md`` is the full reference (request lifecycle, bucketing
rules, warm-start semantics, metrics glossary).
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import itertools
import json
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.core import linear_solve as ls
from repro.core import operators as ops
from repro.core.linear_solve import MAX_DENSE_DIM, SolveInfo
from repro.observability import events as obs_events
from repro.observability import spans as obs_spans
from repro.observability.metrics import LATENCY_BUCKETS, MetricsRegistry

# "argument not given" marker, distinct from None: an explicit ``None`` is a
# real override (e.g. ``precond=None`` clears a spec's preconditioner).
_UNSET = object()


class BucketKey(NamedTuple):
    """The bucket identity: requests sharing a key batch into one solve.

    Every field participates in compiled-program identity — two requests
    with the same key run through the SAME jitted dispatch function at some
    fixed capacity, so serving steady traffic never recompiles.
    """
    d: int                       # instance dimension (raveled)
    solver: str                  # resolved registry solver name
    precond: Optional[str]       # None | "jacobi" | "block_jacobi"
    symmetric: Optional[bool]    # operator's declared symmetry flag
    positive_definite: bool      # operator's declared PD flag
    dtype: str                   # promoted result dtype of (A, b)
    tol: float                   # solve controls are part of the program
    maxiter: int
    ridge: float
    # approximate-backward arm: exact and approximate hypergradient traffic
    # never share a compiled program ("exact" | "one_step" | "neumann_k" |
    # "jacobian_free"; backward_iters is the neumann_k depth, 0 otherwise)
    backward: str = "exact"
    backward_iters: int = 0


def _bucket_label(key: BucketKey) -> str:
    """Compact, stable bucket tag for spans/events (trace breakdowns)."""
    label = f"{key.solver}:d={key.d}:{key.dtype}"
    if key.backward != "exact":
        label += f":{key.backward}"
    return label


def bucket_capacity(n: int, max_batch: int = 64) -> int:
    """Pad a bucket of ``n`` requests to its fixed compiled capacity.

    Power-of-two capacities clamped to ``max_batch`` — a handful of
    compiled programs per ``BucketKey`` covers every load level, and a
    given traffic mix reuses the same programs forever (no recompilation
    during serving).
    """
    if n < 1:
        raise ValueError(f"bucket needs at least one request, got n={n}")
    cap = 1
    while cap < n:
        cap *= 2
    return min(cap, max_batch)


@dataclasses.dataclass
class ServiceResult:
    """What a request's ``Future`` resolves to.

    ``x`` is the request's payload — the solution for a solve request (host
    numpy for a flat ``(d,)`` rhs, the unraveled pytree otherwise), the
    per-θ-argument gradient tuple for a hypergradient request.
    ``info`` is this request's own ``SolveInfo`` slice out of the batched
    dispatch (masked batching preserves each instance's solo iteration
    count).  ``queue_time``/``solve_time`` are seconds spent waiting for a
    flush / inside the batched dispatch; ``bucket_size``/``bucket_capacity``
    expose the occupancy of the dispatch that served this request;
    ``warm_start`` says whether a cached solution seeded the slot.
    """
    uid: int
    x: Any
    info: SolveInfo
    queue_time: float
    solve_time: float
    bucket_size: int
    bucket_capacity: int
    warm_start: bool


@dataclasses.dataclass
class _PendingRequest:
    """Internal queue entry: one admitted, not-yet-dispatched request."""
    uid: int
    key: BucketKey
    A: np.ndarray                # (d, d) materialized operator (host)
    b: np.ndarray                # (d,) raveled right-hand side (host)
    unravel: Optional[Callable]  # flat (d,) -> pytree; None = flat rhs
    future: Future
    fingerprint: Optional[str]   # warm-start cache key (None: cache off)
    init: Optional[np.ndarray]   # cached warm-start solution, if any
    finish: Optional[Callable]   # post-solve hook (hypergrad θ-VJP)
    enqueue_t: float = 0.0
    admit_t: float = 0.0         # admission start (span tracing)


class WarmStartCache:
    """LRU cache of solved systems keyed by a quantized problem fingerprint.

    The fingerprint is a sketch — ``A @ p`` for a fixed per-``d`` probe
    vector ``p``, concatenated with ``b``, normalized and quantized to
    ``qtol`` relative resolution, then hashed.  Exact repeats always
    collide; *nearby* problems (relative perturbation ≲ ``qtol``) usually
    collide, which is the point: under heavy traffic the same and
    slightly-drifted systems recur, and a hit seeds the solver with the
    previous solution so it starts near the answer.  A spurious collision
    only costs a worse initial guess — never a wrong answer (the solver
    still iterates to ``tol``).

    ``hits`` / ``misses`` / ``evictions`` counters and ``hit_rate`` are
    read by the service metrics.  All operations are thread-safe: the
    cache is shared between submitter threads (lookups at admission) and
    the scheduler thread (inserts at dispatch).

    ``save(path)`` / ``WarmStartCache.load(path)`` persist the cache as a
    version-stamped ``.npz`` (fingerprints + solutions + the ``BucketKey``
    provenance of each entry), so warm starts survive service restarts.
    """

    _SAVE_VERSION = 1

    def __init__(self, capacity: int = 256, qtol: float = 1e-3,
                 seed: int = 1234):
        if capacity < 1:
            raise ValueError(f"cache capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.qtol = float(qtol)
        self._seed = int(seed)
        self._mutex = threading.Lock()
        self._store: "collections.OrderedDict[str, np.ndarray]" = \
            collections.OrderedDict()
        self._keys: dict = {}       # fingerprint -> BucketKey provenance
        self._probes: dict = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def _probe(self, d: int) -> np.ndarray:
        """The fixed unit probe vector for dimension ``d`` (built once)."""
        with self._mutex:
            p = self._probes.get(d)
            if p is None:
                rng = np.random.default_rng(self._seed + d)
                p = rng.standard_normal(d)
                p /= np.linalg.norm(p)
                self._probes[d] = p
            return p

    def fingerprint(self, A, b, key: BucketKey) -> str:
        """Hash a problem to its cache key.

        The sketch ``[A @ p, b]`` identifies the operator's action and the
        right-hand side without hashing all of ``A``; quantizing by
        ``qtol`` relative to the sketch norm folds nearby problems onto one
        key.  The ``BucketKey`` participates so distinct solver routings
        never share warm starts of mismatched meaning.
        """
        A = np.asarray(A, np.float64)
        b = np.asarray(b, np.float64)
        sketch = np.concatenate([A @ self._probe(A.shape[-1]), b])
        scale = float(np.linalg.norm(sketch))
        if not np.isfinite(scale) or scale == 0.0:
            scale = 1.0
        q = np.round(sketch / (scale * self.qtol)).astype(np.int64)
        h = hashlib.blake2b(digest_size=16)
        h.update(repr(key).encode())
        h.update(q.tobytes())
        return h.hexdigest()

    def get(self, fingerprint: str) -> Optional[np.ndarray]:
        """Look up a warm start; counts a hit or a miss and refreshes LRU."""
        with self._mutex:
            x = self._store.get(fingerprint)
            if x is None:
                self.misses += 1
                return None
            self.hits += 1
            self._store.move_to_end(fingerprint)
            return x

    def put(self, fingerprint: str, x, key: Optional[BucketKey] = None) -> \
            None:
        """Insert/refresh a solution; evicts the LRU entry over capacity.

        ``key`` records the entry's ``BucketKey`` provenance — carried
        through ``save``/``load`` so a restored cache knows what routing
        produced each solution.
        """
        with self._mutex:
            self._store[fingerprint] = np.asarray(x)
            self._store.move_to_end(fingerprint)
            if key is not None:
                self._keys[fingerprint] = key
            while len(self._store) > self.capacity:
                evicted, _ = self._store.popitem(last=False)
                self._keys.pop(evicted, None)
                self.evictions += 1

    def __len__(self) -> int:
        """Number of cached solutions currently resident."""
        with self._mutex:
            return len(self._store)

    @property
    def hit_rate(self) -> float:
        """Fraction of lookups served from cache (0.0 when none yet)."""
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def save(self, path) -> str:
        """Persist the cache contents to ``path`` as version-stamped ``.npz``.

        Layout: ``format_version``/``qtol``/``seed`` scalars, a
        ``fingerprints`` string array, one ``solution_{i}`` array per entry
        (solutions may differ in ``d``), and a ``bucket_keys`` string array
        of JSON-encoded ``BucketKey`` provenance ("" when unknown).
        Returns the path written (numpy may append ``.npz``).
        """
        path = str(path)
        if not path.endswith(".npz"):
            path += ".npz"
        with self._mutex:
            items = list(self._store.items())
            keys = dict(self._keys)
        payload = {
            "format_version": np.asarray(self._SAVE_VERSION),
            "qtol": np.asarray(self.qtol),
            "seed": np.asarray(self._seed),
            "capacity": np.asarray(self.capacity),
            "fingerprints": np.asarray([fp for fp, _ in items]),
            "bucket_keys": np.asarray(
                [json.dumps(keys[fp]._asdict()) if fp in keys else ""
                 for fp, _ in items]),
        }
        for i, (_, x) in enumerate(items):
            payload[f"solution_{i}"] = np.asarray(x)
        np.savez(path, **payload)
        return path

    @classmethod
    def load(cls, path) -> "WarmStartCache":
        """Restore a cache written by ``save``; rejects unknown versions.

        The restored cache keeps the saved ``qtol``/``seed``/``capacity``
        (fingerprints are a function of both, so lookups keep colliding
        with pre-restart traffic) and starts with fresh hit/miss counters.
        """
        with np.load(str(path), allow_pickle=False) as z:
            version = int(z["format_version"])
            if version != cls._SAVE_VERSION:
                raise ValueError(
                    f"warm-start cache file {path!r} has format version "
                    f"{version}; this build reads version "
                    f"{cls._SAVE_VERSION}")
            cache = cls(capacity=int(z["capacity"]), qtol=float(z["qtol"]),
                        seed=int(z["seed"]))
            fingerprints = [str(fp) for fp in z["fingerprints"]]
            key_blobs = [str(s) for s in z["bucket_keys"]]
            for i, fp in enumerate(fingerprints):
                cache._store[fp] = np.asarray(z[f"solution_{i}"])
                if key_blobs[i]:
                    cache._keys[fp] = BucketKey(**json.loads(key_blobs[i]))
        return cache


class SolveService:
    """Async front end that batches independent solve requests per bucket.

    ``submit`` / ``submit_hypergrad`` enqueue work and return
    ``concurrent.futures.Future`` objects; ``flush()`` drains the queue,
    groups requests by ``BucketKey``, pads each group to a fixed capacity
    and dispatches it as ONE batched masked solve via
    ``linear_solve.route_solve`` on a stacked ``DenseOperator``.  A
    background scheduler thread (``start()`` / ``stop()``) can flush
    continuously; tests and benchmarks drive ``flush()`` explicitly for
    determinism.

    Admission materializes each request's operator to its dense
    ``(d, d)`` instance form (O(1) for ``DenseOperator``/arrays, ``d``
    probing matvecs for matrix-free operators, ``d ≤ MAX_DENSE_DIM``
    enforced) — that is what makes *independent* requests stackable into
    one batch.  The linear solve is the dominant, amortizable cost;
    admission is the price of cross-request batching.

    Parameters:
      max_batch: bucket capacity ceiling (larger groups split into chunks).
      cache: a ``WarmStartCache`` (default: capacity 256) or ``None`` to
        disable warm starts.
      solve / tol / maxiter / ridge / precond: per-request defaults;
        every one can be overridden per ``submit`` call or by a
        routing-only ``ImplicitDiffSpec`` via ``spec=``.
    """

    _DEFAULT_CACHE = object()    # sentinel: build a fresh cache per service

    def __init__(self, *, max_batch: int = 64,
                 cache: Optional[WarmStartCache] = _DEFAULT_CACHE,
                 solve: Union[str, Callable] = "auto", tol: float = 1e-6,
                 maxiter: int = 1000, ridge: float = 0.0,
                 precond: Optional[str] = None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.max_batch = int(max_batch)
        self.cache = WarmStartCache() if cache is self._DEFAULT_CACHE \
            else cache
        self.defaults = dict(solve=solve, tol=float(tol),
                             maxiter=int(maxiter), ridge=float(ridge),
                             precond=precond)
        self._queue: "collections.deque[_PendingRequest]" = \
            collections.deque()
        self._compiled: dict = {}          # (BucketKey, cap) -> jitted fn
        # reentrant: the MetricsRegistry below shares this lock, so every
        # instrument update inside a service critical section — and a
        # snapshot taken against one — stays atomic without deadlocking
        self._lock = threading.RLock()
        self._uid = itertools.count()      # atomic next(): uids never collide
        self._inflight = 0                 # requests popped but not resolved
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.registry = MetricsRegistry(lock=self._lock)
        reg = self.registry
        self._m_requests = reg.counter(
            "repro_service_requests_total", help="requests admitted")
        self._m_dispatches = reg.counter(
            "repro_service_dispatches_total", help="batched dispatches run")
        self._m_instances = reg.counter(
            "repro_service_instances_total",
            help="real (non-padding) instances dispatched")
        self._m_padded = reg.counter(
            "repro_service_padded_total",
            help="padding slots dispatched alongside real instances")
        self._m_occupancy_sum = reg.gauge(
            "repro_service_occupancy_sum",
            help="sum over dispatches of real/capacity occupancy")
        self._m_solve_time = reg.histogram(
            "repro_service_solve_seconds", buckets=LATENCY_BUCKETS,
            help="wall-clock seconds per batched dispatch")
        self._m_queue_wait = reg.histogram(
            "repro_service_queue_wait_seconds", buckets=LATENCY_BUCKETS,
            help="per-request seconds between enqueue and dispatch start")
        self._m_compiled = reg.gauge(
            "repro_service_compiled_programs",
            help="distinct (BucketKey, capacity) programs compiled")
        self._m_cache_hits = reg.gauge(
            "repro_service_cache_hits", help="warm-start cache hits")
        self._m_cache_misses = reg.gauge(
            "repro_service_cache_misses", help="warm-start cache misses")
        self._m_cache_evictions = reg.gauge(
            "repro_service_cache_evictions",
            help="warm-start cache LRU evictions")

    # -- admission -----------------------------------------------------------

    def _routing(self, spec, solve, tol, maxiter, ridge, precond) -> dict:
        """Merge service defaults, a routing-only spec, and per-call kwargs.

        Precedence (lowest to highest): service defaults < ``spec``
        (an ``ImplicitDiffSpec`` — its ``solve``/``tol``/``maxiter``/
        ``ridge``/``precond`` routing fields) < explicit keyword overrides.
        Omitted keywords arrive as ``_UNSET``, so an explicit ``None`` is a
        real override — ``precond=None`` clears a spec's preconditioner
        rather than silently deferring to it.
        """
        r = dict(self.defaults)
        if spec is not None:
            r.update(solve=spec.solve, **spec.routing_kwargs())
        for name, val in (("solve", solve), ("tol", tol),
                          ("maxiter", maxiter), ("ridge", ridge),
                          ("precond", precond)):
            if val is not _UNSET:
                r[name] = val
        if callable(r["solve"]):
            raise ValueError(
                "the solve service buckets by registry solver name; custom "
                "solve callables cannot be batched across requests — call "
                "route_solve directly for those")
        if r["precond"] is not None and not isinstance(r["precond"], str):
            raise ValueError(
                "the solve service buckets by preconditioner kind; pass "
                "precond=None/'jacobi'/'block_jacobi' (a callable M⁻¹ is "
                "request-specific and cannot key a shared bucket)")
        # normalize the numeric controls now so a bad override (e.g. an
        # explicit tol=None) fails in submit(), not at dispatch
        r["tol"] = float(r["tol"])
        r["maxiter"] = int(r["maxiter"])
        r["ridge"] = float(r["ridge"])
        return r

    def _admit_operator(self, A, b, symmetric, positive_definite):
        """Materialize the request operator and ravel the rhs.

        Accepts a ``LinearOperator`` (instance-shaped, ``batch_ndim=0``), a
        dense ``(d, d)`` array, or a bare matvec callable (probed).
        Returns ``(A_host, b_flat, unravel, symmetric, pd)`` with flags
        taken from the operator when it carries them.  ``A_host`` and
        ``b_flat`` are **host numpy** arrays and — for the common case of a
        concrete matrix and a flat rhs — admission never touches JAX at
        all (``unravel is None`` marks the flat fast path).  Keeping
        admission off the device dispatch path is what lets one batched
        dispatch amortize across 64 submits instead of drowning in 64
        rounds of per-request op overhead.
        """
        if isinstance(A, ops.LinearOperator):
            if A.batch_ndim != 0:
                raise ValueError(
                    "submit() takes ONE instance per request (batch_ndim=0);"
                    " the service does the batching — split a batched "
                    "operator into per-instance requests")
            symmetric = A.symmetric if symmetric is None else symmetric
            positive_definite = A.positive_definite or bool(positive_definite)
            A_host = np.asarray(A.materialize())    # d probing matvecs
        elif callable(A) and not hasattr(A, "ndim"):
            op = ops.FunctionOperator(
                A, b, symmetric=symmetric,
                positive_definite=bool(positive_definite))
            A_host = np.asarray(op.materialize())
        else:
            A_host = np.asarray(A)
            if A_host.ndim != 2 or A_host.shape[0] != A_host.shape[1]:
                raise ValueError(
                    f"expected a (d, d) operator, got {A_host.shape}")
            if symmetric is None:       # concrete matrix: detect, don't guess
                if positive_definite:   # declared PD certifies symmetry
                    symmetric = True
                else:                   # allclose semantics, one temporary
                    tol = 1e-8 * max(float(np.abs(A_host).max()), 1.0) + 1e-10
                    symmetric = bool(
                        np.abs(A_host - A_host.T).max() <= tol)
        if isinstance(b, (np.ndarray, jax.Array)) and b.ndim == 1:
            b_flat, unravel = np.asarray(b), None   # flat fast path: no JAX
        else:
            b_jax, unravel = ravel_pytree(b)
            b_flat = np.asarray(b_jax)
        d = b_flat.shape[0]
        if d > MAX_DENSE_DIM:
            raise ValueError(
                f"the solve service batches dense instance systems; d={d} "
                f"exceeds MAX_DENSE_DIM={MAX_DENSE_DIM} — solve oversized "
                "systems directly through linear_solve.solve")
        return A_host, b_flat, unravel, symmetric, bool(positive_definite)

    def _resolve_solver(self, positive_definite: bool, precond, d: int,
                        dtype: str) -> str:
        """Resolve ``"auto"`` ONCE at admission so bucket keys are stable.

        The single-device rule ``linear_solve._resolve_auto`` applies
        (``autotune.single_device_solver``), evaluated host-side so
        admission stays off the JAX dispatch path — a test pins the two
        against each other.  With the warm-start cache enabled the
        resolution assumes an ``init`` may arrive (steering off
        ``pallas_cg``, which always starts from zero) — cold and warm
        requests for the same problem must land in the SAME bucket and
        reuse one compiled program.
        """
        from repro.analysis import autotune
        plain = precond is None and self.cache is None
        return autotune.single_device_solver(positive_definite, d, plain,
                                             dtype)

    def _enqueue(self, pending: _PendingRequest) -> Future:
        pending.enqueue_t = time.perf_counter()
        with self._lock:
            self._queue.append(pending)
            self._m_requests.inc()
        return pending.future

    def _build_request(self, A, b, symmetric, positive_definite, spec,
                       solve, tol, maxiter, ridge, precond,
                       warm_start: bool, backward: str = "exact",
                       backward_iters: int = 0) -> _PendingRequest:
        """Admission: normalize, bucket-key, warm-start lookup (no enqueue)."""
        admit_t = time.perf_counter()
        r = self._routing(spec, solve, tol, maxiter, ridge, precond)
        A_dense, b_flat, unravel, sym, pd = self._admit_operator(
            A, b, symmetric, positive_definite)
        d = int(b_flat.shape[0])
        dtype = str(jax.dtypes.canonicalize_dtype(
            np.result_type(A_dense.dtype, b_flat.dtype)))
        solver = r["solve"]
        if solver == "auto":
            solver = self._resolve_solver(pd, r["precond"], d, dtype)
        # admission-time mirror of linear_solve._check_operator_routing:
        # an unknown solver name or a symmetric-only solver paired with a
        # declared-nonsymmetric operator must fail HERE, in the caller's
        # submit(), not inside a batched dispatch where the whole bucket
        # (and, in background mode, the scheduler thread) would pay for it
        solver_spec = ls.get_spec(solver)
        if solver_spec.symmetric_only and sym is False:
            raise ValueError(
                f"requested solver {solver!r} is symmetric-only, but this "
                f"request's operator declares symmetric={sym} "
                f"(positive_definite={pd}) — route a general solver "
                "(gmres/bicgstab/normal_cg/dense_gmres) instead, or fix "
                "the declared flags if the operator really is symmetric")
        key = BucketKey(d=d, solver=solver, precond=r["precond"],
                        symmetric=sym, positive_definite=pd, dtype=dtype,
                        tol=r["tol"], maxiter=r["maxiter"], ridge=r["ridge"],
                        backward=backward, backward_iters=backward_iters)
        fingerprint = init = None
        if self.cache is not None and warm_start and backward == "exact":
            # approximate buckets skip the warm-start path entirely: the
            # polynomial apply has no init to seed, and caching its
            # truncated output would poison exact buckets' starts
            fingerprint = self.cache.fingerprint(A_dense, b_flat, key)
            init = self.cache.get(fingerprint)
            if init is not None and solver == "pallas_cg":
                init = None     # pallas_cg always starts from zero
            obs_events.emit("cache_hit" if init is not None
                            else "cache_miss", {"solver": solver, "d": d})
        return _PendingRequest(uid=next(self._uid), key=key, A=A_dense,
                               b=b_flat, unravel=unravel, future=Future(),
                               fingerprint=fingerprint, init=init,
                               finish=None, admit_t=admit_t)

    def submit(self, A, b, *, symmetric: Optional[bool] = None,
               positive_definite: bool = False, spec=None, solve=_UNSET,
               tol=_UNSET, maxiter=_UNSET, ridge=_UNSET, precond=_UNSET,
               warm_start: bool = True) -> Future:
        """Enqueue one linear solve ``A x = b``; returns a ``Future``.

        ``A`` is a ``(d, d)`` array (symmetry auto-detected when not
        declared), an instance-shaped ``LinearOperator`` (flags read off
        it), or a matvec callable; ``b`` any pytree raveling to ``d ≤ 512``.
        Routing defaults come from the service; a routing-only
        ``ImplicitDiffSpec`` (``spec=``) or explicit keywords override them
        per request (an explicit ``precond=None`` clears a spec's
        preconditioner — omitted keywords defer, ``None`` overrides).
        Bad routing — an unknown solver name, a symmetric-only solver on a
        declared-nonsymmetric operator — raises here, never at dispatch.
        The future resolves to a ``ServiceResult`` at the flush that
        dispatches this request's bucket.
        """
        return self._enqueue(self._build_request(
            A, b, symmetric, positive_definite, spec, solve, tol, maxiter,
            ridge, precond, warm_start))

    def submit_hypergrad(self, optimality_fun, x_star, theta, cotangent, *,
                         spec=None, solve=_UNSET, tol=_UNSET, maxiter=_UNSET,
                         ridge=_UNSET, precond=_UNSET, backward=_UNSET,
                         backward_iters=_UNSET,
                         warm_start: bool = True) -> Future:
        """Enqueue one implicit hypergradient: resolves to ``vᵀ ∂x*(θ)``.

        Batches the linear-solve step of ``root_vjp`` — the system
        ``Aᵀ u = v`` with ``A = -∂₁F(x*, θ)`` — into the service's shape
        buckets; the cheap per-request θ-VJP ``θ̄ = Bᵀ u`` runs when the
        bucket completes.  ``theta`` is a tuple of θ arguments (a single
        non-tuple value is accepted), ``cotangent`` has the structure of
        ``x*``.  The future's ``ServiceResult.x`` is the per-θ-argument
        gradient tuple, exactly ``root_vjp``'s return value.

        A mapping-carrying ``ImplicitDiffSpec`` may supply *both* the
        optimality mapping (pass ``optimality_fun=None``) and the routing;
        an explicit ``optimality_fun`` wins when both are given.

        ``backward`` selects an approximate cotangent treatment
        (``"one_step"``/``"neumann_k"``/``"jacobian_free"``, with
        ``backward_iters`` the Neumann depth) — resolution order matches
        the routing kwargs (service default "exact" < ``spec`` < explicit
        keyword).  Approximate requests land in their own ``BucketKey``
        arm, never sharing a compiled program (or warm starts) with exact
        traffic, and their ``ServiceResult.info`` reports the
        ``hypergrad_error_estimate`` relative residual.
        """
        if optimality_fun is None:
            if spec is None or spec.is_routing_only:
                raise ValueError("submit_hypergrad needs an optimality "
                                 "mapping: pass optimality_fun= or a spec "
                                 "carrying one")
            optimality_fun = spec.residual_fun
        if not isinstance(theta, tuple):
            theta = (theta,)
        r = self._routing(spec, solve, tol, maxiter, ridge, precond)
        bw = spec.backward if spec is not None else "exact"
        bwk = spec.backward_iters if spec is not None else 8
        if backward is not _UNSET:
            bw = backward
        if backward_iters is not _UNSET:
            bwk = backward_iters
        if bw not in ls.BACKWARD_MODES:
            raise ValueError(f"unknown backward mode {bw!r}; expected one "
                             f"of {ls.BACKWARD_MODES}")
        if bw == "neumann_k" and int(bwk) < 1:
            raise ValueError("backward='neumann_k' needs backward_iters >= "
                             f"1; got {bwk}")
        if bw != "exact" and r["precond"] == "block_jacobi":
            raise ValueError(
                "precond='block_jacobi' inverts the full flat block — that "
                "would make the 'approximate' backward an exact solve; use "
                "precond=None or 'jacobi' with approximate backward modes")
        # one_step/jacobian_free don't consume a depth: pin the key arm to 0
        # so e.g. one_step traffic with different spec defaults still shares
        # one compiled program
        bwk = int(bwk) if bw == "neumann_k" else 0
        solver = r["solve"]
        certified = solver != "auto" and ls.solver_is_symmetric(solver)
        A = ops.JacobianOperator(
            lambda x: optimality_fun(x, *theta), x_star, negate=True,
            symmetric=True if certified else None)
        # the bucketed system is Aᵀ u = v (a symmetric-certified A is its
        # own transpose); the θ-VJP below finishes the hypergradient
        AT = A if certified else A.T

        def finish(u_tree):
            _, vjp_theta = jax.vjp(
                lambda *targs: optimality_fun(x_star, *targs), *theta)
            return vjp_theta(u_tree)

        pending = self._build_request(
            AT, cotangent, A.symmetric, False, spec, solve, tol, maxiter,
            ridge, precond, warm_start, backward=bw, backward_iters=bwk)
        pending.finish = finish
        return self._enqueue(pending)

    # -- dispatch ------------------------------------------------------------

    def _dispatch_fn(self, key: BucketKey, cap: int) -> Callable:
        """The jitted batched dispatch for ``(key, cap)``, compiled once.

        Builds the stacked ``DenseOperator`` (structure flags from the
        bucket key) inside the jit and routes ONE batched masked solve
        through ``route_solve`` with ``return_info=True``.  ``pallas_cg``
        buckets never carry warm starts, so the init argument is dropped
        for them (the kernel always starts from zero).
        """
        with self._lock:
            fn = self._compiled.get((key, cap))
        if fn is not None:
            return fn
        takes_init = key.solver != "pallas_cg"

        if key.backward != "exact":
            # approximate arm: the fixed-budget polynomial apply replaces
            # the converged solve; no warm start (there is no init to
            # seed), and the error estimate is always computed — it IS the
            # approximate modes' honesty contract, at one extra matvec on
            # an already-cheap dispatch
            def dispatch(A_stack, b_stack, init_stack):
                del init_stack
                op = ops.DenseOperator(
                    A_stack, symmetric=key.symmetric,
                    positive_definite=key.positive_definite)
                return ls.approx_inverse_apply(
                    op, b_stack, backward=key.backward,
                    backward_iters=max(key.backward_iters, 1),
                    ridge=key.ridge, precond=key.precond, batch_ndim=1,
                    tol=key.tol, error_estimate=True, return_info=True)
        else:
            def dispatch(A_stack, b_stack, init_stack):
                op = ops.DenseOperator(A_stack, symmetric=key.symmetric,
                                       positive_definite=key.positive_definite)
                return ls.route_solve(
                    key.solver, op, b_stack, tol=key.tol,
                    maxiter=key.maxiter, ridge=key.ridge,
                    precond=key.precond,
                    init=init_stack if takes_init else None,
                    return_info=True)

        fn = jax.jit(dispatch)
        with self._lock:
            # concurrent flushers may race to build the same program; keep
            # the first so compiled-program identity stays stable
            fn = self._compiled.setdefault((key, cap), fn)
            self._m_compiled.set(len(self._compiled))
        return fn

    def _dispatch_bucket(self, key: BucketKey, reqs) -> None:
        """Pad one bucket to capacity and run its single batched solve."""
        n = len(reqs)
        cap = bucket_capacity(n, self.max_batch)
        d = key.d
        dtype = np.dtype(key.dtype)
        label = _bucket_label(key)
        obs_events.emit("dispatch", {"bucket": label, "solver": key.solver},
                        n=n, capacity=cap)
        stage_t = time.perf_counter()
        # host-side staging: padded slots get identity systems with zero
        # rhs/init (they converge at while_loop entry); the jitted dispatch
        # transfers each stacked buffer to device ONCE per flush
        A_stack = np.empty((cap, d, d), dtype)
        b_stack = np.zeros((cap, d), dtype)
        init_stack = np.zeros((cap, d), dtype)
        A_stack[n:] = np.eye(d, dtype=dtype)
        for i, r in enumerate(reqs):
            A_stack[i] = r.A
            b_stack[i] = r.b
            if r.init is not None:
                init_stack[i] = r.init

        fn = self._dispatch_fn(key, cap)
        t0 = time.perf_counter()
        x, info = fn(A_stack, b_stack, init_stack)
        x = jax.block_until_ready(x)
        t1 = time.perf_counter()
        solve_t = t1 - t0

        with self._lock:
            self._m_dispatches.inc()
            self._m_instances.inc(n)
            self._m_padded.inc(cap - n)
            self._m_occupancy_sum.inc(n / cap)
            self._m_solve_time.observe(solve_t)

        x_host = np.asarray(x)
        it = np.asarray(info.iterations).tolist()
        rn = np.asarray(info.residual).tolist()
        cv = np.asarray(info.converged).tolist()
        est = info.hypergrad_error_estimate
        est = [None] * cap if est is None else np.asarray(est).tolist()
        if not isinstance(it, list):        # scalar (unbatched) diagnostics
            it, rn, cv = [it] * cap, [rn] * cap, [cv] * cap
            est = est if isinstance(est, list) else [est] * cap
        tracer = obs_spans.current_tracer()
        for i, req in enumerate(reqs):
            xi = x_host[i]
            if req.fingerprint is not None and self.cache is not None:
                self.cache.put(req.fingerprint, xi, key=req.key)
            queue_t = max(t0 - req.enqueue_t, 0.0)
            deliver_t = time.perf_counter()
            try:
                payload = xi if req.unravel is None \
                    else req.unravel(jnp.asarray(xi))
                if req.finish is not None:
                    payload = req.finish(payload)
                req.future.set_result(ServiceResult(
                    uid=req.uid, x=payload,
                    info=SolveInfo(iterations=it[i], residual=rn[i],
                                   converged=cv[i],
                                   hypergrad_error_estimate=est[i]),
                    queue_time=queue_t, solve_time=solve_t,
                    bucket_size=n, bucket_capacity=cap,
                    warm_start=req.init is not None))
            except Exception as exc:
                req.future.set_exception(exc)
            if tracer is not None:
                # the request lifecycle crosses threads (submitter admits
                # and enqueues; this — possibly the scheduler — thread
                # dispatches and delivers), so the segments are recorded
                # from measured timestamps under an explicit parent id
                end = time.perf_counter()
                root = tracer.record_span(
                    "request", req.admit_t, end, uid=req.uid, bucket=label,
                    warm_start=req.init is not None, iterations=it[i])
                tracer.record_span("admission", req.admit_t, req.enqueue_t,
                                   parent=root)
                tracer.record_span("queue", req.enqueue_t, t0, parent=root)
                tracer.record_span("solve", t0, t1, parent=root,
                                   bucket=label)
                tracer.record_span("delivery", deliver_t, end, parent=root)
        if tracer is not None:
            tracer.record_span("dispatch", stage_t, time.perf_counter(),
                               bucket=label, n=n, capacity=cap)
        with self._lock:
            self._m_queue_wait.observe_many(
                max(t0 - req.enqueue_t, 0.0) for req in reqs)
            if self.cache is not None:
                self._m_cache_hits.set(self.cache.hits)
                self._m_cache_misses.set(self.cache.misses)
                self._m_cache_evictions.set(self.cache.evictions)

    def flush(self) -> int:
        """Drain the queue: dispatch every bucket once; returns #requests.

        An empty queue is a no-op (returns 0) — flushing never pays a
        dispatch for nothing.  Buckets larger than ``max_batch`` split
        into successive full chunks (slot reuse: same compiled program).

        Dispatch failures are **fault-isolated per bucket chunk**: an
        exception inside one batched dispatch is delivered to that chunk's
        futures (``future.result()`` re-raises it) and every other bucket
        still dispatches — a poisoned bucket can neither strand its own
        callers nor kill the background scheduler thread.
        """
        with self._lock:
            pending = list(self._queue)
            self._queue.clear()
            if not pending:
                return 0
            self._inflight += len(pending)
        try:
            buckets: "collections.OrderedDict[BucketKey, list]" = \
                collections.OrderedDict()
            for req in pending:
                buckets.setdefault(req.key, []).append(req)
            for key, reqs in buckets.items():
                for lo in range(0, len(reqs), self.max_batch):
                    chunk = reqs[lo:lo + self.max_batch]
                    try:
                        self._dispatch_bucket(key, chunk)
                    except Exception as exc:
                        for req in chunk:
                            if not req.future.done():
                                req.future.set_exception(exc)
        finally:
            with self._lock:
                self._inflight -= len(pending)
        return len(pending)

    def drain(self, timeout: float = 30.0) -> None:
        """Block until every admitted request has been *resolved*.

        Waits for the queue to empty AND for in-flight dispatches to
        complete — the background thread pops the queue before dispatching,
        so queue emptiness alone would not mean the futures are done.
        After ``drain()`` returns, every future submitted before the call
        carries a result or an exception.
        """
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            with self._lock:
                if not self._queue and self._inflight == 0:
                    return
            time.sleep(0.001)
        raise TimeoutError("solve service did not drain in time")

    # -- background scheduler ------------------------------------------------

    def start(self, interval: float = 0.001) -> None:
        """Start a scheduler thread flushing every ``interval`` seconds."""
        if self._thread is not None:
            raise RuntimeError("service already started")
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                self.flush()
                time.sleep(interval)
            self.flush()                    # final drain

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        """Stop the scheduler thread (flushes once more on the way out)."""
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=10.0)
        self._thread = None

    # -- metrics -------------------------------------------------------------

    @property
    def metrics(self) -> dict:
        """Frozen scheduler-counter snapshot (the legacy flat-dict shape).

        Built atomically under the service lock from the
        :class:`MetricsRegistry` instruments, so a read never observes a
        torn multi-counter update mid-dispatch.  The returned dict is a
        copy — mutating it does not touch the service.
        """
        with self._lock:
            return {
                "requests": int(self._m_requests.value),
                "dispatches": int(self._m_dispatches.value),
                "instances": int(self._m_instances.value),
                "padded": int(self._m_padded.value),
                "occupancy_sum": self._m_occupancy_sum.value,
                "queue_wait_sum": self._m_queue_wait.sum,
                "solve_time_sum": self._m_solve_time.sum,
                "compiled": int(self._m_compiled.value),
                "cache_hits": int(self._m_cache_hits.value),
                "cache_misses": int(self._m_cache_misses.value),
                "cache_evictions": int(self._m_cache_evictions.value),
            }

    @property
    def occupancy(self) -> float:
        """Mean bucket occupancy (real requests / padded capacity)."""
        with self._lock:
            n = self._m_dispatches.value
            return self._m_occupancy_sum.value / n if n else 0.0

    @property
    def hit_rate(self) -> float:
        """Warm-start cache hit rate (0.0 with the cache disabled)."""
        return self.cache.hit_rate if self.cache is not None else 0.0

    @property
    def throughput(self) -> float:
        """Requests served per second of batched solve time."""
        with self._lock:
            t = self._m_solve_time.sum
            return self._m_instances.value / t if t > 0 else 0.0

    def metrics_summary(self) -> dict:
        """One flat dict of scheduler metrics (CLI / benchmark reporting).

        Atomic under the service lock: the counter snapshot and the
        derived rates come from ONE critical section, so concurrent
        dispatches can never skew e.g. ``throughput`` against
        ``instances``.
        """
        with self._lock:
            return dict(self.metrics, occupancy=self.occupancy,
                        hit_rate=self.hit_rate, throughput=self.throughput,
                        cache_size=len(self.cache) if self.cache else 0)

    def metrics_snapshot(self) -> dict:
        """Full structured registry snapshot (names/labels/histograms).

        The JSON-ready form of every service instrument — see
        ``MetricsRegistry.snapshot``; taken atomically under the service
        lock.  ``to_prometheus()`` on :attr:`registry` renders the same
        data in Prometheus text exposition format.
        """
        return self.registry.snapshot()
