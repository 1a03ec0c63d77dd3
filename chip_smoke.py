"""Run the implicit-diff solve path once on a TPU, through its entry points.

    python chip_smoke.py [--seed 0]        # one chip: phases A1, A2, B, C
    python chip_smoke.py --chips 4         # four chips: the sharded phase only

All phases run float32 data generated from ``--seed``, at sizes a
hyperparameter sweep, a data-reweighting job and a solve service hold on
one chip:

  A1  jax.vmap(jax.grad(...)) over a custom_root ridge solver with
      solve="auto" (the README "Batched implicit differentiation"
      example): B=64 datasets of (2048, 512).
  A2  ls.solve(DenseOperator(A, positive_definite=True), b, method="auto")
      (the README "Architecture" example) at (B, d) = (64, 384), (64, 512),
      (12, 128), (64, 448) and (50, 512), at the library's default tol:
      the natively batched pallas_cg regime, including a d off the
      128-lane tile (a solve-service bucket) and a batch padded to a
      multiple of 8.
  B   a matrix-free bilevel job: per-example reweighting of a 10-class
      logistic regression on 60,000 x 784 MNIST-shaped data; LBFGS inner
      solver, backward solve="cg" over the JacobianOperator (d = 7,840),
      3 outer steps of bilevel.solve_bilevel.
  C   the solve service as ``python -m repro.launch.serve --solve-service``
      drives it (``drive_service``, telemetry on): 256 SPD requests, half
      at d=256 and half at d=448, then a warm wave replaying them.
  --chips 4: the batch-sharded hypergradient at B=256 x d=128 through
      SolveSharding on make_solve_mesh(4) (sharded_cg), against the same
      hypergradient on one device.

Every phase prints its route (and whether the compiled program holds the
Pallas kernel, ``tpu_custom_call``), its error against a float32
``jnp.linalg.solve`` reference under ``default_matmul_precision("highest")``,
its converged share, and compile and steady times (steady ends in
``block_until_ready``).  A failed check raises and the script exits
non-zero.  With no TPU it exits non-zero before any phase; it never falls
back to the CPU, interpret mode or the reference kernel.  On success the
last line is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}``.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import observability as obs  # noqa: E402
from repro.analysis import autotune  # noqa: E402
from repro.core import (LBFGS, DenseOperator, ImplicitDiffSpec,  # noqa: E402
                        bilevel, custom_root, implicit_diff)
from repro.core import linear_solve as ls  # noqa: E402
from repro.distributed import SolveSharding  # noqa: E402
from repro.kernels.batched_cg.kernel import block_rows  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_solve_mesh  # noqa: E402
from repro.launch.serve import drive_service  # noqa: E402

# Checks.  The dense systems below have condition numbers of ~10, so an
# f32 solve to the default tol=1e-6 lands within ~1e-5 of the reference.
# A1 and the sharded phase differentiate through the user's own matmuls,
# which the TPU runs at DEFAULT precision (one bf16 pass): their
# hypergradients carry ~1e-3 relative error by construction.
A2_MAX_REL_ERR = 1e-4
A1_MAX_REL_ERR = 2e-2
SERVICE_MAX_REL_ERR = 1e-4
BILEVEL_MIN_COSINE = 0.99
SHARDED_MAX_REL_DIFF = 1e-3


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _rel_err(x, ref):
    """Largest per-instance relative error ``|x - ref| / |ref|``."""
    x = np.asarray(x, np.float64).reshape(len(x), -1)
    ref = np.asarray(ref, np.float64).reshape(len(ref), -1)
    return float(np.max(np.linalg.norm(x - ref, axis=1)
                        / np.linalg.norm(ref, axis=1)))


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")


def _compile(fn, *args):
    """AOT-compile ``jax.jit(fn)``; returns (executable, seconds)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _steady(compiled, *args):
    """One warm call, then one timed call ending in block_until_ready."""
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _routes() -> list:
    """Registry solvers the router picked while tracing (dispatch events)."""
    return sorted({e.tags["solver"] for e in obs.recorded()
                   if e.kind == "dispatch" and "solver" in e.tags})


def _solve_converged() -> np.ndarray:
    """Per-instance converged flags of the solves recorded so far."""
    flags = [np.asarray(e.values["converged"], bool).ravel()
             for e in obs.recorded()
             if e.kind == "solve" and "converged" in e.values]
    return np.concatenate(flags) if flags else np.zeros(0, bool)


class CompileClock:
    """Sums JAX's backend-compile durations while it is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

    def _listen(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


# ---------------------------------------------------------------------------
# A: batched dense hypergradients (the pallas_cg regime)
# ---------------------------------------------------------------------------

def _ridge_hypergrad_ref(X, y, thetas):
    """Closed form of d/dθ |x*(θ)|² for ridge: -2 x*ᵀ (XᵀX + θI)⁻¹ x*."""
    def one(Xi, yi, t):
        d = Xi.shape[1]
        A = Xi.T @ Xi + t * jnp.eye(d, dtype=Xi.dtype)
        x = jnp.linalg.solve(A, Xi.T @ yi)
        return -2.0 * x @ jnp.linalg.solve(A, x)
    return jax.vmap(one)(X, y, thetas)


def phase_a1(key, B: int = 64, m: int = 2048, d: int = 512) -> dict:
    """Vmapped hypergradients of a per-dataset ridge sweep (README)."""
    kx, ky = jax.random.split(key)
    X = jax.random.normal(kx, (B, m, d), jnp.float32)
    y = jax.random.normal(ky, (B, m), jnp.float32)
    thetas = jnp.linspace(1.0, 10.0, B, dtype=jnp.float32)

    def per_dataset_loss(Xi, yi, theta):
        def f(x, t):
            r = Xi @ x - yi
            return (jnp.sum(r ** 2) + t * jnp.sum(x ** 2)) / 2
        F = jax.grad(f, argnums=0)

        def raw_solver(init, t):
            eye = jnp.eye(Xi.shape[1], dtype=Xi.dtype)
            return jnp.linalg.solve(Xi.T @ Xi + t * eye, Xi.T @ yi)

        solver = custom_root(F, solve="auto", tol=1e-6)(raw_solver)
        return jnp.sum(solver(None, theta) ** 2)

    grads_fn = jax.vmap(jax.grad(per_dataset_loss, argnums=2))
    with obs.observe(enabled=True, record=True):
        obs.clear_recorded()
        compiled, t_compile = _compile(grads_fn, X, y, thetas)
        routes = _routes()
        jax.block_until_ready(compiled(X, y, thetas))
        obs.clear_recorded()
        t0 = time.perf_counter()
        grads = jax.block_until_ready(compiled(X, y, thetas))
        t_steady = time.perf_counter() - t0
        converged = _solve_converged()
        obs.clear_recorded()
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(_ridge_hypergrad_ref)(X, y, thetas)
    err = _rel_err(grads[:, None], ref[:, None])
    share = float(converged.mean()) if converged.size else float("nan")
    res = dict(shape=(B, m, d), routes=routes, kernel=_has_kernel(compiled),
               max_rel_err=err, converged_share=share,
               compile_s=t_compile, steady_s=t_steady)
    log("A1", f"vmap(grad) B={B} m={m} d={d}: route={','.join(routes)} "
              f"kernel={'yes' if res['kernel'] else 'no'} "
              f"max_rel_err={err:.3e} converged={share:.3f} "
              f"compile_s={t_compile:.3f} steady_s={t_steady:.6f} "
              "(telemetry on)")
    _check(np.isfinite(np.asarray(grads)).all(), "A1 hypergradients finite")
    _check(err <= A1_MAX_REL_ERR, f"A1 max_rel_err {err:.3e} <= "
                                  f"{A1_MAX_REL_ERR}")
    _check(converged.size >= B and share == 1.0,
           f"A1 every backward solve converged ({share})")
    return res


def _spd_batch(key, B: int, d: int):
    kg, kb = jax.random.split(key)
    G = jax.random.normal(kg, (B, d, d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        A = jnp.einsum("bij,bkj->bik", G, G) / d \
            + 0.5 * jnp.eye(d, dtype=jnp.float32)
    return A, jax.random.normal(kb, (B, d), jnp.float32)


def phase_a2(key, shapes=((64, 384), (64, 512), (12, 128), (64, 448),
                          (50, 512))) -> list:
    """Natively batched dense SPD solves through ``method="auto"``."""
    tol = inspect.signature(ls.solve).parameters["tol"].default

    def solve(A, b):
        return ls.solve(DenseOperator(A, positive_definite=True), b,
                        method="auto", return_info=True)

    out = []
    for i, (B, d) in enumerate(shapes):
        A, b = _spd_batch(jax.random.fold_in(key, i), B, d)
        with obs.observe(enabled=True, record=True):
            obs.clear_recorded()
            compiled, t_compile = _compile(solve, A, b)
            routes = _routes()
            obs.clear_recorded()
        (x, info), t_steady = _steady(compiled, A, b)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda A, b: jnp.linalg.solve(
                A, b[..., None])[..., 0])(A, b)
        err = _rel_err(x, ref)
        share = float(np.mean(np.asarray(info.converged)))
        resid = float(np.max(np.asarray(info.residual)
                             / np.linalg.norm(np.asarray(b), axis=-1)))
        tile = ""
        if routes == ["pallas_cg"]:
            bb, Bp = block_rows(B, d)
            tile = f" block_b={bb}" + (f" padded_B={Bp}" if Bp != B else "")
        res = dict(shape=(B, d), routes=routes, kernel=_has_kernel(compiled),
                   max_rel_err=err, converged_share=share,
                   compile_s=t_compile, steady_s=t_steady)
        log("A2", f"solve B={B} d={d}: route={','.join(routes)}{tile} "
                  f"kernel={'yes' if res['kernel'] else 'no'} "
                  f"max_rel_err={err:.3e} max_rel_residual={resid:.3e} "
                  f"(default tol {tol}) converged={share:.3f} "
                  f"compile_s={t_compile:.3f} steady_s={t_steady:.6f}")
        _check(err <= A2_MAX_REL_ERR,
               f"A2 B={B} d={d} max_rel_err {err:.3e} <= {A2_MAX_REL_ERR}")
        _check(share == 1.0, f"A2 B={B} d={d} every instance converged")
        out.append(res)
    return out


# ---------------------------------------------------------------------------
# B: a matrix-free bilevel job (per-example data reweighting)
# ---------------------------------------------------------------------------

def _reweighting_data(key, n: int, n_val: int, p: int, k: int,
                      noise: float = 0.2):
    """MNIST-shaped features, labels from a linear teacher; a ``noise``
    share of the training labels is replaced by random classes."""
    kt, kx, kv, kc, kl = jax.random.split(key, 5)
    W = 3.0 * jax.random.normal(kt, (p, k), jnp.float32) / np.sqrt(p)
    X = jax.random.normal(kx, (n, p), jnp.float32)
    Xv = jax.random.normal(kv, (n_val, p), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y = jnp.argmax(X @ W, axis=-1)
        yv = jnp.argmax(Xv @ W, axis=-1)
    flip = jax.random.uniform(kc, (n,)) < noise
    y = jnp.where(flip, jax.random.randint(kl, (n,), 0, k), y)
    return X, y, Xv, yv


def phase_b(key, n: int = 60000, n_val: int = 10000, p: int = 784,
            k: int = 10, outer_steps: int = 3, inner_maxiter: int = 100,
            lam: float = 1e-2) -> dict:
    """Per-example reweighting through ``bilevel.solve_bilevel``."""
    X, y, Xv, yv = _reweighting_data(key, n, n_val, p, k)

    def ce(w, X, y):
        logits = X @ w
        return (jax.nn.logsumexp(logits, axis=-1)
                - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0])

    def inner_obj(w, theta):            # theta: one logit per example
        weights = 2.0 * jax.nn.sigmoid(theta)
        return jnp.mean(weights * ce(w, X, y)) + 0.5 * lam * jnp.sum(w * w)

    def outer_loss(w, theta):
        return jnp.mean(ce(w, Xv, yv))

    inner = LBFGS(inner_obj, maxiter=inner_maxiter, tol=1e-4, stepsize=1.0)
    theta0 = jnp.zeros((n,), jnp.float32)
    w0 = jnp.zeros((p, k), jnp.float32)

    # the objectives close over the data, as a user's would, so every
    # program embeds it as constants: its executables (~0.7-1 GB) exceed
    # the persistent compile cache's entry limit and recompile on each run
    with CompileClock() as clock:
        t0 = time.perf_counter()
        sol = bilevel.solve_bilevel(outer_loss, inner, theta0, w0,
                                    outer_steps=outer_steps,
                                    outer_lr=float(n), solve="cg")
        t_job = time.perf_counter() - t0
    info = sol.inner_info
    for s, v in enumerate(np.asarray(sol.outer_values)):
        log("B", f"outer step {s}: val_loss={float(v):.6f} "
                 f"hypergrad_norm={float(sol.hypergrad_norms[s]):.6e}")
    log("B", f"inner_info (last step): iterations="
             f"{int(info.iterations)} error={float(info.error):.3e} "
             f"converged={bool(info.converged)}")
    log("B", f"solve_bilevel {outer_steps} steps: wall_s={t_job:.3f} "
             f"of which compile_s={clock.seconds:.3f}")

    implicit = bilevel.make_implicit_inner(inner, solve="cg")

    def hypergrad(theta):
        return jax.grad(lambda t: outer_loss(implicit(w0, t), t))(theta)

    with CompileClock() as clock:
        t0 = time.perf_counter()
        hg = jax.jit(hypergrad)
        jax.block_until_ready(hg(theta0))
        t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    g = jax.block_until_ready(hg(theta0))
    t_steady = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        g_ref = jax.jit(hypergrad)(theta0)
    g, g_ref = np.asarray(g, np.float64), np.asarray(g_ref, np.float64)
    cos = float(g @ g_ref / (np.linalg.norm(g) * np.linalg.norm(g_ref)))
    log("B", f"hypergradient d_theta={n} (w: {p}x{k}={p * k}): "
             f"cosine_vs_highest={cos:.6f} compile_s={clock.seconds:.3f} "
             f"first_call_s={t_first:.3f} steady_s={t_steady:.6f}")
    _check(np.isfinite(np.asarray(sol.outer_values)).all(),
           "B outer losses finite")
    _check(np.isfinite(g).all() and np.linalg.norm(g) > 0,
           "B hypergradient finite and nonzero")
    _check(cos >= BILEVEL_MIN_COSINE,
           f"B cosine {cos:.6f} >= {BILEVEL_MIN_COSINE}")
    return dict(outer_values=np.asarray(sol.outer_values).tolist(),
                cosine=cos, compile_s=clock.seconds, steady_s=t_steady)


# ---------------------------------------------------------------------------
# C: the solve service
# ---------------------------------------------------------------------------

def phase_c(seed: int, n: int = 256, dims=(256, 448), max_batch: int = 64,
            spot_checks: int = 4) -> dict:
    """The ``--solve-service`` path on a mixed-d SPD traffic of ``n``."""
    rng = np.random.default_rng(seed)
    problems = []
    for i in range(n):
        d = dims[i * len(dims) // n]
        M = rng.standard_normal((d, d), dtype=np.float32)
        problems.append(((M @ M.T + d * np.eye(d, dtype=np.float32)),
                         rng.standard_normal(d, dtype=np.float32)))
    with obs.observe(enabled=True, record=True), CompileClock() as clock:
        obs.clear_recorded()
        svc, stats = drive_service(problems, max_batch=max_batch)
        routes = sorted({(e.tags["bucket"].split(":")[1], e.tags["solver"])
                         for e in obs.recorded() if e.kind == "dispatch"
                         and "bucket" in e.tags})
        obs.clear_recorded()
    log("C", "routes: " + " ".join(f"{dd}->{s}" for dd, s in routes)
             + " (kernel: only pallas_cg buckets carry it; the warm-start "
               "cache steers SPD buckets to dense_gmres)")
    for st in stats:
        log("C", f"{st['wave']} wave: {len(st['results'])} requests in "
                 f"{st['seconds']:.3f}s ({len(st['results']) / st['seconds']:.1f}"
                 f" req/s) compiled={st['compiled']} "
                 f"occupancy={st['occupancy']:.3f} "
                 f"hit_rate={st['hit_rate']:.3f}")
    log("C", f"compile_s={clock.seconds:.3f} (cold wave) "
             f"steady_s={stats[-1]['seconds']:.6f} (warm wave)")
    answered = sum(len(st["results"]) for st in stats)
    _check(answered == n * len(stats), f"C every future resolved ({answered})")
    for st in stats:
        _check(all(bool(r.info.converged) for r in st["results"]),
               f"C {st['wave']} wave: every request converged")
    worst = 0.0
    for d in dims:
        idx = [i for i, (A, _) in enumerate(problems)
               if A.shape[0] == d][:spot_checks]
        for i in idx:
            A, b = problems[i]
            ref = np.linalg.solve(A.astype(np.float64), b.astype(np.float64))
            for st in stats:
                worst = max(worst, _rel_err(st["results"][i].x[None],
                                            ref[None]))
    log("C", f"spot checks ({spot_checks} per d, both waves): "
             f"max_rel_err={worst:.3e}")
    _check(worst <= SERVICE_MAX_REL_ERR,
           f"C max_rel_err {worst:.3e} <= {SERVICE_MAX_REL_ERR}")
    return dict(answered=answered, routes=routes, max_rel_err=worst)


# ---------------------------------------------------------------------------
# --chips 4: the batch-sharded hypergradient
# ---------------------------------------------------------------------------

def _ridge_F(x, theta, X, y):
    r = jnp.einsum("bmd,bd->bm", X, x) - y
    return jnp.einsum("bmd,bm->bd", X, r) + theta[:, None] * x


def _direct_ridge(theta, X, y):
    d = X.shape[-1]
    A = jnp.einsum("bmd,bme->bde", X, X) \
        + theta[:, None, None] * jnp.eye(d, dtype=X.dtype)
    return jnp.linalg.solve(
        A, jnp.einsum("bmd,bm->bd", X, y)[..., None])[..., 0]


def phase_sharded(key, n_devices: int = 4, B: int = 256, m: int = 512,
                  d: int = 128) -> dict:
    """``jax.grad`` of a batched ridge sweep, batch-sharded over a mesh."""
    from jax.sharding import NamedSharding
    kx, ky = jax.random.split(key)
    X = jax.random.normal(kx, (B, m, d), jnp.float32)
    y = jax.random.normal(ky, (B, m), jnp.float32)
    thetas = jnp.linspace(1.0, 10.0, B, dtype=jnp.float32)

    def grad_of(dec):
        return jax.grad(lambda t, X, y: jnp.sum(dec(None, t, X, y) ** 2))

    single = implicit_diff(ImplicitDiffSpec(
        optimality_fun=_ridge_F, solve="cg", tol=1e-6))(
            lambda init, t, X, y: _direct_ridge(t, X, y))

    mesh = make_solve_mesh(n_devices)
    sharding = SolveSharding(mesh, P("data", None), batch_ndim=1,
                             theta_specs=(P("data"), P("data", None, None),
                                          P("data", None)))

    def fwd(init, t, X, y):
        return jax.shard_map(_direct_ridge, mesh=mesh,
                             in_specs=(P("data"), P("data", None, None),
                                       P("data", None)),
                             out_specs=P("data", None),
                             check_vma=False)(t, X, y)

    sharded = implicit_diff(ImplicitDiffSpec(
        optimality_fun=_ridge_F, solve="cg", tol=1e-6, sharding=sharding))(
            fwd)
    put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
    X_sh, y_sh = put(X, P("data", None, None)), put(y, P("data", None))
    t_sh = put(thetas, P("data"))

    with obs.observe(enabled=True, record=True):
        obs.clear_recorded()
        c_single, tc_single = _compile(grad_of(single), thetas, X, y)
        routes_single = _routes()
        obs.clear_recorded()
        c_sharded, tc_sharded = _compile(grad_of(sharded), t_sh, X_sh, y_sh)
        routes_sharded = _routes()
        obs.clear_recorded()
        g_single, ts_single = _steady(c_single, thetas, X, y)
        g_sharded, ts_sharded = _steady(c_sharded, t_sh, X_sh, y_sh)
        converged = _solve_converged()
        obs.clear_recorded()
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(_ridge_hypergrad_ref)(X, y, thetas)
    devices = {s.device for s in g_sharded.addressable_shards}
    diff = _rel_err(np.asarray(g_sharded)[None], np.asarray(g_single)[None])
    err_sh = _rel_err(g_sharded[:, None], ref[:, None])
    err_si = _rel_err(g_single[:, None], ref[:, None])
    log("4chip", f"grad B={B} m={m} d={d}: single route="
                 f"{','.join(routes_single)} compile_s={tc_single:.3f} "
                 f"steady_s={ts_single:.6f}")
    log("4chip", f"sharded over {n_devices} devices route="
                 f"{','.join(routes_sharded)} compile_s={tc_sharded:.3f} "
                 f"steady_s={ts_sharded:.6f} shards_on={len(devices)} "
                 "devices (telemetry on)")
    log("4chip", f"sharded_vs_single rel_diff={diff:.3e} "
                 f"(limit {SHARDED_MAX_REL_DIFF}); vs f32 highest reference:"
                 f" sharded={err_sh:.3e} single={err_si:.3e}; "
                 f"converged={float(converged.mean()):.3f}")
    _check(routes_sharded == ["sharded_cg"], "sharded route is sharded_cg")
    _check(len(devices) == n_devices, f"all {n_devices} devices hold a shard")
    _check(diff <= SHARDED_MAX_REL_DIFF,
           f"sharded vs single {diff:.3e} <= {SHARDED_MAX_REL_DIFF}")
    _check(max(err_sh, err_si) <= A1_MAX_REL_ERR,
           f"both within {A1_MAX_REL_ERR} of the reference")
    return dict(rel_diff=diff, devices=len(devices))


# ---------------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the batch-sharded hypergradient")
    args = ap.parse_args(argv)

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform is "
                         f"{dev.platform!r}); this script runs on the chip "
                         "only")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} TPU devices, found {len(devices)}")
    log("device", f"{dev.platform} {dev.device_kind} x{len(devices)} "
                  f"jax={jax.__version__} seed={args.seed}")
    # routing and tiles from the cold rules, never from a tuning file that
    # the environment names (REPRO_AUTOTUNE_CACHE)
    autotune.set_default_cache(autotune.TuningCache())
    key = jax.random.PRNGKey(args.seed)

    if args.chips == 4:
        phase_sharded(jax.random.fold_in(key, 4), n_devices=4)
    else:
        checked = [phase_a1(jax.random.fold_in(key, 1))]
        checked += phase_a2(jax.random.fold_in(key, 2))
        for res in checked:
            # on the chip the router's pallas_cg must BE the kernel
            _check(res["kernel"] == ("pallas_cg" in res["routes"]),
                   f"kernel present exactly where routed, {res['shape']}")
        phase_b(jax.random.fold_in(key, 3))
        phase_c(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
