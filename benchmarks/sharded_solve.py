"""Sharded vs. single-device hypergradients: the device-count scaling curve.

The workload is the canonical batched implicit-diff hot path — ``jax.grad``
of an ``implicit_diff``-decorated batched ridge solver, whose backward pass
is ONE linear solve with ``A = -∂₁F`` — run two ways:

  * single-device: the classic ``cg`` registry route (the PR 2/3 baseline);
  * sharded: the batch split over an n-device mesh (``SolveSharding`` on
    the spec), forward solve under ``shard_map``, backward solve through
    the ``sharded_cg`` registry route — no host gather (the compiled
    all-gather census is asserted in ``tests/test_sharded_operators.py``).

Rows sweep the mesh size over the available devices (1, 2, 4, ... — the CI
multi-device lane forces 8 host devices), reporting ``sharded/single``
time ratios per device count: the scaling curve the ROADMAP's
sharded-solves item asked for.  On a 1-device process the curve degenerates
to the n=1 row, which then measures pure shard_map overhead.

The measured curve is fed into the dispatch ``TuningCache``, and a final
row routes through ``launch.mesh.auto_mesh_size`` — the tuned path the
examples use — tagged ``dispatch=mesh=<n>`` + ``auto-selected``.  The CI
dispatch-regression gate (``benchmarks/check_dispatch.py``) asserts that
row's ratio stays ≤ 1.1: the tuner must never *choose* a losing mesh.
"""
import functools

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.common import emit, time_fn
from repro.core.diff_api import ImplicitDiffSpec, implicit_diff
from repro.distributed.sharded_operators import SolveSharding
from repro.launch.mesh import auto_mesh_size, make_solve_mesh


def _problem(key, B, m, d):
    X = jax.random.normal(key, (B, m, d))
    y = jax.random.normal(jax.random.fold_in(key, 1), (B, m))
    theta = jnp.linspace(0.5, 2.0, B)
    return X, y, theta


def _ridge_F(x, theta, X, y):
    r = jnp.einsum("bmd,bd->bm", X, x) - y
    return jnp.einsum("bmd,bm->bd", X, r) + theta[:, None] * x


def _local_solver(theta, X, y):
    d = X.shape[-1]
    A = jnp.einsum("bmd,bme->bde", X, X) + theta[:, None, None] * jnp.eye(d)
    return jnp.linalg.solve(
        A, jnp.einsum("bmd,bm->bd", X, y)[..., None])[..., 0]


def _single_device_grad(X, y):
    spec = ImplicitDiffSpec(optimality_fun=_ridge_F, solve="cg", tol=1e-8)
    dec = implicit_diff(spec)(
        lambda init, theta, X, y: _local_solver(theta, X, y))
    return jax.jit(jax.grad(
        lambda t: jnp.sum(dec(None, t, X, y) ** 2)))


def _sharded_grad(mesh, X, y):
    sharding = SolveSharding(mesh, P("data", None), batch_ndim=1,
                             theta_specs=(P("data"), P("data", None, None),
                                          P("data", None)))
    spec = ImplicitDiffSpec(optimality_fun=_ridge_F, solve="cg", tol=1e-8,
                            sharding=sharding)

    def fwd(init, theta, X, y):
        return jax.shard_map(_local_solver, mesh=mesh,
                             in_specs=(P("data"), P("data", None, None),
                                       P("data", None)),
                             out_specs=P("data", None),
                             check_vma=False)(theta, X, y)

    dec = implicit_diff(spec)(fwd)
    X_sh = jax.device_put(X, NamedSharding(mesh, P("data", None, None)))
    y_sh = jax.device_put(y, NamedSharding(mesh, P("data", None)))
    grad = jax.jit(jax.grad(
        lambda t: jnp.sum(dec(None, t, X_sh, y_sh) ** 2)))
    put = functools.partial(jax.device_put,
                            device=NamedSharding(mesh, P("data")))
    return grad, put


def run(emit_fn=emit, smoke: bool = False):
    B, m, d = (64, 24, 16) if smoke else (256, 48, 32)
    key = jax.random.PRNGKey(0)
    X, y, theta = _problem(key, B, m, d)

    single = _single_device_grad(X, y)
    t_single = time_fn(lambda: single(theta), iters=3)
    emit_fn(f"sharded_hypergrad_single_B{B}_d{d}", t_single, "baseline")

    n_dev = len(jax.devices())
    counts, n = [], 1
    while n <= n_dev and B % n == 0:
        counts.append(n)
        n *= 2
    times = {}
    for n in counts:
        mesh = make_solve_mesh(devices=n)
        grad, put = _sharded_grad(mesh, X, y)
        theta_sh = put(theta)
        t_sh = time_fn(lambda: grad(theta_sh), iters=3)
        times[n] = t_sh
        emit_fn(f"sharded_hypergrad_mesh{n}_B{B}_d{d}", t_sh,
                f"sharded/single={t_sh / t_single:.2f}x")

    # Feed the measured end-to-end curve into the dispatch TuningCache
    # (keyed exactly as auto_mesh_size / should_shard look regimes up),
    # then report the extent the tuned path picks.  These puts overwrite
    # any raw-solve sweep entries from benchmarks/autotune_sweep.py with
    # hypergrad-representative timings from THIS process.
    from repro.analysis import autotune
    backend = autotune.current_backend()
    cache = autotune.default_cache()
    cache.put(autotune.TuningKey(
        backend, autotune.single_device_solver(True, d), B, d, "float32",
        1), t_single)
    for n, t_sh in times.items():
        cache.put(autotune.TuningKey(
            backend, "sharded_cg", B, d, "float32", n), t_sh)
    n_auto = auto_mesh_size(B, d)
    t_auto = times[n_auto]
    emit_fn(f"sharded_hypergrad_auto_mesh{n_auto}_B{B}_d{d}", t_auto,
            f"sharded/single={t_auto / t_single:.2f}x,"
            f"dispatch=mesh={n_auto}+solver=sharded_cg,auto-selected")


if __name__ == "__main__":
    run()
