"""Hypergradients of per-class ridge regressions through the fused SPD solve.

Each step deals the configuration's grid of regularisations theta to the
classes in a fresh order and takes ``jax.value_and_grad`` over theta of
the summed validation loss, through
``linear_solve.solve(DenseOperator(G + theta I, positive_definite=True),
C, method="auto")`` at the configuration's ``solve_tol`` and
``solve_maxiter``: the dense SPD route (``pallas_cg`` on the chip)
forward, and its implicit custom VJP backward.  The moments (``G``, the
target products, their validation counterparts) are summed once at set-up,
as a ridge path precomputes them, and passed to the step as arguments.
One call of the compiled program runs the traffic's ``steps_per_call``
steps in a ``lax.scan``, so that the calls kept in flight hold seconds of
work and a host that stands still does not idle the chip.
The systems ``G + theta I`` are formed before the solve; the solve alone
sits under ``jax.named_scope("spd_solve")``, so that the trace reduction
finds its device ops, backward included, and nothing else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.harness import Check, sample, seed_key
from bench.problems import ridge_probe
from repro.core import DenseOperator
from repro.core import linear_solve as ls

HI = jax.lax.Precision.HIGHEST
SCOPE = "spd_solve"


def make_step(config: dict):
    nv = config["val_rows"]

    def step(k, key, G, C, Gv, Bv, yy):
        theta = ridge_probe.thetas(config, key, k)
        eye = jnp.eye(G.shape[-1], dtype=G.dtype)

        def val_loss(theta):
            A = G[None] + theta[:, None, None] * eye
            with jax.named_scope(SCOPE):
                x = ls.solve(DenseOperator(A, positive_definite=True), C,
                             method="auto", tol=config["solve_tol"],
                             maxiter=config["solve_maxiter"])
            r = jnp.einsum("bd,de->be", x, Gv, precision=HI)
            return jnp.sum(0.5 * jnp.sum(x * r, -1) - jnp.sum(Bv * x, -1)
                           + 0.5 * yy) / nv

        val, grad = jax.value_and_grad(val_loss)(theta)
        return k + 1, val, grad

    return step


def make_call(config: dict, steps: int):
    """``steps`` steps of ``make_step`` in one program: ``(k, key, *moments)
    -> (k + steps, (values, hypergradients))``, each stacked by step."""
    step = make_step(config)

    def call(k, key, *moments):
        def body(k, _):
            k, val, grad = step(k, key, *moments)
            return k, (val, grad)
        return jax.lax.scan(body, k, None, length=steps)

    return call


class SpdHypergrad:
    """The closed-loop job: ``step(k)`` dispatches step ``k``."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        key = seed_key(seed)
        self.data_key = jax.random.fold_in(key, 0)
        self.theta_key = jax.random.fold_in(key, 1)
        self.args = ridge_probe.moments(config, self.data_key)
        self.steps = int(traffic.get("steps_per_call", 1))
        self.step_fn = jax.jit(make_call(config, self.steps))
        self.outputs = []
        self.k = jnp.zeros((), jnp.int32)

    def warmup(self):
        jax.block_until_ready(self.step_fn(self.k, self.theta_key, *self.args))

    def step(self, i: int):
        """Call ``i`` of the window, its ``steps`` steps; the step index
        lives on the device."""
        self.k, out = self.step_fn(self.k, self.theta_key, *self.args)
        self.outputs.append(out)
        return out

    def close(self) -> dict:
        self.values = [(v, g) for vals, grads in jax.device_get(self.outputs)
                       for v, g in zip(vals, grads)]
        self.outputs = self.args = None
        finite = [np.isfinite(v).all() and np.isfinite(g).all()
                  for v, g in self.values]
        return {"failed": len(finite) - int(sum(finite)),
                "steps": len(self.values)}

    def check(self, control: bool = False) -> list:
        """The window's hypergradients against the reference, on a sample
        drawn from the seed: ``checked_steps`` steps (the last included),
        ``checked_classes`` classes of each, compared by the root mean
        square of their relative gaps (``ridge_probe.rel_errors``): the
        largest gap is set by a few hypergradients at float32's rounding
        floor, where the control's `high` is not three times the program's,
        and the mean square holds every answer.  With ``control`` the
        reference a precision lower (moments and solves at ``high``, in
        float32) stands in for the program."""
        t = self.traffic
        steps = sample(self.seed, len(self.values), t["checked_steps"])
        nv, errors = self.config["val_rows"], []
        ref = [np.asarray(a, np.float64) for a in jax.device_get(
            ridge_probe.moments(self.config, self.data_key))]
        low = (ridge_probe.moments(self.config, self.data_key, "high")
               if control else None)
        for k in steps:
            cls = sample(self.seed, self.config["classes"],
                         t["checked_classes"], k + 1)
            theta = np.asarray(ridge_probe.thetas(self.config,
                                                  self.theta_key, k))[cls]
            G, C, Gv, Bv, yy = ref
            _, _, g = ridge_probe.solve_classes(G, C[cls], Gv, Bv[cls],
                                                yy[cls], theta, nv)
            if control:
                G, C, Gv, Bv, _ = low
                grad = ridge_probe.solve_classes_high(
                    G, C[np.asarray(cls)], Gv, Bv[np.asarray(cls)],
                    jnp.asarray(theta), nv)
            else:
                grad = self.values[k][1][cls]
            errors.append(ridge_probe.rel_errors(grad, g))
        return [Check("hypergrad_rms", ridge_probe.rms(errors),
                      t["limits"]["hypergrad_rms"])]


def build(config: dict, traffic: dict, seed: int) -> SpdHypergrad:
    return SpdHypergrad(config, traffic, seed)
