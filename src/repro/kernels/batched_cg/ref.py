"""Pure-JAX reference for the fused batched-CG kernel.

Same algorithm as ``kernel.py`` — masked CG over a (B, d) batch inside one
``lax.while_loop``, with residual replacement — expressed with plain jnp
ops.  Used as the correctness oracle for kernel parity tests and as the
CPU/GPU fallback path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.operators import dense_matvec


@jax.jit
def batched_cg_ref(A, b, tol: float = 1e-6, maxiter: int = 64):
    """A: (B, d, d) SPD batch; b: (B, d).  Returns ``(x, rn, counts)``:
    x (B, d), the (B,) true residual norms the systems stopped on, and the
    kernel's (B, 2) int32 counts (own CG steps, matvecs charged), the
    whole batch being one block."""
    dtype = jnp.promote_types(jnp.result_type(A.dtype, b.dtype), jnp.float32)
    out_dtype = b.dtype
    A = A.astype(dtype)
    b = b.astype(dtype)
    b2 = jnp.sum(b * b, axis=-1)
    atol2 = jnp.maximum(tol * tol * b2, 1e-30)

    def cond(state):
        rs, k = state[3], state[4]
        return jnp.logical_and(k < maxiter, jnp.any(rs > atol2))

    def body(state):
        x, r, p, rs, k, steps, restarts = state
        active = rs > atol2
        ap = dense_matvec(A, p)
        denom = jnp.sum(p * ap, axis=-1)
        safe = jnp.where(denom == 0, 1.0, denom)
        alpha = jnp.where(denom == 0, 0.0, rs / safe)
        alpha = jnp.where(active, alpha, 0.0)[:, None]
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = jnp.sum(r * r, axis=-1)
        beta = jnp.where(rs == 0, 0.0, rs_new / jnp.where(rs == 0, 1.0, rs))
        p = jnp.where(active[:, None], r + beta[:, None] * p, p)
        rs = jnp.where(active, rs_new, rs)
        return x, r, p, rs, k + 1, steps + active, restarts

    def replace_residual(state):
        x, _, _, _, k, steps, restarts = lax.while_loop(cond, body, state)
        r = b - dense_matvec(A, x)
        return x, r, r, jnp.sum(r * r, axis=-1), k, steps, restarts + 1

    steps0 = jnp.zeros(b2.shape, jnp.int32)
    x, _, _, rs, k, steps, restarts = lax.while_loop(
        cond, replace_residual,
        (jnp.zeros_like(b), b, b, b2, jnp.int32(0), steps0, jnp.int32(0)))
    counts = jnp.stack([steps, jnp.broadcast_to(k + restarts, steps.shape)],
                       axis=-1)
    return x.astype(out_dtype), jnp.sqrt(rs), counts
