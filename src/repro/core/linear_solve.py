"""Matrix-free linear solvers: the batched solve engine behind implicit diff.

All solvers take an operator — a ``repro.core.operators.LinearOperator`` or a
bare ``matvec: pytree -> pytree`` closure — and a pytree right-hand side and
return a pytree solution.  They are implemented with ``lax.while_loop`` so they
can live inside jit/scan/custom_vjp bodies, and they only touch the operator
through matrix-vector products — exactly the contract the paper's implicit
differentiation needs (access to F only through JVPs/VJPs).  Operators carry
their structure with them (symmetry/definiteness flags, O(1) ``diagonal``/
``materialize`` where available, batch awareness): routing validates
symmetric-only solvers against the flags, ``method="auto"`` picks the regime
(dense small systems auto-materialize, large ones stay matrix-free), and
``"jacobi"``/``"block_jacobi"`` preconditioners derive from
``operator.diagonal()`` instead of probing.

Registry (``SolverSpec``; see ``available_solvers()``):

  * ``cg``        — conjugate gradient (A symmetric PSD; preconditioned)
  * ``normal_cg`` — CG on the normal equations AᵀA x = Aᵀ b (general A,
                    needs ``rmatvec`` or builds it via linear transpose)
  * ``bicgstab``  — BiCGSTAB (general square A)
  * ``gmres``     — restarted GMRES (general square A; left-preconditioned)
  * ``dense_gmres`` — batched GMRES on materialized per-instance operators
                    (the nonsymmetric dense small-system regime, d ≤ 512)
  * ``lu``        — dense direct solve (materializes A; small systems)
  * ``neumann``   — truncated Neumann series for I - M with ||M|| < 1
                    (the "Jacobian-free"/unrolled-free approximation)
  * ``pallas_cg`` — fused Pallas batched-CG kernel for the dense small-system
                    regime (d ≤ 512); materializes per-instance operators

Batching
--------
Every iterative solver is **vmap-safe with per-instance convergence masks**:
the ``lax.while_loop`` state carries a ``done`` flag and converged instances
freeze (their state is held by ``where(done, old, new)``) while stragglers
keep iterating — one while_loop for the whole batch, never N sequential
solves.  Use either

  * ``jax.vmap`` over any solver (or over a ``@custom_root``-decorated solver:
    its backward pass then runs one batched solve), or
  * the uniform entry point ``solve(matvec, b, batch_axes=0, ...)`` where
    ``matvec`` maps batched pytrees to batched pytrees.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import operators
from repro.core.operators import (LinearOperator, RavelView, _ravel1,
                                  dense_matvec, jacobi_preconditioner,
                                  ravel_view)
# bottom-adjacent telemetry (imports nothing from repro.core): solve events
# are staged jit-safely behind the process-level observe() switch — with
# observability disabled (default) every emission below is a trace-time
# no-op and compiled programs are bit-identical to an uninstrumented build
from repro.observability import events as obs_events


# ---------------------------------------------------------------------------
# batch-aware pytree helpers
#
# ``batch_ndim`` is the number of leading batch axes on every leaf (0 or 1).
# Reductions run over the instance axes only, so per-instance scalars
# (step sizes, residual norms, done flags) have the batch shape.
# ---------------------------------------------------------------------------

def _bc(s, leaf, batch_ndim: int):
    """Broadcast a per-instance scalar against an instance-shaped leaf."""
    if batch_ndim == 0:
        return s
    s = jnp.asarray(s)
    return s.reshape(s.shape + (1,) * (jnp.ndim(leaf) - batch_ndim))


def _tree_dot(a, b, batch_ndim: int = 0):
    leaves_a = jax.tree_util.tree_leaves(a)
    leaves_b = jax.tree_util.tree_leaves(b)
    out = 0.0
    for x, y in zip(leaves_a, leaves_b):
        axes = tuple(range(batch_ndim, jnp.ndim(x)))
        out = out + jnp.sum(jnp.conj(x) * y, axis=axes)
    return out


def _tree_add(a, b, alpha=1.0, batch_ndim: int = 0):
    return jax.tree_util.tree_map(
        lambda x, y: x + _bc(alpha, x, batch_ndim) * y, a, b)


def _tree_sub(a, b):
    return jax.tree_util.tree_map(lambda x, y: x - y, a, b)


def _tree_scale(a, alpha, batch_ndim: int = 0):
    return jax.tree_util.tree_map(lambda x: _bc(alpha, x, batch_ndim) * x, a)


def _tree_zeros_like(a):
    return jax.tree_util.tree_map(jnp.zeros_like, a)


def _tree_l2(a, batch_ndim: int = 0):
    return jnp.sqrt(jnp.maximum(_tree_dot(a, a, batch_ndim).real, 0.0))


def _tree_freeze(done, old, new, batch_ndim: int = 0):
    """Hold converged instances: where(done, old, new) leaf-wise."""
    return jax.tree_util.tree_map(
        lambda o, n: jnp.where(_bc(done, o, batch_ndim), o, n), old, new)


def _damped(matvec: Callable, ridge: float) -> Callable:
    if not ridge:
        return matvec
    if isinstance(matvec, LinearOperator):
        return operators.RidgeShifted(matvec, ridge)   # keeps flags/structure
    return lambda v: _tree_add(matvec(v), v, ridge)


def make_rmatvec(matvec: Callable, example_x):
    """Build x ↦ Aᵀx from x ↦ Ax.  ``LinearOperator``s answer directly
    (symmetric ones reuse the forward matvec); bare closures go through
    ``jax.linear_transpose`` (paper §2.1)."""
    if isinstance(matvec, LinearOperator):
        return matvec.rmatvec
    transpose = jax.linear_transpose(matvec, example_x)

    def rmatvec(y):
        (out,) = transpose(y)
        return out

    return rmatvec


def _as_probe_operator(matvec, example, batch_ndim: int) -> LinearOperator:
    """Coerce to an operator with matching batchedness, so the basis-vector
    probing loops live in ONE place (the ``LinearOperator`` defaults)."""
    if isinstance(matvec, LinearOperator) and matvec.batch_ndim == batch_ndim:
        return matvec
    return operators.FunctionOperator(matvec, example, batch_ndim=batch_ndim)


def materialize_matrix(matvec: Callable, example_x) -> jnp.ndarray:
    """Densify a matvec to its (d, d) matrix (diagnostics / direct solve).

    A ``LinearOperator`` materializes itself (O(1) for dense/structured
    operators); bare closures are probed with basis vectors.
    """
    return _as_probe_operator(matvec, example_x, 0).materialize()


# ---------------------------------------------------------------------------
# flat (B, d) view of a batched pytree operator
#
# The view itself lives in repro.core.operators (``ravel_view`` — one ravel
# shim for the whole stack); this layer adds the dense materialization with
# an operator fast path.
# ---------------------------------------------------------------------------

def materialize_batched(matvec: Callable, b, batch_ndim: int = 0,
                        view: Optional[RavelView] = None):
    """Densify a (possibly batched) operator to (B, d, d) plus the flat view.

    A ``LinearOperator`` (with matching batchedness) materializes itself —
    O(1) for ``DenseOperator``/``RidgeShifted`` stacks, which is what makes
    the dense-regime solvers auto-materialize instead of probing.  Bare
    closures are probed with basis vectors broadcast across the batch, so
    the cost is d matvecs regardless of batch size.
    """
    if view is None:
        view = ravel_view(matvec, b, batch_ndim)
    B, d = view.b.shape
    A = _as_probe_operator(matvec, b, batch_ndim).materialize()
    A = A if batch_ndim else A[None]
    return jnp.broadcast_to(A, (B, d, d)), view


# ---------------------------------------------------------------------------
# preconditioning hooks
# ---------------------------------------------------------------------------

def diagonal_of_matvec(matvec: Callable, b, batch_ndim: int = 0):
    """Extract diag(A) with the same (possibly batched) structure as ``b``.

    A ``LinearOperator`` (with matching batchedness) answers via its own
    ``diagonal()`` — O(1) for structured operators; bare closures pay d
    probing matvecs (vmapped across instances).
    """
    return _as_probe_operator(matvec, b, batch_ndim).diagonal()


def _resolve_precond(precond, matvec, b, batch_ndim: int, diag=None,
                     materialized=None):
    """None | callable | "jacobi" | "block_jacobi" -> callable M⁻¹ (or None).

    ``diag``/``materialized`` short-circuit the operator probing when the
    caller already holds the diagonal or the dense matrix (the dense-regime
    solvers materialize anyway — no second probing pass).  ``"block_jacobi"``
    needs a ``LinearOperator`` (the domain's pytree leaves — or a
    ``BlockDiagonal``'s blocks — define the blocks).
    """
    if precond is None or callable(precond):
        return precond
    if precond == "jacobi":
        if diag is None:
            diag = diagonal_of_matvec(matvec, b, batch_ndim)
        return jacobi_preconditioner(diag)
    if precond == "block_jacobi":
        if not isinstance(matvec, LinearOperator):
            raise ValueError("precond='block_jacobi' derives blocks from "
                             "operator structure; pass a LinearOperator "
                             "(or use 'jacobi' / a callable M⁻¹)")
        return operators.block_jacobi_preconditioner(
            matvec, materialized=materialized)
    raise ValueError(f"unknown preconditioner {precond!r}; expected None, "
                     "a callable M⁻¹, 'jacobi', or 'block_jacobi'")


# ---------------------------------------------------------------------------
# solve diagnostics
# ---------------------------------------------------------------------------

class SolveInfo(NamedTuple):
    """Per-instance diagnostics (batch-shaped under vmap / batch_axes).

    ``iterations`` counts the solver's outer steps: matvec iterations for
    cg/normal_cg/bicgstab, *restart cycles* (each up to ``restart`` Arnoldi
    steps) for gmres, 0 for direct solves, and for pallas_cg each system's
    own CG steps, counted by the kernel.
    """
    iterations: jnp.ndarray    # outer steps actually spent per instance
    residual: jnp.ndarray      # final ||b - A x|| per instance
    converged: jnp.ndarray     # residual <= tol * ||b|| per instance
    # relative residual ||rhs - A u|| / ||rhs|| of the implicit system at the
    # returned (co)tangent — populated by the approximate backward modes (and
    # by exact solves when error_estimate=True is requested); None otherwise
    hypergrad_error_estimate: Optional[jnp.ndarray] = None
    # matvecs charged to each instance where a solver runs instances in
    # lockstep (pallas_cg: every matvec of its kernel block, frozen rows
    # included); None otherwise
    matvecs: Optional[jnp.ndarray] = None


def _maybe_info(x, info: Optional[SolveInfo], return_info: bool):
    return (x, info) if return_info else x


def _squeeze_info(info: SolveInfo) -> SolveInfo:
    """Collapse the internal B=1 batch axis for unbatched calls — the one
    place the flat-core solvers' per-instance diagnostics lose their
    synthetic leading axis."""
    return SolveInfo(*(None if leaf is None
                       else jnp.asarray(leaf).reshape(-1)[0] for leaf in info))


# ---------------------------------------------------------------------------
# Conjugate gradient (preconditioned, masked)
# ---------------------------------------------------------------------------

def solve_cg(matvec: Callable, b, *, init=None, tol: float = 1e-6,
             maxiter: int = 1000, ridge: float = 0.0, precond=None,
             return_info: bool = False, batch_ndim: int = 0, reduce=None):
    """(Preconditioned) conjugate gradient for symmetric PSD operators.

    ``ridge`` adds λI damping, the common non-invertibility heuristic.
    ``precond`` is ``None``, a callable v ↦ M⁻¹v, or ``"jacobi"``.
    Vmap-safe: converged instances freeze inside the single while_loop.
    ``reduce`` post-processes every dot-product/norm reduction — the hook
    the sharded solvers use to ``psum`` partial sums when the instance
    dims are split across devices (``None``: plain local sums).
    """
    nb = batch_ndim
    red = (lambda s: s) if reduce is None else reduce
    tdot = lambda u, w: red(_tree_dot(u, w, nb))
    tl2 = lambda u: jnp.sqrt(jnp.maximum(tdot(u, u).real, 0.0))
    matvec = _damped(matvec, ridge)
    M = _resolve_precond(precond, matvec, b, nb)
    x0 = _tree_zeros_like(b) if init is None else init
    r0 = _tree_sub(b, matvec(x0))
    z0 = M(r0) if M is not None else r0
    p0 = z0
    rz0 = tdot(r0, z0)
    rr0 = tdot(r0, r0).real
    b_norm = tl2(b)
    atol2 = jnp.maximum(tol * b_norm, 1e-30) ** 2
    done0 = rr0 <= atol2
    it0 = jnp.zeros_like(b_norm, dtype=jnp.int32)
    # trace-time flag: per-iteration telemetry is opt-in (a host callback
    # per loop step); the default compiles an uninstrumented loop body
    iter_events = obs_events.observing_iterations()

    def cond(state):
        k = state[-2]
        done = state[-1]
        return jnp.logical_and(k < maxiter, jnp.logical_not(jnp.all(done)))

    def body(state):
        x, r, p, rz, rr, it, k, done = state
        ap = matvec(p)
        denom = tdot(p, ap)
        alpha = jnp.where(denom == 0, 0.0, rz / jnp.where(denom == 0, 1.0,
                                                          denom))
        x1 = _tree_add(x, p, alpha, nb)
        r1 = _tree_add(r, ap, -alpha, nb)
        rr1 = tdot(r1, r1).real
        z1 = M(r1) if M is not None else r1
        rz1 = tdot(r1, z1)
        beta = rz1 / jnp.where(rz == 0, 1.0, rz)
        beta = jnp.where(rz == 0, 0.0, beta)
        p1 = _tree_add(z1, p, beta, nb)
        # freeze instances that were already done at loop entry
        x = _tree_freeze(done, x, x1, nb)
        r = _tree_freeze(done, r, r1, nb)
        p = _tree_freeze(done, p, p1, nb)
        rz = jnp.where(done, rz, rz1)
        rr = jnp.where(done, rr, rr1)
        it = it + jnp.logical_not(done)
        done = jnp.logical_or(done, rr <= atol2)
        if iter_events:
            obs_events.jit_event("iteration", {"solver": "cg"},
                                 step=k + 1, residual_sq=rr)
        return x, r, p, rz, rr, it, k + 1, done

    x, r, _, _, rr, it, _, done = lax.while_loop(
        cond, body, (x0, r0, p0, rz0, rr0, it0, 0, done0))
    info = SolveInfo(iterations=it, residual=jnp.sqrt(rr),
                     converged=rr <= atol2)
    return _maybe_info(x, info, return_info)


def solve_normal_cg(matvec: Callable, b, *, init=None, rmatvec=None,
                    tol: float = 1e-6, maxiter: int = 1000,
                    ridge: float = 0.0, precond=None,
                    return_info: bool = False, batch_ndim: int = 0,
                    reduce=None):
    """Solve A x = b via CG on AᵀA x = Aᵀ b.  Works for any square A."""
    example = _tree_zeros_like(b) if init is None else init
    if rmatvec is None:
        rmatvec = make_rmatvec(matvec, example)

    def normal_mv(v):
        return rmatvec(matvec(v))

    return solve_cg(normal_mv, rmatvec(b), init=init, tol=tol,
                    maxiter=maxiter, ridge=ridge, precond=precond,
                    return_info=return_info, batch_ndim=batch_ndim,
                    reduce=reduce)


# ---------------------------------------------------------------------------
# BiCGSTAB (masked)
# ---------------------------------------------------------------------------

def solve_bicgstab(matvec: Callable, b, *, init=None, tol: float = 1e-6,
                   maxiter: int = 1000, ridge: float = 0.0, precond=None,
                   return_info: bool = False, batch_ndim: int = 0):
    """BiCGSTAB (van der Vorst, 1992) for general square operators.

    ``precond`` applies as a left preconditioner (wraps the operator); the
    loop iterates on the preconditioned residual, but ``SolveInfo`` always
    reports the TRUE residual ||b - A x|| so ``converged`` means the same
    thing across solvers.  Vmap-safe: per-instance done/breakdown masks
    inside one while_loop.
    """
    nb = batch_ndim
    matvec = _damped(matvec, ridge)
    matvec0, b0 = matvec, b
    M = _resolve_precond(precond, matvec, b, nb)
    if M is not None:
        inner = matvec
        matvec = lambda v: M(inner(v))
        b = M(b)
    x0 = _tree_zeros_like(b) if init is None else init
    r0 = _tree_sub(b, matvec(x0))
    rhat = r0
    b_norm = _tree_l2(b, nb)
    atol = jnp.maximum(tol * b_norm, 1e-30)
    rn0 = _tree_l2(r0, nb)
    done0 = rn0 <= atol

    init_state = dict(x=x0, r=r0, p=r0, rho=_tree_dot(rhat, r0, nb),
                      alpha=jnp.ones_like(b_norm),
                      omega=jnp.ones_like(b_norm),
                      rnorm=rn0, it=jnp.zeros_like(b_norm, dtype=jnp.int32),
                      k=0, done=done0,
                      breakdown=jnp.zeros_like(done0))

    def cond(s):
        return jnp.logical_and(s["k"] < maxiter,
                               jnp.logical_not(jnp.all(s["done"])))

    def body(s):
        x, r, p, rho, done = s["x"], s["r"], s["p"], s["rho"], s["done"]
        v = matvec(p)
        denom = _tree_dot(rhat, v, nb)
        breakdown = denom == 0
        alpha = rho / jnp.where(breakdown, 1.0, denom)
        alpha = jnp.where(breakdown, 0.0, alpha)
        h = _tree_add(x, p, alpha, nb)
        sres = _tree_add(r, v, -alpha, nb)
        t = matvec(sres)
        tt = _tree_dot(t, t, nb)
        omega = _tree_dot(t, sres, nb) / jnp.where(tt == 0, 1.0, tt)
        omega = jnp.where(tt == 0, 0.0, omega)
        x1 = _tree_add(h, sres, omega, nb)
        r1 = _tree_add(sres, t, -omega, nb)
        rho1 = _tree_dot(rhat, r1, nb)
        beta = (rho1 / jnp.where(rho == 0, 1.0, rho)) * \
               (alpha / jnp.where(omega == 0, 1.0, omega))
        p1 = _tree_add(r1, _tree_add(p, v, -omega, nb), beta, nb)
        rn1 = _tree_l2(r1, nb)
        breakdown = jnp.logical_or(breakdown, rho == 0)
        # freeze instances that were already done at loop entry
        x = _tree_freeze(done, x, x1, nb)
        r = _tree_freeze(done, r, r1, nb)
        p = _tree_freeze(done, p, p1, nb)
        rho = jnp.where(done, rho, rho1)
        alpha = jnp.where(done, s["alpha"], alpha)
        omega = jnp.where(done, s["omega"], omega)
        rnorm = jnp.where(done, s["rnorm"], rn1)
        it = s["it"] + jnp.logical_not(done)
        done = jnp.logical_or(done, jnp.logical_or(rnorm <= atol, breakdown))
        return dict(x=x, r=r, p=p, rho=rho, alpha=alpha, omega=omega,
                    rnorm=rnorm, it=it, k=s["k"] + 1, done=done,
                    breakdown=jnp.logical_or(s["breakdown"], breakdown))

    out = lax.while_loop(cond, body, init_state)
    if return_info:
        rn, cutoff = out["rnorm"], atol
        if M is not None:   # report the true residual, not M(b - A x)
            rn = _tree_l2(_tree_sub(b0, matvec0(out["x"])), nb)
            cutoff = jnp.maximum(tol * _tree_l2(b0, nb), 1e-30)
        return out["x"], SolveInfo(iterations=out["it"], residual=rn,
                                   converged=rn <= cutoff)
    return out["x"]


# ---------------------------------------------------------------------------
# GMRES (restarted; flat (B, d) core, masked restarts)
# ---------------------------------------------------------------------------

def _flat_init(init, b_flat, batch_ndim: int):
    """Flatten an init pytree to the (B, d) layout (zeros when None)."""
    if init is None:
        return jnp.zeros_like(b_flat)
    if batch_ndim == 0:
        return _ravel1(init)[None]
    return jax.vmap(_ravel1)(init)


def _gmres_flat(mv: Callable, b_flat, x0, *, tol: float, restart: int,
                maxiter: int):
    """Shared restarted-GMRES core on the flat (B, d) layout.

    Runs batched Arnoldi cycles in one masked while_loop; returns
    ``(x, rn, it, atol)`` with per-instance residuals/iteration counts.
    ``maxiter`` is the total matvec budget; the cycle cap is
    ``ceil(maxiter / restart)``.
    """
    B, d = b_flat.shape
    m = min(restart, d)
    max_cycles = max(1, -(-maxiter // m))       # ceil: total matvec budget

    b_norm = jnp.linalg.norm(b_flat, axis=-1)                    # (B,)
    atol = jnp.maximum(tol * b_norm, 1e-30)

    def arnoldi_cycle(x):
        r = b_flat - mv(x)                                       # (B, d)
        beta = jnp.linalg.norm(r, axis=-1)                       # (B,)
        safe_beta = jnp.where(beta == 0, 1.0, beta)
        V = jnp.zeros((B, m + 1, d), b_flat.dtype)
        V = V.at[:, 0].set(r / safe_beta[:, None])
        H = jnp.zeros((B, m + 1, m), b_flat.dtype)

        def step(carry, j):
            V, H = carry
            w = mv(V[:, j])                                      # (B, d)
            # modified Gram-Schmidt against all basis vectors (masked)
            def ortho(i, w_h):
                w, H = w_h
                hij = jnp.where(i <= j,
                                jnp.sum(jnp.conj(V[:, i]) * w, axis=-1), 0.0)
                w = w - hij[:, None] * V[:, i]
                H = H.at[:, i, j].set(jnp.where(i <= j, hij, H[:, i, j]))
                return w, H
            w, H = lax.fori_loop(0, m, ortho, (w, H))
            hn = jnp.linalg.norm(w, axis=-1)
            H = H.at[:, j + 1, j].set(hn)
            V = V.at[:, j + 1].set(w / jnp.where(hn == 0, 1.0, hn)[:, None])
            return (V, H), None

        (V, H), _ = lax.scan(step, (V, H), jnp.arange(m))
        # least squares per instance: min ||beta e1 - H y||
        e1 = jnp.zeros((B, m + 1), b_flat.dtype).at[:, 0].set(beta)
        y = jax.vmap(lambda Hi, ei: jnp.linalg.lstsq(Hi, ei, rcond=None)[0])(
            H, e1)
        return x + jnp.einsum("bmd,bm->bd", V[:, :m], y)

    rn0 = jnp.linalg.norm(b_flat - mv(x0), axis=-1)
    done0 = rn0 <= atol
    it0 = jnp.zeros((B,), jnp.int32)

    def cond(state):
        _, _, _, k, done = state
        return jnp.logical_and(k < max_cycles, jnp.logical_not(jnp.all(done)))

    def body(state):
        x, rn, it, k, done = state
        x1 = arnoldi_cycle(x)
        rn1 = jnp.linalg.norm(b_flat - mv(x1), axis=-1)
        x = jnp.where(done[:, None], x, x1)                      # freeze
        rn = jnp.where(done, rn, rn1)
        it = it + jnp.logical_not(done)
        done = jnp.logical_or(done, rn <= atol)
        return x, rn, it, k + 1, done

    x, rn, it, _, done = lax.while_loop(cond, body,
                                        (x0, rn0, it0, 0, done0))
    return x, rn, it, atol


def solve_gmres(matvec: Callable, b, *, init=None, tol: float = 1e-6,
                restart: int = 20, maxiter: int = 1000, ridge: float = 0.0,
                precond=None, return_info: bool = False, batch_ndim: int = 0):
    """Restarted GMRES.  Flattens instances to run batched Arnoldi cycles.

    ``maxiter`` is the total matvec budget, like the other iterative
    solvers; the cycle cap is ``ceil(maxiter / restart)`` (so the uniform
    engine default of 1000 means ~50 restart cycles, not 1000).
    ``precond`` applies as a left preconditioner; the loop iterates on the
    preconditioned residual, but ``SolveInfo`` always reports the TRUE
    residual.  Converged instances skip further cycles via per-instance
    masks.
    """
    matvec = _damped(matvec, ridge)
    matvec0, b0 = matvec, b
    M = _resolve_precond(precond, matvec, b, batch_ndim)
    if M is not None:
        inner = matvec
        matvec = lambda v: M(inner(v))
        b = M(b)

    view = ravel_view(matvec, b, batch_ndim)
    x0 = _flat_init(init, view.b, batch_ndim)
    x, rn, it, atol = _gmres_flat(view.mv, view.b, x0, tol=tol,
                                  restart=restart, maxiter=maxiter)
    x_tree = view.to_tree(x)
    if not return_info:
        return x_tree
    cutoff = atol
    if M is not None:   # report the true residual, not M(b - A x)
        rn = _tree_l2(_tree_sub(b0, matvec0(x_tree)), batch_ndim)
        cutoff = jnp.maximum(tol * _tree_l2(b0, batch_ndim), 1e-30)
    info = SolveInfo(iterations=it, residual=rn, converged=rn <= cutoff)
    if batch_ndim == 0:
        info = _squeeze_info(info)
    return x_tree, info


def solve_dense_gmres(matvec: Callable, b, *, init=None, tol: float = 1e-6,
                      restart: int = 20, maxiter: int = 1000,
                      ridge: float = 0.0, precond=None,
                      return_info: bool = False, batch_ndim: int = 0):
    """Batched preconditioned GMRES for the nonsymmetric *dense* regime.

    The nonsymmetric sibling of ``pallas_cg``'s regime: materializes the
    per-instance operators once (d probing matvecs for the whole batch,
    d ≤ ``MAX_DENSE_DIM``) and then runs the shared restarted-Arnoldi core
    with each matvec as one batched (B, d, d) × (B, d) contraction — no
    re-tracing of the user's matvec closure inside the cycles.  ``"jacobi"``
    preconditioning reads the diagonal straight off the materialized
    operator (no extra probing); a callable ``precond`` is applied on the
    flat (instance-shaped) vectors as a left preconditioner.  ``SolveInfo``
    always reports the TRUE residual.
    """
    matvec = _damped(matvec, ridge)
    view = ravel_view(matvec, b, batch_ndim)
    d = view.b.shape[-1]
    if d > MAX_DENSE_DIM:   # guard BEFORE the d-matvec dense materialization
        raise ValueError(
            f"dense_gmres materializes dense systems; d={d} exceeds "
            f"MAX_DENSE_DIM={MAX_DENSE_DIM} — use method='gmres' instead")
    A, _ = materialize_batched(matvec, b, batch_ndim, view=view)

    def dense_mv(vf):                                   # (B, d) -> (B, d)
        return dense_matvec(A, vf)

    # "jacobi" reads the diagonal straight off the materialized operator
    # (no extra probing); validation and the safe-diagonal threshold live
    # in _resolve_precond/jacobi_preconditioner, shared with all solvers.
    M_tree = _resolve_precond(
        precond, matvec, b, batch_ndim,
        diag=view.to_tree(jnp.diagonal(A, axis1=-2, axis2=-1)),
        materialized=A if view.batched else A[0])
    if M_tree is None:
        M_flat = None
    elif view.batched:
        M_flat = lambda vf: jax.vmap(_ravel1)(M_tree(view.to_tree(vf)))
    else:
        M_flat = lambda vf: _ravel1(M_tree(view.to_tree(vf)))[None]

    mv = dense_mv if M_flat is None else (lambda vf: M_flat(dense_mv(vf)))
    b_flat = view.b if M_flat is None else M_flat(view.b)
    x0 = _flat_init(init, view.b, batch_ndim)
    x, rn, it, atol = _gmres_flat(mv, b_flat, x0, tol=tol, restart=restart,
                                  maxiter=maxiter)
    x_tree = view.to_tree(x)
    if not return_info:
        return x_tree
    if M_flat is not None:   # report the true residual, not M(b - A x)
        rn = jnp.linalg.norm(view.b - dense_mv(x), axis=-1)
        atol = jnp.maximum(tol * jnp.linalg.norm(view.b, axis=-1), 1e-30)
    info = SolveInfo(iterations=it, residual=rn, converged=rn <= atol)
    if batch_ndim == 0:
        info = _squeeze_info(info)
    return x_tree, info


# ---------------------------------------------------------------------------
# Direct and Neumann
# ---------------------------------------------------------------------------

def solve_lu(matvec: Callable, b, *, init=None, tol: float = 1e-6,
             ridge: float = 0.0, return_info: bool = False,
             batch_ndim: int = 0, **_):
    """Materialize A and solve densely.  For small/d≤few-thousand systems."""
    del init
    matvec = _damped(matvec, ridge)
    A, view = materialize_batched(matvec, b, batch_ndim)
    x = jnp.linalg.solve(A, view.b[..., None])[..., 0]
    if return_info:
        rn = jnp.linalg.norm(view.b - dense_matvec(A, x), axis=-1)
        atol = jnp.maximum(tol * jnp.linalg.norm(view.b, axis=-1), 1e-30)
        it = jnp.zeros_like(rn, dtype=jnp.int32)
        # rn <= atol is False for NaN residuals (singular A) — reported honestly
        info = SolveInfo(iterations=it, residual=rn, converged=rn <= atol)
        if batch_ndim == 0:
            info = _squeeze_info(info)
        return view.to_tree(x), info
    return view.to_tree(x)


def solve_neumann(matvec: Callable, b, *, init=None, maxiter: int = 10,
                  tol: float = 0.0, ridge: float = 0.0,
                  return_info: bool = False, batch_ndim: int = 0, **_):
    """Approximate (I - M)⁻¹ b ≈ Σ_{k<K} Mᵏ b where matvec(v) = v - M v.

    I.e. interprets ``matvec`` as A = I - M and truncates the Neumann series.
    Matches "Jacobian-free backprop" / phantom-gradient style approximations.
    ``ridge`` damps A (shrinks M, improving contraction) like the other
    solvers.  Vmap-safe: instances whose series term drops below tolerance
    freeze while stragglers keep summing, and the loop exits early once the
    whole batch is done (so the engine-level maxiter is a cap, not a cost).
    The local default ``tol=0`` preserves the classic fixed-K truncation;
    ``solve()`` forwards its tol, making engine-routed calls tol-aware.
    """
    del init
    nb = batch_ndim
    matvec = _damped(matvec, ridge)
    atol = jnp.maximum(tol * _tree_l2(b, nb), 1e-30)

    def mfun(v):  # M v = v - A v
        return _tree_sub(v, matvec(v))

    it0 = jnp.zeros_like(atol, dtype=jnp.int32)
    done0 = _tree_l2(b, nb) <= atol   # b = first series term

    def cond(state):
        _, _, _, k, done = state
        return jnp.logical_and(k < maxiter, jnp.logical_not(jnp.all(done)))

    def body(state):
        acc, term, it, k, done = state
        term1 = mfun(term)
        acc = _tree_freeze(done, acc, _tree_add(acc, term1), nb)
        term = _tree_freeze(done, term, term1, nb)
        it = it + jnp.logical_not(done)
        done = jnp.logical_or(done, _tree_l2(term, nb) <= atol)
        return acc, term, it, k + 1, done

    acc, _, it, _, _ = lax.while_loop(cond, body, (b, b, it0, 0, done0))
    if return_info:
        rn = _tree_l2(_tree_sub(b, matvec(acc)), nb)
        # rn <= atol is False for NaN/diverged series — reported honestly
        info = SolveInfo(iterations=it, residual=rn, converged=rn <= atol)
        return acc, info
    return acc


# ---------------------------------------------------------------------------
# approximate backward application (fixed matvec budget, no convergence loop)
# ---------------------------------------------------------------------------

BACKWARD_MODES = ("exact", "one_step", "neumann_k", "jacobian_free")


def approx_matvec_count(backward: str, backward_iters: int = 8) -> int:
    """Operator applications an approximate backward mode spends (host int).

    ``jacobian_free`` → 0, ``one_step`` → 1, ``neumann_k`` → k.  The error
    estimate, when requested, costs one extra matvec on top of this.
    """
    if backward == "jacobian_free":
        return 0
    if backward == "one_step":
        return 1
    if backward == "neumann_k":
        return int(backward_iters)
    raise ValueError(f"unknown approximate backward mode {backward!r}; "
                     f"expected one of {BACKWARD_MODES[1:]}")


def approx_inverse_apply(matvec: Callable, b, *, backward: str,
                         backward_iters: int = 8, ridge: float = 0.0,
                         precond=None, batch_ndim: int = 0, tol: float = 1e-6,
                         error_estimate: bool = True,
                         return_info: bool = False):
    """Apply an O(k)-matvec polynomial approximation of ``A⁻¹`` to ``b``.

    The cheap-backward counterpart of ``route_solve``: instead of iterating a
    solver to convergence, spend a *fixed* matvec budget — trip counts are
    static, so jit/vmap shapes never depend on conditioning:

    - ``"jacobian_free"``: ``u = b`` (0 matvecs — the Bolte et al. 2023 limit
      where ``A ≈ I``; any ``precond`` is ignored by construction).
    - ``"one_step"``: one preconditioned Richardson step from ``u₀ = M⁻¹b``,
      i.e. ``u = u₀ + M⁻¹(b − A u₀)`` (1 matvec).  Unpreconditioned this is
      the hand formula ``u = 2b − A b``.
    - ``"neumann_k"``: exactly ``k = backward_iters`` preconditioned
      Richardson steps ``u ← u + M⁻¹(b − A u)`` from ``u₀ = M⁻¹b`` (k
      matvecs, one ``fori_loop`` with a static trip count; contrast
      ``solve_neumann``'s tolerance-masked loop).  Unpreconditioned this
      is the truncated Neumann series ``Σ_{j≤k} (I − A)ʲ b``, which
      converges iff ``‖I − A‖ < 1`` — true for contractive fixed-point
      declarations (``A = I − ∂T``), NOT for stationarity declarations
      (``A = −H`` with ``H ⪰ 0``), where ``precond="jacobi"`` restores
      ``‖I − M⁻¹A‖ < 1`` for diagonally dominant Hessians.

    ``ridge`` damps ``A`` exactly as in the iterative solvers.  With
    ``return_info=True`` returns ``(u, SolveInfo)`` where ``iterations`` is
    the matvec budget spent and — when ``error_estimate=True`` — the
    ``hypergrad_error_estimate`` field carries the relative residual
    ``‖b − A u‖ / ‖b‖`` (one extra matvec, the honesty contract of the
    approximate modes).  For a contraction ``‖I − A‖ = ρ`` the neumann_k
    estimate is exactly ``ρ`` to the power ``k+1``-ish, hence monotone
    decreasing in ``k``.
    """
    if backward == "exact" or backward not in BACKWARD_MODES:
        raise ValueError(f"approx_inverse_apply handles {BACKWARD_MODES[1:]}; "
                         f"got backward={backward!r} (route 'exact' through "
                         "route_solve)")
    nb = batch_ndim
    mv = _damped(matvec, ridge)
    if backward == "jacobian_free":
        u = b
    elif backward == "one_step":
        M = _resolve_precond(precond, mv, b, nb)
        if M is None:
            u = _tree_sub(_tree_scale(b, 2.0, nb), mv(b))
        else:
            u0 = M(b)
            u = _tree_add(u0, M(_tree_sub(b, mv(u0))), batch_ndim=nb)
    else:  # neumann_k
        k = int(backward_iters)
        if k < 1:
            raise ValueError("backward='neumann_k' needs backward_iters >= 1")
        M = _resolve_precond(precond, mv, b, nb)

        if M is None:
            def body(_, u):
                return _tree_add(u, _tree_sub(b, mv(u)), batch_ndim=nb)
            u0 = b
        else:
            def body(_, u):
                return _tree_add(u, M(_tree_sub(b, mv(u))), batch_ndim=nb)
            u0 = M(b)

        u = lax.fori_loop(0, k, body, u0)

    if not return_info:
        return u
    bn = _tree_l2(b, nb)
    spent = jnp.full(bn.shape, approx_matvec_count(backward, backward_iters),
                     dtype=jnp.int32)
    if error_estimate:
        rn = _tree_l2(_tree_sub(b, mv(u)), nb)
        est = rn / jnp.maximum(bn, 1e-30)
        info = SolveInfo(iterations=spent, residual=rn,
                         converged=rn <= jnp.maximum(tol * bn, 1e-30),
                         hypergrad_error_estimate=est)
    else:
        rn = jnp.full(bn.shape, jnp.nan, dtype=bn.dtype)
        info = SolveInfo(iterations=spent, residual=rn,
                         converged=jnp.zeros(bn.shape, dtype=bool))
    if obs_events.observing():
        tags = _solve_event_tags(f"approx_{backward}", matvec, b,
                                 {"batch_ndim": nb})
        extra = ({"hypergrad_error_estimate": info.hypergrad_error_estimate}
                 if info.hypergrad_error_estimate is not None else {})
        obs_events.jit_event("solve", tags, iterations=info.iterations,
                             residual=info.residual,
                             converged=info.converged, **extra)
    return u, info


# ---------------------------------------------------------------------------
# Pallas fused batched-CG (dense small-system regime)
# ---------------------------------------------------------------------------

MAX_DENSE_DIM = 512


def solve_pallas_cg(matvec: Callable, b, *, init=None, tol: float = 1e-6,
                    maxiter: int = 1000, ridge: float = 0.0, precond=None,
                    return_info: bool = False, batch_ndim: int = 0,
                    interpret: Optional[bool] = None, block_b="auto",
                    tap=None):
    """Materialize per-instance operators and run the fused Pallas CG kernel.

    Dense small-system regime (d ≤ ``MAX_DENSE_DIM``) that dominates
    hyperopt and DEQ workloads: the whole batch of (d × d) systems iterates
    inside one kernel, VMEM-resident, with per-instance convergence masks.

    ``block_b`` defaults to ``"auto"``: the tile height resolves through
    the autotuning cache (``analysis.autotune.choose_block_b``) per
    ``(backend, B, d, dtype)``, falling back to the legacy schedule when
    the regime was never swept — so the solve service's bucket dispatch
    and ``IterativeSolver``'s backward solve ride tuned schedules with no
    caller changes.  Pass an int to pin the schedule by hand.

    ``info.residual`` is the true residual ``|b - A x|`` the kernel itself
    stopped on (it recomputes it and restarts CG while it is above
    ``tol``), so ``info.converged`` reports the kernel's own decision.
    ``info.iterations`` is each system's own CG steps and ``info.matvecs``
    the matvecs its kernel block ran, both counted by the kernel.

    ``tap`` (a zero array of shape ``batch + (2,)``) reads the same two
    counts for the backward solve: they are its cotangent when the caller
    differentiates with respect to it (``batched_cg``'s ``tap``).
    """
    if init is not None:
        raise ValueError("pallas_cg always starts from zero; warm starts "
                         "are not supported — use method='cg' instead")
    if precond is not None:
        raise ValueError("pallas_cg does not support preconditioning")
    from repro.kernels.batched_cg.ops import batched_cg  # lazy: avoid cycle

    matvec = _damped(matvec, ridge)
    view = ravel_view(matvec, b, batch_ndim)
    d = view.b.shape[-1]
    if d > MAX_DENSE_DIM:   # guard BEFORE the d-matvec dense materialization
        raise ValueError(
            f"pallas_cg materializes dense systems; d={d} exceeds "
            f"MAX_DENSE_DIM={MAX_DENSE_DIM} — use a matrix-free solver")
    A, _ = materialize_batched(matvec, b, batch_ndim, view=view)
    if tap is not None:
        tap = jnp.reshape(tap, (-1, 2))
    x, rn, counts = batched_cg(A, view.b, tol=tol, maxiter=maxiter,
                               block_b=block_b, interpret=interpret, tap=tap,
                               return_info=True)
    if return_info:
        atol = jnp.maximum(tol * jnp.linalg.norm(view.b, axis=-1), 1e-30)
        info = SolveInfo(iterations=counts[:, 0], residual=rn,
                         converged=rn <= atol, matvecs=counts[:, 1])
        if batch_ndim == 0:
            info = _squeeze_info(info)
        return view.to_tree(x), info
    return view.to_tree(x)


# ---------------------------------------------------------------------------
# SolverSpec registry and the uniform entry point
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SolverSpec:
    """A registered linear solver and its dispatch-relevant properties."""
    name: str
    fn: Callable
    symmetric_only: bool = False     # requires A symmetric (PSD)
    matrix_free: bool = True         # False: materializes A densely
    supports_precond: bool = False
    description: str = ""


_REGISTRY: dict = {}


def _solve_event_tags(name, matvec, b, kw) -> dict:
    """Trace-time static tags for a solve event: solver, B, d, dtype (+
    mesh_size for mesh-placed operators).  Shapes/dtypes are read off the
    rhs tracers, so this is jit/vmap-safe."""
    nb = kw.get("batch_ndim")
    if nb is None and isinstance(matvec, LinearOperator):
        nb = matvec.batch_ndim
    nb = int(nb or 0)
    leaves = jax.tree_util.tree_leaves(b)
    B, total, dtype = 1, 0, ""
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        size = 1
        for s in shape:
            size *= int(s)
        total += size
    if leaves:
        first = getattr(leaves[0], "shape", ())
        dtype = str(getattr(leaves[0], "dtype", ""))
        if nb >= 1 and len(first) >= 1:
            B = int(first[0])
    tags = {"solver": str(name), "B": B, "d": total // max(B, 1),
            "dtype": dtype}
    if getattr(matvec, "is_sharded", False):
        tags["mesh_size"] = int(matvec.mesh.size)
    return tags


def _observed(name: str, fn: Callable) -> Callable:
    """Wrap a registry solver with jit-safe solve telemetry.

    The wrapper is the instrumentation seam for *every* registry solver:
    with observability off (the default) it is a pure pass-through, so
    traced programs are bit-identical to an uninstrumented build.  With
    ``observe(enabled=True)`` at trace time it forces ``return_info=True``
    on the underlying solver and stages the ``solve_start``/``solve``
    event pair carrying the per-instance diagnostics as ONE
    ``jax.debug.callback`` (host callbacks dominate enabled-mode cost),
    returning exactly what the caller asked for.  Because the seam sits
    *outside* the sharded solvers' ``shard_map``, the callback fires once
    per compiled program execution — not once per device.
    """
    @functools.wraps(fn)
    def wrapper(matvec, b, **kw):
        if not obs_events.observing():
            return fn(matvec, b, **kw)
        tags = _solve_event_tags(name, matvec, b, kw)
        want_info = bool(kw.pop("return_info", False))
        try:
            x, info = fn(matvec, b, return_info=True, **kw)
        except TypeError:
            # a custom-registered solver outside the return_info contract:
            # announce the solve, run it uninstrumented rather than fail
            obs_events.jit_event("solve_start", tags)
            if want_info:
                return fn(matvec, b, return_info=True, **kw)
            return fn(matvec, b, **kw)
        extra = {}
        if getattr(info, "hypergrad_error_estimate", None) is not None:
            extra["hypergrad_error_estimate"] = info.hypergrad_error_estimate
        obs_events.jit_event_pair("solve_start", "solve", tags,
                                  iterations=info.iterations,
                                  residual=info.residual,
                                  converged=info.converged, **extra)
        return (x, info) if want_info else x

    wrapper.__wrapped__ = fn
    return wrapper


def register_solver(name: str, fn: Callable, **attrs) -> SolverSpec:
    """Register (or override) a solver under ``name`` in the global registry.

    The stored ``fn`` is wrapped with the jit-safe telemetry seam (see
    ``_observed``) — a pure pass-through unless ``repro.observability``
    is enabled at trace time.
    """
    spec = SolverSpec(name=name, fn=_observed(name, fn), **attrs)
    _REGISTRY[name] = spec
    return spec


def get_spec(name: str) -> SolverSpec:
    """Look up a registered ``SolverSpec`` by name (ValueError if absent)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown linear solver {name!r}; "
                         f"available: {available_solvers()}") from None


def available_solvers():
    """Sorted names of every solver currently in the registry."""
    return sorted(_REGISTRY)


def get_solver(name_or_fn):
    """Resolve a registry name (or pass through a callable) to a solver fn.

    Returns the function as *registered*: the registry stores solvers
    behind the jit-safe telemetry seam (``_observed``), which is a
    routing detail — it is unwrapped here, so
    ``get_solver(name) is fn`` holds after ``register_solver(name, fn)``.
    """
    if callable(name_or_fn):
        return name_or_fn
    fn = get_spec(name_or_fn).fn
    return getattr(fn, "__wrapped__", fn)


def solver_is_symmetric(name_or_fn) -> bool:
    """True when the routed solver asserts a symmetric operator.

    The implicit-diff layer consults this when it *constructs* its
    ``JacobianOperator``: choosing a symmetric-only solver (``cg``,
    ``pallas_cg``) certifies ``A = Aᵀ``, so the operator is built with
    ``symmetric=True`` and the cotangent system ``Aᵀ u = v`` reuses the
    forward matvec (``A.T is A``).  Downstream, everything reads the flag
    off the operator, not off this hook.  Custom callables conservatively
    report False (general A).
    """
    if callable(name_or_fn):
        return False
    return get_spec(name_or_fn).symmetric_only


def _check_operator_routing(spec: SolverSpec, A) -> None:
    """Symmetric-only solvers must never receive an operator that declares
    itself nonsymmetric (an undeclared ``symmetric=None`` trusts the
    caller's solver choice, as matvec closures always had to).  The error
    names BOTH sides of the mismatch — the requested solver and the
    operator's declared flags — so auto-routing failures point at the
    declaration to fix."""
    if (isinstance(A, LinearOperator) and spec.symmetric_only
            and A.symmetric is False):
        raise ValueError(
            f"requested solver {spec.name!r} is symmetric-only, but the "
            f"operator {A!r} declares symmetric={A.symmetric} "
            f"(positive_definite={A.positive_definite}) — route a general "
            "solver (gmres/bicgstab/normal_cg/dense_gmres) instead, or fix "
            "the operator's declared flags if it really is symmetric")


def _resolve_auto(A, example, precond=None, init=None) -> str:
    """Pick a registry solver from operator structure + system size.

    Sharded operands dispatch first: a ``ShardedOperator`` (carrying a mesh
    + PartitionSpecs) routes to the distributed variants — ``sharded_cg``
    for declared-SPD, ``sharded_dense_gmres`` for small nonsymmetric
    systems whose instance dims stay device-local (each shard materializes
    its own batch slice), ``sharded_normal_cg`` otherwise — so every solve
    a mesh-placed operator reaches runs inside ``shard_map`` with no host
    gather.

    Sharded routing is COST-GATED (PR 9): the structural candidate above
    only wins when ``analysis.autotune.should_shard`` predicts it beats
    the single-device path at the operand's mesh size — measured tuning
    entries first, roofline model cold (which preserves the structural
    choice for batch sharding until measurements prove a regime loses).
    A refused regime falls back to the MATRIX-FREE classic solver
    (``cg``/``normal_cg``): the operator's matvec still runs its own
    ``shard_map``, but the solve loop stays out of the losing sharded
    dispatch.  Materializing fallbacks are never chosen — densifying a
    mesh-placed operator yields per-shard pieces, not the global stack.

    Single-device (``autotune.single_device_solver``, which the solve
    service mirrors): the dense small-system regime (d ≤ ``MAX_DENSE_DIM``)
    auto-materializes: SPD operators take the fused ``pallas_cg`` kernel
    (falling back to the batched ``dense_gmres`` when a preconditioner or a
    warm start is requested — ``pallas_cg`` supports neither — or when the
    system is float64, which the compiled kernel refuses), everything
    else ``dense_gmres``.  Above the crossover the solve stays matrix-free:
    ``cg`` only for declared-SPD operators (symmetric alone is not enough —
    CG on a symmetric *indefinite* system can report convergence with a
    wrong answer), ``normal_cg`` (general, transpose-capable) otherwise.
    ``example`` is one instance-shaped right-hand side (sizes the system).
    """
    spd = A.positive_definite if isinstance(A, LinearOperator) else False
    d = _ravel1(example).shape[0]
    if getattr(A, "is_sharded", False):
        from repro.analysis import autotune  # lazy: avoid import cycle
        Bn, _, dtype = autotune.operator_regime(A)
        plain = precond is None and init is None
        if autotune.should_shard(Bn, d, mesh_size=int(A.mesh.size),
                                 instance_sharded=A.instance_sharded,
                                 spd=spd, dtype=dtype, precond=precond,
                                 plain=plain):
            if spd:
                return "sharded_cg"
            if d <= MAX_DENSE_DIM and not A.instance_sharded:
                return "sharded_dense_gmres"
            return "sharded_normal_cg"
        return "cg" if spd else "normal_cg"
    from repro.analysis import autotune  # lazy: avoid import cycle
    leaves = jax.tree_util.tree_leaves(
        (example, A.example if isinstance(A, LinearOperator) else ()))
    return autotune.single_device_solver(
        spd, d, plain=precond is None and init is None,
        dtype=str(jnp.result_type(*leaves)))


# A mesh-placed operator upgrades the classic method names to their
# distributed variants, so ``solve="cg"`` in an ``ImplicitDiffSpec`` (which
# also certifies symmetry — see ``solver_is_symmetric``) transparently runs
# the sharded solve once placement is attached.  The single-device
# MATERIALIZING solvers also upgrade (``pallas_cg`` → ``sharded_cg``,
# ``lu`` → ``sharded_dense_gmres``): densifying a mesh-placed operator
# outside shard_map would gather the global (B, d, d) stack to one device,
# which this subsystem exists to avoid.  Matrix-free general solvers
# (gmres/bicgstab/neumann) keep their names: their matvecs already run
# under shard_map through the operator, with reductions partitioned by XLA.
_SHARDED_UPGRADE = {"cg": "sharded_cg", "normal_cg": "sharded_normal_cg",
                    "dense_gmres": "sharded_dense_gmres",
                    "pallas_cg": "sharded_cg",
                    "lu": "sharded_dense_gmres"}


def _upgrade_for_sharded(method, matvec, *, precond=None):
    """Upgrade a classic solver name for a mesh-placed operand — when the
    cost model approves the operand's mesh size.

    Matrix-free upgrades (``cg``/``normal_cg``) are COST-GATED through
    ``analysis.autotune.should_shard``: with measured evidence that this
    (B, d, mesh) regime loses to the single-device path, the classic name
    is kept (its matvec still runs under the operator's ``shard_map``;
    only the solve-loop dispatch stays single-device).  MATERIALIZING
    names (``pallas_cg``/``lu``/``dense_gmres``) always upgrade: their
    single-device forms would densify a mesh-placed operator into
    per-shard pieces, so the sharded variant is a correctness matter, not
    a tuning choice.  ``mesh.size == 1`` always upgrades (a 1-device mesh
    IS the single-device path, under the declared placement).
    """
    if callable(method) or not getattr(matvec, "is_sharded", False):
        return method
    target = _SHARDED_UPGRADE.get(method)
    if target is None:
        return method
    spec = _REGISTRY.get(method)
    if spec is not None and not spec.matrix_free:
        return target
    from repro.analysis import autotune  # lazy: avoid import cycle
    Bn, d, dtype = autotune.operator_regime(matvec)
    if autotune.should_shard(Bn, d, mesh_size=int(matvec.mesh.size),
                             instance_sharded=matvec.instance_sharded,
                             spd=bool(spec and spec.symmetric_only),
                             dtype=dtype, precond=precond):
        return target
    return method


def _emit_dispatch(requested, routed, matvec, b) -> None:
    """Report a routing decision as a trace-time ``dispatch`` event."""
    if obs_events.observing():
        name = lambda s: s if isinstance(s, str) else getattr(
            s, "__name__", "custom")
        obs_events.emit("dispatch",
                        dict(_solve_event_tags(name(routed), matvec, b, {}),
                             requested=name(requested)))


def route_solve(solve, matvec, b, *, tol: float = 1e-6, maxiter: int = 1000,
                ridge: float = 0.0, precond=None, init=None,
                return_info: bool = False):
    """Route one instance-shaped solve to a registry solver or a callable.

    The single dispatch point the differentiation layer calls for both the
    tangent (``A dx = b``) and cotangent (``Aᵀ u = v``) systems — ``solve``
    is a registry name, ``"auto"``, or a bare callable ``fn(matvec, b, tol,
    maxiter, ridge)``.  ``matvec`` may be a ``LinearOperator``: its
    symmetry flag is validated against the routed solver (symmetric-only
    solvers never receive a declared-nonsymmetric operator), ``"auto"``
    dispatches on its structure (dense small systems auto-materialize — see
    ``_resolve_auto``), and ``"jacobi"``/``"block_jacobi"`` preconditioners
    derive from ``operator.diagonal()`` instead of probing.  Mirrors
    ``solve()``'s contract: ``precond`` requires a registry solver that
    supports it and is never silently dropped.  Vmap-safe like every
    registry solver: batched tracers dispatch ONE masked solve for the
    whole batch.

    A *batch-aware* operator (``batch_ndim == 1``, e.g. a stacked
    ``DenseOperator`` the solve service dispatches per bucket) routes the
    whole batch as ONE masked solve — registry solvers receive
    ``batch_ndim=1`` and ``b``/``init`` carry the batch axis on every leaf.

    ``init`` warm-starts the routed solver (``"auto"`` then steers off
    ``pallas_cg``, which always starts from zero); ``return_info`` also
    returns the per-instance ``SolveInfo``.  Both require a registry
    solver — custom callables own their initialization and diagnostics.
    """
    requested = solve
    if solve == "auto":
        # _resolve_auto sizes the system from ONE instance: batch-aware
        # operators (batch_ndim == 1, e.g. sharded batched systems) carry
        # a leading batch axis on b that must not inflate d
        example = b
        if isinstance(matvec, LinearOperator) and matvec.batch_ndim == 1:
            example = jax.tree_util.tree_map(lambda l: l[0], b)
        solve = _resolve_auto(matvec, example, precond, init)
    solve = _upgrade_for_sharded(solve, matvec, precond=precond)
    _emit_dispatch(requested, solve, matvec, b)
    if callable(solve):
        if precond is not None:
            raise ValueError("precond requires a registry solver name; "
                             "bake it into the custom solve callable instead")
        if init is not None or return_info:
            raise ValueError("init/return_info require a registry solver "
                             "name; custom solve callables own their "
                             "initialization and diagnostics")
        return solve(matvec, b, tol=tol, maxiter=maxiter, ridge=ridge)
    spec = get_spec(solve)
    _check_operator_routing(spec, matvec)
    if precond is not None and not spec.supports_precond:
        raise ValueError(f"solver {spec.name!r} does not support "
                         "preconditioning; see SolverSpec.supports_precond")
    kwargs = dict(tol=tol, maxiter=maxiter, ridge=ridge)
    if precond is not None:
        kwargs["precond"] = precond
    if init is not None:
        kwargs["init"] = init
    if return_info:
        kwargs["return_info"] = True
    if isinstance(matvec, LinearOperator) and matvec.batch_ndim == 1 \
            and not spec.name.startswith("sharded_"):
        # sharded SOLVERS read batchedness off the operator themselves
        # (inside shard_map); every other batch-aware operator — including
        # a mesh-placed one whose sharded upgrade the cost model refused —
        # gets the whole batch dispatched as ONE masked solve
        kwargs["batch_ndim"] = 1
    return spec.fn(matvec, b, **kwargs)


register_solver("cg", solve_cg, symmetric_only=True, supports_precond=True,
                description="conjugate gradient (A symmetric PSD)")
register_solver("normal_cg", solve_normal_cg, supports_precond=True,
                description="CG on the normal equations (general A)")
register_solver("bicgstab", solve_bicgstab, supports_precond=True,
                description="BiCGSTAB (general square A)")
register_solver("gmres", solve_gmres, supports_precond=True,
                description="restarted GMRES (general square A)")
register_solver("dense_gmres", solve_dense_gmres, supports_precond=True,
                matrix_free=False,
                description="batched dense GMRES (materializes A; "
                            "nonsymmetric, d<=512)")
register_solver("lu", solve_lu, matrix_free=False,
                description="dense direct solve (materializes A)")
register_solver("neumann", solve_neumann,
                description="truncated Neumann series for I - M")
register_solver("pallas_cg", solve_pallas_cg, symmetric_only=True,
                matrix_free=False,
                description="fused Pallas batched-CG kernel (dense, d<=512)")


# --- distributed variants (impl in repro.distributed.sharded_operators) ----
# Registered here with lazy stubs so the registry surface is deterministic
# (importing repro.core never pulls the distributed layer; the import cycle
# linear_solve -> sharded_operators -> linear_solve resolves because this
# side is deferred to call time).  They require a ShardedOperator operand —
# the whole masked solve loop runs inside one shard_map on its mesh.

def solve_sharded_cg(matvec, b, **kw):
    """Distributed CG (SPD): whole masked loop under ``shard_map``; dot
    products go through the operator's ``psum`` reduction hook."""
    from repro.distributed import sharded_operators as dso
    return dso.sharded_solve_cg(matvec, b, **kw)


def solve_sharded_normal_cg(matvec, b, **kw):
    """Distributed CG on the normal equations (general square A)."""
    from repro.distributed import sharded_operators as dso
    return dso.sharded_solve_normal_cg(matvec, b, **kw)


def solve_sharded_dense_gmres(matvec, b, **kw):
    """Distributed dense GMRES: each shard materializes + solves its batch
    slice (batch sharding only)."""
    from repro.distributed import sharded_operators as dso
    return dso.sharded_solve_dense_gmres(matvec, b, **kw)


register_solver("sharded_cg", solve_sharded_cg, symmetric_only=True,
                supports_precond=True,
                description="distributed CG under shard_map "
                            "(ShardedOperator; A symmetric PSD)")
register_solver("sharded_normal_cg", solve_sharded_normal_cg,
                supports_precond=True,
                description="distributed normal-equations CG under "
                            "shard_map (ShardedOperator; general A)")
register_solver("sharded_dense_gmres", solve_sharded_dense_gmres,
                supports_precond=True, matrix_free=False,
                description="per-shard dense GMRES under shard_map "
                            "(ShardedOperator; batch sharding, d<=512)")

def __getattr__(name):
    # Back-compat: the pre-registry name -> fn mapping, computed live so
    # register_solver() stays visible.  Extend via register_solver, not by
    # mutating this dict (mutations are discarded).
    if name == "SOLVERS":
        return {n: spec.fn for n, spec in _REGISTRY.items()}
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def solve(matvec: Callable, b, *, method="cg", batch_axes: Optional[int] = None,
          precond=None, tol: float = 1e-6, maxiter: int = 1000,
          ridge: float = 0.0, init=None, return_info: bool = False,
          **solver_kwargs):
    """Uniform entry point of the batched linear-solve engine.

    Args:
      matvec: linear operator — a ``LinearOperator`` or a matvec closure.
        Unbatched: maps an instance pytree to an instance pytree.  With
        ``batch_axes`` set: maps *batched* pytrees (every leaf carrying the
        batch axis) to batched pytrees — i.e. the block-diagonal operator
        over all instances, applied at once.  A batch-aware operator
        (``batch_ndim == 1``) implies ``batch_axes=0`` automatically, and
        its symmetry/definiteness flags drive validation, ``"auto"``
        dispatch, and preconditioner derivation.
      b: right-hand side pytree (batched along ``batch_axes`` if set).
      method: registry name (see ``available_solvers()``), ``"auto"``
        (structure-driven dispatch: dense small systems auto-materialize to
        ``pallas_cg``/``dense_gmres``, large ones stay matrix-free), or a
        solver callable ``fn(matvec, b, **kw)``.  Callables cannot be
        combined with ``batch_axes`` (they would need to handle batching
        themselves); a batch-aware *operator* passes to a callable as-is,
        batching included.
      batch_axes: ``None`` for a single system, or an int axis carried by
        every leaf of ``b``/``init`` along which independent systems stack.
        The whole batch is solved by ONE masked while_loop: converged
        instances freeze while stragglers iterate.
      precond: ``None``, a callable v ↦ M⁻¹v, ``"jacobi"`` (diagonal — from
        ``operator.diagonal()`` when available, else probing), or
        ``"block_jacobi"`` (``LinearOperator`` only; blocks from the
        domain's pytree leaves or a ``BlockDiagonal``'s blocks).
      tol / maxiter / ridge / init: the usual solver controls.
      return_info: also return a ``SolveInfo`` with per-instance iteration
        counts, residuals and convergence flags.
    """
    # a callable method takes the operator as-is (it owns batching); the
    # batch-axes implication below is for registry solvers only
    if isinstance(matvec, LinearOperator) and not callable(method):
        if batch_axes is None and matvec.batch_ndim == 1:
            batch_axes = 0
        expected = 0 if batch_axes is None else 1
        if matvec.batch_ndim != expected or batch_axes not in (None, 0):
            raise ValueError(
                f"operator batch_ndim={matvec.batch_ndim} is incompatible "
                f"with batch_axes={batch_axes}; batch-aware operators carry "
                "their batch on axis 0")
    requested = method
    if method == "auto":
        example = b
        if batch_axes is not None:
            example = jax.tree_util.tree_map(
                lambda l: jnp.take(l, 0, axis=int(batch_axes)), b)
        method = _resolve_auto(matvec, example, precond, init)
    method = _upgrade_for_sharded(method, matvec, precond=precond)
    _emit_dispatch(requested, method, matvec, b)
    if callable(method):
        if batch_axes is not None:
            raise ValueError("batch_axes requires a registry solver name; "
                             "custom callables must handle batching")
        if precond is not None or return_info:
            raise ValueError("precond/return_info require a registry solver "
                             "name; pass them to the callable directly")
        return method(matvec, b, tol=tol, maxiter=maxiter, ridge=ridge,
                      init=init, **solver_kwargs)

    spec = get_spec(method)
    _check_operator_routing(spec, matvec)
    if precond is not None and not spec.supports_precond:
        raise ValueError(f"solver {spec.name!r} does not support "
                         "preconditioning; see SolverSpec.supports_precond")
    if batch_axes is None:
        return spec.fn(matvec, b, init=init, tol=tol, maxiter=maxiter,
                       ridge=ridge, precond=precond,
                       return_info=return_info, **solver_kwargs)

    axis = int(batch_axes)
    if axis != 0:
        move_in = functools.partial(jax.tree_util.tree_map,
                                    lambda l: jnp.moveaxis(l, axis, 0))
        move_out = functools.partial(jax.tree_util.tree_map,
                                     lambda l: jnp.moveaxis(l, 0, axis))
        inner_mv = matvec
        matvec = lambda v: move_in(inner_mv(move_out(v)))
        b = move_in(b)
        init = move_in(init) if init is not None else None

    out = spec.fn(matvec, b, init=init, tol=tol, maxiter=maxiter,
                  ridge=ridge, precond=precond, return_info=return_info,
                  batch_ndim=1, **solver_kwargs)
    if axis == 0:
        return out
    if return_info:
        x, info = out
        return move_out(x), info
    return move_out(out)
