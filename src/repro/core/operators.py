"""Pytree-native linear operators: the shared matvec abstraction.

Every layer of the stack ultimately touches the same object — a linear map
``A`` over a pytree domain, accessed through matrix-vector products.  The
paper's implicit differentiation needs ``A = -∂₁F(x*, θ)`` only through
JVPs/VJPs; the solve engine needs ``matvec``/``rmatvec`` plus structure
(symmetry, definiteness, diagonal access) to pick solvers and
preconditioners; the dense kernels need ``materialize()``.  This module
makes that object first class so the knowledge travels with the operator
instead of through side channels:

  * ``LinearOperator`` — the protocol: ``matvec`` / ``rmatvec`` /
    ``transpose()`` (``.T``) / ``diagonal()`` / ``materialize()`` /
    ``ravel_view()``, plus ``symmetric`` / ``positive_definite`` flags and
    ``batch_ndim`` batch-axis awareness.
  * ``JacobianOperator`` — ``∂f(x)`` (optionally negated) of a pytree
    mapping, with ``matvec`` as a JVP and ``rmatvec`` as a VJP — exactly the
    operator implicit differentiation solves against (paper §2.1).
  * ``DenseOperator`` — an explicit ``(d, d)`` or batched ``(B, d, d)``
    matrix acting on pytrees through a ravel.
  * ``RidgeShifted`` — ``A + λI`` damping that preserves structure
    (diagonal/materialize shift; symmetry survives, definiteness improves).
  * ``BlockDiagonal`` — independent blocks over a tuple of sub-domains;
    the source of block-Jacobi preconditioners.
  * ``ComposedOperator`` — ``outer ∘ inner`` products (preconditioner
    wrapping).
  * ``ravel_view()`` — the single flat ``(B, d)`` view of a (possibly
    batched) operator, shared by every dense-regime solver.

Defaults are matrix-free: ``rmatvec`` falls back to ``jax.linear_transpose``
(or reuses ``matvec`` when the operator declares symmetry), and
``diagonal()`` / ``materialize()`` fall back to basis-vector probing
(``d`` matvecs, batched across instances).  Structured operators override
them with O(1) access, which is what lets the dense small-system regime
auto-materialize instead of probing.

Example::

    F = jax.grad(inner_objective)                  # optimality mapping
    A = JacobianOperator(lambda x: F(x, theta), x_star,
                         negate=True, symmetric=True)
    u = linear_solve.route_solve("cg", A.T, cotangent, tol=1e-8)
    M = jacobi_preconditioner_from(A)              # from A.diagonal()

This module is the bottom layer: it imports nothing from ``repro`` so the
solve registry, the diff API, the runtime and the kernels can all build on
it without cycles.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.flatten_util  # registers jax.flatten_util.ravel_pytree
import jax.numpy as jnp
import numpy as np


def _ravel1(tree) -> jnp.ndarray:
    """Ravel one instance-shaped pytree to a flat vector."""
    return jax.flatten_util.ravel_pytree(tree)[0]


def _tree_add_scaled(a, b, alpha):
    return jax.tree_util.tree_map(lambda x, y: x + alpha * y, a, b)


def dense_matvec(A, v):
    """Batched dense matvec ``(B, d, d) × (B, d)`` at full f32 precision.

    The TPU runs f32 contractions at DEFAULT precision as one bf16 pass
    (~3 significant digits), which would floor every residual far above
    the solvers' tolerances.  What the extra passes cost on the chip is not
    measured.
    """
    return jnp.einsum("bij,bj->bi", A, v, precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# flat (B, d) view of a (possibly batched) operator
# ---------------------------------------------------------------------------

class RavelView(NamedTuple):
    """Batched flat representation: leaves ``(B, ...)`` <-> matrix ``(B, d)``.

    Unbatched calls get a synthetic ``B = 1`` axis (``batched=False``), so
    the dense-regime solver cores run one uniform ``(B, d)`` layout.
    """
    mv: Callable          # (B, d) -> (B, d)
    b: jnp.ndarray        # (B, d) raveled right-hand side
    to_tree: Callable     # (B, d) -> (batched) pytree
    batched: bool         # whether the original call was batch_ndim == 1


def ravel_view(matvec: Callable, b, batch_ndim: int = 0) -> RavelView:
    """The single flat view of an operator: ``matvec`` on raveled vectors.

    ``matvec`` may be a bare callable or a ``LinearOperator`` (operators are
    callable).  ``b`` supplies the domain structure and the raveled
    right-hand side.
    """
    if batch_ndim == 0:
        b_flat, unravel = jax.flatten_util.ravel_pytree(b)

        def mv(vf):  # (1, d) -> (1, d)
            return _ravel1(matvec(unravel(vf[0])))[None]

        return RavelView(mv, b_flat[None], lambda xf: unravel(xf[0]), False)

    example = jax.tree_util.tree_map(lambda l: l[0], b)
    _, unravel = jax.flatten_util.ravel_pytree(example)
    b_flat = jax.vmap(_ravel1)(b)

    def mv(vf):  # (B, d) -> (B, d)
        return jax.vmap(_ravel1)(matvec(jax.vmap(unravel)(vf)))

    return RavelView(mv, b_flat, jax.vmap(unravel), True)


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------

class LinearOperator:
    """A linear map over a pytree domain, known through matvecs + metadata.

    Attributes:
      example: an instance of the domain pytree (batched leaves when
        ``batch_ndim == 1``) — the structural witness every ravel-based
        default needs.
      batch_ndim: 0 for one system, 1 when every leaf carries a leading
        batch axis of independent systems (the block-diagonal-over-batch
        operator the vmap-safe solvers consume).
      symmetric: ``True`` (A = Aᵀ per instance), ``False`` (known general),
        or ``None`` (unknown — routing trusts the caller's solver choice).
      positive_definite: ``True`` asserts per-instance SPD (enables CG-family
        routing and Cholesky-style consumers downstream).

    Subclasses implement ``matvec``; everything else has matrix-free
    defaults.  Operators are callable (``A(v) == A.matvec(v)``) so they pass
    anywhere a matvec closure is expected.

    ``is_sharded`` marks mesh-placed operators
    (``repro.distributed.sharded_operators.ShardedOperator``); the solve
    registry reads it to dispatch the distributed solver variants without
    this bottom layer importing the distribution layer.
    """

    is_sharded = False

    def __init__(self, example, *, batch_ndim: int = 0,
                 symmetric: Optional[bool] = None,
                 positive_definite: bool = False):
        if batch_ndim not in (0, 1):
            raise ValueError(f"batch_ndim must be 0 or 1, got {batch_ndim}")
        if positive_definite and symmetric is False:
            raise ValueError("positive_definite=True asserts symmetry; "
                             "symmetric=False contradicts it")
        self.example = example
        self.batch_ndim = batch_ndim
        self.symmetric = True if positive_definite else symmetric
        self.positive_definite = positive_definite

    # -- core ------------------------------------------------------------
    def matvec(self, v):
        """Apply the operator to ``v`` (pytree → pytree)."""
        raise NotImplementedError

    def __call__(self, v):
        return self.matvec(v)

    def rmatvec(self, v):
        """Aᵀ v.  Symmetric operators reuse ``matvec``; the general default
        builds the transpose via ``jax.linear_transpose``.  Built per call,
        NOT cached on the instance: operators are long-lived public API and
        a closure traced under one jit/vmap leaks its tracers into later
        calls under a different (or no) transformation."""
        if self.symmetric:
            return self.matvec(v)
        (out,) = jax.linear_transpose(self.matvec, self.example)(v)
        return out

    def transpose(self) -> "LinearOperator":
        """Aᵀ as an operator (``self`` when symmetry is declared)."""
        if self.symmetric:
            return self
        return TransposedOperator(self)

    @property
    def T(self) -> "LinearOperator":
        """The transposed operator (alias for ``transpose()``)."""
        return self.transpose()

    # -- structure access (matrix-free probing defaults) -----------------
    def ravel_view(self, b=None) -> RavelView:
        """The flat ``(B, d)`` view of this operator (``b`` defaults to the
        structural example)."""
        return ravel_view(self.matvec, self.example if b is None else b,
                          self.batch_ndim)

    def _instance_dim(self) -> int:
        example = self.example
        if self.batch_ndim:
            example = jax.tree_util.tree_map(lambda l: l[0], example)
        return _ravel1(example).shape[0]

    def diagonal(self):
        """diag(A) with the domain's structure (default: ``d`` probing
        matvecs, batched across instances)."""
        view = self.ravel_view()
        B, d = view.b.shape

        def entry(i):
            e = jnp.zeros(d, view.b.dtype).at[i].set(1.0)
            return view.mv(jnp.broadcast_to(e, (B, d)))[:, i]   # (B,)

        diag = jax.vmap(entry)(jnp.arange(d)).T                 # (B, d)
        return view.to_tree(diag)

    def materialize(self) -> jnp.ndarray:
        """The dense matrix: ``(d, d)`` unbatched, ``(B, d, d)`` batched.

        Default probes with basis vectors broadcast across the batch, so the
        cost is ``d`` matvecs regardless of batch size; structured operators
        (``DenseOperator``, ``RidgeShifted`` over one) override with O(1)
        access — the auto-materialization the dense solvers rely on.
        """
        view = self.ravel_view()
        B, d = view.b.shape

        def col(i):
            e = jnp.zeros(d, view.b.dtype).at[i].set(1.0)
            return view.mv(jnp.broadcast_to(e, (B, d)))         # (B, d)

        cols = jax.vmap(col)(jnp.arange(d))                     # (d, B, d)
        A = cols.transpose(1, 2, 0)                             # A[b][:, i]
        return A if self.batch_ndim else A[0]

    def raveled(self) -> "RaveledOperator":
        """This operator re-expressed on the raveled flat vector domain."""
        return RaveledOperator(self)

    def __repr__(self):
        flags = []
        if self.symmetric:
            flags.append("symmetric")
        if self.positive_definite:
            flags.append("PD")
        if self.batch_ndim:
            flags.append("batched")
        return (f"{type(self).__name__}(d={self._instance_dim()}"
                + (", " + ",".join(flags) if flags else "") + ")")


class TransposedOperator(LinearOperator):
    """Aᵀ of a wrapped operator; transpose of the transpose is the original.

    Assumes a square operator (domain == codomain structure), which is what
    every implicit-diff system in this codebase is.
    """

    def __init__(self, op: LinearOperator):
        super().__init__(op.example, batch_ndim=op.batch_ndim,
                         symmetric=op.symmetric,
                         positive_definite=op.positive_definite)
        self.op = op

    def matvec(self, v):
        """Apply ``Aᵀ`` (the base operator's ``rmatvec``)."""
        return self.op.rmatvec(v)

    def rmatvec(self, v):
        """Apply ``A`` (the base operator's ``matvec``)."""
        return self.op.matvec(v)

    def transpose(self) -> LinearOperator:
        """The original operator back."""
        return self.op


# ---------------------------------------------------------------------------
# concrete operators
# ---------------------------------------------------------------------------

class FunctionOperator(LinearOperator):
    """Adapt a matvec closure (and optional rmatvec) to the protocol.

    The bridge between the callable world and the operator world: routing
    layers wrap incoming closures with the flags they know, and everything
    downstream reads the flags off the operator.
    """

    def __init__(self, matvec: Callable, example, *,
                 rmatvec: Optional[Callable] = None, batch_ndim: int = 0,
                 symmetric: Optional[bool] = None,
                 positive_definite: bool = False):
        super().__init__(example, batch_ndim=batch_ndim, symmetric=symmetric,
                         positive_definite=positive_definite)
        self._matvec = matvec
        self._rmatvec = rmatvec

    def matvec(self, v):
        """Apply the wrapped matvec callable."""
        return self._matvec(v)

    def rmatvec(self, v):
        """Apply the adjoint (supplied, or derived via ``jax.vjp``)."""
        if self._rmatvec is not None:
            return self._rmatvec(v)
        return super().rmatvec(v)


class JacobianOperator(LinearOperator):
    """``∂f(x₀)`` (optionally negated) of a pytree mapping ``f``.

    ``matvec`` is a JVP at ``x₀`` and ``rmatvec`` a VJP (linearized once and
    cached), so the operator is exactly the paper's access pattern: the
    implicit system ``A dx = b`` with ``A = -∂₁F(x*, θ)`` is
    ``JacobianOperator(lambda x: F(x, *theta), x_star, negate=True)``.

    ``symmetric=True`` certifies ``A = Aᵀ`` — true whenever ``f`` is itself
    a gradient mapping (A is then a Hessian), which is what lets the
    cotangent system reuse the forward matvec.
    """

    def __init__(self, fun: Callable, primal, *, negate: bool = False,
                 batch_ndim: int = 0, symmetric: Optional[bool] = None,
                 positive_definite: bool = False):
        super().__init__(primal, batch_ndim=batch_ndim, symmetric=symmetric,
                         positive_definite=positive_definite)
        self.fun = fun
        self.primal = primal
        self.negate = negate
        self._sign = -1.0 if negate else 1.0

    def matvec(self, v):
        """Jacobian-vector product: JVP of the map at the primal point."""
        _, jv = jax.jvp(self.fun, (self.primal,), (v,))
        return jax.tree_util.tree_map(jnp.negative, jv) if self.negate else jv

    def rmatvec(self, v):
        """Vector-Jacobian product: VJP of the map at the primal point."""
        if self.symmetric:
            return self.matvec(v)
        # linearized per call (not cached on the instance): a VJP closure
        # traced under one transformation would leak its tracers into
        # calls made under another — see LinearOperator.rmatvec
        _, vjp_fun = jax.vjp(self.fun, self.primal)
        (out,) = vjp_fun(v)
        return jax.tree_util.tree_map(jnp.negative, out) if self.negate \
            else out


class SampledJacobianOperator(LinearOperator):
    """Monte-Carlo estimate of an expectation Jacobian ``E_b[∂₁f(x₀, b)]``.

    ``fun(x, batch)`` maps the domain pytree to itself for one minibatch
    (the canonical case: a minibatch gradient mapping, whose Jacobian is a
    minibatch Hessian); ``batches`` is a pytree whose leaves carry a
    leading resample axis of length ``k``.  ``matvec`` vmaps one JVP per
    batch and averages over the resample axis — ``k`` Hessian-vector
    products per application when ``fun`` is a gradient mapping.  The
    average is an unbiased estimate of the full-batch Jacobian-vector
    product whose variance shrinks like ``1/k``; when the ``k`` batches
    are equal-sized and partition the dataset, the average IS the
    full-batch product exactly (the stochastic implicit-diff layer's
    ``backward_data="full"`` escape hatch relies on this identity).

    ``negate`` flips the sign (the implicit system solves against
    ``A = -∂₁F``); ``symmetric=True`` certifies every per-batch Jacobian
    is symmetric (``fun`` a per-batch gradient mapping), which makes the
    mean symmetric and lets the cotangent solve reuse ``matvec``.
    """

    def __init__(self, fun: Callable, primal, batches, *,
                 negate: bool = False, batch_ndim: int = 0,
                 symmetric: Optional[bool] = None,
                 positive_definite: bool = False):
        super().__init__(primal, batch_ndim=batch_ndim, symmetric=symmetric,
                         positive_definite=positive_definite)
        leaves = jax.tree_util.tree_leaves(batches)
        if not leaves:
            raise ValueError("batches must be a non-empty pytree whose "
                             "leaves carry a leading resample axis")
        self.fun = fun
        self.primal = primal
        self.batches = batches
        self.negate = negate
        self.num_samples = int(leaves[0].shape[0])

    def _mean(self, stacked):
        sign = -1.0 if self.negate else 1.0
        return jax.tree_util.tree_map(
            lambda leaf: sign * jnp.mean(leaf, axis=0), stacked)

    def matvec(self, v):
        """Resample-averaged JVP of the per-batch map at the primal."""
        def one(batch):
            _, jv = jax.jvp(lambda x: self.fun(x, batch),
                            (self.primal,), (v,))
            return jv

        return self._mean(jax.vmap(one)(self.batches))

    def rmatvec(self, v):
        """Resample-averaged VJP (reuses ``matvec`` under declared
        symmetry).  Linearized per call, not cached on the instance — see
        ``LinearOperator.rmatvec``."""
        if self.symmetric:
            return self.matvec(v)

        def one(batch):
            _, vjp_fun = jax.vjp(lambda x: self.fun(x, batch), self.primal)
            return vjp_fun(v)[0]

        return self._mean(jax.vmap(one)(self.batches))


class DenseOperator(LinearOperator):
    """An explicit matrix ``(d, d)`` (or batched ``(B, d, d)``) acting on
    pytrees through a ravel.  ``diagonal``/``materialize`` are O(1)."""

    def __init__(self, A: jnp.ndarray, example=None, *,
                 symmetric: Optional[bool] = None,
                 positive_definite: bool = False):
        A = jnp.asarray(A)
        if A.ndim not in (2, 3) or A.shape[-1] != A.shape[-2]:
            raise ValueError(f"expected (d, d) or (B, d, d), got {A.shape}")
        batch_ndim = 1 if A.ndim == 3 else 0
        d = A.shape[-1]
        if example is None:
            example = jnp.zeros(A.shape[:-1], A.dtype)
        super().__init__(example, batch_ndim=batch_ndim, symmetric=symmetric,
                         positive_definite=positive_definite)
        self.A = A
        if self._instance_dim() != d:
            raise ValueError(f"example ravels to d={self._instance_dim()} "
                             f"but the matrix is {d}x{d}")

    def matvec(self, v):
        """Dense matvec ``A @ v`` (batched over ``batch_ndim``), at full
        f32 precision (``dense_matvec``)."""
        view = ravel_view(lambda t: t, v, self.batch_ndim)  # structure only
        out = dense_matvec(self.A if self.batch_ndim else self.A[None],
                           view.b)
        return view.to_tree(out)

    def rmatvec(self, v):
        """Dense adjoint matvec ``Aᵀ @ u``."""
        if self.symmetric:
            return self.matvec(v)
        return DenseOperator(jnp.swapaxes(self.A, -1, -2),
                             self.example).matvec(v)

    def transpose(self) -> LinearOperator:
        """Operator over the transposed matrix (``self`` when symmetric)."""
        if self.symmetric:
            return self
        return DenseOperator(jnp.swapaxes(self.A, -1, -2), self.example,
                             symmetric=self.symmetric)

    def diagonal(self):
        """The matrix diagonal, O(1)."""
        diag = jnp.diagonal(self.A, axis1=-2, axis2=-1)
        view = ravel_view(lambda t: t, self.example, self.batch_ndim)
        return view.to_tree(diag if self.batch_ndim else diag[None])

    def materialize(self) -> jnp.ndarray:
        """The stored dense matrix itself, O(1)."""
        return self.A


class RidgeShifted(LinearOperator):
    """``A + λI``: the damping every solver applies, as structure-preserving
    composition — symmetry survives, definiteness survives (and ``λ > 0``
    turns a *PSD* operator SPD, but that promotion needs knowledge this
    wrapper doesn't have: symmetric alone does not rule out negative
    eigenvalues, so assert it explicitly via ``positive_definite=True`` when
    the base operator is known PSD).  ``diagonal``/``materialize`` shift
    instead of re-probing.
    """

    def __init__(self, op: LinearOperator, ridge: float, *,
                 positive_definite: Optional[bool] = None):
        pd = op.positive_definite if positive_definite is None \
            else positive_definite
        super().__init__(op.example, batch_ndim=op.batch_ndim,
                         symmetric=op.symmetric, positive_definite=pd)
        self.op = op
        self.ridge = ridge

    def matvec(self, v):
        """Apply ``A + ridge·I``."""
        return _tree_add_scaled(self.op.matvec(v), v, self.ridge)

    def rmatvec(self, v):
        """Apply ``(A + ridge·I)ᵀ``."""
        return _tree_add_scaled(self.op.rmatvec(v), v, self.ridge)

    def transpose(self) -> LinearOperator:
        """Ridge shift of the transposed base operator."""
        if self.symmetric:
            return self
        return RidgeShifted(self.op.transpose(), self.ridge,
                            positive_definite=self.positive_definite)

    def diagonal(self):
        """Base diagonal plus ``ridge``."""
        return jax.tree_util.tree_map(lambda dg: dg + self.ridge,
                                      self.op.diagonal())

    def materialize(self) -> jnp.ndarray:
        """Base matrix plus ``ridge·I``."""
        A = self.op.materialize()
        eye = jnp.eye(A.shape[-1], dtype=A.dtype)
        return A + self.ridge * eye


class BlockDiagonal(LinearOperator):
    """Independent blocks over a tuple domain: ``A = diag(A₁, …, Aₖ)``.

    The domain is a tuple with one entry per block (each entry any pytree).
    Symmetry/definiteness are the conjunction of the blocks'; ``diagonal``
    concatenates block diagonals — the natural source of block-Jacobi
    preconditioners (``block_jacobi_preconditioner``).
    """

    def __init__(self, ops: Sequence[LinearOperator]):
        ops = tuple(ops)
        if not ops:
            raise ValueError("BlockDiagonal needs at least one block")
        batch = {op.batch_ndim for op in ops}
        if len(batch) != 1:
            raise ValueError("blocks disagree on batch_ndim")
        syms = [op.symmetric for op in ops]
        symmetric = (True if all(s is True for s in syms)
                     else False if any(s is False for s in syms) else None)
        super().__init__(tuple(op.example for op in ops),
                         batch_ndim=batch.pop(), symmetric=symmetric,
                         positive_definite=all(op.positive_definite
                                               for op in ops))
        self.ops = ops

    def matvec(self, v):
        """Apply each block to its leaf of the domain pytree."""
        return tuple(op.matvec(vi) for op, vi in zip(self.ops, v))

    def rmatvec(self, v):
        """Apply each block's adjoint to its leaf."""
        return tuple(op.rmatvec(vi) for op, vi in zip(self.ops, v))

    def transpose(self) -> LinearOperator:
        """Blockwise transpose."""
        if self.symmetric:
            return self
        return BlockDiagonal(tuple(op.transpose() for op in self.ops))

    def diagonal(self):
        """Blockwise diagonals as a pytree."""
        return tuple(op.diagonal() for op in self.ops)

    def materialize(self) -> jnp.ndarray:
        """Dense block-diagonal matrix in ravel order."""
        blocks = [op.materialize() for op in self.ops]
        d = sum(b.shape[-1] for b in blocks)
        if self.batch_ndim:
            B = blocks[0].shape[0]
            A = jnp.zeros((B, d, d), blocks[0].dtype)
        else:
            A = jnp.zeros((d, d), blocks[0].dtype)
        i = 0
        for b in blocks:
            n = b.shape[-1]
            A = A.at[..., i:i + n, i:i + n].set(b)
            i += n
        return A


class ComposedOperator(LinearOperator):
    """``outer ∘ inner`` — the product operator, e.g. a left-preconditioned
    system ``M⁻¹ A``.  Flags default to unknown (products rarely preserve
    them) unless asserted explicitly."""

    def __init__(self, outer: LinearOperator, inner: LinearOperator, *,
                 symmetric: Optional[bool] = None,
                 positive_definite: bool = False):
        super().__init__(inner.example, batch_ndim=inner.batch_ndim,
                         symmetric=symmetric,
                         positive_definite=positive_definite)
        self.outer = outer
        self.inner = inner

    def matvec(self, v):
        """Apply the composition right to left."""
        return self.outer.matvec(self.inner.matvec(v))

    def rmatvec(self, v):
        """Apply the adjoint composition left to right."""
        return self.inner.rmatvec(self.outer.rmatvec(v))

    def transpose(self) -> LinearOperator:
        """Compose the transposes in reverse order."""
        if self.symmetric:
            return self
        # (M A)ᵀ = Aᵀ Mᵀ; symmetry/definiteness are properties of the
        # product as a whole, so the declared flags carry over verbatim
        return ComposedOperator(self.inner.transpose(),
                                self.outer.transpose(),
                                symmetric=self.symmetric,
                                positive_definite=self.positive_definite)


class RaveledOperator(LinearOperator):
    """An operator re-expressed on its raveled flat-vector domain.

    The one place the differentiation layer needs a flat system:
    ``lax.custom_linear_solve`` binds per-leaf cotangents without
    instantiating symbolic zeros, so the transposable tangent solve must run
    on ONE vector leaf.  ``ravel``/``unravel`` move right-hand sides and
    solutions across, and ``ravel_fn`` lifts tree-to-tree callables (user
    preconditioners) to the flat domain.  Unbatched operators only —
    batching is vmap's job at this layer.
    """

    def __init__(self, op: LinearOperator):
        if op.batch_ndim != 0:
            raise ValueError("RaveledOperator wraps instance-shaped "
                             "operators; vmap supplies batching")
        flat_example, unravel = jax.flatten_util.ravel_pytree(op.example)
        super().__init__(flat_example, batch_ndim=0, symmetric=op.symmetric,
                         positive_definite=op.positive_definite)
        self.op = op
        self._unravel = unravel

    def ravel(self, tree) -> jnp.ndarray:
        """Ravel a domain pytree to the flat vector domain."""
        return _ravel1(tree)

    def unravel(self, flat):
        """Unravel a flat vector back to the domain pytree."""
        return self._unravel(flat)

    def ravel_fn(self, fn: Callable) -> Callable:
        """Lift a tree→tree linear map (e.g. a preconditioner) to flat."""
        return lambda vf: _ravel1(fn(self._unravel(vf)))

    def matvec(self, vf):
        """Flat-domain matvec (unravel → base matvec → ravel)."""
        return _ravel1(self.op.matvec(self._unravel(vf)))

    def rmatvec(self, vf):
        """Flat-domain adjoint matvec."""
        return _ravel1(self.op.rmatvec(self._unravel(vf)))

    def diagonal(self):
        """Base diagonal, raveled flat."""
        return _ravel1(self.op.diagonal())

    def materialize(self) -> jnp.ndarray:
        """The base operator's dense matrix (already ravel-ordered)."""
        return self.op.materialize()

    def raveled(self) -> "RaveledOperator":
        """Already flat: ``self``."""
        return self


# ---------------------------------------------------------------------------
# adapters and derived preconditioners
# ---------------------------------------------------------------------------

def as_operator(obj, example=None, *, batch_ndim: int = 0,
                symmetric: Optional[bool] = None,
                positive_definite: bool = False) -> LinearOperator:
    """Coerce to a ``LinearOperator``.

    Operators pass through unchanged (flags must not conflict); a 2-D/3-D
    array becomes a ``DenseOperator``; a callable becomes a
    ``FunctionOperator`` (``example`` required for the domain structure).
    """
    if isinstance(obj, LinearOperator):
        return obj
    if isinstance(obj, (np.ndarray, jnp.ndarray)) and obj.ndim in (2, 3):
        return DenseOperator(obj, example, symmetric=symmetric,
                             positive_definite=positive_definite)
    if callable(obj):
        if example is None:
            raise ValueError("as_operator(callable) needs an example of the "
                             "domain pytree")
        return FunctionOperator(obj, example, batch_ndim=batch_ndim,
                                symmetric=symmetric,
                                positive_definite=positive_definite)
    raise TypeError(f"cannot interpret {type(obj)!r} as a LinearOperator")


def jacobi_preconditioner(diag) -> Callable:
    """``M⁻¹ v = v / diag``, elementwise over a pytree of diagonals (the
    one safe-divide definition — ``linear_solve`` re-exports it)."""
    safe = jax.tree_util.tree_map(
        lambda dg: jnp.where(jnp.abs(dg) > 1e-30, dg, 1.0), diag)
    return lambda v: jax.tree_util.tree_map(lambda x, dg: x / dg, v, safe)


def jacobi_preconditioner_from(op: LinearOperator) -> Callable:
    """``M⁻¹ v = v / diag(A)`` derived from ``op.diagonal()``.

    Structured operators provide the diagonal in O(1); matrix-free ones pay
    ``d`` probing matvecs exactly once, here, instead of inside the solver.
    """
    return jacobi_preconditioner(op.diagonal())


def block_jacobi_preconditioner(op: LinearOperator,
                                materialized=None) -> Callable:
    """Per-block dense inverse preconditioner from the operator's structure.

    For a ``BlockDiagonal`` operator this is exact (each block materialized
    and inverted); for any other operator the *leaves* of the domain pytree
    define the blocks — the corresponding diagonal sub-blocks of ``A`` are
    extracted from one materialization and inverted, off-diagonal coupling
    dropped.  ``materialized`` short-circuits that materialization when the
    caller already holds the dense matrix (e.g. a dense-regime solver).
    Returns a tree→tree callable usable as ``precond``.  Intended for the
    dense small-system regime (one materialize + per-block ``n³``).
    """
    if isinstance(op, BlockDiagonal):
        if materialized is None:
            mats = [blk.materialize() for blk in op.ops]
        else:   # slice the supplied dense matrix along the declared blocks
            mats, i = [], 0
            for blk in op.ops:
                example = blk.example
                if blk.batch_ndim:
                    example = jax.tree_util.tree_map(lambda l: l[0], example)
                n = _ravel1(example).shape[0]
                mats.append(materialized[..., i:i + n, i:i + n])
                i += n
        inv_ops = [DenseOperator(jnp.linalg.inv(m), blk.example,
                                 symmetric=blk.symmetric)
                   for m, blk in zip(mats, op.ops)]

        def M_blockwise(v):
            return tuple(inv.matvec(vi) for inv, vi in zip(inv_ops, v))

        return M_blockwise

    example = op.example
    if op.batch_ndim:
        example = jax.tree_util.tree_map(lambda l: l[0], example)
    leaves, treedef = jax.tree_util.tree_flatten(example)
    sizes = [int(leaf.size) for leaf in leaves]
    A = op.materialize() if materialized is None else materialized
    bounds, i = [], 0
    for n in sizes:
        bounds.append((i, i + n))
        i += n
    invs = [jnp.linalg.inv(A[..., s:e, s:e]) for s, e in bounds]

    def M(v):
        vleaves = jax.tree_util.tree_leaves(v)
        batch_shape = () if op.batch_ndim == 0 else vleaves[0].shape[:1]
        out = [jnp.einsum("...ij,...j->...i", inv,
                          vl.reshape(batch_shape + (-1,))).reshape(vl.shape)
               for inv, vl in zip(invs, vleaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    return M
