"""Mesh-aware sharded operators: distributed linear solves behind one seam.

The paper's implicit differentiation rides "on top of any state-of-the-art
solver" once the optimality conditions ``F`` are specified — and at
production scale the solver runs on a mesh.  The Jacobian operator
``A = -∂₁F`` should never be gathered to one device: its matvec is a JVP
that executes under ``shard_map`` with the same PartitionSpecs as the
forward solve.  This module makes placement a property of the operator,
exactly like symmetry and batching already are (PR 4):

  * ``ShardedOperator`` — wraps any ``LinearOperator`` (or a per-shard
    *factory* of one) with a ``Mesh`` + in/out ``PartitionSpec`` trees.
    ``matvec``/``rmatvec`` run under ``shard_map``; ``diagonal()`` /
    ``materialize()`` return per-shard pieces; the dot-product/norm
    reductions CG needs go through a pluggable ``psum``-based hook.
  * ``SolveSharding`` — the placement bundle the implicit-diff layer
    threads through ``ImplicitDiffSpec.sharding``: mesh + spec for the
    solution ``x`` (+ optional per-theta specs), so ``JacobianOperator``
    inherits the primal solution's placement and ``jax.grad``/``jax.jvp``
    of a decorated solver execute ONE sharded backward solve with no host
    gather.
  * ``sharded_solve_cg`` / ``sharded_solve_normal_cg`` /
    ``sharded_solve_dense_gmres`` — the distributed variants behind the
    ``"sharded_cg"`` / ``"sharded_normal_cg"`` / ``"sharded_dense_gmres"``
    ``SolverSpec`` registry names: the WHOLE masked solve loop runs inside
    one ``shard_map`` (per-instance convergence masks intact), with
    cross-device communication confined to the reduction hook.

Shard-locality contract
-----------------------
``shard_map`` hands the wrapped operator *local shards*.  The base
operator's matvec must therefore be **shard-local**: applying it to the
local shard of ``v`` yields the local shard of ``A v``.  That holds for

  * batch sharding (``batch_ndim == 1``, the leading batch axis sharded):
    the operator is block-diagonal over instances, so each device's local
    matvec over its batch slice is exact — the production case for batched
    hypergradients;
  * instance-dim sharding of operators that are block-diagonal along the
    sharded dim (diagonal/elementwise systems), or whose matvec performs
    its own collectives (mesh axis names are in scope inside the matvec).

Anything the matvec *closes over* is replicated into every shard; arrays
that must be sharded alongside the domain (the Jacobian's primal point,
batched theta) are passed as ``operands`` with ``operand_specs`` and reach
the operator through a per-shard factory.

Reductions: per-instance scalars (step sizes, residual norms, ``done``
masks) are local under pure batch sharding — the only cross-device
communication is the ``psum`` over *instance-sharding* axes, which is why
the hook receives exactly those axes.  Devices holding different batch
shards never communicate and may even exit their solve loops at different
iteration counts.

Example::

    mesh = make_solve_mesh()                      # 1-D mesh over devices
    sh = SolveSharding(mesh, P("data", None), batch_ndim=1,
                       theta_specs=(P("data"),))
    spec = ImplicitDiffSpec(optimality_fun=F, solve="cg", sharding=sh)
    solver = implicit_diff(spec)(my_sharded_solver)
    jax.grad(loss)(theta)    # ONE sharded backward solve, no host gather
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import linear_solve as ls
from repro.core import operators as ops
from repro.core.operators import LinearOperator


# ---------------------------------------------------------------------------
# spec utilities
# ---------------------------------------------------------------------------

def spec_tree(spec, tree):
    """Broadcast a single ``PartitionSpec`` over ``tree`` (a matching pytree
    of specs passes through)."""
    if isinstance(spec, P):
        return jax.tree_util.tree_map(lambda _: spec, tree)
    return spec


def _spec_leaves(specs):
    return jax.tree_util.tree_leaves(specs,
                                     is_leaf=lambda x: isinstance(x, P))


def _axes_of(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        out: Tuple[str, ...] = ()
        for e in entry:
            out += _axes_of(e)
        return out
    return (entry,)


def instance_axes(specs, batch_ndim: int) -> Tuple[str, ...]:
    """Mesh axes that shard *instance* dims (spec positions ≥ batch_ndim) —
    the axes a distributed dot product must ``psum`` over."""
    found: list = []
    for leaf in _spec_leaves(specs):
        for entry in tuple(leaf)[batch_ndim:]:
            for name in _axes_of(entry):
                if name not in found:
                    found.append(name)
    return tuple(found)


def batch_axes(specs, batch_ndim: int) -> Tuple[str, ...]:
    """Mesh axes that shard the leading batch dim (spec position 0 when
    ``batch_ndim == 1``)."""
    if batch_ndim == 0:
        return ()
    found: list = []
    for leaf in _spec_leaves(specs):
        entries = tuple(leaf)
        if entries:
            for name in _axes_of(entries[0]):
                if name not in found:
                    found.append(name)
    return tuple(found)


def psum_reduction(axis_names: Tuple[str, ...]) -> Callable:
    """The default reduction hook: ``lax.psum`` over the instance-sharding
    axes (identity when nothing cross-device is needed, e.g. pure batch
    sharding).  Plug a custom hook for hierarchical/approximate reductions.
    """
    if not axis_names:
        return lambda x: x
    return lambda x: jax.lax.psum(x, axis_names)


# ---------------------------------------------------------------------------
# the sharded operator
# ---------------------------------------------------------------------------

def _overrides(op: LinearOperator, name: str) -> bool:
    """Whether ``op`` brings its own ``name`` instead of the matrix-free
    base default.  ``FunctionOperator.rmatvec`` only counts when an
    explicit rmatvec closure was supplied (its override otherwise falls
    through to the base default)."""
    if name == "rmatvec" and isinstance(op, ops.FunctionOperator):
        return op._rmatvec is not None
    return getattr(type(op), name) is not getattr(LinearOperator, name)


class _LocalShardView(LinearOperator):
    """A plain-captured operator re-examined at the LOCAL shard.

    Inside ``shard_map`` the base operator still carries its GLOBAL
    structural ``example``, so its matrix-free defaults — ``rmatvec`` via
    ``jax.linear_transpose``, probing ``diagonal``/``materialize`` — would
    trace the matvec at global shapes against local shards (shape errors,
    or worse: silently duplicated probing output concatenated across
    shards).  This view delegates genuinely overridden methods and
    re-anchors the defaults on the local example, so they trace at shard
    shapes.  Square systems (domain structure == codomain structure), like
    everything the implicit-diff stack solves.
    """

    def __init__(self, op: LinearOperator, example_local):
        super().__init__(example_local, batch_ndim=op.batch_ndim,
                         symmetric=op.symmetric,
                         positive_definite=op.positive_definite)
        self._op = op

    def matvec(self, v):
        return self._op.matvec(v)

    def rmatvec(self, v):
        if self._op.symmetric or _overrides(self._op, "rmatvec"):
            return self._op.rmatvec(v)
        return super().rmatvec(v)       # linear_transpose at LOCAL shapes

    def diagonal(self):
        if _overrides(self._op, "diagonal"):
            return self._op.diagonal()
        return super().diagonal()       # probing at LOCAL shapes

    def materialize(self):
        if _overrides(self._op, "materialize"):
            return self._op.materialize()
        return super().materialize()    # probing at LOCAL shapes

class ShardedOperator(LinearOperator):
    """A ``LinearOperator`` placed on a mesh.

    ``op`` is either a plain operator (its matvec must be shard-local with
    replicated captures — see the module docstring) or a *factory*
    ``factory(*operands_local) -> LinearOperator`` building the per-shard
    operator from sharded operands (the Jacobian case: the primal point and
    batched theta shard alongside the domain).  ``in_specs``/``out_specs``
    are ``PartitionSpec`` trees over the domain/codomain (square systems
    default ``out_specs = in_specs``); a single spec broadcasts over the
    tree.  ``reduce`` overrides the ``psum``-over-instance-axes reduction
    hook the sharded solvers use for their dot products.

    Flags (``symmetric``/``positive_definite``/``batch_ndim``) and the
    structural ``example`` are read off the (template) base operator, so
    routing, validation and preconditioner derivation see through the
    placement wrapper unchanged.
    """

    is_sharded = True

    def __init__(self, op, mesh: Mesh, in_specs, *, out_specs=None,
                 operands: tuple = (), operand_specs: tuple = (),
                 reduce: Optional[Callable] = None):
        if isinstance(op, LinearOperator):
            if operands:
                raise ValueError("operands require a factory; a plain "
                                 "LinearOperator captures its arrays "
                                 "(replicated into every shard)")
            template = op
        elif callable(op):
            template = op(*operands)
            if not isinstance(template, LinearOperator):
                raise TypeError("factory must build a LinearOperator; got "
                                f"{type(template)!r}")
        else:
            raise TypeError(f"cannot shard {type(op)!r}; expected a "
                            "LinearOperator or a factory callable")
        if len(operands) != len(operand_specs):
            raise ValueError(f"{len(operands)} operands but "
                             f"{len(operand_specs)} operand_specs")
        super().__init__(template.example, batch_ndim=template.batch_ndim,
                         symmetric=template.symmetric,
                         positive_definite=template.positive_definite)
        self.mesh = mesh
        self.in_specs = spec_tree(in_specs, template.example)
        self.out_specs = self.in_specs if out_specs is None \
            else spec_tree(out_specs, template.example)
        self._psum_axes = instance_axes(self.in_specs, self.batch_ndim)
        self._batch_axes = batch_axes(self.in_specs, self.batch_ndim)
        self._plain = isinstance(op, LinearOperator)
        if self._plain:
            op, operands, operand_specs = self._lift_plain(op)
            self._plain = not operands      # DenseOperator auto-lift is
            # a factory over local matrices — already local-examined
        self._factory = op
        self.operands = tuple(operands)
        self.operand_specs = tuple(
            spec_tree(s, o) for s, o in zip(operand_specs, self.operands))
        self._reduce_arg = reduce
        self.reduce = reduce if reduce is not None \
            else psum_reduction(self._psum_axes)

    def _lift_plain(self, op: LinearOperator):
        """Turn a plain operator into (factory, operands, operand_specs).

        A batch-sharded ``DenseOperator`` carries its ``(B, d, d)`` stack as
        a sharded operand (each device holds its batch slice of matrices);
        everything else is captured by closure — replicated into every
        shard, so its matvec must be shard-local (see module docstring).
        """
        if isinstance(op, ops.DenseOperator) and self.batch_ndim == 1 \
                and not self.instance_sharded and self._batch_axes:
            baxis = self._batch_axes[0] if len(self._batch_axes) == 1 \
                else self._batch_axes
            sym, pd = op.symmetric, op.positive_definite

            def dense_factory(A_local):
                return ops.DenseOperator(A_local, symmetric=sym,
                                         positive_definite=pd)

            return dense_factory, (op.A,), (P(baxis, None, None),)
        return (lambda: op), (), ()

    # -- shard-level access ----------------------------------------------
    @property
    def instance_sharded(self) -> bool:
        """Whether instance dims (not just the batch) are split across
        devices — i.e. whether dot products need cross-device reduction."""
        return bool(self._psum_axes)

    def local_operator(self, *operands_local,
                       example_local=None) -> LinearOperator:
        """The per-shard base operator (called INSIDE ``shard_map``).

        Factory-built operators are already anchored on local operands; a
        plain-captured operator is re-examined at ``example_local`` (the
        local shard) so the matrix-free base defaults trace at shard
        shapes — see ``_LocalShardView``.
        """
        local = self._factory(*operands_local)
        if self._plain and example_local is not None:
            if isinstance(local, ops.TransposedOperator):
                # re-anchor the UNDERLYING operator, then transpose: the
                # transposed matvec is the base rmatvec, which must trace
                # at local shapes too
                return _LocalShardView(local.op,
                                       example_local).transpose()
            return _LocalShardView(local, example_local)
        return local

    def shard_map(self, body: Callable, extra_in_specs: tuple,
                  out_specs) -> Callable:
        """``shard_map`` ``body(*operands_local, *extra_local)`` on this
        operator's mesh, with the operands automatically prepended."""
        mapped = jax.shard_map(body, mesh=self.mesh,
                               in_specs=(*self.operand_specs,
                                         *extra_in_specs),
                               out_specs=out_specs, check_vma=False)
        return lambda *extra: mapped(*self.operands, *extra)

    # -- LinearOperator protocol -----------------------------------------
    def matvec(self, v):
        def body(*args):
            *ops_l, v_l = args
            # example_local matters for transposed plain-capture wrappers,
            # whose matvec is the base linear-transpose default
            return self.local_operator(*ops_l,
                                       example_local=v_l).matvec(v_l)

        return self.shard_map(body, (self.in_specs,), self.out_specs)(v)

    def rmatvec(self, v):
        if self.symmetric:
            return self.matvec(v)

        def body(*args):
            *ops_l, v_l = args
            # square system: the codomain shard doubles as the local
            # domain example for the linear-transpose default
            return self.local_operator(*ops_l,
                                       example_local=v_l).rmatvec(v_l)

        return self.shard_map(body, (self.out_specs,), self.in_specs)(v)

    def transpose(self) -> LinearOperator:
        if self.symmetric:
            return self
        out = ShardedOperator(
            lambda *o: self._factory(*o).transpose(), self.mesh,
            self.out_specs, out_specs=self.in_specs,
            operands=self.operands, operand_specs=self.operand_specs,
            reduce=self._reduce_arg)
        out._plain = self._plain    # plain-capture local re-examining
        # survives transposition (the wrapper factory is ours, not a
        # user factory over local operands)
        return out

    def diagonal(self):
        """diag(A), assembled from per-shard diagonals (each device probes
        only its local block)."""
        def body(*args):
            *ops_l, ex_l = args
            return self.local_operator(*ops_l,
                                       example_local=ex_l).diagonal()

        return self.shard_map(body, (self.in_specs,),
                              self.in_specs)(self.example)

    def materialize(self) -> jnp.ndarray:
        """Per-shard dense pieces.  Batch sharding assembles the global
        ``(B, d, d)`` stack (each device holds its batch slice); instance
        sharding returns the local diagonal blocks stacked along a leading
        shard axis ``(n_shards, d_local, d_local)`` — there is no global
        dense form without a gather, which this subsystem never does.
        """
        if not self.instance_sharded:
            bspec = self._batch_axes[0] if len(self._batch_axes) == 1 \
                else (self._batch_axes or None)
            out = P(bspec, None, None) if self.batch_ndim else P(None, None)

            def body(*args):
                *ops_l, ex_l = args
                return self.local_operator(
                    *ops_l, example_local=ex_l).materialize()

            return self.shard_map(body, (self.in_specs,),
                                  out)(self.example)

        out = P(self._psum_axes if len(self._psum_axes) > 1
                else self._psum_axes[0], None, None)

        def body(*args):
            *ops_l, ex_l = args
            return self.local_operator(
                *ops_l, example_local=ex_l).materialize()[None]

        return self.shard_map(body, (self.in_specs,), out)(self.example)


# ---------------------------------------------------------------------------
# the placement bundle the diff layer threads through ImplicitDiffSpec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SolveSharding:
    """Mesh placement for an implicit system (``ImplicitDiffSpec.sharding``).

    ``spec`` is the PartitionSpec (tree) of the solution ``x`` — the specs
    the backward/tangent solve inherits from the primal solution.
    ``theta_specs`` aligns with the solver's *differentiable* theta
    arguments (``None`` → replicated; per-entry ``None`` → that argument
    replicated).  ``batch_ndim = 1`` declares a leading batch axis on every
    ``x`` leaf (independent instances → per-instance convergence masks in
    the sharded solvers).  ``reduce`` overrides the ``psum`` reduction hook.
    """
    mesh: Mesh
    spec: Any
    theta_specs: Optional[Tuple[Any, ...]] = None
    batch_ndim: int = 0
    reduce: Optional[Callable] = None

    def x_specs(self, x):
        return spec_tree(self.spec, x)

    def theta_spec(self, i: int, arg):
        specs = self.theta_specs
        entry = None if specs is None or i >= len(specs) else specs[i]
        return spec_tree(P() if entry is None else entry, arg)

    def wrap(self, factory: Callable, operands: tuple) -> ShardedOperator:
        """Place a per-shard operator factory on the mesh.  ``operands``
        are ``(x_like, *theta)``: the first operand shards like the
        solution, the rest per ``theta_specs``."""
        operand_specs = (self.x_specs(operands[0]),) + tuple(
            self.theta_spec(i, a) for i, a in enumerate(operands[1:]))
        return ShardedOperator(factory, self.mesh, self.x_specs(
            operands[0]), operands=operands, operand_specs=operand_specs,
            reduce=self.reduce)

    def constrain(self, tree):
        """Pin ``tree`` to this placement: ``device_put`` for concrete
        arrays, ``with_sharding_constraint`` for tracers (inside jit)."""
        specs = spec_tree(self.spec, tree)
        named = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        leaves = jax.tree_util.tree_leaves(tree)
        if not leaves:
            return tree
        if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
            return jax.lax.with_sharding_constraint(tree, named)
        return jax.device_put(tree, named)


# ---------------------------------------------------------------------------
# sharded registry solvers: the whole masked loop inside ONE shard_map
# ---------------------------------------------------------------------------

def _require_sharded(name: str, matvec) -> ShardedOperator:
    if not isinstance(matvec, ShardedOperator):
        raise ValueError(
            f"solver {name!r} runs inside shard_map and needs mesh + "
            f"PartitionSpecs; wrap the operator in a ShardedOperator "
            f"(got {type(matvec).__name__})")
    return matvec


def _info_specs(op: ShardedOperator):
    """SolveInfo leaves are per-instance scalars: sharded along the batch
    axes under batch sharding, replicated (post-``psum``) otherwise."""
    if op.batch_ndim and op._batch_axes:
        axes = op._batch_axes[0] if len(op._batch_axes) == 1 \
            else op._batch_axes
        leaf = P(axes)
    else:
        leaf = P()
    return ls.SolveInfo(iterations=leaf, residual=leaf, converged=leaf)


def _sharded_call(inner: Callable, name: str, matvec, b, *, init=None,
                  return_info: bool = False, batch_ndim: int = 0,
                  with_reduce: bool = True, **kw):
    """Run ``inner(local_op, b_local, ...)`` inside one ``shard_map``."""
    op = _require_sharded(name, matvec)
    if batch_ndim not in (0, op.batch_ndim):
        raise ValueError(f"batch_ndim={batch_ndim} does not match the "
                         f"sharded operator's batch_ndim={op.batch_ndim}")
    kw = dict(kw, batch_ndim=op.batch_ndim, return_info=return_info)
    if with_reduce:
        kw["reduce"] = op.reduce
    n_op = len(op.operands)
    has_init = init is not None

    def body(*args):
        ops_l = args[:n_op]
        b_l = args[n_op]
        init_l = args[n_op + 1] if has_init else None
        # square system: the codomain rhs shard doubles as the local
        # domain example for the plain-capture path's defaults
        local = op.local_operator(*ops_l, example_local=b_l)
        return inner(local, b_l, init=init_l, **kw)

    # the right-hand side lives in the CODOMAIN (out_specs); the warm start
    # and the solution in the domain (in_specs) — identical for the square
    # same-placement common case, distinct for transposed operators built
    # with out_specs != in_specs
    extra_in = (op.out_specs,) + ((op.in_specs,) if has_init else ())
    out_specs = (op.in_specs, _info_specs(op)) if return_info \
        else op.in_specs
    args = (b, init) if has_init else (b,)
    return op.shard_map(body, extra_in, out_specs)(*args)


def sharded_solve_cg(matvec, b, **kw):
    """Distributed CG: one ``shard_map``, matvec per shard, dot products
    through the operator's reduction hook, per-instance masks intact."""
    return _sharded_call(ls.solve_cg, "sharded_cg", matvec, b, **kw)


def sharded_solve_normal_cg(matvec, b, **kw):
    """Distributed CG on the normal equations (general square A; the local
    operator answers ``rmatvec`` per shard)."""
    return _sharded_call(ls.solve_normal_cg, "sharded_normal_cg", matvec, b,
                         **kw)


def sharded_solve_dense_gmres(matvec, b, **kw):
    """Distributed dense GMRES: each device materializes + solves its local
    batch slice.  Batch sharding only — a dense instance-sharded system has
    no local (d, d) form."""
    op = _require_sharded("sharded_dense_gmres", matvec)
    if op.instance_sharded:
        raise ValueError(
            "sharded_dense_gmres materializes per-shard dense systems, "
            "which needs the instance dims unsharded (batch sharding only);"
            " use sharded_cg/sharded_normal_cg for instance-dim sharding")
    return _sharded_call(ls.solve_dense_gmres, "sharded_dense_gmres",
                         matvec, b, with_reduce=False, **kw)
