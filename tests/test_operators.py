"""Tests for the pytree-native LinearOperator subsystem.

Covers the protocol (matvec/rmatvec/transpose/diagonal/materialize/
ravel_view against dense ground truth), the concrete operators, routing
integration (flag validation, ``"auto"`` dispatch, operator-derived
preconditioners), the solver symmetry-metadata contract for every registry
solver, and the diff-API invariants now routed through operators.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import diff_api, operators as ops
from repro.core import linear_solve as ls


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def _spd(rng, d, scale=1.0):
    M = rng.randn(d, d)
    return jnp.asarray(M @ M.T * scale / d + np.eye(d))


def _tree_example(d=3):
    return {"w": jnp.zeros((d, 2)), "b": jnp.zeros(d)}


def _tree_map_fun(theta):
    """A linear tree→tree mapping with a nontrivial (nonsymmetric) dense
    form, for Jacobian ground-truthing."""
    def f(t):
        w, b = t["w"], t["b"]
        return {"w": 2.0 * w + b[:, None] * theta,
                "b": jnp.sin(theta) * b + w.sum(axis=1)}
    return f


# ---------------------------------------------------------------------------
# protocol defaults against dense ground truth
# ---------------------------------------------------------------------------

class TestProtocol:

    def test_jacobian_operator_matches_dense_jacobian(self, rng):
        x = {"w": jnp.asarray(rng.randn(3, 2)), "b": jnp.asarray(rng.randn(3))}
        f = _tree_map_fun(0.7)
        J = ops.JacobianOperator(f, x)
        flat = J.raveled()
        x_flat, unravel = jax.flatten_util.ravel_pytree(x)
        dense = jax.jacobian(lambda v: jax.flatten_util.ravel_pytree(
            f(unravel(v)))[0])(x_flat)
        np.testing.assert_allclose(J.materialize(), dense, atol=1e-6)
        v = jnp.asarray(rng.randn(x_flat.shape[0]))
        np.testing.assert_allclose(flat.matvec(v), dense @ v, atol=1e-6)
        np.testing.assert_allclose(flat.rmatvec(v), dense.T @ v, atol=1e-6)
        np.testing.assert_allclose(flat.diagonal(), jnp.diag(dense),
                                   atol=1e-6)

    def test_transpose_roundtrip_and_symmetric_shortcut(self, rng):
        A_dense = jnp.asarray(rng.randn(4, 4))
        J = ops.JacobianOperator(lambda v: A_dense @ v, jnp.zeros(4))
        assert isinstance(J.T, ops.TransposedOperator)
        assert J.T.transpose() is J          # transpose of transpose
        S = ops.DenseOperator(_spd(rng, 4), positive_definite=True)
        assert S.T is S          # symmetry certificate short-circuits
        A = ops.DenseOperator(A_dense, symmetric=False)
        v = jnp.asarray(rng.randn(4))
        np.testing.assert_allclose(A.T.matvec(v), A_dense.T @ v, atol=1e-6)
        np.testing.assert_allclose(J.T.matvec(v), A_dense.T @ v, atol=1e-6)

    def test_negate_flag(self, rng):
        x = jnp.asarray(rng.randn(5))
        A_dense = jnp.asarray(rng.randn(5, 5))
        J = ops.JacobianOperator(lambda v: A_dense @ v, x, negate=True)
        v = jnp.asarray(rng.randn(5))
        np.testing.assert_allclose(J.matvec(v), -A_dense @ v, atol=1e-6)
        np.testing.assert_allclose(J.T.matvec(v), -A_dense.T @ v, atol=1e-6)

    def test_pd_implies_symmetric_and_conflict_rejected(self, rng):
        A = ops.DenseOperator(_spd(rng, 3), positive_definite=True)
        assert A.symmetric is True
        with pytest.raises(ValueError, match="symmetric"):
            ops.DenseOperator(_spd(rng, 3), symmetric=False,
                              positive_definite=True)

    def test_ravel_view_roundtrip_batched(self, rng):
        b = {"w": jnp.asarray(rng.randn(4, 3, 2)),
             "b": jnp.asarray(rng.randn(4, 3))}
        view = ops.ravel_view(lambda t: jax.tree_util.tree_map(
            lambda l: 2.0 * l, t), b, batch_ndim=1)
        assert view.batched and view.b.shape == (4, 9)
        np.testing.assert_allclose(view.mv(view.b), 2.0 * view.b, atol=1e-6)
        rt = view.to_tree(view.b)
        jax.tree_util.tree_map(np.testing.assert_allclose, rt, b)

    def test_function_operator_explicit_rmatvec(self, rng):
        A_dense = jnp.asarray(rng.randn(4, 4))
        calls = []

        def rmv(v):
            calls.append(1)
            return A_dense.T @ v

        A = ops.FunctionOperator(lambda v: A_dense @ v, jnp.zeros(4),
                                 rmatvec=rmv, symmetric=False)
        v = jnp.asarray(rng.randn(4))
        np.testing.assert_allclose(A.rmatvec(v), A_dense.T @ v, atol=1e-6)
        assert calls  # the explicit rmatvec was used, not linear_transpose


# ---------------------------------------------------------------------------
# structured operators
# ---------------------------------------------------------------------------

class TestStructured:

    def test_ridge_shifted(self, rng):
        A_spd = _spd(rng, 5)
        A = ops.RidgeShifted(
            ops.DenseOperator(A_spd, positive_definite=True), 0.3)
        assert A.positive_definite   # PD survives damping
        np.testing.assert_allclose(A.materialize(),
                                   A_spd + 0.3 * jnp.eye(5), atol=1e-6)
        np.testing.assert_allclose(A.diagonal(), jnp.diag(A_spd) + 0.3,
                                   atol=1e-6)
        # symmetric-but-not-declared-PD does NOT get promoted (indefinite
        # symmetric operators stay indefinite under small ridge); the PSD
        # caller asserts explicitly
        S = ops.RidgeShifted(ops.DenseOperator(A_spd, symmetric=True), 0.3)
        assert not S.positive_definite
        P = ops.RidgeShifted(ops.DenseOperator(A_spd, symmetric=True), 0.3,
                             positive_definite=True)
        assert P.positive_definite

    def test_block_diagonal(self, rng):
        A1, A2 = _spd(rng, 3), jnp.asarray(rng.randn(2, 2))
        B = ops.BlockDiagonal([
            ops.DenseOperator(A1, positive_definite=True),
            ops.DenseOperator(A2, symmetric=False)])
        assert B.symmetric is False and not B.positive_definite
        full = B.materialize()
        np.testing.assert_allclose(full[:3, :3], A1, atol=1e-6)
        np.testing.assert_allclose(full[3:, 3:], A2, atol=1e-6)
        assert float(jnp.abs(full[:3, 3:]).sum()) == 0.0
        v = (jnp.asarray(rng.randn(3)), jnp.asarray(rng.randn(2)))
        out = B.matvec(v)
        np.testing.assert_allclose(out[0], A1 @ v[0], atol=1e-6)
        np.testing.assert_allclose(out[1], A2 @ v[1], atol=1e-6)

    def test_composed(self, rng):
        A1, A2 = jnp.asarray(rng.randn(4, 4)), jnp.asarray(rng.randn(4, 4))
        C = ops.ComposedOperator(ops.DenseOperator(A1, symmetric=False),
                                 ops.DenseOperator(A2, symmetric=False))
        v = jnp.asarray(rng.randn(4))
        np.testing.assert_allclose(C.matvec(v), A1 @ (A2 @ v), atol=1e-5)
        np.testing.assert_allclose(C.T.matvec(v), (A1 @ A2).T @ v, atol=1e-5)

    def test_dense_batched(self, rng):
        Ab = jnp.stack([_spd(rng, 3), _spd(rng, 3, 2.0)])
        A = ops.DenseOperator(Ab, positive_definite=True)
        assert A.batch_ndim == 1
        v = jnp.asarray(rng.randn(2, 3))
        np.testing.assert_allclose(A.matvec(v),
                                   jnp.einsum("bij,bj->bi", Ab, v),
                                   atol=1e-6)
        np.testing.assert_allclose(A.diagonal(),
                                   jnp.diagonal(Ab, axis1=-2, axis2=-1),
                                   atol=1e-6)

    def test_composed_transpose_keeps_flags(self, rng):
        A1, A2 = jnp.asarray(rng.randn(4, 4)), jnp.asarray(rng.randn(4, 4))
        C = ops.ComposedOperator(ops.DenseOperator(A1, symmetric=False),
                                 ops.DenseOperator(A2, symmetric=False),
                                 symmetric=False)
        assert C.T.symmetric is False   # validation survives transposition
        with pytest.raises(ValueError, match="symmetric"):
            ls.route_solve("cg", C.T, jnp.ones(4))

    def test_rmatvec_under_jit_then_eager_does_not_leak_tracers(self, rng):
        """Operators are long-lived public objects: the first rmatvec
        happening under jit must not poison later eager calls (regression:
        the linear-transpose/VJP closures used to be cached on the
        instance, leaking the jit trace's tracers)."""
        A_dense = jnp.asarray(rng.randn(3, 3))
        op = ops.FunctionOperator(lambda v: A_dense @ v, jnp.zeros(3))
        v = jnp.asarray(rng.randn(3))
        jitted = jax.jit(op.rmatvec)(v)
        eager = op.rmatvec(v)           # used to raise UnexpectedTracerError
        np.testing.assert_allclose(eager, A_dense.T @ v, atol=1e-12)
        np.testing.assert_allclose(jitted, eager, atol=1e-12)
        J = ops.JacobianOperator(lambda x: jnp.tanh(A_dense @ x),
                                 jnp.asarray(rng.randn(3)))
        jax.jit(J.rmatvec)(v)
        np.testing.assert_allclose(J.rmatvec(v),
                                   jax.jit(J.rmatvec)(v), atol=1e-12)

    def test_symmetric_refusal_names_solver_and_operator_flags(self, rng):
        """The refusal error must name BOTH sides of the mismatch: the
        requested solver AND the operator's declared symmetric /
        positive_definite flags (auto-routing failures are undebuggable
        when the operator side is omitted)."""
        A = ops.DenseOperator(jnp.asarray(rng.randn(4, 4)), symmetric=False)
        with pytest.raises(ValueError) as err:
            ls.route_solve("cg", A, jnp.ones(4))
        msg = str(err.value)
        assert "'cg'" in msg                      # the requested solver
        assert "symmetric=False" in msg           # the operator's flag
        assert "positive_definite=False" in msg   # ...and the PD flag
        with pytest.raises(ValueError, match="'pallas_cg'"):
            ls.solve(A, jnp.ones(4), method="pallas_cg")

    def test_as_operator(self, rng):
        A_dense = _spd(rng, 4)
        assert isinstance(ops.as_operator(A_dense), ops.DenseOperator)
        # plain numpy matrices coerce too
        assert isinstance(ops.as_operator(np.eye(4)), ops.DenseOperator)
        F = ops.as_operator(lambda v: A_dense @ v, jnp.zeros(4),
                            symmetric=True)
        assert isinstance(F, ops.FunctionOperator) and F.symmetric
        assert ops.as_operator(F) is F
        with pytest.raises(ValueError, match="example"):
            ops.as_operator(lambda v: v)

    def test_preconditioners_from_structure(self, rng):
        x = _tree_example()
        f = _tree_map_fun(0.3)
        A = ops.JacobianOperator(f, x)
        # jacobi: exact on the diagonal
        M = ops.jacobi_preconditioner_from(A)
        v = jax.tree_util.tree_map(lambda l: jnp.ones_like(l), x)
        expect = jax.tree_util.tree_map(lambda d_: 1.0 / d_, A.diagonal())
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
            M(v), expect)
        # block-jacobi inverts each leaf block exactly
        Mb = ops.block_jacobi_preconditioner(A)
        dense = A.materialize()
        out_flat, _ = jax.flatten_util.ravel_pytree(Mb(v))
        v_flat, _ = jax.flatten_util.ravel_pytree(v)
        nb = x["b"].size    # dict leaves ravel in key order: "b" then "w"
        blocks = jnp.zeros_like(dense)
        blocks = blocks.at[:nb, :nb].set(dense[:nb, :nb])
        blocks = blocks.at[nb:, nb:].set(dense[nb:, nb:])
        np.testing.assert_allclose(out_flat,
                                   jnp.linalg.solve(blocks, v_flat),
                                   atol=1e-5)

    def test_block_jacobi_exact_for_block_diagonal(self, rng):
        A1, A2 = _spd(rng, 3), _spd(rng, 2)
        B = ops.BlockDiagonal([ops.DenseOperator(A1, positive_definite=True),
                               ops.DenseOperator(A2, positive_definite=True)])
        M = ops.block_jacobi_preconditioner(B)
        v = (jnp.asarray(rng.randn(3)), jnp.asarray(rng.randn(2)))
        out = M(B.matvec(v))   # M = B⁻¹ exactly
        np.testing.assert_allclose(out[0], v[0], atol=1e-5)
        np.testing.assert_allclose(out[1], v[1], atol=1e-5)
        # the exact per-block inverse survives a caller-supplied dense
        # matrix (the declared blocks slice it; no leaf-granularity fallback)
        Mm = ops.block_jacobi_preconditioner(B, materialized=B.materialize())
        out_m = Mm(B.matvec(v))
        np.testing.assert_allclose(out_m[0], v[0], atol=1e-5)
        np.testing.assert_allclose(out_m[1], v[1], atol=1e-5)


# ---------------------------------------------------------------------------
# routing integration: flags, auto dispatch, preconditioners
# ---------------------------------------------------------------------------

class TestRouting:

    def test_operator_through_solve_infers_batch(self, rng):
        Ab = jnp.stack([_spd(rng, 4), _spd(rng, 4, 3.0)])
        bb = jnp.asarray(rng.randn(2, 4))
        A = ops.DenseOperator(Ab, positive_definite=True)
        x = ls.solve(A, bb, method="cg", tol=1e-12)   # batch_axes inferred
        np.testing.assert_allclose(jnp.einsum("bij,bj->bi", Ab, x), bb,
                                   atol=1e-5)

    def test_batched_operator_with_callable_method(self, rng):
        """A callable method receives the batch-aware operator as-is (it
        owns batching) — the registry-only batch_axes implication must not
        reject it."""
        Ab = jnp.stack([_spd(rng, 4), _spd(rng, 4, 3.0)])
        bb = jnp.asarray(rng.randn(2, 4))
        A = ops.DenseOperator(Ab, positive_definite=True)

        def my_solve(matvec, b, **kw):
            return ls.solve_cg(matvec, b, tol=1e-12, batch_ndim=1)

        x = ls.solve(A, bb, method=my_solve)
        np.testing.assert_allclose(jnp.einsum("bij,bj->bi", Ab, x), bb,
                                   atol=1e-5)

    def test_batch_mismatch_rejected(self, rng):
        A = ops.DenseOperator(_spd(rng, 4), positive_definite=True)
        with pytest.raises(ValueError, match="batch"):
            ls.solve(A, jnp.ones((2, 4)), method="cg", batch_axes=0)

    def test_auto_dispatch_small_vs_large(self, rng):
        spd_small = ops.DenseOperator(_spd(rng, 8).astype(jnp.float32),
                                      positive_definite=True)
        gen_small = ops.DenseOperator(jnp.asarray(rng.randn(8, 8)) +
                                      8 * jnp.eye(8), symmetric=False)
        f32 = jnp.zeros(8, jnp.float32)
        assert ls._resolve_auto(spd_small, f32) == "pallas_cg"
        assert ls._resolve_auto(gen_small, f32) == "dense_gmres"
        # the compiled kernel is 32-bit: float64 systems stay on XLA
        spd_f64 = ops.DenseOperator(_spd(rng, 8).astype(jnp.float64),
                                    positive_definite=True)
        assert ls._resolve_auto(spd_f64, jnp.zeros(8, jnp.float64)) == \
            "dense_gmres"
        # a requested preconditioner or warm start steers SPD small systems
        # off pallas_cg (which supports neither) onto dense_gmres
        assert ls._resolve_auto(spd_small, f32,
                                precond="jacobi") == "dense_gmres"
        assert ls._resolve_auto(spd_small, f32,
                                init=jnp.ones(8, jnp.float32)) == "dense_gmres"
        big = jnp.zeros(ls.MAX_DENSE_DIM + 1)
        spd_big = ops.FunctionOperator(lambda v: 2.0 * v, big,
                                       positive_definite=True)
        sym_big = ops.FunctionOperator(lambda v: 2.0 * v, big, symmetric=True)
        gen_big = ops.FunctionOperator(lambda v: 2.0 * v, big)
        assert ls._resolve_auto(spd_big, big) == "cg"
        # symmetric alone is NOT enough for CG (indefinite systems lie)
        assert ls._resolve_auto(sym_big, big) == "normal_cg"
        assert ls._resolve_auto(gen_big, big) == "normal_cg"

    def test_auto_solve_end_to_end(self, rng):
        A_spd = _spd(rng, 6)
        b = jnp.asarray(rng.randn(6))
        x = ls.solve(ops.DenseOperator(A_spd, positive_definite=True), b,
                     method="auto", tol=1e-10)
        np.testing.assert_allclose(A_spd @ x, b, atol=1e-4)
        # warm-started auto solve reroutes off pallas_cg instead of raising
        xw = ls.solve(ops.DenseOperator(A_spd, positive_definite=True), b,
                      method="auto", tol=1e-10, init=x)
        np.testing.assert_allclose(A_spd @ xw, b, atol=1e-4)
        A_gen = jnp.asarray(rng.randn(6, 6)) + 6 * jnp.eye(6)
        x2 = ls.solve(ops.DenseOperator(A_gen, symmetric=False), b,
                      method="auto", tol=1e-10)
        np.testing.assert_allclose(A_gen @ x2, b, atol=1e-4)

    def test_operator_jacobi_precond_skips_probing(self, rng):
        """'jacobi' on an operator reads diagonal() (O(1) for dense) rather
        than probing with d matvecs."""
        calls = []
        A_spd = _spd(rng, 5)

        class CountingDense(ops.DenseOperator):
            def matvec(self, v):
                calls.append(1)
                return super().matvec(v)

        A = CountingDense(A_spd, positive_definite=True)
        b = jnp.asarray(rng.randn(5))
        x = ls.solve(A, b, method="cg", precond="jacobi", tol=1e-12)
        np.testing.assert_allclose(A_spd @ x, b, atol=1e-5)
        # CG itself iterates; the diagonal probe would add exactly d=5
        # leading matvecs before the first iteration.  Resolve again
        # directly and check no matvec fires.
        n = len(calls)
        M = ls._resolve_precond("jacobi", A, b, 0)
        assert len(calls) == n and M is not None

    def test_block_jacobi_requires_operator(self, rng):
        with pytest.raises(ValueError, match="block_jacobi"):
            ls.solve(lambda v: v, jnp.ones(3), method="cg",
                     precond="block_jacobi")

    def test_dense_operator_materialize_feeds_lu(self, rng):
        A_dense = jnp.asarray(rng.randn(5, 5)) + 5 * jnp.eye(5)
        b = jnp.asarray(rng.randn(5))
        x = ls.solve(ops.DenseOperator(A_dense, symmetric=False), b,
                     method="lu")
        np.testing.assert_allclose(A_dense @ x, b, atol=1e-5)


# ---------------------------------------------------------------------------
# solver symmetry metadata: declared flags match numeric behavior
# ---------------------------------------------------------------------------

class TestSolverSymmetryMetadata:
    """Property: for every registry solver, (a) it solves a random SPD
    system it is routed (declared-symmetric operators are legal everywhere),
    and (b) symmetric-only solvers are never routed a declared-nonsymmetric
    operator by route_solve."""

    def _spd_system(self, seed, d=6):
        rng = np.random.RandomState(seed)
        # near-identity SPD so neumann's contraction condition also holds
        M = rng.randn(d, d) * 0.1
        A = jnp.asarray(0.5 * (M + M.T) + np.eye(d))
        b = jnp.asarray(rng.randn(d))
        return A, b

    @staticmethod
    def _maybe_shard(name, A):
        """The sharded registry variants demand a mesh-placed operator —
        the property extends to them through a ShardedOperator over the
        local devices (replicated specs: the metadata contract under test
        is independent of the split)."""
        if not name.startswith("sharded_"):
            return A
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharded_operators import ShardedOperator
        from repro.launch.mesh import make_solve_mesh
        return ShardedOperator(A, make_solve_mesh(), P(None))

    @pytest.mark.parametrize("name", sorted(ls.available_solvers()))
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_solves_declared_spd_system(self, name, seed):
        A_dense, b = self._spd_system(seed)
        A = self._maybe_shard(
            name, ops.DenseOperator(A_dense, positive_definite=True))
        x = ls.route_solve(name, A, b, tol=1e-10, maxiter=2000)
        np.testing.assert_allclose(A_dense @ x, b, atol=5e-4,
                                   err_msg=f"{name} failed its declared "
                                           "regime (SPD)")

    @pytest.mark.parametrize("name", sorted(ls.available_solvers()))
    def test_symmetric_only_never_gets_nonsymmetric_operator(self, name,
                                                             rng):
        # near-identity (general solvers all converge, incl. neumann's
        # contraction condition) but NOT symmetric
        A_dense = jnp.asarray(rng.randn(6, 6) * 0.1 + np.eye(6))
        A = self._maybe_shard(name,
                              ops.DenseOperator(A_dense, symmetric=False))
        b = jnp.asarray(rng.randn(6))
        spec = ls.get_spec(name)
        if spec.symmetric_only:
            with pytest.raises(ValueError, match="symmetric"):
                ls.route_solve(name, A, b, tol=1e-8)
        else:
            x = ls.route_solve(name, A, b, tol=1e-10, maxiter=2000)
            np.testing.assert_allclose(A_dense @ x, b, atol=5e-4,
                                       err_msg=f"general solver {name} "
                                               "failed a nonsymmetric solve")

    def test_undeclared_symmetry_trusts_solver_choice(self, rng):
        """symmetric=None keeps the historical contract: the caller's
        solver choice is the assertion (closures can't declare)."""
        A_spd = _spd(rng, 5)
        A = ops.FunctionOperator(lambda v: A_spd @ v, jnp.zeros(5))
        assert A.symmetric is None
        b = jnp.asarray(rng.randn(5))
        x = ls.route_solve("cg", A, b, tol=1e-10)
        np.testing.assert_allclose(A_spd @ x, b, atol=1e-5)


# ---------------------------------------------------------------------------
# diff API through operators
# ---------------------------------------------------------------------------

class TestDiffApiOperators:

    def _wrapped_ridge(self, rng, **spec_kw):
        X = jnp.asarray(rng.randn(12, 4))
        y = jnp.asarray(rng.randn(12))
        F = jax.grad(lambda w, t: 0.5 * jnp.sum((X @ w - y) ** 2)
                     + 0.5 * t * jnp.sum(w ** 2), argnums=0)
        spec = diff_api.ImplicitDiffSpec(optimality_fun=F, **spec_kw)
        solver = diff_api.implicit_diff(spec)(
            lambda init, t: jnp.linalg.solve(
                X.T @ X + t * jnp.eye(4), X.T @ y))
        closed = lambda t: jnp.linalg.solve(X.T @ X + t * jnp.eye(4), X.T @ y)
        return solver, closed

    @pytest.mark.parametrize("spec_kw", [
        dict(solve="cg"),
        dict(solve="auto"),
        dict(solve="cg", precond="jacobi"),
        dict(solve="cg", precond="block_jacobi"),
        # materializing route: the precond string rides through to the
        # dense solver, which derives it off its own materialized matrix
        dict(solve="dense_gmres", precond="jacobi"),
        dict(solve="dense_gmres", precond="block_jacobi"),
    ])
    def test_jacfwd_jacrev_agree_through_operators(self, rng, spec_kw):
        solver, closed = self._wrapped_ridge(rng, **spec_kw)
        t = 2.0
        Jf = jax.jacfwd(solver, argnums=1)(None, t)
        Jr = jax.jacrev(solver, argnums=1)(None, t)
        J_true = jax.jacobian(closed)(t)
        np.testing.assert_allclose(Jf, J_true, atol=1e-5)
        np.testing.assert_allclose(Jr, J_true, atol=1e-5)

    def test_root_vjp_jvp_operator_path(self, rng):
        A_spd = _spd(rng, 4)
        F = lambda x, t: A_spd @ x - t          # root: x*(t) = A⁻¹ t
        x_star = jnp.linalg.solve(A_spd, jnp.ones(4))
        v = jnp.asarray(rng.randn(4))
        (g,) = diff_api.root_vjp(F, x_star, (jnp.ones(4),), v, solve="cg",
                                 tol=1e-12)
        np.testing.assert_allclose(g, jnp.linalg.solve(A_spd, v), atol=1e-6)
        jv = diff_api.root_jvp(F, x_star, (jnp.ones(4),), (v,), solve="cg",
                               tol=1e-12)
        np.testing.assert_allclose(jv, jnp.linalg.solve(A_spd, v), atol=1e-6)

    def test_no_handrolled_ravel_closures_left(self):
        """Acceptance: diff_api contains no hand-rolled ravel closures and
        linear_solve no _FlatView — the operator layer owns raveling."""
        import inspect
        src = inspect.getsource(diff_api)
        assert "ravel_pytree" not in src
        ls_src = inspect.getsource(ls)
        assert "_FlatView" not in ls_src and "_flat_view" not in ls_src

    def test_vmap_grad_one_batched_operator_solve(self, rng):
        """The counting invariant survives the operator rebase: vmap of a
        gradient executes ONE batched masked solve, and the matvec the
        registry receives is a LinearOperator."""
        X = jnp.asarray(rng.randn(10, 3))
        y = jnp.asarray(rng.randn(10))
        executed, operator_seen = [], []

        def counting_cg(matvec, b, **kw):
            operator_seen.append(isinstance(matvec, ops.LinearOperator))
            jax.debug.callback(lambda _: executed.append(1), jnp.zeros(()))
            return ls.solve_cg(matvec, b, **kw)

        ls.register_solver("counting_cg_ops", counting_cg,
                           symmetric_only=True, supports_precond=True)
        try:
            F = jax.grad(lambda w, t: 0.5 * jnp.sum((X @ w - y) ** 2)
                         + 0.5 * t * jnp.sum(w ** 2), argnums=0)
            solver = diff_api.implicit_diff(
                diff_api.ImplicitDiffSpec(optimality_fun=F,
                                          solve="counting_cg_ops"))(
                lambda init, t: jnp.linalg.solve(
                    X.T @ X + t * jnp.eye(3), X.T @ y))
            loss = lambda t: jnp.sum(solver(None, t) ** 2)
            thetas = jnp.array([0.5, 1.0, 2.0])
            g_vmap = jax.vmap(jax.grad(loss))(thetas)
            jax.effects_barrier()
            assert len(executed) == 1
            assert operator_seen and all(operator_seen)
            g_loop = jnp.stack([jax.grad(loss)(t) for t in thetas])
        finally:
            ls._REGISTRY.pop("counting_cg_ops", None)
        np.testing.assert_allclose(g_vmap, g_loop, rtol=1e-10)


# ---------------------------------------------------------------------------
# kernel boundary: batched_cg takes an operator
# ---------------------------------------------------------------------------

class TestKernelOperatorEntry:

    def test_batched_cg_operator_input(self, rng):
        from repro.kernels.batched_cg.ops import batched_cg
        Ab = jnp.stack([_spd(rng, 4), _spd(rng, 4, 2.0)])
        bb = jnp.asarray(rng.randn(2, 4))
        A = ops.DenseOperator(Ab, positive_definite=True)
        x = batched_cg(A, bb, tol=1e-10)
        np.testing.assert_allclose(jnp.einsum("bij,bj->bi", Ab, x), bb,
                                   atol=1e-5)

    def test_batched_cg_rejects_nonsymmetric_operator(self, rng):
        from repro.kernels.batched_cg.ops import batched_cg
        A = ops.DenseOperator(jnp.asarray(rng.randn(2, 4, 4)),
                              symmetric=False)
        with pytest.raises(ValueError, match="SPD"):
            batched_cg(A, jnp.ones((2, 4)))
