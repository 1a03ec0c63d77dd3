"""Batched linear-solve engine tests: vmap equivalence, per-instance
early-stop masking, Pallas batched-CG kernel parity, and batched implicit
differentiation through @custom_root."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import custom_root
from repro.core import linear_solve as ls
from repro.kernels.batched_cg.kernel import batched_cg_pallas
from repro.kernels.batched_cg.ops import batched_cg
from repro.kernels.batched_cg.ref import batched_cg_ref


def _spd_batch(key, B, d, cond=20.0):
    def one(k):
        A = jax.random.normal(k, (d, d))
        A = A @ A.T
        return A + (jnp.trace(A) / d / cond) * jnp.eye(d)
    return jax.vmap(one)(jax.random.split(key, B))


ITERATIVE = ["cg", "normal_cg", "bicgstab", "gmres"]


class TestVmapEquivalence:
    """Batched solve == stacked sequential solves, within tolerance."""

    @pytest.mark.parametrize("method", ITERATIVE + ["lu"])
    def test_engine_matches_sequential(self, rng, method):
        B, d = 6, 12
        As = _spd_batch(rng, B, d)
        bs = jax.random.normal(jax.random.fold_in(rng, 1), (B, d))
        batched = ls.solve(lambda v: jnp.einsum("bij,bj->bi", As, v), bs,
                           method=method, batch_axes=0, tol=1e-11,
                           maxiter=500)
        seq = jnp.stack([
            ls.solve(lambda v, A=As[i]: A @ v, bs[i], method=method,
                     tol=1e-11, maxiter=500)
            for i in range(B)])
        np.testing.assert_allclose(np.asarray(batched), np.asarray(seq),
                                   atol=1e-6)

    @pytest.mark.parametrize("method", ITERATIVE)
    def test_vmap_of_solver_matches_sequential(self, rng, method):
        B, d = 5, 10
        As = _spd_batch(rng, B, d)
        bs = jax.random.normal(jax.random.fold_in(rng, 2), (B, d))
        fn = ls.get_solver(method)
        vmapped = jax.vmap(
            lambda A, b: fn(lambda v: A @ v, b, tol=1e-11, maxiter=500))(
                As, bs)
        seq = jnp.stack([fn(lambda v, A=As[i]: A @ v, bs[i], tol=1e-11,
                            maxiter=500) for i in range(B)])
        np.testing.assert_allclose(np.asarray(vmapped), np.asarray(seq),
                                   atol=1e-6)

    def test_batch_axes_nonzero(self, rng):
        """Systems stacked along axis 1 solve identically to axis 0."""
        B, d = 4, 8
        As = _spd_batch(rng, B, d)
        bs = jax.random.normal(jax.random.fold_in(rng, 3), (B, d))
        x0 = ls.solve(lambda v: jnp.einsum("bij,bj->bi", As, v), bs,
                      method="cg", batch_axes=0, tol=1e-11)
        x1 = ls.solve(
            lambda v: jnp.einsum("bij,jb->ib", As, v), bs.T,
            method="cg", batch_axes=1, tol=1e-11)
        np.testing.assert_allclose(np.asarray(x0), np.asarray(x1.T),
                                   atol=1e-9)

    def test_pytree_batched(self, rng):
        """The engine batches pytree-structured systems, not just flat ones."""
        B = 4
        k1, k2 = jax.random.split(rng)
        Qa = _spd_batch(k1, B, 5)
        Qb = _spd_batch(k2, B, 3)

        def matvec(t):
            return {"a": jnp.einsum("bij,bj->bi", Qa, t["a"]),
                    "b": jnp.einsum("bij,bj->bi", Qb, t["b"])}

        b = {"a": jnp.ones((B, 5)), "b": jnp.ones((B, 3))}
        x = ls.solve(matvec, b, method="cg", batch_axes=0, tol=1e-11)
        res = matvec(x)
        np.testing.assert_allclose(np.asarray(res["a"]), 1.0, atol=1e-7)
        np.testing.assert_allclose(np.asarray(res["b"]), 1.0, atol=1e-7)


class TestEarlyStopMasking:
    """Converged instances freeze while stragglers keep iterating."""

    def test_per_instance_iteration_counts(self, rng):
        d = 16
        easy = jnp.eye(d)                       # converges in one iteration
        hard = _spd_batch(rng, 1, d, cond=1e4)[0]
        As = jnp.stack([easy, hard])
        bs = jax.random.normal(jax.random.fold_in(rng, 1), (2, d))
        x, info = ls.solve(lambda v: jnp.einsum("bij,bj->bi", As, v), bs,
                           method="cg", batch_axes=0, tol=1e-10,
                           return_info=True)
        iters = np.asarray(info.iterations)
        assert iters[0] <= 2                    # identity: immediate
        assert iters[1] > iters[0]              # straggler kept iterating
        assert bool(np.all(np.asarray(info.converged)))
        np.testing.assert_allclose(
            np.asarray(jnp.einsum("bij,bj->bi", As, x)), np.asarray(bs),
            atol=1e-5)

    def test_frozen_instance_solution_unchanged(self, rng):
        """The easy instance's solution is not degraded by extra iterations
        run for the straggler (its state is frozen, not re-updated)."""
        d = 8
        easy = 2.0 * jnp.eye(d)
        hard = _spd_batch(rng, 1, d, cond=1e5)[0]
        As = jnp.stack([easy, hard])
        bs = jnp.ones((2, d))
        x = ls.solve(lambda v: jnp.einsum("bij,bj->bi", As, v), bs,
                     method="cg", batch_axes=0, tol=1e-12, maxiter=300)
        np.testing.assert_allclose(np.asarray(x[0]), 0.5, atol=1e-12)

    def test_bicgstab_masking(self, rng):
        d = 12
        As = jnp.stack([jnp.eye(d), _spd_batch(rng, 1, d, cond=1e3)[0]])
        bs = jax.random.normal(jax.random.fold_in(rng, 2), (2, d))
        x, info = ls.solve(lambda v: jnp.einsum("bij,bj->bi", As, v), bs,
                           method="bicgstab", batch_axes=0, tol=1e-10,
                           return_info=True)
        iters = np.asarray(info.iterations)
        assert iters[0] < iters[1]
        np.testing.assert_allclose(
            np.asarray(jnp.einsum("bij,bj->bi", As, x)), np.asarray(bs),
            atol=1e-5)

    def test_maxiter_reports_nonconverged(self, rng):
        d = 16
        As = _spd_batch(rng, 2, d, cond=1e6)
        bs = jax.random.normal(jax.random.fold_in(rng, 3), (2, d))
        _, info = ls.solve(lambda v: jnp.einsum("bij,bj->bi", As, v), bs,
                           method="cg", batch_axes=0, tol=1e-14, maxiter=2,
                           return_info=True)
        assert not bool(np.all(np.asarray(info.converged)))


class TestSolverRegistry:

    def test_available_solvers(self):
        names = ls.available_solvers()
        for expected in ["cg", "normal_cg", "bicgstab", "gmres", "lu",
                         "neumann", "pallas_cg"]:
            assert expected in names

    def test_spec_properties(self):
        assert ls.get_spec("cg").symmetric_only
        assert not ls.get_spec("lu").matrix_free
        assert ls.get_spec("gmres").supports_precond

    def test_unknown_solver_raises(self):
        with pytest.raises(ValueError, match="unknown linear solver"):
            ls.get_spec("does_not_exist")

    def test_register_custom(self):
        def trivial(matvec, b, **kw):
            return b
        ls.register_solver("identity_test", trivial)
        try:
            assert ls.get_solver("identity_test") is trivial
        finally:
            ls._REGISTRY.pop("identity_test")

    def test_callable_with_batch_axes_rejected(self, rng):
        with pytest.raises(ValueError, match="batch_axes"):
            ls.solve(lambda v: v, jnp.ones((2, 3)),
                     method=lambda mv, b, **kw: b, batch_axes=0)


class TestPreconditioning:

    def test_jacobi_exact_for_diagonal(self, rng):
        d = 12
        diag = jnp.arange(1.0, d + 1.0)
        b = jax.random.normal(rng, (d,))
        x, info = ls.solve_cg(lambda v: diag * v, b, precond="jacobi",
                              tol=1e-12, return_info=True)
        assert int(info.iterations) <= 2        # M⁻¹A = I: immediate
        np.testing.assert_allclose(np.asarray(diag * x), np.asarray(b),
                                   atol=1e-10)

    def test_jacobi_reduces_iterations(self, rng):
        d = 32
        # badly scaled SPD system: diagonal spans 4 orders of magnitude
        scales = 10.0 ** jnp.linspace(-2, 2, d)
        A = _spd_batch(rng, 1, d)[0]
        A = scales[:, None] * A * scales[None, :]
        b = jax.random.normal(jax.random.fold_in(rng, 1), (d,))
        _, plain = ls.solve_cg(lambda v: A @ v, b, tol=1e-8, maxiter=4000,
                               return_info=True)
        _, jac = ls.solve_cg(lambda v: A @ v, b, precond="jacobi", tol=1e-8,
                             maxiter=4000, return_info=True)
        assert int(jac.iterations) < int(plain.iterations)

    def test_callable_precond(self, rng):
        d = 8
        A = _spd_batch(rng, 1, d)[0]
        b = jax.random.normal(jax.random.fold_in(rng, 1), (d,))
        M = ls.jacobi_preconditioner(jnp.diagonal(A))
        x = ls.solve_cg(lambda v: A @ v, b, precond=M, tol=1e-12)
        np.testing.assert_allclose(np.asarray(A @ x), np.asarray(b),
                                   atol=1e-8)

    def test_diagonal_of_matvec(self, rng):
        A = jax.random.normal(rng, (6, 6))
        diag = ls.diagonal_of_matvec(lambda v: A @ v, jnp.zeros(6))
        np.testing.assert_allclose(np.asarray(diag),
                                   np.asarray(jnp.diagonal(A)), atol=1e-12)


class TestPallasBatchedCG:
    """Pallas kernel vs ref.py parity on CPU interpret mode."""

    @pytest.mark.parametrize("B,d,block_b", [(8, 16, 8), (16, 32, 8),
                                             (4, 64, 2), (8, 8, 1)])
    def test_kernel_matches_ref(self, rng, B, d, block_b):
        As = _spd_batch(rng, B, d).astype(jnp.float32)
        bs = jax.random.normal(jax.random.fold_in(rng, 1), (B, d),
                               jnp.float32)
        out, _, _ = batched_cg_pallas(As, bs, tol=1e-6, maxiter=2 * d,
                                   block_b=block_b, interpret=True)
        ref, _, _ = batched_cg_ref(As, bs, tol=1e-6, maxiter=2 * d)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_ref_solves(self, rng):
        B, d = 8, 24
        As = _spd_batch(rng, B, d).astype(jnp.float32)
        bs = jax.random.normal(jax.random.fold_in(rng, 1), (B, d),
                               jnp.float32)
        x, _, _ = batched_cg_ref(As, bs, tol=1e-8, maxiter=4 * d)
        res = jnp.linalg.norm(jnp.einsum("bij,bj->bi", As, x) - bs, axis=-1)
        rel = res / jnp.linalg.norm(bs, axis=-1)
        assert float(jnp.max(rel)) < 1e-5

    def test_op_custom_vjp_matches_dense_solve(self, rng):
        B, d = 4, 12
        As = _spd_batch(rng, B, d)
        bs = jax.random.normal(jax.random.fold_in(rng, 1), (B, d))

        def loss_cg(A, b):
            return jnp.sum(batched_cg(A, b, tol=1e-12, maxiter=40 * d) ** 2)

        def loss_dense(A, b):
            return jnp.sum(jnp.linalg.solve(A, b[..., None])[..., 0] ** 2)

        gA, gb = jax.grad(loss_cg, argnums=(0, 1))(As, bs)
        rA, rb = jax.grad(loss_dense, argnums=(0, 1))(As, bs)
        np.testing.assert_allclose(np.asarray(gA), np.asarray(rA), rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(gb), np.asarray(rb), rtol=1e-4,
                                   atol=1e-6)

    def test_registry_pallas_cg_path(self, rng):
        B, d = 8, 16
        As = _spd_batch(rng, B, d).astype(jnp.float32)
        bs = jax.random.normal(jax.random.fold_in(rng, 1), (B, d),
                               jnp.float32)
        x = ls.solve(lambda v: jnp.einsum("bij,bj->bi", As, v), bs,
                     method="pallas_cg", batch_axes=0, tol=1e-6,
                     interpret=True)
        res = jnp.linalg.norm(jnp.einsum("bij,bj->bi", As, x) - bs, axis=-1)
        rel = res / jnp.linalg.norm(bs, axis=-1)
        assert float(jnp.max(rel)) < 1e-4

    @pytest.mark.parametrize("interpret", [None, True])
    def test_converged_on_true_residual_f32(self, rng, interpret):
        """f32 CG at tol=1e-6 on cond-100 systems: the recursive residual
        drifts below the true one, so stopping on it alone leaves every
        true residual here above tol (1.3-4.2x).  With residual replacement
        every system converges on its true residual."""
        from repro.core import DenseOperator
        B, d, tol = 8, 64, 1e-6

        def one(k):
            Q, _ = jnp.linalg.qr(jax.random.normal(k, (d, d)))
            return (Q * jnp.logspace(0.0, 2.0, d)) @ Q.T

        As = jax.vmap(one)(jax.random.split(rng, B))
        As = ((As + As.transpose(0, 2, 1)) / 2).astype(jnp.float32)
        bs = jax.random.normal(jax.random.fold_in(rng, 1), (B, d),
                               jnp.float32)
        x, info = ls.solve(DenseOperator(As, positive_definite=True), bs,
                           method="pallas_cg", tol=tol, maxiter=10 * d,
                           return_info=True, interpret=interpret)
        A64, b64 = np.asarray(As, np.float64), np.asarray(bs, np.float64)
        atol = tol * np.linalg.norm(b64, axis=-1)
        true = np.linalg.norm(
            b64 - np.einsum("bij,bj->bi", A64, np.asarray(x, np.float64)),
            axis=-1)
        assert bool(np.all(np.asarray(info.converged)))
        assert np.all(np.asarray(info.residual) <= atol)
        assert np.all(true <= atol), true / atol

    def test_dense_dim_guard(self, rng):
        d = ls.MAX_DENSE_DIM + 1
        b = jnp.ones((2, d))
        with pytest.raises(ValueError, match="MAX_DENSE_DIM"):
            ls.solve(lambda v: v, b, method="pallas_cg", batch_axes=0)


class TestLanePadding:
    """Interpret-path coverage for d not a multiple of the 128-lane VMEM
    tile width — the shape-legalization half of the tuned TPU block
    schedule (identity pad, exact embedding; see kernel.pad_to_lanes)."""

    def test_pad_shape_math(self):
        from repro.kernels.batched_cg.kernel import LANES, pad_to_lanes
        A = jnp.eye(96)[None]
        b = jnp.ones((1, 96))
        Ap, bp, d0 = pad_to_lanes(A, b)
        assert Ap.shape == (1, 128, 128) and bp.shape == (1, 128)
        assert d0 == 96 and LANES == 128
        # padded block is the identity, coupling blocks are zero
        np.testing.assert_array_equal(np.asarray(Ap[0, 96:, 96:]),
                                      np.eye(32))
        assert float(jnp.abs(Ap[0, :96, 96:]).max()) == 0.0
        # already lane-aligned: no-op
        A128, b128, d0 = pad_to_lanes(jnp.eye(128)[None],
                                      jnp.ones((1, 128)))
        assert A128.shape == (1, 128, 128) and d0 == 128

    @pytest.mark.parametrize("B,d,block_b", [(4, 7, 2), (8, 96, 4),
                                             (4, 130, 2)])
    def test_interpret_padded_matches_ref(self, rng, B, d, block_b):
        from repro.kernels.batched_cg.kernel import pad_to_lanes
        As = _spd_batch(rng, B, d).astype(jnp.float32)
        bs = jax.random.normal(jax.random.fold_in(rng, 1), (B, d),
                               jnp.float32)
        out, _, _ = batched_cg_pallas(As, bs, tol=1e-6, maxiter=2 * d,
                                   block_b=block_b, interpret=True,
                                   pad_lanes=True)
        ref, _, _ = batched_cg_ref(As, bs, tol=1e-6, maxiter=2 * d)
        assert out.shape == (B, d)      # solution sliced back to d
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        assert pad_to_lanes(As, bs)[0].shape[-1] % 128 == 0

    def test_op_grad_with_padding_matches_dense(self, rng):
        """The implicit-diff custom VJP survives padding: the backward
        solve runs on the same padded system."""
        B, d = 4, 10
        As = _spd_batch(rng, B, d)
        bs = jax.random.normal(jax.random.fold_in(rng, 1), (B, d))

        def loss_cg(A, b):
            return jnp.sum(batched_cg(A, b, tol=1e-12, maxiter=40 * d,
                                      interpret=True, pad_lanes=True) ** 2)

        def loss_dense(A, b):
            return jnp.sum(jnp.linalg.solve(A, b[..., None])[..., 0] ** 2)

        gA, gb = jax.grad(loss_cg, argnums=(0, 1))(As, bs)
        rA, rb = jax.grad(loss_dense, argnums=(0, 1))(As, bs)
        np.testing.assert_allclose(np.asarray(gA), np.asarray(rA),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gb), np.asarray(rb),
                                   rtol=1e-4, atol=1e-6)


class TestBatchedImplicitDiff:
    """jax.vmap over a @custom_root solver == Python-loop baseline (1e-5)."""

    def _loss(self, Xi, yi, theta, solve_name):
        d = Xi.shape[1]

        def f(x, t):
            r = Xi @ x - yi
            return (jnp.sum(r ** 2) + t * jnp.sum(x ** 2)) / 2

        F = jax.grad(f, argnums=0)

        def raw(init, t):
            del init
            return jnp.linalg.solve(Xi.T @ Xi + t * jnp.eye(d), Xi.T @ yi)

        solver = custom_root(F, solve=solve_name, tol=1e-12)(raw)
        return jnp.sum(solver(None, theta) ** 2)

    @pytest.mark.parametrize("solve_name", ["cg", "normal_cg", "bicgstab"])
    def test_vmapped_grads_match_loop(self, rng, solve_name):
        B, m, d = 8, 20, 5
        X = jax.random.normal(rng, (B, m, d))
        y = jax.random.normal(jax.random.fold_in(rng, 1), (B, m))
        thetas = jnp.linspace(0.5, 5.0, B)

        g_loop = jnp.stack([
            jax.grad(self._loss, argnums=2)(X[i], y[i], thetas[i],
                                            solve_name)
            for i in range(B)])
        g_vmap = jax.vmap(
            lambda Xi, yi, t: jax.grad(self._loss, argnums=2)(
                Xi, yi, t, solve_name))(X, y, thetas)
        np.testing.assert_allclose(np.asarray(g_vmap), np.asarray(g_loop),
                                   atol=1e-5)

    def test_vmapped_jacobian_matches_closed_form(self, rng):
        """Whole-batch Jacobian dx*/dθ via vmap matches the analytic form."""
        B, m, d = 4, 15, 4
        X = jax.random.normal(rng, (B, m, d))
        y = jax.random.normal(jax.random.fold_in(rng, 1), (B, m))
        thetas = jnp.linspace(1.0, 4.0, B)

        def solve_one(Xi, yi, t):
            def f(x, tt):
                r = Xi @ x - yi
                return (jnp.sum(r ** 2) + tt * jnp.sum(x ** 2)) / 2
            F = jax.grad(f, argnums=0)

            def raw(init, tt):
                del init
                return jnp.linalg.solve(Xi.T @ Xi + tt * jnp.eye(d),
                                        Xi.T @ yi)
            return custom_root(F, solve="cg", tol=1e-12)(raw)(None, t)

        J = jax.vmap(jax.jacobian(solve_one, argnums=2))(X, y, thetas)
        for i in range(B):
            A = X[i].T @ X[i] + thetas[i] * jnp.eye(d)
            J_ref = -jnp.linalg.solve(A, jnp.linalg.solve(A, X[i].T @ y[i]))
            np.testing.assert_allclose(np.asarray(J[i]), np.asarray(J_ref),
                                       atol=1e-6)


class TestDenseGMRES:
    """Batched preconditioned GMRES for the nonsymmetric dense regime."""

    def _nonsym_batch(self, key, B, d, shift=6.0):
        A = jax.random.normal(key, (B, d, d))
        return A + shift * jnp.eye(d)

    def test_registered_with_correct_spec(self):
        spec = ls.get_spec("dense_gmres")
        assert spec.supports_precond
        assert not spec.matrix_free
        assert not spec.symmetric_only

    def test_batched_matches_dense_solve(self, rng):
        B, d = 6, 10
        As = self._nonsym_batch(rng, B, d)
        bs = jax.random.normal(jax.random.fold_in(rng, 1), (B, d))
        x, info = ls.solve(lambda v: jnp.einsum("bij,bj->bi", As, v), bs,
                           method="dense_gmres", batch_axes=0, tol=1e-11,
                           return_info=True)
        x_ref = jnp.linalg.solve(As, bs[..., None])[..., 0]
        np.testing.assert_allclose(np.asarray(x), np.asarray(x_ref),
                                   atol=1e-7)
        assert bool(np.asarray(info.converged).all())

    def test_vmap_of_solver_matches_sequential(self, rng):
        """vmap-equivalence: one batched masked solve == the python loop."""
        B, d = 5, 8
        As = self._nonsym_batch(rng, B, d)
        bs = jax.random.normal(jax.random.fold_in(rng, 2), (B, d))
        vmapped = jax.vmap(
            lambda A, b: ls.solve_dense_gmres(lambda v: A @ v, b,
                                              tol=1e-11))(As, bs)
        seq = jnp.stack([
            ls.solve_dense_gmres(lambda v, A=As[i]: A @ v, bs[i], tol=1e-11)
            for i in range(B)])
        np.testing.assert_allclose(np.asarray(vmapped), np.asarray(seq),
                                   atol=1e-8)

    def test_jacobi_precond_true_residual(self, rng):
        """Badly row-scaled batch: jacobi preconditioning converges and the
        reported residual is the TRUE one (not the preconditioned one)."""
        B, d = 4, 12
        scales = 10.0 ** jnp.linspace(-2, 2, d)
        As = self._nonsym_batch(rng, B, d) * scales[None, :, None]
        bs = jax.random.normal(jax.random.fold_in(rng, 3), (B, d))
        mv = lambda v: jnp.einsum("bij,bj->bi", As, v)
        x, info = ls.solve(mv, bs, method="dense_gmres", batch_axes=0,
                           tol=1e-10, precond="jacobi", return_info=True)
        true_rn = jnp.linalg.norm(bs - mv(x), axis=-1)
        np.testing.assert_allclose(np.asarray(info.residual),
                                   np.asarray(true_rn), rtol=1e-6, atol=1e-12)
        assert bool(np.asarray(info.converged).all())

    def test_callable_precond(self, rng):
        d = 9
        A = jax.random.normal(rng, (d, d)) + 5 * jnp.eye(d)
        b = jax.random.normal(jax.random.fold_in(rng, 4), (d,))
        M = lambda v: v / jnp.diagonal(A)
        x = ls.solve_dense_gmres(lambda v: A @ v, b, tol=1e-11, precond=M)
        np.testing.assert_allclose(np.asarray(A @ x), np.asarray(b),
                                   atol=1e-7)

    def test_dense_dim_guard(self):
        with pytest.raises(ValueError, match="MAX_DENSE_DIM"):
            ls.solve_dense_gmres(lambda v: v, jnp.ones(ls.MAX_DENSE_DIM + 1))

    def test_backward_solve_via_registry(self, rng):
        """dense_gmres as the custom_root backward solver: nonsymmetric
        fixed-point Jacobian matches the closed form."""
        M = 0.4 * jax.random.normal(rng, (6, 6))   # nonsymmetric contraction

        def T(x, theta):
            return M @ x + theta

        def raw(init, theta):
            return jnp.linalg.solve(jnp.eye(6) - M, theta)

        from repro.core import custom_fixed_point
        J = jax.jacobian(
            custom_fixed_point(T, solve="dense_gmres", tol=1e-12)(raw),
            argnums=1)(None, jnp.ones(6))
        np.testing.assert_allclose(np.asarray(J),
                                   np.asarray(jnp.linalg.inv(jnp.eye(6) - M)),
                                   atol=1e-8)
