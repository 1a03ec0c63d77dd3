"""The batched-CG kernel's own work counts, forward and backward.

The kernel (interpret mode on the CPU) and its reference count each
system's own CG steps and the matvecs charged to it: every step of its
block's loop plus one per true-residual recomputation.  The reference runs
the whole batch as one block, so the two are compared block by block.
The backward (transposed) solve's counts come out as the cotangent of the
``tap`` operand, with no host callback.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import observability as obs
from repro.core import DenseOperator
from repro.core import linear_solve as ls
from repro.kernels.batched_cg import kernel
from repro.kernels.batched_cg.kernel import batched_cg_pallas
from repro.kernels.batched_cg.ops import batched_cg
from repro.kernels.batched_cg.ref import batched_cg_ref


def _spd(key, B, d, cond, dtype=jnp.float32):
    """B SPD systems of size d, eigenvalues log-spaced over [1, cond]."""
    def one(k):
        Q, _ = jnp.linalg.qr(jax.random.normal(k, (d, d)))
        return (Q * jnp.logspace(0.0, np.log10(cond), d)) @ Q.T

    A = jax.vmap(one)(jax.random.split(key, B))
    return ((A + A.transpose(0, 2, 1)) / 2).astype(dtype)


def _rhs(key, B, d, dtype=jnp.float32):
    return jax.random.normal(jax.random.fold_in(key, 1), (B, d), dtype)


def _kernel(A, b, maxiter=1000, block_b=8):
    return batched_cg_pallas(A, b, tol=1e-6, maxiter=maxiter,
                             block_b=block_b, interpret=True)


def _ref_by_block(A, b, block, maxiter=1000):
    """The reference's counts, run one kernel block at a time."""
    return np.concatenate([
        np.asarray(batched_cg_ref(A[i:i + block], b[i:i + block], tol=1e-6,
                                  maxiter=maxiter)[2])
        for i in range(0, A.shape[0], block)])


@pytest.mark.parametrize("B,d,cond", [(8, 32, 10.0), (16, 32, 10.0),
                                      (16, 64, 100.0)])
def test_counts_match_ref_exactly_when_well_conditioned(rng, B, d, cond):
    """Well inside float32's reach of tol, kernel and reference take the
    same steps: the counts agree exactly, block by block."""
    A, b = _spd(rng, B, d, cond), _rhs(rng, B, d)
    _, _, counts = _kernel(A, b)
    assert counts.dtype == jnp.int32 and counts.shape == (B, 2)
    np.testing.assert_array_equal(np.asarray(counts), _ref_by_block(A, b, 8))


def test_counts_match_ref_within_a_step_at_the_float32_floor(rng):
    """At condition 1e3 float32 cannot meet tol 1e-6: the block restarts on
    its true residual until maxiter, and rounding at tol decides each
    restart, so a row's own steps may differ by one and the block's
    matvecs by one step and one recomputation."""
    B, d = 8, 64
    A, b = _spd(rng, B, d, 1e3), _rhs(rng, B, d)
    _, _, counts = _kernel(A, b, maxiter=300)
    ref = _ref_by_block(A, b, 8, maxiter=300)
    counts = np.asarray(counts)
    assert np.all(np.abs(counts[:, 0] - ref[:, 0]) <= 1)
    assert np.all(np.abs(counts[:, 1] - ref[:, 1]) <= 2)
    assert counts[0, 1] > 300            # restarts are charged too


def test_block_charges_every_row_its_matvecs(rng):
    """Each row is charged its own block's matvecs; a block of easy
    systems is charged less than one holding a hard system."""
    d = 32
    A = jnp.concatenate([_spd(rng, 8, d, 2.0), _spd(rng, 8, d, 100.0)])
    b = _rhs(rng, 16, d)
    _, _, counts = _kernel(A, b)
    counts = np.asarray(counts)
    for blk in (counts[:8], counts[8:]):
        assert np.all(blk[:, 1] == blk[0, 1])
        assert blk[0, 1] >= blk[:, 0].max() + 1
    assert counts[0, 1] < counts[8, 1]


@pytest.mark.parametrize("path", ["kernel", "ref"])
def test_three_eigenvalues_take_at_most_three_steps(path):
    """A diagonal SPD batch with 3 distinct eigenvalues: CG is exact in 3
    steps, so no system takes more before the true-residual check, which
    costs the block one matvec more and restarts nothing."""
    eig = jnp.tile(jnp.array([1.0, 3.0, 7.0]), 4)
    A = jnp.broadcast_to(jnp.diag(eig), (8, 12, 12)).astype(jnp.float32)
    b = jnp.ones((8, 12), jnp.float32).at[1, 1::3].set(0.0)
    if path == "kernel":
        _, _, counts = _kernel(A, b, maxiter=50)
    else:
        _, _, counts = batched_cg_ref(A, b, tol=1e-6, maxiter=50)
    counts = np.asarray(counts)
    assert np.all(counts[:, 0] <= 3)
    assert counts[1, 0] == 2             # two eigenvalues in its rhs
    assert np.all(counts[:, 1] == counts[:, 0].max() + 1)


def test_identity_padded_rows_count_zero(rng):
    """The rows ``kernel.block_rows`` pads a batch with (identity systems,
    zero right-hand sides) take no step and leave the block's matvecs as
    the real rows alone make them."""
    d = 32
    A, b = _spd(rng, 4, d, 50.0), _rhs(rng, 4, d)
    eye = jnp.broadcast_to(jnp.eye(d, dtype=A.dtype), (4, d, d))
    Ap = jnp.concatenate([A, eye])
    bp = jnp.concatenate([b, jnp.zeros_like(b)])
    _, _, counts = _kernel(Ap, bp)
    counts = np.asarray(counts)
    np.testing.assert_array_equal(counts[4:, 0], 0)
    np.testing.assert_array_equal(
        counts[:4], np.asarray(batched_cg_ref(A, b, tol=1e-6,
                                              maxiter=1000)[2]))


def test_padded_batch_counts_its_real_rows(rng, monkeypatch):
    """A batch the tile rule pads (12 rows, an 8-row block within a budget
    cut to force it): the last block holds 4 real rows and 4 identity
    rows, and is charged what those 4 real rows take alone."""
    d = 8
    monkeypatch.setattr(kernel, "BLOCK_BUDGET_BYTES", 8 * d * d * 8)
    assert kernel.block_rows(12, d) == (8, 16)
    A, b = _spd(rng, 12, d, 30.0), _rhs(rng, 12, d)
    _, _, counts = _kernel(A, b)
    counts = np.asarray(counts)
    assert counts.shape == (12, 2)
    np.testing.assert_array_equal(counts, _ref_by_block(A, b, 8))


@pytest.mark.parametrize("cond", [10.0, 1e3])
def test_matvecs_at_least_own_steps(rng, cond):
    A, b = _spd(rng, 16, 32, cond), _rhs(rng, 16, 32)
    _, _, counts = _kernel(A, b, maxiter=200)
    counts = np.asarray(counts)
    assert np.all(counts[:, 0] >= 1)
    assert np.all(counts[:, 1] >= counts[:, 0])


@pytest.mark.parametrize("interpret", [None, True])
def test_tap_cotangent_is_the_transposed_solve_counts(rng, interpret):
    """Differentiating with respect to the tap reads the counts of the
    backward solve ``Aᵀ u = ∂L/∂x``, the same as solving it directly."""
    B, d = 8, 32
    A = _spd(rng, B, d, 50.0)
    A = A + 0.01 * jnp.triu(A, 1)        # Aᵀ is another batch of systems
    b = _rhs(rng, B, d)
    kw = dict(tol=1e-6, maxiter=500, interpret=interpret)

    def loss(A, tap):
        return jnp.sum(batched_cg(A, b, tap=tap, **kw) ** 2)

    tap = jnp.zeros((B, 2), jnp.float32)
    _, dtap = jax.grad(loss, argnums=(0, 1))(A, tap)
    x = batched_cg(A, b, **kw)
    _, _, direct = batched_cg(A.transpose(0, 2, 1), 2 * x, return_info=True,
                              **kw)
    assert dtap.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(dtap), np.asarray(direct))
    assert np.all(np.asarray(dtap)[:, 1] >= np.asarray(dtap)[:, 0])


@pytest.mark.parametrize("interpret", [None, True])
def test_hypergradients_bit_identical_with_and_without_tap(rng, interpret):
    """The tap changes no derivative: hypergradients of a ridge validation
    loss through ``linear_solve.solve`` are the same bits either way."""
    B, d = 8, 32
    G = _spd(rng, 1, d, 20.0)[0]
    C, theta = _rhs(rng, B, d), jnp.linspace(0.5, 2.0, B, dtype=jnp.float32)

    def val_loss(theta, tap=None):
        A = G[None] + theta[:, None, None] * jnp.eye(d, dtype=G.dtype)
        extra = {} if tap is None else {"tap": tap}
        x = ls.solve(DenseOperator(A, positive_definite=True), C,
                     method="pallas_cg", tol=1e-6, maxiter=200,
                     interpret=interpret, **extra)
        return jnp.sum((x - 1.0) ** 2)

    plain = jax.grad(val_loss)(theta)
    tapped, counts = jax.grad(val_loss, argnums=(0, 1))(
        theta, jnp.zeros((B, 2), jnp.float32))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(tapped))
    assert np.all(np.asarray(counts) >= 1)


def test_solveinfo_reports_the_kernel_counts(rng):
    """``pallas_cg`` fills ``iterations`` with each system's own steps and
    ``matvecs`` with the matvecs charged to it — no ``-1`` placeholder."""
    B, d = 8, 32
    A, b = _spd(rng, B, d, 50.0), _rhs(rng, B, d)
    x, info = ls.solve(DenseOperator(A, positive_definite=True), b,
                       method="pallas_cg", tol=1e-6, maxiter=500,
                       return_info=True, interpret=True)
    _, _, counts = _kernel(A, b, maxiter=500)
    np.testing.assert_array_equal(np.asarray(info.iterations),
                                  np.asarray(counts[:, 0]))
    np.testing.assert_array_equal(np.asarray(info.matvecs),
                                  np.asarray(counts[:, 1]))
    assert np.all(np.asarray(info.iterations) > 0)
    assert bool(np.all(np.asarray(info.converged)))


def test_unbatched_solve_counts_and_tap(rng):
    """One system: the counts come out as scalars, and a (2,) tap reads
    the backward's."""
    d = 16
    A, b = _spd(rng, 1, d, 20.0)[0], _rhs(rng, 1, d)[0]

    def loss(b, tap):
        return jnp.sum(ls.solve(lambda v: A @ v, b, method="pallas_cg",
                                tol=1e-6, maxiter=200, tap=tap) ** 2)

    _, info = ls.solve(lambda v: A @ v, b, method="pallas_cg", tol=1e-6,
                       maxiter=200, return_info=True)
    assert info.iterations.shape == () and int(info.iterations) > 0
    assert int(info.matvecs) == int(info.iterations) + 1
    _, dtap = jax.grad(loss, argnums=(0, 1))(b, jnp.zeros(2, jnp.float32))
    assert dtap.shape == (2,) and float(dtap[0]) > 0
    assert float(dtap[1]) >= float(dtap[0])


def test_solve_event_carries_the_kernel_counts(rng):
    """With observability on, the registry's ``solve`` event for
    ``pallas_cg`` carries the same counts as ``SolveInfo``."""
    B, d = 8, 16
    A, b = _spd(rng, B, d, 20.0), _rhs(rng, B, d)
    obs.clear_recorded()
    with obs.observe(enabled=True, record=True):
        x, info = ls.solve(DenseOperator(A, positive_definite=True), b,
                           method="pallas_cg", tol=1e-6, return_info=True)
        jax.block_until_ready(x)
        events = [e for e in obs.recorded() if e.kind == "solve"]
    assert len(events) == 1
    its = np.asarray(events[0].values["iterations"])
    np.testing.assert_array_equal(its, np.asarray(info.iterations))
    assert np.all(its > 0)
