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


# -- the ordered tiling: blocks filled in a given order of the systems ------

def _ridge_batch(key, B=64, d=32):
    """``G + θI`` with θ a log grid dealt to the systems in a shuffled
    order: each 8-row block mixes fast (high θ) and slow (low θ) systems,
    as the benchmark's per-class ridge step does."""
    M = jax.random.normal(key, (4 * d, d), jnp.float32) / 8
    theta = jnp.logspace(-3.0, 1.0, B, dtype=jnp.float32)[
        jax.random.permutation(jax.random.fold_in(key, 2), B)]
    A = M.T @ M + theta[:, None, None] * jnp.eye(d, dtype=jnp.float32)
    return A, _rhs(key, B, d)


def _orders(counts):
    own = np.asarray(counts[:, 0])
    B = own.shape[0]
    return {"own_steps": np.argsort(own, kind="stable"),
            "reversed": np.arange(B)[::-1],
            "shuffled": np.asarray(jax.random.permutation(
                jax.random.PRNGKey(7), B))}


@pytest.mark.parametrize("pad_lanes", [False, True])
@pytest.mark.parametrize("kind", ["own_steps", "reversed", "shuffled"])
def test_ordered_solve_is_bitwise_the_unordered_one(rng, kind, pad_lanes):
    """Each row's iterates depend on its own system alone, so solving the
    batch in another block order changes no solution, residual norm or
    own step count, and all come back in the caller's order."""
    A, b = _ridge_batch(rng)
    kw = dict(tol=1e-6, maxiter=1000, interpret=True, pad_lanes=pad_lanes)
    x, rn, counts = batched_cg_pallas(A, b, **kw)
    order = _orders(counts)[kind]
    xo, rno, co = batched_cg_pallas(A, b, order=jnp.asarray(order), **kw)
    np.testing.assert_array_equal(np.asarray(xo), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(rno), np.asarray(rn))
    np.testing.assert_array_equal(np.asarray(co[:, 0]),
                                  np.asarray(counts[:, 0]))
    # each row is charged the matvecs of the block it was solved in
    blocks = np.asarray(co[:, 1])[order].reshape(-1, 8)
    assert np.all(blocks == blocks[:, :1])


def test_ordered_solve_of_a_padded_batch(rng, monkeypatch):
    """A batch the tile rule pads (12 rows in blocks of 8): the identity
    rows keep their place at the end, and the 12 real rows come back
    bitwise as the unordered solve gives them."""
    d = 8
    monkeypatch.setattr(kernel, "BLOCK_BUDGET_BYTES", 8 * d * d * 8)
    assert kernel.block_rows(12, d) == (8, 16)
    A, b = _spd(rng, 12, d, 30.0), _rhs(rng, 12, d)
    x, rn, counts = _kernel(A, b)
    xo, rno, co = batched_cg_pallas(A, b, tol=1e-6, maxiter=1000,
                                    interpret=True,
                                    order=jnp.arange(12)[::-1])
    np.testing.assert_array_equal(np.asarray(xo), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(rno), np.asarray(rn))
    np.testing.assert_array_equal(np.asarray(co[:, 0]),
                                  np.asarray(counts[:, 0]))


def test_ordering_by_own_steps_charges_fewer_matvecs(rng):
    """Blocks of systems that finish at about the same step spend fewer
    matvecs on frozen rows: the charged total falls below the unordered
    one, while the systems' own steps stay the same."""
    A, b = _ridge_batch(rng)
    _, _, counts = _kernel(A, b)
    order = _orders(counts)["own_steps"]
    _, _, co = batched_cg_pallas(A, b, tol=1e-6, maxiter=1000,
                                 interpret=True, order=jnp.asarray(order))
    co, counts = np.asarray(co), np.asarray(counts)
    assert co[:, 1].sum() < counts[:, 1].sum()
    assert co[:, 1].sum() >= co[:, 0].sum()


def _ridge_hypergrad(A, C):
    """Hypergradients over θ of a validation loss through
    ``linear_solve.solve(method="pallas_cg")``, with the backward's counts
    read through the tap."""
    B, d = A.shape[:2]

    def val_loss(theta, tap):
        Ai = A + theta[:, None, None] * jnp.eye(d, dtype=A.dtype)
        x = ls.solve(DenseOperator(Ai, positive_definite=True), C,
                     method="pallas_cg", tol=1e-6, maxiter=1000,
                     interpret=True, tap=tap)
        return jnp.sum((x - 1.0) ** 2)

    return jax.grad(val_loss, argnums=(0, 1))(
        jnp.zeros((B,), A.dtype), jnp.zeros((B, 2), jnp.float32))


def test_ordered_backward_hypergradients_are_bitwise_the_unordered(
        rng, monkeypatch):
    """The backward tiles its blocks by the forward's own steps: the
    hypergradients and the backward's own steps are the bits the unordered
    backward gives, and it is charged fewer matvecs."""
    from repro.kernels.batched_cg import ops
    A, C = _ridge_batch(rng)
    grad, tap = _ridge_hypergrad(A, C)
    unordered = ops.batched_cg_pallas

    def without_order(*args, order=None, **kw):
        return unordered(*args, **kw)

    monkeypatch.setattr(ops, "batched_cg_pallas", without_order)
    grad0, tap0 = _ridge_hypergrad(A, C)
    np.testing.assert_array_equal(np.asarray(grad), np.asarray(grad0))
    np.testing.assert_array_equal(np.asarray(tap[:, 0]),
                                  np.asarray(tap0[:, 0]))
    assert float(tap[:, 1].sum()) < float(tap0[:, 1].sum())


def test_tap_cotangent_is_the_ordered_transposed_solve_counts(rng):
    """On a multi-block batch the tap reads the counts of the transposed
    solve tiled by the forward's own steps: the same as solving ``Aᵀ u =
    ∂L/∂x`` directly in that order."""
    A, b = _ridge_batch(rng)
    A = A + 0.01 * jnp.triu(A, 1)        # Aᵀ is another batch of systems
    B = A.shape[0]
    kw = dict(tol=1e-6, maxiter=1000, interpret=True)

    def loss(A, tap):
        return jnp.sum(batched_cg(A, b, tap=tap, **kw) ** 2)

    _, dtap = jax.grad(loss, argnums=(0, 1))(A, jnp.zeros((B, 2), jnp.float32))
    x, _, fwd = batched_cg(A, b, return_info=True, **kw)
    order = jnp.argsort(fwd[:, 0], stable=True)
    _, _, direct = batched_cg_pallas(A.transpose(0, 2, 1), 2 * x, order=order,
                                     **kw)
    np.testing.assert_array_equal(np.asarray(dtap), np.asarray(direct))


def test_single_block_batch_ignores_the_order(rng):
    """A batch that is one block cannot be tiled otherwise: the order is
    not used (even one that is no permutation), and no row is gathered."""
    B, d = 8, 32
    A, b = _spd(rng, B, d, 50.0), _rhs(rng, B, d)
    kw = dict(tol=1e-6, maxiter=500, interpret=True)
    out = batched_cg_pallas(A, b, **kw)
    bad = jnp.zeros((B,), jnp.int32)
    ordered = batched_cg_pallas(A, b, order=bad, **kw)
    for got, want in zip(ordered, out):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    jaxpr = str(jax.make_jaxpr(
        lambda A, b, o: batched_cg_pallas(A, b, order=o, **kw))(A, b, bad))
    assert "gather" not in jaxpr


def test_vmapped_ordered_solve_is_each_instance_alone(rng):
    """``jax.vmap`` over ordered multi-block solves, each instance with its
    own order (as the backward of a vmapped hypergradient gets): every
    instance comes back as its ordered solve alone gives it."""
    V, B, d = 3, 24, 16
    A = jnp.stack([_ridge_batch(jax.random.fold_in(rng, i), B, d)[0]
                   for i in range(V)])
    b = jax.random.normal(rng, (V, B, d), jnp.float32)
    orders = jnp.stack([jax.random.permutation(jax.random.fold_in(rng, i), B)
                        for i in range(V)])

    def solve(A, b, order):
        return batched_cg_pallas(A, b, tol=1e-6, maxiter=1000,
                                 interpret=True, order=order)

    batched = jax.vmap(solve)(A, b, orders)
    for i in range(V):
        for got, want in zip(batched, solve(A[i], b[i], orders[i])):
            np.testing.assert_array_equal(np.asarray(got[i]),
                                          np.asarray(want))
