"""A linear probe fit as one ridge regression per class: data, plain reference.

Row i of the training split has class ``i mod K`` and features
x_i = mu_class + z_i, with z_i ~ N(0, S) and class means mu ~ sep N(0, S).
The covariance S = Q diag(s) Q^T has a power-law spectrum s_j = j^-alpha,
as the features of trained networks have, in a random basis Q: so G is
far from diagonal, as a real probe's is.  The seed draws Q; the standard
normal draws behind the rows and the class means are the same for every
seed (``ROWS_KEY``).  So every seed's systems are the same systems in
another basis, with the same spectra and right-hand sides, and an
iterative solver does the same work on each.
Class c is fit one-vs-rest to the targets y_ic = +1 if row i is of class c
and -1 otherwise, as scikit-learn's ``RidgeClassifier`` does.  At its own
regularisation theta_c its weights are x_c = A_c^-1 c_c with
A_c = G + theta_c I, G = sum_i x_i x_i^T (shared by the classes) and
c_c = sum_i y_ic x_i.  Its validation loss is |Xv x_c - yv_c|^2 / (2 n_v)
and the hypergradient is d loss_c / d theta_c = -u_c . x_c with
u_c = A_c^-1 (Gv x_c - bv_c) / n_v.

Nothing here imports the system under test.  The moments are summed on the
device over blocks of rows, at float32 ``highest`` precision; the
reference solves in float64 on the host.  ``solve_classes_high`` is the
same reference a precision lower, the control: every product at the
chip's ``high`` precision, the solves by Cholesky in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
#: rows of each class in one block of the sums
PER_CLASS = 2
#: the key of the normal draws behind the rows and the class means
ROWS_KEY = 0


def _highest(subscripts, a, b):
    return jnp.einsum(subscripts, a, b, precision=HI)


def _split(v):
    """``v`` as a sum of two parts of bfloat16's precision, made with
    ``reduce_precision``, which the compiler keeps: a round trip through a
    bfloat16 array is an excess-precision convert pair that XLA on the TPU
    folds away, which would leave one pass."""
    hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    return hi, jax.lax.reduce_precision(v - hi, exponent_bits=8,
                                        mantissa_bits=7)


def dot_bf16x3(subscripts, a, b):
    """An einsum at the chip's ``high`` precision, on any backend: each
    float32 operand split in two, three of the four products kept, sums in
    float32 (what XLA's ``bf16_3x`` does)."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    # products of two bfloat16-exact numbers are exact in float32
    return _highest(subscripts, ah, bh) + (_highest(subscripts, ah, bl)
                                           + _highest(subscripts, al, bh))


def mul_bf16x3(a, b):
    """An elementwise product at ``high`` precision, as ``dot_bf16x3``."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    return ah * bh + (ah * bl + al * bh)


DOTS = {"highest": _highest, "high": dot_bf16x3}


@functools.partial(jax.jit, static_argnames=("rows", "precision"))
def _moments(key, mu, root, rows: int, precision: str):
    """``(G, C, yy)`` of ``rows`` rows: the Gram matrix, the products with
    the one-vs-rest targets (one row per class), and the targets' squares.
    ``root`` maps standard normal rows to rows of covariance S."""
    K, d = mu.shape
    dot = DOTS[precision]
    block = PER_CLASS * K
    targets = 2.0 * jnp.eye(K, dtype=jnp.float32) - 1.0

    def body(carry, j):
        G, C = carry
        z = _highest("pkd,de->pke", jax.random.normal(
            jax.random.fold_in(key, j), (PER_CLASS, K, d), jnp.float32), root)
        # row j * block + p * K + k is of class k
        row = j * block + jnp.arange(block).reshape(PER_CLASS, K)
        keep = (row < rows)[..., None]
        X = jnp.where(keep, mu[None] + z, 0.0).reshape(block, d)
        Y = jnp.where(keep[..., 0].reshape(block, 1),
                      jnp.tile(targets, (PER_CLASS, 1)), 0.0)
        return (G + dot("nd,ne->de", X, X), C + dot("nk,nd->kd", Y, X)), None

    zero = (jnp.zeros((d, d), jnp.float32), jnp.zeros((K, d), jnp.float32))
    (G, C), _ = jax.lax.scan(body, zero, jnp.arange(-(-rows // block)))
    return G, C, jnp.full((K,), float(rows), jnp.float32)


def moments(config: dict, key, precision: str = "highest"):
    """``(G, C, Gv, Bv, yy)`` on the device: the training split's Gram and
    target products, the validation split's, and the validation targets'
    squares, each class a row of ``C``, ``Bv`` and ``yy``; ``key`` draws
    the basis."""
    kmu, ktrain, kval = jax.random.split(jax.random.key(ROWS_KEY), 3)
    K, d = config["classes"], config["dim"]
    root = _root(key, d, float(config["spectrum_alpha"]))
    mu = float(config["class_sep"]) * (
        _highest("kd,de->ke", jax.random.normal(kmu, (K, d), jnp.float32),
                 root))
    G, C, _ = _moments(ktrain, mu, root, config["train_rows"], precision)
    Gv, Bv, yy = _moments(kval, mu, root, config["val_rows"], precision)
    return G, C, Gv, Bv, yy


@functools.partial(jax.jit, static_argnames=("d",))
def _root(key, d: int, alpha):
    """``diag(s)^1/2 Q^T``: rows of standard normals times it have
    covariance ``Q diag(s) Q^T``, with s_j = j^-alpha and Q a random
    orthogonal basis."""
    q, _ = jnp.linalg.qr(jax.random.normal(key, (d, d), jnp.float32))
    s = jnp.arange(1, d + 1, dtype=jnp.float32) ** -alpha
    return jnp.sqrt(s)[:, None] * q.T


def thetas(config: dict, key, k):
    """The regularisation of every class at step ``k`` (on the device): the
    same log-spaced grid over ``theta_range`` at every step and for every
    seed, one value per class, dealt to the classes in an order drawn from
    the key and the step."""
    lo, hi = np.log(config["theta_range"])
    K = config["classes"]
    grid = lo + (hi - lo) * (jnp.arange(K, dtype=jnp.float32) + 0.5) / K
    order = jax.random.permutation(jax.random.fold_in(key, k), K)
    return jnp.exp(grid[order])


def solve_classes(G, C, Gv, Bv, yy, theta, nv: int, dtype=np.float64):
    """Per class (rows of ``C``, ``Bv``, ``yy``, ``theta``):
    ``(x, validation loss, hypergradient)`` by dense solves."""
    G, C, Gv, Bv, yy, theta = (np.asarray(a, dtype)
                               for a in (G, C, Gv, Bv, yy, theta))
    A = G[None] + theta[:, None, None] * np.eye(G.shape[-1], dtype=dtype)
    x = np.linalg.solve(A, C[..., None])[..., 0]
    r = x @ Gv
    loss = (0.5 * np.sum(x * r, -1) - np.sum(Bv * x, -1) + 0.5 * yy) / nv
    u = np.linalg.solve(A, ((r - Bv) / nv)[..., None])[..., 0]
    return x, loss, -np.sum(u * x, -1)


def _cholesky_high(A):
    """Lower Cholesky factor of one float32 matrix, each product at
    ``high`` precision (an unblocked right-looking factorisation)."""
    idx = jnp.arange(A.shape[-1])

    def column(k, carry):
        S, L = carry
        col = jnp.where(idx >= k, S[:, k] / jnp.sqrt(S[k, k]), 0.0)
        return (S - mul_bf16x3(col[:, None], col[None, :]),
                L.at[:, k].set(col))
    return jax.lax.fori_loop(0, A.shape[-1], column,
                             (A, jnp.zeros_like(A)))[1]


def _cholesky_solve_high(L, b):
    """``(L L^T)^-1 b`` by two triangular solves, products at ``high``."""
    d = L.shape[-1]

    def forward(i, y):
        return y.at[i].set((b[i] - jnp.sum(mul_bf16x3(L[i], y))) / L[i, i])
    y = jax.lax.fori_loop(0, d, forward, jnp.zeros_like(b))

    def backward(t, x):
        i = d - 1 - t
        return x.at[i].set((y[i] - jnp.sum(mul_bf16x3(L[:, i], x)))
                           / L[i, i])
    return jax.lax.fori_loop(0, d, backward, jnp.zeros_like(b))


@functools.partial(jax.jit, static_argnames=("nv",))
def solve_classes_high(G, C, Gv, Bv, theta, nv: int):
    """The hypergradients of ``solve_classes`` a precision lower: float32,
    every product at ``high``, the solves by Cholesky."""
    A = G[None] + theta[:, None, None] * jnp.eye(G.shape[-1], dtype=G.dtype)
    L = jax.vmap(_cholesky_high)(A)
    solve = jax.vmap(_cholesky_solve_high)
    x = solve(L, C)
    u = solve(L, (dot_bf16x3("bd,de->be", x, Gv) - Bv) / nv)
    return -jnp.sum(mul_bf16x3(u, x), -1)


def rel_errors(value, ref):
    """The gap of each element of ``value`` from ``ref``, measured against
    the larger of its own reference and the median reference."""
    value = np.asarray(value, np.float64).ravel()
    ref = np.asarray(ref, np.float64).ravel()
    scale = np.maximum(np.abs(ref), np.median(np.abs(ref)))
    return np.abs(value - ref) / scale


def rms(errors) -> float:
    """Root mean square of the gaps of every sampled answer."""
    e = np.concatenate([np.ravel(x) for x in errors])
    return float(np.sqrt(np.mean(e ** 2)))
