"""Public batched-CG op with implicit-differentiation custom VJP.

Forward: one fused Pallas kernel solves the whole (B, d, d) batch of SPD
systems on the TPU (``ref.py`` on other backends, interpret mode on
request).  Backward: instead of
differentiating through the CG iterations, we apply the paper's move at the
kernel boundary — x = A⁻¹b is implicitly defined by Ax − b = 0, so

    u  = A⁻ᵀ g          (one more batched solve, same kernel)
    ∂b = u,   ∂A = −u xᵀ

which makes the op exactly as differentiable as a dense solve at the cost of
one extra batched CG.

The backward tiles its blocks by the forward's own CG steps: a block
loops until its slowest system meets tol, and the op requires SPD, so
Aᵀ = A and each system takes about as many steps backward as it took
forward.  The transposed solve gets ``argsort`` of those steps as its
``order`` (``kernel.batched_cg_pallas``), so a block holds systems that
finish together; its solutions are bitwise those of the unordered solve.

Both solves count their own work on the device (``kernel.py``): each
system's own CG steps and the matvecs charged to it, those of the block it
was solved in (in the backward, a block of the ordered tiling).  The
forward's counts are an output; the backward's reach the caller as the
cotangent of ``tap``, a zero (B, 2) operand that only a caller who wants
them passes and differentiates against — no host callback, and with no
tap the program is the one it would be without counters.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.operators import LinearOperator, ravel_view
from repro.kernels.batched_cg.kernel import batched_cg_pallas
from repro.kernels.batched_cg.ref import batched_cg_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _solve(A, b, tap, order, tol, maxiter, block_b, interpret, pad_lanes):
    """``(x, rn, counts)``: the solutions, the true residual norms they
    stopped on and the (B, 2) int32 counts (own CG steps, matvecs
    charged).  ``rn`` and ``counts`` are diagnostics: the custom VJP
    ignores their cotangents.  ``tap`` (None, or a (B, 2) zero array) takes
    the transposed solve's counts as its cotangent.  ``order`` (None, or a
    (B,) permutation) is the order in which the kernel tiles the systems
    into blocks; the results come back in the caller's order either way."""
    if interpret is None:      # no TPU: identical masked-CG reference path
        return batched_cg_ref(A, b, tol=tol, maxiter=maxiter)
    return batched_cg_pallas(A, b, tol=tol, maxiter=maxiter, block_b=block_b,
                             interpret=interpret, pad_lanes=pad_lanes,
                             order=order)


def _fwd(A, b, tap, order, tol, maxiter, block_b, interpret, pad_lanes):
    out = _solve(A, b, tap, order, tol, maxiter, block_b, interpret,
                 pad_lanes)
    return out, (A, out[0], tap, out[2][:, 0])


def _bwd(tol, maxiter, block_b, interpret, pad_lanes, res, g):
    A, x, tap, steps = res
    # Aᵀ = A for the SPD batch, so each system takes about as many steps
    # backward as it took forward: tiling by those steps puts systems that
    # finish together in one block
    order = None if interpret is None else jnp.argsort(steps, stable=True)
    u, _, counts = _solve(A.transpose(0, 2, 1), g[0], None, order, tol,
                          maxiter, block_b, interpret, pad_lanes)
    dA = -u[:, :, None] * x[:, None, :]
    return dA, u, None if tap is None else counts.astype(tap.dtype), None


_solve.defvjp(_fwd, _bwd)


def batched_cg(A, b, *, tol: float = 1e-6, maxiter: Optional[int] = None,
               block_b=8, interpret: Optional[bool] = None,
               pad_lanes: bool = False, tap=None, return_info: bool = False):
    """Solve the batch of SPD systems A[i] x[i] = b[i] in one fused kernel.

    Args:
      A: (B, d, d) symmetric positive-definite operators, d ≤ 512 — or a
        batch-aware SPD ``LinearOperator``, which auto-materializes
        (O(1) for dense/structured operators, d probing matvecs otherwise)
        with ``b`` the matching pytree of right-hand sides.
      b: (B, d) right-hand sides ((batched) pytree for operator input).
      tol: relative residual tolerance per instance.
      maxiter: CG iteration cap (default: d, the exact-arithmetic bound).
      block_b: wanted instances per Pallas program (VMEM tile height,
        legalized by ``kernel.block_rows``), or
        ``"auto"`` to resolve a tuned tile for this ``(backend, B, d,
        dtype)`` from the autotuning cache (host-side, at trace time;
        falls back to the legacy default-8 schedule when the regime was
        never swept — see ``analysis.autotune.choose_block_b``).
      interpret: True forces Pallas interpret mode; None auto-selects the
        pure-JAX reference path off-TPU and the compiled kernel on TPU
        (which refuses float64 inputs — ``method="auto"`` routes those to
        ``dense_gmres``).
      pad_lanes: embed d into the next multiple of the 128-lane VMEM tile
        width (identity pad, exact — see ``kernel.pad_to_lanes``) before
        the Pallas call; ignored on the reference path, which has no
        tiling constraint.
      tap: None, or a (B, 2) floating zero array (one row for operator
        input with ``batch_ndim == 0``); differentiating with respect to
        it reads the backward (transposed) solve's counts, own CG steps
        and matvecs charged (those of its block in the tiling by the
        forward's own steps), as its cotangent.  The solution and its
        derivatives do not depend on it.
      return_info: also return the per-instance true residual norms
        ``|b - A x|`` that convergence was judged on and the (B, 2) int32
        counts of the forward solve (own CG steps, matvecs charged), as
        ``(x, rn, counts)``; neither is differentiated.

    Differentiable in A and b via the implicit-diff custom VJP (operator
    input: in b, through the materialized matrix).
    """
    if isinstance(A, LinearOperator):
        if A.symmetric is False:
            raise ValueError(f"batched_cg requires an SPD operator; {A!r} "
                             "declares symmetric=False")
        view = ravel_view(A, b, A.batch_ndim)
        dense = A.materialize()
        if A.batch_ndim == 0:
            dense = dense[None]
        x, rn, counts = batched_cg(dense, view.b, tol=tol, maxiter=maxiter,
                                   block_b=block_b, interpret=interpret,
                                   pad_lanes=pad_lanes, tap=tap,
                                   return_info=True)
        if A.batch_ndim == 0:
            rn, counts = rn[0], counts[0]
        x = view.to_tree(x)
        return (x, rn, counts) if return_info else x
    B, d, _ = A.shape
    if maxiter is None:
        maxiter = d
    if block_b == "auto":
        # resolved HOST-SIDE before the custom-VJP call (block_b is a
        # nondiff static arg): shapes are concrete even under jit tracing
        from repro.analysis import autotune
        block_b = autotune.choose_block_b(B, d, dtype=str(A.dtype),
                                          pad_lanes=pad_lanes)
    if interpret is None and jax.default_backend() != "tpu":
        interpret = None   # sentinel: ref path (see _solve)
    elif interpret is None:
        interpret = False
    x, rn, counts = _solve(A, b, tap, None, float(tol), int(maxiter),
                           int(block_b), interpret, bool(pad_lanes))
    return (x, rn, counts) if return_info else x
