"""Fused batched conjugate-gradient as a Pallas TPU kernel.

The implicit-differentiation hot path (paper §2.1) solves many small,
independent, dense SPD systems — one per example in a bilevel batch, one per
dataset in a hyperparameter sweep, one per molecule in a sensitivity scan.
Launching an XLA while_loop per system wastes the chip on dispatch and HBM
round-trips; here the whole block of systems lives in VMEM and every CG
iteration is one fused step:

  * the batched matvec ``A p`` is a single (block_b, d, d) × (block_b, d)
    contraction on the MXU, at full f32 precision,
  * the reductions (α, β, residual norms) are VPU row-reductions,
  * per-instance ``active`` masks freeze converged systems while stragglers
    iterate, and the while_loop exits as soon as the whole block converged,
  * convergence is judged on the true residual ``b - A x``: the recursive
    residual drifts from it by rounding, so it is recomputed once the
    recursive one meets ``tol`` and CG restarts where it is still above,
  * the kernel counts its own work: each system's own CG steps, and the
    matvecs its block ran, which every system of the block pays for
    whether it was still iterating or frozen.

A block loops until its slowest system meets tol, so which systems share a
block decides how many matvecs go to frozen rows.  By default block i holds
rows ``[block_b·i, block_b·(i+1))`` in the caller's order, fetched by the
``BlockSpec`` pipeline.  Given an ``order`` (a permutation of the batch),
block i holds rows ``order[block_b·i + j]`` instead: the operator stays in
HBM and the kernel copies each row's (d, d) matrix into one of two
block-sized VMEM slots, the next block's rows while this one iterates.
Every row's iterates depend on its own system alone, so the order changes
no solution, residual or own step count, only the matvecs each block runs.

Dense small-system regime: d ≤ 512.  The operator block is double-buffered
in VMEM (two pipeline buffers, or the ordered kernel's two slots), so an
(8, 512, 512) f32 block takes 16 MiB, which with the other buffers is more
than the 16 MiB scoped-VMEM default of a v5e: that is why the kernel asks
for ``VMEM_LIMIT_BYTES`` and ``block_rows`` sizes the block against
``BLOCK_BUDGET_BYTES``.  For larger or matrix-free systems use the masked
solvers in ``repro.core.linear_solve``.

The compiled kernel computes in 32-bit: float64 inputs are refused (the
TPU's Mosaic compiler has no 64-bit vector types); interpret mode keeps
the input precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _batched_cg_kernel(a_ref, b_ref, x_ref, rn_ref, *, tol: float,
                       maxiter: int):
    # compute in the input precision, floored at f32 (interpret mode keeps
    # f64 solves under jax_enable_x64 at f64; the compiled path is f32)
    dtype = jnp.promote_types(jnp.result_type(a_ref.dtype, b_ref.dtype),
                              jnp.float32)
    b = b_ref[...].astype(dtype)                        # (bb, d)

    def matvec(p):                                      # (bb, d) -> (bb, d)
        # read the operator block from its VMEM buffer on every step: no
        # second block-sized copy lives across the loop
        return lax.dot_general(
            a_ref[...].astype(dtype), p,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=dtype)

    b2 = jnp.sum(b * b, axis=-1)
    atol2 = jnp.maximum(tol * tol * b2, 1e-30)

    def cond(state):
        rs, k = state[3], state[4]
        return jnp.logical_and(k < maxiter, jnp.any(rs > atol2))

    def body(state):
        x, r, p, rs, k, steps, restarts = state
        active = rs > atol2                             # (bb,)
        ap = matvec(p)
        denom = jnp.sum(p * ap, axis=-1)
        safe = jnp.where(denom == 0, 1.0, denom)
        alpha = jnp.where(denom == 0, 0.0, rs / safe)
        alpha = jnp.where(active, alpha, 0.0)[:, None]  # frozen rows: no-op
        x = x + alpha * p
        r = r - alpha * ap
        rs_new = jnp.sum(r * r, axis=-1)
        beta = jnp.where(rs == 0, 0.0, rs_new / jnp.where(rs == 0, 1.0, rs))
        p = jnp.where(active[:, None], r + beta[:, None] * p, p)
        rs = jnp.where(active, rs_new, rs)
        steps = steps + jnp.where(active, 1, 0)         # each row's own steps
        return x, r, p, rs, k + 1, steps, restarts

    def replace_residual(state):
        # run CG until the recursive residuals meet tol, then replace them
        # with the true residuals b - A x and restart CG from x wherever
        # rounding left those above tol
        x, _, _, _, k, steps, restarts = lax.while_loop(cond, body, state)
        r = b - matvec(x)
        return x, r, r, jnp.sum(r * r, axis=-1), k, steps, restarts + 1

    x0 = jnp.zeros_like(b)                              # r = b - A·0 = b
    steps0 = jnp.zeros(b2.shape, jnp.int32)
    x, _, _, rs, k, steps, restarts = lax.while_loop(
        cond, replace_residual,
        (x0, b, b, b2, jnp.int32(0), steps0, jnp.int32(0)))
    x_ref[...] = x.astype(x_ref.dtype)
    # lane 0: the true residual norm each row stopped on; lane 1: the row's
    # own CG steps; lane 2: the matvecs its block ran (every CG step of the
    # block plus one per true-residual recomputation), which every row of
    # the block pays for
    shape = rn_ref.shape
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    own = jnp.broadcast_to(steps[:, None], shape).astype(dtype)
    charged = jnp.broadcast_to(k + restarts, shape).astype(dtype)
    rn = jnp.broadcast_to(jnp.sqrt(rs)[:, None], shape)
    rn_ref[...] = jnp.where(lane == 0, rn, jnp.where(
        lane == 1, own, jnp.where(lane == 2, charged, 0))).astype(
            rn_ref.dtype)


def _ordered_cg_kernel(order_ref, a_hbm, b_ref, x_ref, rn_ref, a_buf, sems,
                       *, tol: float, maxiter: int):
    # block i solves rows order[bb*i : bb*(i+1)] of the batch: each row's
    # operator is copied from HBM into one of two VMEM slots, and the next
    # block's copies run while this block iterates.  b arrives, and x and
    # rn leave, already in that order (the caller permutes them)
    i, n = pl.program_id(0), pl.num_programs(0)
    bb = a_buf.shape[1]
    slot = i % 2

    def rows(blk, slot):
        return [pltpu.make_async_copy(a_hbm.at[order_ref[blk * bb + j]],
                                      a_buf.at[slot, j], sems.at[slot, j])
                for j in range(bb)]

    @pl.when(i == 0)
    def _():
        for copy in rows(0, 0):
            copy.start()

    @pl.when(i + 1 < n)
    def _():
        for copy in rows(i + 1, 1 - slot):
            copy.start()

    for copy in rows(i, slot):
        copy.wait()
    _batched_cg_kernel(a_buf.at[slot], b_ref, x_ref, rn_ref, tol=tol,
                       maxiter=maxiter)


LANES = 128     # TPU vector-lane width: the last dim of a VMEM tile
SUBLANES = 8    # sublane height: the second-to-last dim of a VMEM tile
#: scoped VMEM the compiled kernel asks for (a v5e core has 128 MiB)
VMEM_LIMIT_BYTES = 48 * 1024 * 1024
#: what the double-buffered (block_b, d, d) operator block may take of it
BLOCK_BUDGET_BYTES = 32 * 1024 * 1024


def tile_heights(B: int, d: int, itemsize: int = 4) -> list:
    """Every block height the compiled kernel accepts for a (B, d) batch.

    The (block_b, d) right-hand-side block needs ``block_b`` to be a
    multiple of ``SUBLANES`` or the whole batch, ``block_b`` must divide
    ``B``, and the double-buffered operator block (plus its f32 copy when
    the input is narrower) must fit ``BLOCK_BUDGET_BYTES``.
    """
    per_row = d * d * (2 * itemsize + (4 if itemsize < 4 else 0))
    heights = [bb for bb in range(SUBLANES, B + 1, SUBLANES) if B % bb == 0]
    if B not in heights:
        heights.append(B)
    return [bb for bb in heights if bb * per_row <= BLOCK_BUDGET_BYTES]


def block_rows(B: int, d: int, itemsize: int = 4,
               want: int = SUBLANES) -> tuple:
    """The tile rule: ``(block_b, B_padded)`` for a (B, d) batch.

    ``block_b`` is the largest legal height (``tile_heights``) not above
    ``want``, else the smallest legal one.  When no height divides ``B``
    within the budget (e.g. B=100 at d=512), the batch is padded to the next
    multiple of ``SUBLANES`` — with identity systems and zero right-hand
    sides, which converge at loop entry — and tiled by that.
    """
    Bp = B
    heights = tile_heights(B, d, itemsize)
    if not heights:
        Bp = -(-B // SUBLANES) * SUBLANES
        heights = tile_heights(Bp, d, itemsize)
    if not heights:
        raise ValueError(
            f"no batched-CG block of {d}x{d} systems ({itemsize}-byte) fits "
            f"the {BLOCK_BUDGET_BYTES >> 20} MiB VMEM budget; solve d={d} "
            "with a matrix-free solver")
    below = [bb for bb in heights if bb <= want]
    return (max(below) if below else min(heights)), Bp


def pad_to_lanes(A, b, lanes: int = LANES):
    """Embed the (B, d, d) batch into the next lane multiple d' ≥ d.

    The pad block is the identity and the padded right-hand side is zero,
    so CG on the embedded system reproduces the original iterates exactly:
    the padded residual/search-direction components start at zero and
    ``A' e_pad = e_pad`` keeps them there (no coupling into the original
    coordinates), while per-instance step sizes and convergence masks are
    untouched.  This is the shape-legalization step of the tuned TPU block
    schedule — a (block_b, d', d') VMEM tile wants d' % 128 == 0 — shared
    with the interpret path so CPU tests cover the exact padded system the
    TPU kernel will run.  Returns ``(A_padded, b_padded, d_original)``.
    """
    B, d, d2 = A.shape
    assert d == d2, (d, d2)
    dp = -(-d // lanes) * lanes
    if dp == d:
        return A, b, d
    pad = dp - d
    A = jnp.pad(A, ((0, 0), (0, pad), (0, pad)))
    eye_pad = jnp.eye(pad, dtype=A.dtype)
    A = A.at[:, d:, d:].set(eye_pad)
    b = jnp.pad(b, ((0, 0), (0, pad)))
    return A, b, d


def batched_cg_pallas(A, b, *, tol: float = 1e-6, maxiter: int = 64,
                      block_b: int = SUBLANES, interpret: bool = False,
                      pad_lanes: bool = False, order=None):
    """A: (B, d, d) SPD batch; b: (B, d).  Returns ``(x, rn, counts)``:
    x (B, d) with A x ≈ b, the (B,) true residual norms the systems stopped
    on, and (B, 2) int32 counts: each system's own CG steps, and the
    matvecs charged to it (those of the block it was solved in: every step
    of the block's loop, plus one per true-residual recomputation).

    Each system runs CG until its recursive residual meets ``tol``, then
    the kernel recomputes the true residual ``b - A x`` and restarts CG
    from ``x`` while that one is above ``tol`` (residual replacement), all
    within the ``maxiter`` step budget.

    ``block_b`` is the wanted tile height; ``block_rows`` legalizes it (and
    pads the batch when it must), in interpret mode too, so CPU tests run
    the schedule the chip runs.  ``pad_lanes=True`` embeds systems whose d
    is not a multiple of the 128-lane VMEM tile width into the next lane
    multiple (identity pad — see ``pad_to_lanes``) and slices the solution
    back.

    ``order`` (None, or a (B,) integer permutation of the batch) fills the
    blocks in that order: block i solves systems ``order[block_b·i + j]``,
    whose operators the kernel copies row by row from HBM (scalar-prefetched
    indices, two VMEM slots), while ``b`` is gathered and the results
    scattered back in XLA, so they come back in the caller's order.  The
    solutions, residual norms and own steps are those of the unordered
    solve; only the matvecs charged follow the new blocks.  A batch that
    is a single block ignores the order.
    """
    if pad_lanes:
        A, b, d0 = pad_to_lanes(A, b)
        x, rn, counts = batched_cg_pallas(A, b, tol=tol, maxiter=maxiter,
                                          block_b=block_b,
                                          interpret=interpret, order=order)
        return x[:, :d0], rn, counts
    B, d, d2 = A.shape
    assert d == d2, (d, d2)
    assert b.shape == (B, d), (A.shape, b.shape)
    itemsize = jnp.dtype(A.dtype).itemsize
    if not interpret and max(itemsize, jnp.dtype(b.dtype).itemsize) > 4:
        raise TypeError(
            f"the compiled batched-CG kernel computes in 32-bit; got "
            f"{A.dtype}/{b.dtype} — route float64 systems to dense_gmres or "
            "cg (method='auto' does)")
    block_b, Bp = block_rows(B, d, itemsize, int(block_b))
    if Bp != B:
        eye = jnp.broadcast_to(jnp.eye(d, dtype=A.dtype), (Bp - B, d, d))
        A = jnp.concatenate([A, eye])
        b = jnp.pad(b, ((0, Bp - B), (0, 0)))
    rn_dtype = jnp.promote_types(jnp.result_type(A.dtype, b.dtype),
                                 jnp.float32)
    out_shape = [jax.ShapeDtypeStruct((Bp, d), b.dtype),
                 jax.ShapeDtypeStruct((Bp, LANES), rn_dtype)]
    cost_estimate = pl.CostEstimate(   # whole-call totals, worst case
        flops=2 * maxiter * Bp * d * d,
        bytes_accessed=itemsize * (Bp * d * d + 2 * Bp * d),
        transcendentals=0)
    kw = dict(tol=tol, maxiter=maxiter)
    if order is None or Bp == block_b:    # one block: no order to keep
        x, rn = pl.pallas_call(
            functools.partial(_batched_cg_kernel, **kw),
            grid=(Bp // block_b,),
            in_specs=[pl.BlockSpec((block_b, d, d), lambda i: (i, 0, 0)),
                      pl.BlockSpec((block_b, d), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((block_b, d), lambda i: (i, 0)),
                       pl.BlockSpec((block_b, LANES), lambda i: (i, 0))],
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            cost_estimate=cost_estimate, interpret=interpret,
        )(A, b)
        return x[:B], rn[:B, 0], rn[:B, 1:3].astype(jnp.int32)
    # padded rows keep their place at the end; each block's next rows are
    # fetched while it iterates, so the grid runs in sequence
    order = jnp.concatenate([jnp.asarray(order, jnp.int32),
                             jnp.arange(B, Bp, dtype=jnp.int32)])
    x, rn = pl.pallas_call(
        functools.partial(_ordered_cg_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Bp // block_b,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((block_b, d), lambda i, o: (i, 0))],
            out_specs=[pl.BlockSpec((block_b, d), lambda i, o: (i, 0)),
                       pl.BlockSpec((block_b, LANES), lambda i, o: (i, 0))],
            scratch_shapes=[pltpu.VMEM((2, block_b, d, d), A.dtype),
                            pltpu.SemaphoreType.DMA((2, block_b))]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=cost_estimate, interpret=interpret,
    )(order, A, b[order])
    # back to the caller's order: gather by the inverse permutation
    where = jnp.zeros_like(order).at[order].set(
        jnp.arange(Bp, dtype=jnp.int32))[:B]
    rn = rn[where, :3]
    return x[where], rn[:, 0], rn[:, 1:3].astype(jnp.int32)
