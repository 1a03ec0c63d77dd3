"""Compile the solve path's Pallas kernel for a described TPU v5e chip.

Nothing runs here: each test lowers and compiles for a ``v5e:2x2``
topology that ``jax.experimental.topologies`` describes without the chip,
so the TPU compiler refuses here what it would refuse on the chip (block
shapes off the (8, 128) tiling, more VMEM than a kernel may take).  The
shapes are the ones the tile rule (``kernel.block_rows``) and the router
produce on the main path.  Code that asks ``jax.default_backend()`` still
sees the CPU, so the ``tpu_program`` fixture steers it to the TPU branch
for the duration of these tests, turns x64 off (programs run float32) and
keeps the persistent compilation cache off (a described chip's programs
cannot be read back).

All compiles stay in this one file, in this process: only one process may
load the TPU library, and it keeps it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import DenseOperator, ImplicitDiffSpec, custom_root
from repro.core import implicit_diff
from repro.core import linear_solve as ls
from repro.kernels.batched_cg.kernel import batched_cg_pallas, block_rows
from repro.kernels.batched_cg.ops import batched_cg


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def tpu_program():
    """float32 programs on the TPU branch, compile cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    x64 = jax.config.jax_enable_x64
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        yield
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", cache_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, tpu_program):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("B,d", [(12, 128), (64, 128), (64, 384),
                                 (64, 512), (100, 512)])
def test_natively_batched_kernel_compiles(one_chip, B, d):
    """``block_b="auto"`` resolves through the tile rule to a block the
    compiler accepts: a multiple of 8 or the whole batch, inside the VMEM
    budget — B=100 at d=512 has none and pads the batch to 104."""
    bb, Bp = block_rows(B, d)
    assert (bb % 8 == 0 or bb == Bp) and Bp % bb == 0
    assert Bp == (104 if B == 100 else B)
    text = _compiled_text(
        lambda A, b: batched_cg(A, b, tol=1e-6, block_b="auto"),
        _spec((B, d, d), one_chip), _spec((B, d), one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("solve,kernel", [("auto", False),
                                          ("pallas_cg", True)])
def test_vmapped_hypergradient_compiles(one_chip, solve, kernel):
    """``jax.vmap(jax.grad(...))`` over a ``custom_root`` ridge solver at
    B=64 datasets of (2048, 512).  ``solve="auto"`` routes the backward
    solve to ``dense_gmres`` (the Jacobian operator is not declared
    positive definite); ``"pallas_cg"`` keeps the kernel, one B=1 tile per
    vmapped instance."""
    B, m, d = 64, 2048, 512

    def per_dataset_loss(Xi, yi, theta):
        def f(x, t):
            r = Xi @ x - yi
            return (jnp.sum(r ** 2) + t * jnp.sum(x ** 2)) / 2

        def raw_solver(init, t):
            eye = jnp.eye(d, dtype=Xi.dtype)
            return jnp.linalg.solve(Xi.T @ Xi + t * eye, Xi.T @ yi)

        solver = custom_root(jax.grad(f, argnums=0), solve=solve,
                             tol=1e-6)(raw_solver)
        return jnp.sum(solver(None, theta) ** 2)

    text = _compiled_text(jax.vmap(jax.grad(per_dataset_loss, argnums=2)),
                          _spec((B, m, d), one_chip),
                          _spec((B, m), one_chip), _spec((B,), one_chip))
    assert ("tpu_custom_call" in text) == kernel


def test_service_bucket_pallas_cg_entry_compiles(one_chip):
    """The registry's ``pallas_cg`` entry on a batched ``DenseOperator`` at
    a full service bucket (capacity 64, d=448 — not a lane multiple)."""
    def dispatch(A, b):
        op = DenseOperator(A, symmetric=True, positive_definite=True)
        return ls.route_solve("pallas_cg", op, b, tol=1e-6, maxiter=1000,
                              return_info=True)

    text = _compiled_text(dispatch, _spec((64, 448, 448), one_chip),
                          _spec((64, 448), one_chip))
    assert "tpu_custom_call" in text


def test_auto_keeps_float64_off_the_kernel(one_chip):
    """Under x64, ``method="auto"`` sends a float64 SPD batch to XLA's
    ``dense_gmres``; the compiled kernel itself refuses float64."""
    with jax.enable_x64(True):
        A = _spec((8, 128, 128), one_chip, jnp.float64)
        b = _spec((8, 128), one_chip, jnp.float64)
        op = DenseOperator(jnp.eye(128, dtype=jnp.float64)[None],
                           positive_definite=True)
        assert ls._resolve_auto(op, jnp.zeros(128, jnp.float64)) == \
            "dense_gmres"
        with pytest.raises(TypeError, match="32-bit"):
            jax.jit(lambda A, b: batched_cg(A, b)).lower(A, b)


def test_batch_sharded_hypergradient_compiles_on_four_chips(topo,
                                                            tpu_program):
    """The ``--chips 4`` path of ``chip_smoke.py``: a batch-sharded ridge
    hypergradient (B=256, d=128) whose backward solve routes to
    ``sharded_cg`` on a 4-device mesh."""
    from repro.distributed import SolveSharding
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    B, m, d = 256, 512, 128

    def F(x, theta, X, y):
        r = jnp.einsum("bmd,bd->bm", X, x) - y
        return jnp.einsum("bmd,bm->bd", X, r) + theta[:, None] * x

    def local_solve(theta, X, y):
        A = jnp.einsum("bmd,bme->bde", X, X) \
            + theta[:, None, None] * jnp.eye(d, dtype=X.dtype)
        return jnp.linalg.solve(
            A, jnp.einsum("bmd,bm->bd", X, y)[..., None])[..., 0]

    specs = (P("data"), P("data", None, None), P("data", None))

    def fwd(init, theta, X, y):
        return jax.shard_map(local_solve, mesh=mesh, in_specs=specs,
                             out_specs=P("data", None),
                             check_vma=False)(theta, X, y)

    sharding = SolveSharding(mesh, P("data", None), batch_ndim=1,
                             theta_specs=specs)
    dec = implicit_diff(ImplicitDiffSpec(optimality_fun=F, solve="cg",
                                         tol=1e-6, sharding=sharding))(fwd)
    grad = jax.grad(lambda t, X, y: jnp.sum(dec(None, t, X, y) ** 2))
    args = [_spec(shape, NamedSharding(mesh, s))
            for shape, s in zip(((B,), (B, m, d), (B, m)), specs)]
    compiled = jax.jit(grad).lower(*args).compile()
    assert compiled.output_shardings.spec == P("data")


def _host_transfers(text: str) -> int:
    """Host callbacks in a compiled TPU program: on the TPU they lower to
    send/recv pairs marked as host transfers."""
    return text.count("is_host_transfer=true")


def test_counting_kernel_compiles_at_the_ridge_probe_size(one_chip):
    """1,000 systems of d=512 at the 8-row tile the rule picks: the kernel
    that writes its step and matvec counts into the residual tile's lanes
    still fits the 48 MiB VMEM limit."""
    assert block_rows(1000, 512) == (8, 1000)
    text = _compiled_text(
        lambda A, b: batched_cg(A, b, tol=1e-6, maxiter=1000, block_b=8,
                                return_info=True),
        _spec((1000, 512, 512), one_chip), _spec((1000, 512), one_chip))
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def _counted_hypergradient(K, d):
    """Hypergradients over per-class ridge regularisations through the
    dense SPD solve, with the forward's counts (``return_info``) and the
    backward's (the tap's cotangent) as outputs."""
    def step(theta, G, C):
        def val_loss(theta, tap):
            A = G[None] + theta[:, None, None] * jnp.eye(d, dtype=G.dtype)
            x, info = ls.solve(DenseOperator(A, positive_definite=True), C,
                               method="auto", tol=1e-6, maxiter=1000,
                               return_info=True, tap=tap)
            counts = jnp.stack([info.iterations, info.matvecs], -1)
            return jnp.sum(x * x), counts

        tap = jnp.zeros((K, 2), jnp.float32)
        (_, fwd), (grad, bwd) = jax.value_and_grad(
            val_loss, argnums=(0, 1), has_aux=True)(theta, tap)
        return grad, fwd, bwd

    return step


def test_counted_hypergradient_has_two_kernels_and_no_host_callback(
        one_chip):
    """At the ridge probe's size (1,000 classes, d=512) the counted step
    holds the forward and the transposed kernel and nothing that talks to
    the host; the same step with observability on does, so the check can
    fail."""
    from repro import observability as obs
    K, d = 1000, 512
    text = _compiled_text(_counted_hypergradient(K, d),
                          _spec((K,), one_chip), _spec((d, d), one_chip),
                          _spec((K, d), one_chip))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert _host_transfers(text) == 0
    with obs.observe(enabled=True):
        observed = _compiled_text(_counted_hypergradient(8, 128),
                                  _spec((8,), one_chip),
                                  _spec((128, 128), one_chip),
                                  _spec((8, 128), one_chip))
    assert _host_transfers(observed) > 0


def _kernel_operands(lowered_text: str) -> list:
    """The operand types of each ``tpu_custom_call`` in a lowered program."""
    return [line.rsplit(" : (", 1)[1].split(") -> ")[0]
            for line in lowered_text.splitlines()
            if "@tpu_custom_call(" in line]


def test_ordered_backward_compiles_at_the_ridge_probe_size(one_chip):
    """The transposed solve tiles its blocks by the forward's own steps:
    at 1,000 systems of d=512 the ordered kernel, which copies each row's
    operator from HBM into one of two 8-row VMEM slots, compiles within
    the 48 MiB VMEM limit, and in the counted hypergradient the backward
    kernel is the one that takes the order (the scalar-prefetched int32
    operand) while the forward's takes none."""
    K, d = 1000, 512
    text = _compiled_text(
        lambda A, b, o: batched_cg_pallas(A, b, tol=1e-6, maxiter=1000,
                                          order=o),
        _spec((K, d, d), one_chip), _spec((K, d), one_chip),
        _spec((K,), one_chip, jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    lowered = jax.jit(_counted_hypergradient(K, d)).lower(
        _spec((K,), one_chip), _spec((d, d), one_chip),
        _spec((K, d), one_chip)).as_text()
    systems = f"tensor<{K}x{d}x{d}xf32>, tensor<{K}x{d}xf32>"
    assert _kernel_operands(lowered) == [systems,
                                         f"tensor<{K}xi32>, {systems}"]
