"""Public-API surface snapshot for ``repro.core``.

The implicit-diff API redesign touches every layer of the package; this
snapshot pins the re-exported surface so an accidental rename, a dropped
re-export, or an unintended new public name fails CI immediately (the
fast lane runs this file first).  Update ``EXPECTED_SURFACE`` *explicitly*
when the public API changes on purpose — the diff then documents the
change in review.
"""
import importlib

import repro.core


# Names intentionally re-exported from repro.core (functions/classes), plus
# the submodules that importing repro.core necessarily binds on the package.
EXPECTED_SURFACE = {
    # pytree-native linear operators
    "LinearOperator", "JacobianOperator", "SampledJacobianOperator",
    "DenseOperator", "RidgeShifted", "BlockDiagonal", "ComposedOperator",
    "as_operator",
    # implicit-diff API (mode-polymorphic)
    "ImplicitDiffSpec", "implicit_diff",
    "custom_root", "custom_fixed_point",
    "custom_root_jvp", "custom_fixed_point_jvp",      # deprecated shims
    "root_vjp", "root_jvp",
    # solver runtime
    "IterativeSolver", "OptInfo",
    "GradientDescent", "ProximalGradient", "ProjectedGradient",
    "MirrorDescent", "BlockCoordinateDescent", "Newton", "LBFGS",
    "FixedPointIteration", "AndersonAcceleration",
    # batched linear-solve engine
    "solve", "SolverSpec", "SolveInfo",
    "register_solver", "get_solver", "get_spec", "available_solvers",
    "jacobi_preconditioner",
    "solve_cg", "solve_normal_cg", "solve_bicgstab", "solve_gmres",
    "solve_dense_gmres", "solve_lu", "solve_neumann",
    # DEQ layer
    "deq_fixed_point", "make_deq_block", "make_deq_solver",
    # submodules bound on the package by importing repro.core
    "bilevel", "diff_api", "implicit_layer", "linear_solve", "operators",
    "optimality", "projections", "prox", "solver_runtime", "solvers",
}


def test_core_public_surface_matches_snapshot():
    public = {n for n in dir(repro.core) if not n.startswith("_")}
    missing = EXPECTED_SURFACE - public
    unexpected = public - EXPECTED_SURFACE
    assert not missing, f"public names dropped from repro.core: {missing}"
    assert not unexpected, \
        f"new public names on repro.core (extend the snapshot): {unexpected}"


def test_implicit_diff_is_the_entry_point_not_the_module():
    """``repro.core.implicit_diff`` is the mode-polymorphic wrapper function
    (the submodule of the same name stays importable by full path)."""
    assert callable(repro.core.implicit_diff)
    assert not isinstance(repro.core.implicit_diff, type(importlib))
    module = importlib.import_module("repro.core.implicit_diff")
    assert module.implicit_diff is repro.core.implicit_diff


def test_registry_snapshot():
    """The built-in linear-solver registry — implicit-diff routing depends
    on these names (and their symmetry flags feed the transpose hook).
    The ``sharded_*`` names are registered here as lazy stubs (impl in
    ``repro.distributed.sharded_operators``), so the surface is identical
    whether or not the distribution layer was ever imported."""
    assert repro.core.available_solvers() == [
        "bicgstab", "cg", "dense_gmres", "gmres", "lu", "neumann",
        "normal_cg", "pallas_cg", "sharded_cg", "sharded_dense_gmres",
        "sharded_normal_cg"]
    from repro.core import linear_solve as ls
    assert ls.solver_is_symmetric("cg")
    assert ls.solver_is_symmetric("pallas_cg")
    assert ls.solver_is_symmetric("sharded_cg")
    assert not ls.solver_is_symmetric("normal_cg")
    assert not ls.solver_is_symmetric("gmres")
    assert not ls.solver_is_symmetric("sharded_normal_cg")


def test_sharded_upgrade_map_snapshot():
    """Placement-driven upgrades: classic names with a mesh-placed operand
    route to their distributed variants (and nothing else is remapped)."""
    from repro.core import linear_solve as ls
    assert ls._SHARDED_UPGRADE == {
        "cg": "sharded_cg", "normal_cg": "sharded_normal_cg",
        "dense_gmres": "sharded_dense_gmres", "pallas_cg": "sharded_cg",
        "lu": "sharded_dense_gmres"}
    # every upgrade target exists in the registry with matching symmetry
    for src, dst in ls._SHARDED_UPGRADE.items():
        assert ls.get_spec(dst).symmetric_only == \
            ls.get_spec(src).symmetric_only


def test_distributed_public_surface():
    """The distribution layer re-exports the sharded-solve seam."""
    import repro.distributed as dist
    assert callable(dist.ShardedOperator)
    assert callable(dist.SolveSharding)
    assert callable(dist.psum_reduction)
    spec = repro.core.ImplicitDiffSpec(optimality_fun=lambda x: x)
    assert spec.sharding is None          # placement is opt-in


def test_runtime_solvers_expose_diff_spec():
    """Every runtime solver can describe itself as an ImplicitDiffSpec."""
    import jax.numpy as jnp
    solver = repro.core.GradientDescent(
        lambda x, t: jnp.sum((x - t) ** 2), solve="cg", linsolve_tol=1e-9,
        ridge=1e-12)
    spec = solver.diff_spec()
    assert isinstance(spec, repro.core.ImplicitDiffSpec)
    assert spec.solve == "cg"
    assert spec.tol == 1e-9
    assert spec.ridge == 1e-12
    assert spec.has_aux       # run() returns (params, OptInfo)


def test_runtime_service_public_surface():
    """The serving layer re-exports the solve-service front end."""
    import repro.runtime as rt
    for name in ("SolveService", "ServiceResult", "WarmStartCache",
                 "BucketKey", "bucket_capacity"):
        assert callable(getattr(rt, name)), name
    # the service resolves "auto" host-side; its static policy must agree
    # with the registry resolver in the dense serving regime
    import jax.numpy as jnp
    from repro.core import DenseOperator
    from repro.core.linear_solve import _resolve_auto
    svc_cold = rt.SolveService(cache=None)
    svc_warm = rt.SolveService()
    for dtype in (jnp.float32, jnp.float64):
        b = jnp.ones(8, dtype)
        for pd in (True, False):
            for precond in (None, "jacobi"):
                op = DenseOperator(jnp.eye(8, dtype=dtype), symmetric=True,
                                   positive_definite=pd)
                name = str(b.dtype)
                assert svc_cold._resolve_solver(pd, precond, 8, name) == \
                    _resolve_auto(op, b, precond, None)
                assert svc_warm._resolve_solver(pd, precond, 8, name) == \
                    _resolve_auto(op, b, precond, b)


def test_backward_mode_surface():
    """The approximate-backward feature's public contract: the mode tuple,
    the spec fields (with their defaults), and the polynomial apply."""
    from repro.core import linear_solve as ls
    assert ls.BACKWARD_MODES == ("exact", "one_step", "neumann_k",
                                 "jacobian_free")
    assert callable(ls.approx_inverse_apply)
    assert ls.approx_matvec_count("jacobian_free") == 0

    spec = repro.core.ImplicitDiffSpec(optimality_fun=lambda x: x)
    assert spec.backward == "exact"
    assert spec.backward_iters == 8
    assert spec.error_estimate is True
    assert spec.backward_kwargs() == {"backward": "exact",
                                      "backward_iters": 8}

    fields = set(repro.core.ImplicitDiffSpec.__dataclass_fields__)
    assert {"backward", "backward_iters", "error_estimate"} <= fields
    # info structures expose the accounting field, defaulted off
    assert ls.SolveInfo._field_defaults["hypergrad_error_estimate"] is None
    from repro.core.solver_runtime import OptInfo
    assert OptInfo._field_defaults["hypergrad_error_estimate"] is None


def test_submit_hypergrad_signature():
    """``SolveService.submit_hypergrad`` carries the approximate-backward
    selection; the deprecated decorator shims must NOT."""
    import inspect

    import repro.runtime as rt
    params = inspect.signature(rt.SolveService.submit_hypergrad).parameters
    assert "backward" in params and "backward_iters" in params

    from repro.core import custom_fixed_point, custom_root
    for fn in (custom_root, custom_fixed_point):
        p = inspect.signature(fn).parameters
        assert "backward" in p and p["backward"].default == "exact"
    # the runtime solvers default to the exact backward
    solver = repro.core.GradientDescent(lambda x, t: ((x - t) ** 2).sum())
    assert solver.backward == "exact"
    assert solver.diff_spec().backward == "exact"


def test_stochastic_public_surface():
    """The stochastic layer re-exports the data-scale solver seam, and the
    spec grew the ``system_operator`` hook it plugs into."""
    import repro.stochastic as sto
    for name in ("MinibatchSampler", "StochasticSolver", "SGD",
                 "MomentumSGD", "Adam", "run_stochastic",
                 "make_stochastic_train_step", "stochastic_data_iter"):
        assert callable(getattr(sto, name)), name
    assert sto.AVERAGING_MODES == ("polyak", "ema", "last")
    assert sto.BACKWARD_DATA_MODES == ("sampled", "full")
    # the spec hook the sampled backward rides on (None = classic path)
    fields = set(repro.core.ImplicitDiffSpec.__dataclass_fields__)
    assert "system_operator" in fields
    spec = repro.core.ImplicitDiffSpec(optimality_fun=lambda x: x)
    assert spec.system_operator is None
    # stochastic instances are IterativeSolvers (one runtime seam) and are
    # marked for the bilevel driver's error accounting
    import jax.numpy as jnp
    sampler = sto.MinibatchSampler(data=jnp.ones((4, 2)), batch_size=2)
    solver = sto.SGD(lambda x, b, t: jnp.sum(x ** 2), sampler=sampler)
    assert isinstance(solver, repro.core.IterativeSolver)
    assert solver.is_stochastic
    assert solver.backward == "neumann_k"       # truncated by default
    assert solver.precond == "jacobi"           # the PR-7 pairing
    assert solver.diff_spec().system_operator is not None


def test_bench_smoke_report_includes_stochastic_rows():
    """The committed smoke report carries the stochastic-vs-full rows with
    the cosine gate recorded."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_smoke.json")
    with open(path) as f:
        report = json.load(f)
    assert report["failed"] == []
    rows = [r for r in report["rows"] if r["name"].startswith("stochastic_")]
    quad = [r for r in rows if "_sgd_" in r["name"]]
    lm = [r for r in rows if "lm_datascale" in r["name"]]
    assert quad and lm, rows
    for r in quad:
        assert "cos=" in r["derived"] and "speedup=" in r["derived"], r
    for r in lm:
        assert "cos=" in r["derived"] and "val_drop=" in r["derived"], r


def test_bench_smoke_report_includes_approx_rows():
    """The committed smoke report must be green and carry the
    error-vs-cost rows of the approximate backward modes (the fast lane
    asserts the artifact the bench lane regenerates)."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "BENCH_smoke.json")
    with open(path) as f:
        report = json.load(f)
    assert report["failed"] == []
    approx = [r for r in report["rows"] if r["name"].startswith(
        "approx_backward_")]
    modes_seen = {m for m in ("one_step", "neumann_k", "jacobian_free")
                  for r in approx if m in r["name"]}
    assert modes_seen == {"one_step", "neumann_k", "jacobian_free"}, approx
    for row in approx:
        if "exact" not in row["name"]:
            assert "est=" in row["derived"], row
            assert "speedup=" in row["derived"], row
    # interpret-mode Pallas rows are tagged and excluded from the summary
    interp = [r["name"] for r in report["rows"]
              if "interpret-mode" in r["derived"]]
    assert interp, "kernel micro rows lost their interpret-mode tag"
    summary = report["speedup_summary"]
    assert summary and not set(interp) & set(summary["rows"])
