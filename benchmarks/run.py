"""Benchmark driver — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  fig3    Jacobian precision (ridge; Thm 1 bound + unroll comparison)
  fig4    multiclass-SVM hyperopt: implicit vs unrolled, 3 solvers x 2 FPs
  fig5    dataset distillation: implicit vs unrolled bilevel
  table2  task-driven dictionary learning vs baselines
  fig6    molecular-dynamics position sensitivity (implicit JVP)
  kernels micro-benchmarks of the Pallas ops (interpret mode on CPU)
  batched batched-vs-looped linear-solve engine speedups
  bilevel batched-vs-looped hypergradients through the solver runtime
  fwdrev  JVP-mode vs VJP-mode implicit Jacobians across (p, d) regimes
  oproute matrix-free vs auto-materialized dense operator-routing crossover
  autotune offline tuning sweep: Pallas block_b schedules + solver/mesh
          candidates, recorded into the dispatch TuningCache (runs before
          "sharded" so downstream auto rows report tuned picks)
  sharded sharded vs single-device hypergradients (device-count scaling;
          run under XLA_FLAGS=--xla_force_host_platform_device_count=8
          for the full curve — the CI multi-device lane does)
  service solve-service scheduler: batched-bucket vs per-request dispatch
          at 64 concurrent requests, warm vs cold cache
  approx  approximate backward modes (one_step / neumann_k / jacobian_free)
          error-vs-cost sweep against the exact converged backward
  stochastic stochastic vs full-batch bilevel hypergradients at growing
          dataset size (B=64 quadratic sweep + LM data-scale demo with
          the hypergrad cosine-similarity gate)
  obs     observability overhead gates: disabled-mode telemetry must
          stage a jaxpr-identical program (<= 2% by construction),
          enabled-mode callbacks <= 15% wall-clock on the B=64 batched CG
  roofline per-(arch x shape) terms from the dry-run artifacts

``--smoke`` runs a fast CI subset (kernels + batched + bilevel + fwdrev +
oproute + autotune + sharded + service + approx + stochastic + obs) and
writes the rows to ``BENCH_smoke.json`` (override with ``--out``) for
artifact upload.  The report's ``speedup_summary`` aggregates every
``speedup=..x`` derived tag, excluding interpret-mode Pallas rows (CPU
interpreter timings are correctness-scale, not perf-scale) whose names it
lists under ``skipped``; ``dispatch_summary`` collects the ``dispatch=``
tags documenting every decision the autotuner made (chosen solver, mesh
size, block_b).
"""
import argparse
import sys
import traceback


# "autotune" runs BEFORE "sharded": the sweep populates the in-process
# TuningCache, so every auto-dispatch row downstream reports tuned picks
SMOKE_BENCHES = ["kernels", "batched", "bilevel", "fwdrev", "oproute",
                 "autotune", "sharded", "service", "approx", "stochastic",
                 "obs"]
# accept run(emit, smoke=True)
SMOKE_KWARG_BENCHES = {"batched", "bilevel", "fwdrev", "oproute", "autotune",
                       "sharded", "service", "approx", "stochastic", "obs"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset; writes a BENCH_*.json report")
    ap.add_argument("--out", default="BENCH_smoke.json",
                    help="JSON report path (with --smoke)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (approx_backward, autotune_sweep, batched_solve,
                            bilevel_hypergrad, dictionary_learning,
                            distillation, fwd_vs_rev_hypergrad,
                            jacobian_precision, kernels_micro,
                            molecular_dynamics, obs_overhead,
                            operator_routing, roofline_report, sharded_solve,
                            solve_service, stochastic_bilevel, svm_hyperopt)
    from benchmarks.common import (Collector, emit, summarize_dispatch,
                                   summarize_speedups)
    all_benches = {
        "fig3": jacobian_precision.run,
        "fig4": svm_hyperopt.run,
        "fig5": distillation.run,
        "table2": dictionary_learning.run,
        "fig6": molecular_dynamics.run,
        "kernels": kernels_micro.run,
        "batched": batched_solve.run,
        "bilevel": bilevel_hypergrad.run,
        "fwdrev": fwd_vs_rev_hypergrad.run,
        "oproute": operator_routing.run,
        "autotune": autotune_sweep.run,
        "sharded": sharded_solve.run,
        "service": solve_service.run,
        "approx": approx_backward.run,
        "stochastic": stochastic_bilevel.run,
        "obs": obs_overhead.run,
        "roofline": roofline_report.run,
    }
    if args.only:
        names = args.only.split(",")     # --only wins, also under --smoke
    elif args.smoke:
        names = SMOKE_BENCHES
    else:
        names = list(all_benches)

    emit_fn = Collector() if args.smoke else emit
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        try:
            if args.smoke and name in SMOKE_KWARG_BENCHES:
                all_benches[name](emit_fn, smoke=True)
            else:
                all_benches[name](emit_fn)
        except Exception:
            failed.append(name)
            traceback.print_exc()
            print(f"{name},nan,ERROR")
    if args.smoke:
        import jax
        path = emit_fn.write_json(args.out, backend=jax.default_backend(),
                                  failed=failed,
                                  speedup_summary=summarize_speedups(
                                      emit_fn.rows),
                                  dispatch_summary=summarize_dispatch(
                                      emit_fn.rows))
        print(f"wrote {path}", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
