"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --smoke \
        --steps 100 --batch 8 --seq 64 [--mesh 1x1] [--ckpt-dir /tmp/ckpt]

On the CPU container this runs REDUCED configs end-to-end (the full configs
are exercised via the dry-run).  The same driver binds to a real mesh on
TPU: ``--mesh DxM`` selects (data, model) axes over available devices.
Fault tolerance: SIGTERM checkpoints and exits; rerunning with the same
``--ckpt-dir`` resumes exactly (deterministic data stream).
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticLMStream
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.optim import adamw, schedules
from repro.runtime import (PreemptionHandler, StragglerMonitor,
                           TrainStepConfig, make_train_state,
                           make_train_step, run_train_loop)
from repro.runtime import train_loop as tl_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.names())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default=None, help="DxM, e.g. 4x2")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = configs.get(args.arch, smoke=args.smoke)
    optimizer = adamw(schedules.linear_warmup_cosine(
        args.lr, warmup=10, total=args.steps), weight_decay=0.01)
    tcfg = TrainStepConfig(microbatches=args.microbatches,
                           remat=not args.smoke,
                           compress_grads=args.compress_grads)
    step_fn = make_train_step(cfg, optimizer, tcfg)

    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
        rules = shd.ShardingRules()
        state0 = make_train_state(cfg, optimizer, jax.random.PRNGKey(
            args.seed), compress=args.compress_grads)
        pspecs = shd.params_specs(state0.params, rules, mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.optim.optimizer import OptState
        sspec = tl_mod.TrainState(
            params=pspecs,
            opt_state=OptState(step=P(), mu=pspecs, nu=pspecs),
            err_state=pspecs if args.compress_grads else None)
        N = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda z: isinstance(z, P))
        step_fn = jax.jit(step_fn,
                          in_shardings=(N(sspec),
                                        NamedSharding(mesh, P("data")),
                                        NamedSharding(mesh, P("data"))),
                          out_shardings=(N(sspec), None))
        state = state0
    else:
        step_fn = jax.jit(step_fn)
        state = make_train_state(cfg, optimizer,
                                 jax.random.PRNGKey(args.seed),
                                 compress=args.compress_grads)

    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(state.params))
    print(f"[train] arch={cfg.name} params={n_params/1e6:.1f}M "
          f"steps={args.steps}", flush=True)

    stream = SyntheticLMStream(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=args.seed))

    mgr = None
    start_step = 0
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3)
        latest = mgr.latest_step()
        if latest is not None:
            target = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
            state = mgr.restore(latest, target)
            start_step = latest
            print(f"[train] resumed from step {latest}", flush=True)

    def data_iter():
        step = start_step
        while True:
            yield step, stream.batch_at(step)
            step += 1

    handler = PreemptionHandler(install=True)
    monitor = StragglerMonitor()
    state, hist = run_train_loop(
        step_fn, state, data_iter(), num_steps=args.steps - start_step,
        checkpoint_manager=mgr, checkpoint_every=args.ckpt_every,
        monitor=monitor, preemption_flag=handler, log_every=10,
        start_step=start_step)
    for h in hist:
        print(f"[train] step={int(h['step'])} loss={h['loss']:.4f} "
              f"gnorm={h['grad_norm']:.3f}", flush=True)
    if mgr:
        mgr.save(args.steps, state, blocking=True)
    print("[train] done", flush=True)


if __name__ == "__main__":
    main()
