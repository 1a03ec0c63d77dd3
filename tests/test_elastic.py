"""Elastic scaling integration test: train on an 8-device mesh, checkpoint,
'lose' half the fleet, restore and continue on a 4-device mesh — losses must
continue from the same trajectory (the data stream is deterministic, so the
post-restore loss is bit-comparable to an uninterrupted run at the same
batch schedule)."""
import os
import subprocess
import sys
import textwrap

import pytest


def run_subprocess(code: str, devices: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


PHASE = """
import sys, json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticLMStream
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.optim import sgd
from repro.optim.optimizer import OptState
from repro.runtime import TrainStepConfig, TrainState, make_train_state, \\
    make_train_step

mesh_shape = {mesh_shape}
start_step, num_steps = {start_step}, {num_steps}
ckpt_dir = {ckpt_dir!r}

cfg = configs.get("qwen1.5-4b", smoke=True)
opt = sgd(1e-2, momentum=0.0)
step = make_train_step(cfg, opt, TrainStepConfig(remat=False))
state = make_train_state(cfg, opt, jax.random.PRNGKey(0))

mesh = make_mesh(mesh_shape, ("data", "model"))
rules = shd.ShardingRules()
pspecs = shd.params_specs(state.params, rules, mesh)
sspec = TrainState(params=pspecs,
                   opt_state=OptState(step=P(), mu=pspecs, nu=None),
                   err_state=None)
N = lambda t: jax.tree_util.tree_map(
    lambda s: NamedSharding(mesh, s), t, is_leaf=lambda z: isinstance(z, P))
jstep = jax.jit(step, in_shardings=(N(sspec), NamedSharding(mesh, P("data")),
                                    NamedSharding(mesh, P("data"))),
                out_shardings=(N(sspec), None))

mgr = CheckpointManager(ckpt_dir)
latest = mgr.latest_step()
if latest is not None:
    target = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    state = mgr.restore(latest, target)     # full arrays; jit re-shards

stream = SyntheticLMStream(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                      global_batch=8))
losses = []
for s in range(start_step, start_step + num_steps):
    x, y = stream.batch_at(s)
    state, m = jstep(state, x, y)
    losses.append(float(m["loss"]))
mgr.save(start_step + num_steps, state, blocking=True)
print("LOSSES", json.dumps(losses))
"""


@pytest.mark.slow
def test_elastic_restart_on_smaller_mesh(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    # phase 1: 8 devices (4x2)
    run_subprocess(PHASE.format(mesh_shape=(4, 2), start_step=0,
                                num_steps=6, ckpt_dir=ckpt),
                   devices=8)
    # phase 2: HALF the fleet (2x2) — elastic restore, continue training
    out2 = run_subprocess(PHASE.format(mesh_shape=(2, 2), start_step=6,
                                       num_steps=4, ckpt_dir=ckpt),
                          devices=4)
    # control: uninterrupted single-mesh run of the full schedule
    import json
    ckpt2 = str(tmp_path / "ckpt2")
    ref = run_subprocess(PHASE.format(mesh_shape=(2, 2), start_step=0,
                                      num_steps=10, ckpt_dir=ckpt2),
                         devices=4)
    l2 = json.loads(out2.split("LOSSES", 1)[1])
    lref = json.loads(ref.split("LOSSES", 1)[1])[6:]
    # same data schedule + restored state: the continued trajectory matches
    # the uninterrupted one (bf16 tolerance)
    assert len(l2) == len(lref) == 4
    for a, b in zip(l2, lref):
        assert abs(a - b) < 5e-2, (l2, lref)
