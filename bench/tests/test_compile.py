"""Each cell's step program compiles for a described TPU v5e at full size.

Nothing runs: the programs are lowered from shapes and compiled for one
chip of a ``v5e:2x2`` topology that ``jax.experimental.topologies``
describes without the chip, so the TPU compiler refuses here what it would
refuse there.  Code that asks ``jax.default_backend()`` is steered to the
TPU branch for the duration, x64 is off and the persistent compilation
cache is off (a described chip's programs cannot be read back).  All the
compiles stay in this one file, in this process: only one process may load
the TPU library.
"""
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench.lib.harness import seed_key

ROOT = pathlib.Path(__file__).resolve().parents[2]


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


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
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    x64 = jax.config.jax_enable_x64
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", cache_on)
    cc.reset_cache()


def spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def key_spec(sharding):
    k = jax.eval_shape(lambda: seed_key(0))
    return jax.ShapeDtypeStruct(k.shape, k.dtype, sharding=sharding)


def test_batched_spd_step_holds_the_kernel(one_chip):
    from bench.entries import spd_hypergrad
    c = config("resnet18_in1k_probe")
    K, d = c["classes"], c["dim"]
    args = (spec((), one_chip, jnp.int32), key_spec(one_chip),
            spec((d, d), one_chip), spec((K, d), one_chip),
            spec((d, d), one_chip), spec((K, d), one_chip),
            spec((K,), one_chip))
    steps = json.loads((ROOT / "bench/traffic/batched_spd.json").read_text())[
        "steps_per_call"]
    text = jax.jit(spd_hypergrad.make_call(c, steps)).lower(
        *args).compile().as_text()
    # the forward and the transposed solve each run the fused kernel, and
    # forming G + theta I lies outside the solve's scope
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "transpose(jvp(spd_solve))/pallas_call" in text
    assert "spd_solve)/add" not in text


def test_moments_compile(one_chip):
    """Set-up's sums over the full training split, and the control's
    solves at the size a check samples."""
    from bench.problems import ridge_probe
    c = config("resnet18_in1k_probe")
    K, d = c["classes"], c["dim"]
    mu, root = spec((K, d), one_chip), spec((d, d), one_chip)
    for precision in ("highest", "high"):
        ridge_probe._moments.lower(key_spec(one_chip), mu, root,
                                   rows=c["train_rows"],
                                   precision=precision).compile()
    n = json.loads((ROOT / "bench/traffic/batched_spd.json").read_text())[
        "checked_classes"]
    ridge_probe.solve_classes_high.lower(
        spec((d, d), one_chip), spec((n, d), one_chip),
        spec((d, d), one_chip), spec((n, d), one_chip),
        spec((n,), one_chip), nv=c["val_rows"]).compile()
