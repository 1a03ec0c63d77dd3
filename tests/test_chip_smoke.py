"""``chip_smoke.py`` off the chip.

The script runs only on a TPU: here it must refuse, before any phase, with
a message that names the missing chip.  Its phase functions take their
sizes as arguments, so each is rehearsed at a tiny size on the CPU (where
``pallas_cg`` runs the reference path) with every check it makes on the
chip.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=300, cwd=tmp_path)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_phase_a1_tiny(smoke):
    res = smoke.phase_a1(jax.random.PRNGKey(0), B=4, m=32, d=8)
    assert res["routes"] == ["dense_gmres"]
    assert res["converged_share"] == 1.0


def test_phase_a2_tiny(smoke):
    out = smoke.phase_a2(jax.random.PRNGKey(0), shapes=((4, 16), (12, 8)))
    assert [r["shape"] for r in out] == [(4, 16), (12, 8)]
    assert all(r["routes"] == ["pallas_cg"] for r in out)


def test_phase_b_tiny(smoke):
    res = smoke.phase_b(jax.random.PRNGKey(0), n=256, n_val=64, p=16, k=3,
                        inner_maxiter=50)
    assert len(res["outer_values"]) == 3


def test_phase_c_tiny(smoke):
    res = smoke.phase_c(0, n=8, dims=(8, 12), max_batch=4, spot_checks=2)
    assert res["answered"] == 16
