"""Distribution layer tests.

These run in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
where multi-device execution is required (the main test process must keep the
default 1-device view for everything else).  Pure spec-construction tests run
in-process against a degenerate mesh.
"""
import os
import subprocess
import sys
import textwrap

import jax

from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed import sharding as shd


def run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestSpecConstruction:

    def _mesh(self):
        return jax.make_mesh((1, 1), ("data", "model"))

    def test_matrix_megatron_pairing(self):
        mesh = self._mesh()
        rules = shd.ShardingRules()
        # column-parallel in
        s = shd.param_spec(("blocks", "attn", "w_q"), (256, 256), rules,
                           mesh)
        assert s == P(None, "model") or s == P("data", "model")
        # row-parallel out
        s = shd.param_spec(("blocks", "attn", "w_o"), (256, 256), rules,
                           mesh)
        assert s[0] == "model"

    def test_embed_vocab_on_model_only(self):
        mesh = self._mesh()
        s = shd.param_spec(("embed", "tok"), (50304, 512),
                           shd.ShardingRules(), mesh)
        assert s == P("model", None)
        s = shd.param_spec(("embed", "unembed"), (512, 50304),
                           shd.ShardingRules(), mesh)
        assert s == P(None, "model")

    def test_vectors_replicated(self):
        mesh = self._mesh()
        s = shd.param_spec(("blocks", "ln1", "scale"), (512,),
                           shd.ShardingRules(), mesh)
        assert s == P()

    def test_moe_expert_dim_on_model_when_divisible(self):
        # shape-only: AbstractMesh needs no physical devices
        mesh = jax.sharding.AbstractMesh((1, 16), ("data", "model"))
        rules = shd.ShardingRules()
        s = shd.param_spec(("blocks", "mlp", "w_gate"), (160, 5120, 1536),
                           rules, mesh)
        assert s[0] == "model"
        # 40 experts don't divide 16: falls to matmul-dim sharding
        s = shd.param_spec(("blocks", "mlp", "w_gate"), (40, 1536, 512),
                           rules, mesh)
        assert s[0] is None and "model" in s

    def test_blocks_leading_layer_axis_never_sharded(self):
        mesh = self._mesh()
        cfg = configs.get("llama3-405b", smoke=True)
        from repro.models import model as mdl
        params = mdl.init_params_abstract(jax.random.PRNGKey(0), cfg)
        specs = shd.params_specs(params, shd.ShardingRules(), mesh)
        flat, _ = jax.tree_util.tree_flatten_with_path(specs)
        for path, spec in flat:
            keys = [getattr(k, "key", None) for k in path]
            if keys[0] == "blocks":
                assert spec[0] is None, (keys, spec)

    def test_all_archs_specs_constructible(self):
        """Spec construction must succeed for every assigned arch (full-size
        configs — shapes only, no allocation)."""
        mesh = jax.sharding.AbstractMesh((1, 16), ("data", "model"))
        from repro.models import model as mdl
        for name in configs.names():
            cfg = configs.get(name)
            params = mdl.init_params_abstract(jax.random.PRNGKey(0), cfg)
            specs = shd.params_specs(params, shd.ShardingRules(), mesh)
            # every leaf got a spec of matching rank
            flat_p = jax.tree_util.tree_leaves(params)
            flat_s = jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P))
            assert len(flat_p) == len(flat_s)


class TestMultiDeviceExecution:
    """Real sharded execution on 8 host devices (subprocess)."""

    def test_sharded_train_step_matches_single_device(self):
        out = run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P, NamedSharding
            from repro import configs
            from repro.distributed import sharding as shd
            from repro.optim import sgd
            from repro.runtime import (TrainStepConfig, make_train_state,
                                       make_train_step)
            cfg = configs.get("llama3-405b", smoke=True)
            opt = sgd(1e-2, momentum=0.0)
            tcfg = TrainStepConfig(microbatches=1, remat=False)
            step = make_train_step(cfg, opt, tcfg)
            state = make_train_state(cfg, opt, jax.random.PRNGKey(0))
            x = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                   cfg.vocab_size)
            y = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0,
                                   cfg.vocab_size)
            # single device reference
            s_ref, m_ref = jax.jit(step)(state, x, y)

            mesh = jax.make_mesh((4, 2), ("data", "model"))
            rules = shd.ShardingRules()
            pspecs = shd.params_specs(state.params, rules, mesh)
            import repro.optim.optimizer as O
            from repro.runtime import TrainState
            sspec = TrainState(params=pspecs,
                               opt_state=O.OptState(step=P(), mu=pspecs,
                                                    nu=None),
                               err_state=None)
            N = lambda t: jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), t,
                is_leaf=lambda z: isinstance(z, P))
            jstep = jax.jit(step, in_shardings=(N(sspec), NamedSharding(
                mesh, P("data")), NamedSharding(mesh, P("data"))),
                out_shardings=(N(sspec), None))
            s_sh, m_sh = jstep(state, x, y)
            print("LOSS", float(m_ref["loss"]), float(m_sh["loss"]))
            w_ref = jax.tree_util.tree_leaves(s_ref.params)[3]
            w_sh = jax.tree_util.tree_leaves(s_sh.params)[3]
            err = float(jnp.max(jnp.abs(w_ref.astype(jnp.float32)
                                        - w_sh.astype(jnp.float32))))
            print("WERR", err)
            assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 5e-2
            assert err < 5e-2
            print("OK")
        """)
        assert "OK" in out

    def test_pipeline_parallel_matches_sequential(self):
        out = run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.distributed.pipeline import pipeline_forward
            mesh = jax.make_mesh((4,), ("stage",))
            L, M, mb, d = 8, 8, 4, 16
            key = jax.random.PRNGKey(0)
            W = 0.3 * jax.random.normal(key, (L, d, d))

            def block(w, x):
                return jnp.tanh(x @ w)

            xs = jax.random.normal(jax.random.fold_in(key, 1), (M, mb, d))
            # sequential reference
            def seq(x):
                for i in range(L):
                    x = block(W[i], x)
                return x
            ref = jax.vmap(seq)(xs.reshape(M * mb, d)[None])[0] \
                .reshape(M, mb, d) if False else \
                jnp.stack([seq(xs[i]) for i in range(M)])
            out = pipeline_forward(block, W, xs, mesh)
            err = float(jnp.max(jnp.abs(out - ref)))
            print("ERR", err)
            assert err < 1e-5
            print("OK")
        """)
        assert "OK" in out

    def test_decode_state_sharding_executes(self):
        out = run_subprocess("""
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P, NamedSharding
            from repro import configs
            from repro.distributed import sharding as shd
            from repro.models import (init_params, init_decode_state,
                                      decode_step)
            from repro.models import model as mdl
            cfg = configs.get("llama3-405b", smoke=True)
            mesh = jax.make_mesh((2, 4), ("data", "model"))
            rules = shd.ShardingRules()
            params = init_params(jax.random.PRNGKey(0), cfg)
            state = init_decode_state(cfg, 4, 32)
            pspecs = shd.params_specs(params, rules, mesh)
            sspecs = mdl.DecodeState(
                caches=shd.decode_state_specs(state.caches, rules, cfg,
                                              mesh),
                index=P())
            N = lambda t: jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), t,
                is_leaf=lambda z: isinstance(z, P))
            step = jax.jit(lambda p, s, t: decode_step(p, cfg, s, t),
                           in_shardings=(N(pspecs), N(sspecs),
                                         NamedSharding(mesh, P("data"))),
                           out_shardings=(NamedSharding(mesh, P("data")),
                                          N(sspecs)))
            tok = jnp.zeros((4, 1), jnp.int32)
            logits, state2 = step(params, state, tok)
            assert logits.shape == (4, 1, cfg.vocab_size)
            assert int(state2.index) == 1
            print("OK")
        """)
        assert "OK" in out


class TestShardedImplicitDiff:
    """The paper's machinery under sharding: hypergradient linear solves run
    on a mesh with the same collectives as the forward pass."""

    def test_sharded_custom_root_matches_single_device(self):
        out = run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P, NamedSharding
            jax.config.update("jax_enable_x64", True)
            from repro.core import custom_root
            from repro.launch.mesh import make_solve_mesh
            mesh = make_solve_mesh(8)
            m, d = 64, 16
            key = jax.random.PRNGKey(0)
            X = jax.random.normal(key, (m, d))
            y = jax.random.normal(jax.random.fold_in(key, 1), (m,))

            def f(x, theta):
                r = X @ x - y
                return 0.5 * jnp.sum(r ** 2) + 0.5 * theta * jnp.sum(x ** 2)

            F = jax.grad(f, argnums=0)

            @custom_root(F, tol=1e-12)
            def solver(init, theta):
                return jnp.linalg.solve(X.T @ X + theta * jnp.eye(d),
                                        X.T @ y)

            def outer(theta):
                return jnp.sum(solver(None, theta) ** 2)

            g_single = jax.grad(outer)(2.0)
            # shard the data matrix across devices and re-run under jit
            Xs = jax.device_put(X, NamedSharding(mesh, P("data", None)))
            ys = jax.device_put(y, NamedSharding(mesh, P("data")))

            def f2(x, theta):
                r = Xs @ x - ys
                return 0.5 * jnp.sum(r ** 2) + 0.5 * theta * jnp.sum(x ** 2)

            F2 = jax.grad(f2, argnums=0)

            @custom_root(F2, tol=1e-12)
            def solver2(init, theta):
                return jnp.linalg.solve(Xs.T @ Xs + theta * jnp.eye(d),
                                        Xs.T @ ys)

            g_shard = jax.jit(jax.grad(
                lambda t: jnp.sum(solver2(None, t) ** 2)))(2.0)
            print("G", float(g_single), float(g_shard))
            assert abs(float(g_single) - float(g_shard)) < 1e-8
            print("OK")
        """)
        assert "OK" in out
