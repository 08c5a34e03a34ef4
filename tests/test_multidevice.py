"""Multi-device semantics, via subprocesses with forced host devices
(jax locks the device count at first init, so each scenario gets its own
process). Validates: sharded train step, EP shard_map == gather MoE,
SPMD chain replication == local chain, pipeline parallelism == plain stack.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(code: str, n: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_sharded_train_step_runs():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced
        from repro.launch.mesh import make_context
        from repro.models import init_params, loss_fn, postprocess_grads
        from repro.parallel.sharding import param_specs
        from repro.optim import AdamWConfig, init as opt_init, update as opt_update

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        cfg = reduced(get_config("qwen2.5-14b")).replace(
            dtype="float32", num_heads=4, num_kv_heads=2, head_dim=8, d_model=32)
        ctx = make_context(mesh, cfg)
        params = init_params(jax.random.key(0), cfg, ctx)
        specs = param_specs(params, ctx)
        params = jax.device_put(params, jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P)))
        ocfg = AdamWConfig(weight_decay=0.0)
        opt = opt_init(params, ocfg)
        tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)
        batch = {"tokens": jax.device_put(tokens, NamedSharding(mesh, P("data", None))),
                 "labels": jax.device_put(tokens, NamedSharding(mesh, P("data", None)))}

        @jax.jit
        def step(p, o, b):
            (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b, cfg, ctx, chunk=8)
            g = postprocess_grads(g, cfg, ctx)
            p, o, _ = opt_update(g, o, p, 1e-2, ocfg)
            return p, o, l

        l0 = None
        for i in range(5):
            params, opt, l = step(params, opt, batch)
            if i == 0: l0 = float(l)
        assert float(l) < l0, (float(l), l0)
        # kv replicas stay tied through sharded training
        wk = np.asarray(jax.device_get(params["layers"]["attn"]["wk"]))
        np.testing.assert_allclose(wk[:, :, 0], wk[:, :, 1], rtol=1e-5)
        print("sharded train OK", l0, float(l))
    """)


def test_moe_ep_shardmap_matches_gather():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced
        from repro.models import moe as moe_mod
        from repro.parallel.sharding import ParallelContext

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        ctx = ParallelContext(mesh=mesh, use_ep=True)
        cfg = reduced(get_config("qwen3-moe-30b-a3b")).replace(
            dtype="float32", num_experts=8, num_experts_per_tok=2,
            d_model=16, d_ff=8, capacity_factor=16.0)
        params = moe_mod.moe_init(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(1), (4, 8, 16), jnp.float32)
        y_ref, aux_ref = moe_mod.moe_apply(params, x, cfg, ctx._replace(mesh=None))
        pp = jax.device_put(params, {
            "router": NamedSharding(mesh, P()),
            "w_gate": NamedSharding(mesh, P("model", None, None)),
            "w_in": NamedSharding(mesh, P("model", None, None)),
            "w_out": NamedSharding(mesh, P("model", None, None)),
        })
        xx = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        y_ep, aux_ep = jax.jit(
            lambda pr, xv: moe_mod.moe_apply_ep_shardmap(pr, xv, cfg, ctx)
        )(pp, xx)
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=1e-4)
        print("EP OK")
    """)


def test_chain_commit_spmd_matches_local():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.core import transaction as tx

        cfg = tx.TxConfig(num_keys=64, val_words=2, max_ops=3, chain_len=4,
                          log_capacity=32)
        mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
        chain = tx.make_chain(cfg)
        rng = np.random.default_rng(0)
        w = tx.tx_words(cfg)
        batch = np.zeros((5, w), np.int32)
        for i in range(5):
            n = int(rng.integers(1, 4)); batch[i, 0] = n
            for j in range(n):
                base = 1 + j * 3
                batch[i, base] = int(rng.integers(0, 32))
                batch[i, base+1:base+3] = rng.integers(0, 9, 2)
        b = jnp.asarray(batch)
        local, p_l, d_l = tx.chain_commit_local(chain, b, cfg)
        # the pallas-dispatched local walk agrees with the ref default
        pal, p_k, d_k = tx.chain_commit_local(chain, b, cfg,
                                              kernel_backend="pallas")
        chain_sh = jax.device_put(chain, jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P("data")), chain))
        spmd, p_s, d_s = tx.chain_commit_spmd(chain_sh, b, cfg, mesh,
                                              axis="data",
                                              kernel_backend="ref")
        # the pallas commit also runs under shard_map/ppermute
        spmd_k, p_sk, _ = tx.chain_commit_spmd(chain_sh, b, cfg, mesh,
                                               axis="data",
                                               kernel_backend="pallas")
        np.testing.assert_array_equal(np.asarray(p_l), np.asarray(p_s))
        np.testing.assert_array_equal(np.asarray(p_l), np.asarray(p_k))
        np.testing.assert_array_equal(np.asarray(p_l), np.asarray(p_sk))
        for ref, *others in zip(*(jax.tree_util.tree_leaves(t) for t in
                                  (local, spmd, pal, spmd_k))):
            for o in others:
                np.testing.assert_array_equal(np.asarray(ref), np.asarray(o))
        print("SPMD chain OK")
    """)


def test_pipeline_parallel_matches_stack():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced
        from repro.models import transformer as tf
        from repro.parallel.pipeline import pipeline_apply
        from repro.parallel.sharding import ParallelContext

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"), axis_types=(AxisType.Auto,) * 3)
        ctx = ParallelContext(mesh=mesh, pod_axis="pod")
        cfg = reduced(get_config("deepseek-7b")).replace(
            dtype="float32", num_layers=4, num_heads=2, num_kv_heads=2,
            head_dim=8, d_model=16, remat=False)
        plan = tf.plan_for(cfg, ctx._replace(mesh=None))
        layers = tf.stack_init(jax.random.key(0), cfg, plan)
        x = jax.random.normal(jax.random.key(1), (8, 8, 16), jnp.float32)
        pos = jnp.arange(8)[None, :]
        y_ref, _, _ = tf.stack_apply(layers, x, cfg, plan,
                                     ParallelContext(mesh=None), pos, chunk=8)
        layers_sh = jax.device_put(layers, jax.tree_util.tree_map(
            lambda l: NamedSharding(mesh, P("pod", *([None]*(l.ndim-1)))), layers))
        x_sh = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        y_pp = pipeline_apply(layers_sh, x_sh, cfg, ctx, pos,
                              microbatches=2, chunk=8)
        np.testing.assert_allclose(np.asarray(y_pp), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-5)
        print("PP OK")
    """)


def test_elastic_checkpoint_reshard():
    """Save on a 4-device mesh, restore onto 2-device mesh (elastic)."""
    run_with_devices("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.checkpoint import save, restore

        mesh4 = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,))
        w = jnp.arange(32.0).reshape(8, 4)
        wsh = jax.device_put(w, NamedSharding(mesh4, P("model", None)))
        with tempfile.TemporaryDirectory() as d:
            save(d, 1, {"w": wsh})
            mesh2 = jax.make_mesh((2,), ("model",), axis_types=(AxisType.Auto,))
            out, _ = restore(d, 1, {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32)},
                             {"w": NamedSharding(mesh2, P(None, "model"))})
            np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(w))
            assert len(out["w"].sharding.device_set) == 2
        print("elastic OK")
    """)
