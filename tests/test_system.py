"""End-to-end system behaviour: the full launchers (train with checkpoint
resume, ORCA LM serving) and the dry-run on a scaled-down production mesh."""
import os
import subprocess
import sys
import tempfile
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(args, env_extra=None, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    out = subprocess.run([sys.executable] + args, capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_train_driver_runs_and_resumes():
    with tempfile.TemporaryDirectory() as d:
        out = _run(["-m", "repro.launch.train", "--arch", "qwen1.5-0.5b",
                    "--steps", "25", "--seq-len", "32", "--batch", "4",
                    "--ckpt-every", "10", "--ckpt-dir", d])
        assert "[done]" in out
        out2 = _run(["-m", "repro.launch.train", "--arch", "qwen1.5-0.5b",
                     "--steps", "5", "--seq-len", "32", "--batch", "4",
                     "--ckpt-every", "10", "--ckpt-dir", d])
        assert "[resume] restored step 24" in out2


def test_train_driver_with_grad_compression():
    with tempfile.TemporaryDirectory() as d:
        out = _run(["-m", "repro.launch.train", "--arch", "deepseek-7b",
                    "--steps", "12", "--seq-len", "16", "--batch", "2",
                    "--ckpt-every", "0", "--ckpt-dir", d, "--compress-grads"])
        assert "[done]" in out


def test_serve_driver_completes_all_requests():
    out = _run(["-m", "repro.launch.serve", "--arch", "qwen1.5-0.5b",
                "--requests", "10", "--prompt-len", "8", "--gen-len", "4",
                "--reduced"])
    assert "served 10/10" in out


def test_dryrun_small_mesh_every_family():
    """The dry-run machinery itself, on a 4x2 mesh with reduced configs:
    lower+compile a decode cell per family representative and run the
    loop-aware HLO analysis on it."""
    code = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.configs import get_config, reduced, SHAPES
    from repro.launch.mesh import make_context
    from repro.launch.hlo_analysis import analyze
    from repro.models import model as lm
    from repro.parallel.sharding import param_specs

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    for arch in ("qwen2.5-14b", "qwen3-moe-30b-a3b", "rwkv6-1.6b", "hymba-1.5b"):
        cfg = reduced(get_config(arch))
        ctx = make_context(mesh, cfg)
        params_abs = lm.abstract_params(cfg, ctx)
        specs = param_specs(params_abs, ctx)
        psh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        shape = dataclasses.replace(SHAPES["decode_32k"], seq_len=64, global_batch=8)
        state_abs = jax.eval_shape(lambda: lm.make_decode_state(cfg, ctx, 8, 64))
        ssh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            lm.decode_state_specs(cfg, ctx, 8),
            is_leaf=lambda x: isinstance(x, P))
        toks = lm.input_specs(cfg, shape)["tokens"]

        fn = jax.jit(lambda p, t, s: lm.decode_step(p, t, s, cfg, ctx),
                     in_shardings=(psh, None, ssh), out_shardings=(ssh, None))
        compiled = fn.lower(params_abs, toks, state_abs).compile()
        cost = analyze(compiled.as_text(), pod_size=8)
        assert cost.bytes > 0, arch
        print(arch, "decode ok", int(cost.flops), int(cost.collective_bytes))
    print("ALL FAMILIES OK")
    """
    out = _run(["-c", textwrap.dedent(code)])
    assert "ALL FAMILIES OK" in out
