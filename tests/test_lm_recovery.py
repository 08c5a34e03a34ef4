"""The LM engine inside the persistence domain (ISSUE 10 tentpole).

PagedKVState snapshots + dirty-page WAL deltas must restore the paged
decode engine bit-for-bit; with a host cold tier attached the parked slabs
and residency maps ride the same stream and ``recover(..., cold=)``
rebuilds the tier; the crash soak composes it all across an engine-death
boundary with a torn streaming-WAL segment tail; and the serve launcher
drives the identical path end-to-end (``--host-pages`` + ``--snapshot-dir``
is no longer refused).
"""
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.fault import recovery as frec
from repro.fault import soak
from repro.serving import kv_cache as pk
from tests.test_recovery import _assert_tree_equal

I32 = jnp.int32

# matches run_lm_crash_soak's geometry so every test shares one compiled step
ECFG = engine.LMEngineConfig(
    num_queues=2, capacity=8, prompt_len=4, gen_len=6, slots=3,
    admit_per_step=2, cache_len=16, paged=True, page_size=2,
    num_pages=8, host_pages=10, expected_gen_len=3, kernel_backend="ref")
ECFG_NOCOLD = ECFG._replace(host_pages=0, expected_gen_len=0)


def _fresh(ecfg, cfg, ctx):
    # the jitted step donates its input: every twin owns unaliased buffers
    return jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                                  engine.lm_make_paged(ecfg, cfg, ctx))


def _inject(state, ecfg, cfg, rng, n=2):
    qids = [i % ecfg.num_queues for i in range(n)]
    rows = rng.integers(1, cfg.vocab_size,
                        (n, ecfg.prompt_len)).astype(np.int32)
    caps = rng.integers(1, ecfg.gen_len + 1, n).astype(np.int32)
    return engine.lm_inject(state, jnp.asarray(qids, I32),
                            jnp.asarray(rows, I32),
                            gen_caps=jnp.asarray(caps, I32))


def _host(state):
    return jax.tree_util.tree_map(np.asarray, jax.device_get(state))


def test_lm_snapshot_roundtrip():
    ecfg = ECFG_NOCOLD
    cfg, ctx, step = soak._compiled_lm(0, ecfg)
    state = _fresh(ecfg, cfg, ctx)
    rng = np.random.default_rng(1)
    with tempfile.TemporaryDirectory() as d:
        mgr = frec.DurabilityManager(
            frec.DurabilityConfig(d, every=1, mode="full"))
        for t in range(6):
            if t < 3:
                state = _inject(state, ecfg, cfg, rng)
            state = step(state)
        mgr.flush(state)
        mgr.wait()
        live = _host(state)
        recovered, covered = frec.recover(
            d, engine.lm_make_paged(ecfg, cfg, ctx))
        assert covered == int(live.steps)
        _assert_tree_equal(live, _host(recovered))


def test_lm_delta_recovery_bitforbit_and_cheaper():
    ecfg = ECFG_NOCOLD
    cfg, ctx, step = soak._compiled_lm(0, ecfg)
    state = _fresh(ecfg, cfg, ctx)
    rng = np.random.default_rng(2)
    with tempfile.TemporaryDirectory() as d:
        mgr = frec.DurabilityManager(frec.DurabilityConfig(
            d, every=1, snapshot_every=1000, mode="delta", group_records=2))
        recs = []
        for t in range(8):
            if t < 3:
                state = _inject(state, ecfg, cfg, rng)
            state = step(state)
            recs.append(mgr.flush(state))
        mgr.wait()
        kinds = [r.kind for r in recs]
        assert kinds[0] == "full" and kinds[1:] == ["delta"] * 7
        # a dirty-page delta ships only touched page rows, not the pool
        assert max(r.bytes for r in recs[1:]) < recs[0].bytes
        assert mgr.fsyncs < mgr.wal_records  # group commit amortized
        live = _host(state)
        recovered, covered = frec.recover(
            d, engine.lm_make_paged(ecfg, cfg, ctx))
        assert covered == int(live.steps)
        _assert_tree_equal(live, _host(recovered))


def test_lm_cold_tier_rides_the_stream():
    """Flush with a cold tier attached, recover into a FRESH tier of the
    same geometry: engine state, parked slabs, eviction FIFO, free list,
    and counters must all come back exactly."""
    ecfg = ECFG
    cfg, ctx, step = soak._compiled_lm(0, ecfg)
    swap, cold, pcfg = engine.make_swap_service(ecfg, cfg, ctx)
    state = _fresh(ecfg, cfg, ctx)
    rng = np.random.default_rng(3)
    with tempfile.TemporaryDirectory() as d:
        mgr = frec.DurabilityManager(
            frec.DurabilityConfig(d, every=1, mode="full"), cold=cold)
        sent = 0
        for t in range(40):
            if sent < 8:
                state = _inject(state, ecfg, cfg, rng)
                sent += 2
            state = step(state)
            state = swap(state)
            if cold.evictions >= 1 and t >= 6:
                break
        assert cold.evictions >= 1, "pool never spilled to the cold tier"
        mgr.flush(state)
        mgr.wait()
        live = _host(state)
        live_cold = cold.state_arrays()

        fresh_cold = pk.HostColdTier(pcfg, ecfg.host_pages,
                                     dtype=jnp.dtype(cfg.dtype))
        recovered, covered = frec.recover(
            d, engine.lm_make_paged(ecfg, cfg, ctx), cold=fresh_cold)
        assert covered == int(live.steps)
        _assert_tree_equal(live, _host(recovered))
        rec_cold = fresh_cold.state_arrays()
        assert set(live_cold) == set(rec_cold)
        for k in live_cold:
            np.testing.assert_array_equal(live_cold[k], rec_cold[k],
                                          err_msg=f"cold array {k!r}")
        assert fresh_cold.evictions == cold.evictions
        assert list(fresh_cold.order) == list(cold.order)
        assert fresh_cold.free == cold.free


def test_lm_crash_soak_end_to_end():
    report = soak.run_lm_crash_soak(seed=3, steps=30, n_requests=8)
    assert report["main"]["crash"]["torn_segment_truncated"]
    assert report["main"]["evictions"] >= 1
    st = report["stats"]
    assert st["fsyncs"] < st["wal_records"]
    # delivered multisets already asserted inside; spot-check conservation
    for q, n in report["main"]["target"].items():
        assert len(report["main"]["delivered"][q]) == n


def test_serve_recovers_with_host_pages():
    """The launcher no longer refuses --snapshot-dir with --host-pages:
    serve, kill (exit), then --recover resumes from the stream."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.TemporaryDirectory() as d:
        base = [sys.executable, "-m", "repro.launch.serve",
                "--requests", "6", "--prompt-len", "6", "--gen-len", "4",
                "--queues", "2", "--paged", "--page-size", "2",
                "--num-pages", "12", "--host-pages", "36", "--vary-caps",
                "--snapshot-dir", d, "--snapshot-every", "4",
                "--durability-mode", "adaptive", "--reduced"]
        out = subprocess.run(base, capture_output=True, text=True,
                             timeout=900, env=env)
        assert out.returncode == 0, out.stderr[-3000:]
        assert "served 6/6" in out.stdout
        assert "durability:" in out.stdout
        out2 = subprocess.run(base + ["--recover"], capture_output=True,
                              text=True, timeout=900, env=env)
        assert out2.returncode == 0, out2.stderr[-3000:]
        assert "recovered engine state at step" in out2.stdout
