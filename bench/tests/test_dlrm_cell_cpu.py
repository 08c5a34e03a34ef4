"""CPU rehearsal of ``dlrm-dcnv2-multihot`` through ``run.main``: the
configuration's widths with at most 4,096 rows a table and 2 clients x 8
outstanding. ``tiny.resize`` has no DLRM branch, so the cell gets its own
resize here. The run is correct, and with one of table 20's lookups left
out of every pool, or the tables held as int8 codes with a scale a row
(``dlrm_control.py``'s ``drop_lookup`` and ``int8_tables``), it is not.

    JAX_PLATFORMS=cpu python3 bench/tests/test_dlrm_cell_cpu.py --workload dlrm-dcnv2-multihot --seed 5 --seconds 6
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
CELL = "dlrm-dcnv2-multihot"
ROWS = 4096


def resize(config: dict, traffic: dict):
    config, traffic = dict(config), dict(traffic)
    config["num_embeddings_per_feature"] = [
        min(r, ROWS) for r in config["num_embeddings_per_feature"]]
    traffic.update(queues=2, window=8, budget=8, warm_rounds=2)
    return config, traffic


def _run(*extra):
    args = [sys.executable, __file__, "--workload", CELL, "--seed",
            str(2 ** 31 + 91), "--seconds", "6", *extra]
    p = subprocess.run(args, cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=900)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if p.returncode == 0 and lines else None)


def test_cell_runs_and_is_correct():
    p, res = _run()
    assert p.returncode == 0, p.stderr[-3000:]
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"unanswered", "status", "logit"}
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"ops_per_s", "p95_ms", "setup_s"} <= set(res["metrics"])


def test_dropped_lookup_is_caught():
    p, res = _run("--control", "drop_lookup")
    assert p.returncode == 0, p.stderr[-3000:]
    assert res["correct"] is False
    assert res["checks"]["logit"]["value"] > res["attempted"] // 2
    assert res["checks"]["status"]["value"] == 0


def test_int8_tables_is_caught():
    p, res = _run("--control", "int8_tables")
    assert p.returncode == 0, p.stderr[-3000:]
    assert res["correct"] is False
    assert res["checks"]["logit"]["value"] > res["attempted"] // 2
    assert res["checks"]["status"]["value"] == 0


def test_requests_lie_in_the_rows_held():
    import numpy as np
    from bench.generators import criteo_mh

    config = json.loads((ROOT / "bench/configs/dlrm-dcnv2-criteo1tb.json").read_text())
    traffic = json.loads((ROOT / "bench/traffic/criteo-multihot.json").read_text())
    a = criteo_mh.Requests(traffic, config, 2 ** 40 + 3).chunk(1)
    b = criteo_mh.Requests(traffic, config, 2 ** 40 + 3).chunk(1)
    assert all(np.array_equal(a[k], b[k]) for k in a)  # a function of (seed, serial)
    rows = np.repeat(config["num_embeddings_per_feature"], config["multi_hot_sizes"])
    assert a["ids"].shape == (criteo_mh.CHUNK, 214)
    assert (a["ids"] >= 0).all() and (a["ids"] < rows).all()
    x = np.expm1(a["dense"].astype(np.float64))
    assert a["dense"].shape == (criteo_mh.CHUNK, 13) and x.min() > 0.99
    assert 9 < x.mean() < 11  # geometric of mean 10
    # each table's first id is skewed: its most drawn row of table 0 is far
    # above uniform's share; a later id is not
    top = np.unique(a["ids"][:, 0], return_counts=True)[1].max()
    assert top > 100 and np.unique(a["ids"][:, 1], return_counts=True)[1].max() < 10


def test_table_values_alike_in_numpy_and_jax():
    """The program fills its tables with ``jax.numpy``, the reference draws
    rows with either: the same bits, for any uint32 salt. Every value is a
    bfloat16 of magnitude in [2^-7, 2^-3) and the draws use all 8 bits of
    its significand: no int8 code times one scale holds them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.reference.dlrm import table_values

    t, r = np.array([[0, 5, 20, 25]]), np.array([[7, 2, 4999999, 35]])
    for salt in (0, 2 ** 31 - 1, 2 ** 31, 2 ** 32 - 1):
        a = table_values(t, r, salt, 128)
        b = table_values(jnp.asarray(t), jnp.asarray(r), salt, 128, xp=jnp)
        c = jax.jit(lambda s: table_values(jnp.asarray(t), jnp.asarray(r), s, 128,
                                           xp=jnp))(jnp.uint32(salt))
        assert np.array_equal(a, b) and np.array_equal(a, c)
        assert np.array_equal(np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32), a)
        assert (np.abs(a) >= 2 ** -7).all() and (np.abs(a) < 2 ** -3).all()
    v = table_values(np.arange(8)[:, None], np.arange(512)[None, :], 3, 128)
    k = np.abs(v) * 2 ** 14  # (128 + m) << e: m has its 7 bits, e its 4 values
    e = np.floor(np.log2(k)).astype(int) - 7
    m = k / 2.0 ** e - 128
    assert set(np.unique(e)) == {0, 1, 2, 3} and set(np.unique(m)) == set(range(128))
    assert (v < 0).mean() > 0.45 and (v > 0).mean() > 0.45


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    from bench import dlrm_control, run

    run.FINISH_SECONDS = 30.0
    if "--control" in argv:
        return dlrm_control.main(argv, platform="cpu", resize=resize)
    return run.main(argv, platform="cpu", resize=resize)


if __name__ == "__main__":
    sys.exit(main())
