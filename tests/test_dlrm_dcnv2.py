"""DLRM-DCNv2 through the engine against the plain reference
(``core/dlrm_reference.py``), at a CPU size: MLPerf's 26 tables with their
published pooling factors (214 ids a sample, up to 100 from one table), 2 to
64 rows a table, width 16, small MLPs and a cross net of rank 8."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dlrm, dlrm_reference, engine as eng
from repro.core import status as stc
from repro.kernels import ops

I32, F32 = jnp.int32, jnp.float32
POOLING = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27,
           10, 3, 1, 1)
ROWS = (64, 40, 17, 24, 20, 3, 7, 15, 6, 64, 48, 40, 10, 22, 11, 15, 4, 9, 14,
        64, 64, 64, 41, 12, 10, 2)
CFG = dlrm.DLRMConfig(num_tables=26, dim=16, dense_features=13, bottom=(32,),
                      top=(64, 32, 1), table_rows=ROWS, pooling=POOLING,
                      interaction="dcn", cross_layers=3, cross_rank=8,
                      matmul_precision="highest")
# The logit tolerance: |got - want| <= ATOL + RTOL |want|. Both sides sum
# the same float32 rows in the same order and differ only in how XLA orders
# each matmul's accumulation: at most 1.2e-7 over three seeds of 8 samples,
# logits of 0.01 to 1.1. bf16 operands in the cross net (DEFAULT precision
# on a TPU) moved every logit by 3.5e-5 to 1.2e-3, one lookup of table 20
# left out by 4.5e-4 or more; tables through int8 codes with a scale a row
# moved every logit too.
ATOL, RTOL = 2e-6, 2e-6
BATCH = 6


def _params(seed=0):
    p = dlrm.init_params(jax.random.key(seed), CFG)
    p["tables"] = p["tables"].astype(jnp.bfloat16)
    # give the MLPs non-zero biases, so the reference is checked on them too
    k = jax.random.key(seed + 100)
    for name in ("bottom", "top"):
        for i, layer in enumerate(p[name]):
            layer["b"] = 0.1 * jax.random.normal(jax.random.fold_in(k, i), layer["b"].shape)
            k = jax.random.fold_in(k, 1000)
    return p


def _requests(rng, n=BATCH):
    dense = np.log1p(rng.geometric(0.1, (n, CFG.dense_features))).astype(np.float32)
    ids = np.concatenate([rng.integers(0, r, (n, p)) for r, p in zip(ROWS, POOLING)],
                         axis=1).astype(np.int32)
    return dense, ids


def _payload(dense, ids):
    pay = np.zeros((len(ids), dlrm.request_words(CFG)), np.int32)
    pay[:, 0] = dlrm.OP_INFER
    pay[:, 1:1 + CFG.dense_features] = dense.view(np.int32)
    pay[:, 1 + CFG.dense_features:] = ids
    return pay


def _reference(params, dense, ids):
    tables = dlrm_reference.split(params["tables"], ROWS)
    return np.asarray(dlrm_reference.forward(params, tables, jnp.asarray(dense),
                                             jnp.asarray(ids), POOLING))


def _serve(params, pay, backend):
    """One engine step over ``pay``, spread over two queues; returns the
    answers in request order and the app state after the step."""
    w = dlrm.request_words(CFG)
    ecfg = eng.EngineConfig(num_queues=2, capacity=8, req_words=w, resp_words=w,
                            budget=8, kernel_backend=backend)
    state = eng.make(ecfg, params)
    app_fn = eng.bind_app(dlrm.app_step, CFG, ecfg)
    for j in range(0, len(pay), 2):
        q = jnp.arange(min(2, len(pay) - j), dtype=I32)
        state = eng.inject(state, q, jnp.asarray(pay[j:j + 2]))
    state, _ = jax.jit(lambda s: eng.engine_step(s, app_fn, ecfg))(state)
    out, counts, state = eng.drain_responses(state, 8)
    out, counts = np.asarray(out), np.asarray(counts)
    # queue q answered requests q, q + 2, ... in order
    got = np.zeros((len(pay), 2), np.int32)
    for q in range(2):
        got[q::2] = out[q, :counts[q], :2]
    return got, state.app


def _within(got, want):
    return np.abs(got - want) <= ATOL + RTOL * np.abs(want)


@pytest.mark.parametrize("backend", ["ref", "auto"])
def test_engine_step_matches_reference(backend):
    params = _params()
    dense, ids = _requests(np.random.default_rng(1))
    tables = dlrm_reference.split(params["tables"], ROWS)
    want_pool = np.stack(dlrm_reference.pool(tables, jnp.asarray(ids), POOLING), 1)
    got_pool = dlrm.pool(params["tables"], jnp.asarray(ids), CFG, backend=backend)
    np.testing.assert_array_equal(np.asarray(got_pool), want_pool)
    got, _ = _serve(params, _payload(dense, ids), backend)
    assert (got[:, 0] == stc.OK).all()
    want = _reference(params, dense, ids)
    assert _within(got[:, 1].view(np.float32), want).all(), (got[:, 1].view(np.float32), want)


def test_one_cross_layer_is_the_equation():
    """x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l, in float64 numpy, for the
    first layer (x_l = x0) and the second (x_l = x_1)."""
    rng = np.random.default_rng(2)
    width, rank = 48, 8
    x0 = rng.normal(size=(3, width))
    layers = [{"v": rng.normal(size=(width, rank)) / 7, "w": rng.normal(size=(rank, width)) / 3,
               "b": rng.normal(size=width)} for _ in range(2)]
    x1 = x0 * ((x0 @ layers[0]["v"]) @ layers[0]["w"] + layers[0]["b"]) + x0
    x2 = x0 * ((x1 @ layers[1]["v"]) @ layers[1]["w"] + layers[1]["b"]) + x1
    f32 = [{k: jnp.asarray(v, F32) for k, v in layer.items()} for layer in layers]
    for n, want in ((1, x1), (2, x2)):
        got = dlrm.cross_net(jnp.asarray(x0, F32), f32[:n], CFG.matmul_precision)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_id_checked_against_its_own_table():
    """Table 5 has 3 rows, table 0 has 64: id 3 is out of range in the
    first and a real row of the second."""
    params = _params()
    dense, ids = _requests(np.random.default_rng(3), 2)
    col = np.repeat(np.arange(26), POOLING)
    ids[0, np.flatnonzero(col == 5)[0]] = ROWS[5]
    ids[1, np.flatnonzero(col == 0)[0]] = ROWS[5]
    pay = jnp.asarray(_payload(dense, ids))
    new, resp = dlrm.app_step(params, pay, jnp.ones(2, bool), CFG, kernel_backend="ref")
    assert int(resp[0, 0]) == stc.MALFORMED and int(resp[0, 1]) == 0
    assert int(resp[1, 0]) == stc.OK
    assert int(new["malformed"]) == 1 and dlrm.ids_pooled(new, CFG) == sum(POOLING)


def _cross_bf16(x0, layers, precision=None):
    """The cross net with bf16 operands and f32 accumulation: what
    ``precision=DEFAULT`` does to a float32 matmul on a TPU."""
    bf = jnp.bfloat16
    x = x0
    for layer in layers:
        y = jnp.dot(x.astype(bf), layer["v"].astype(bf), preferred_element_type=F32)
        y = jnp.dot(y.astype(bf), layer["w"].astype(bf), preferred_element_type=F32)
        x = x0 * (y + layer["b"]) + x
    return x


def _pool_dropping_one_of_table_20(real):
    col = int(np.flatnonzero(np.repeat(np.arange(26), POOLING) == 20)[-1])
    short = CFG._replace(pooling=POOLING[:20] + (99,) + POOLING[21:])

    def pool(tables, ids, cfg, **kw):
        return real(tables, jnp.delete(ids, col, axis=1), short, **kw)
    return pool


def _int8_rows(table):
    """Each row as int8 codes times a power-of-two scale of its own, the
    smallest that fits the row's largest magnitude in 127 steps."""
    x = table.astype(F32)
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127)))
    return (jnp.clip(jnp.round(x / scale), -127, 127) * scale).astype(table.dtype)


@pytest.mark.parametrize("control", ["cross_bf16", "drop_lookup", "int8_tables"])
def test_precision_controls_fail_the_tolerance(monkeypatch, control):
    params = _params()
    dense, ids = _requests(np.random.default_rng(4))
    if control == "cross_bf16":
        monkeypatch.setattr(dlrm, "cross_net", _cross_bf16)
    elif control == "drop_lookup":
        monkeypatch.setattr(dlrm, "pool", _pool_dropping_one_of_table_20(dlrm.pool))
    served = params
    if control == "int8_tables":
        served = {**params, "tables": _int8_rows(params["tables"])}
        assert (served["tables"] != params["tables"]).any()
    got, _ = _serve(served, _payload(dense, ids), "ref")
    assert (got[:, 0] == stc.OK).all()
    assert not _within(got[:, 1].view(np.float32), _reference(params, dense, ids)).any()


def test_row_shares_add_up_to_the_uncut_tables():
    """A deployment divides each table of more than 47 rows (here the five
    "40 M" tables of 64 rows and table 10) over 8 chips by rows; each chip
    pools the ids that fall in its rows. The chips' partial pools add up to
    the uncut pool, and with the replicated tables and the dense part
    counted once the cross net's input is the uncut one."""
    chips, cut = 8, 47
    rng = np.random.default_rng(5)
    # values k / 1024 with |k| <= 128 are exact in bf16, and any sum of up to
    # 100 of them is exact in f32: partial sums add up bit for bit
    flat = jnp.asarray(rng.integers(-128, 128, (sum(ROWS), CFG.dim)) / 1024, jnp.bfloat16)
    params = {**_params(), "tables": flat}
    tables = dlrm_reference.split(flat, ROWS)
    dense, ids = _requests(rng)
    whole = np.stack(dlrm_reference.pool(tables, jnp.asarray(ids), POOLING), 1)
    col = np.repeat(np.arange(26), POOLING)
    seg = np.arange(BATCH)[:, None] * 26 + col[None, :]
    shares = np.zeros_like(whole)
    for c in range(chips):
        for t, rows in enumerate(ROWS):
            if rows <= cut and c:
                continue  # replicated: one chip's share counts it
            lo, hi = (c * rows // chips, (c + 1) * rows // chips) if rows > cut else (0, rows)
            mine = (col[None, :] == t) & (ids >= lo) & (ids < hi)
            if not mine.any():
                continue
            part = ops.embedding_reduce(
                tables[t][lo:hi], jnp.asarray(ids[mine] - lo, I32),
                jnp.asarray(seg[mine], I32), BATCH * 26)
            shares += np.asarray(part).reshape(BATCH, 26, -1)
    np.testing.assert_array_equal(shares, whole)
    # the cross net's input from the shares is the uncut one, so the logits are
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(dense)
        for layer in params["bottom"]:
            x = jax.nn.relu(x @ layer["w"] + layer["b"])
    x0 = jnp.concatenate([x, jnp.asarray(shares).reshape(BATCH, -1)], 1)
    z = dlrm.cross_net(x0, params["cross"], CFG.matmul_precision)
    got = dlrm._mlp_apply(params["top"], z, final_linear=True,
                          precision=CFG.matmul_precision)[:, 0]
    assert _within(np.asarray(got), _reference(params, dense, ids)).all()


def test_lookups_counter_counts_live_ids():
    params = _params()
    dense, ids = _requests(np.random.default_rng(6))
    pay = _payload(dense, ids)
    pay[1, 0] = 7  # an unknown op: NACKed, pools nothing
    pay[2, 0] = dlrm.OP_NOP  # answered, pools nothing
    got, app = _serve(params, pay, "ref")
    assert got[1, 0] == stc.MALFORMED and got[2, 0] == 0
    assert dlrm.ids_pooled(app, CFG) == (BATCH - 2) * sum(POOLING) == (BATCH - 2) * 214
    assert int(app["malformed"]) == 1


def test_lookups_counter_counts_past_2_31_ids():
    """The ids pooled pass 2^31 after ~10 M samples (10 minutes at the
    cell's rate); the count stays exact, as the device counts samples."""
    start = 10_034_968  # 214 ids a sample: the 2^31st id lies within this step
    params = {**_params(), "samples": jnp.int32(start)}
    assert start * 214 < 2 ** 31 < (start + BATCH) * 214
    dense, ids = _requests(np.random.default_rng(7))
    got, app = _serve(params, _payload(dense, ids), "ref")
    assert (got[:, 0] == stc.OK).all()
    assert dlrm.ids_pooled(app, CFG) == (start + BATCH) * 214
