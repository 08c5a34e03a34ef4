"""ORCA-DLRM (§IV-C): recommendation inference as CPU↔accelerator
collaboration.

The split follows the paper exactly:
* the **host** (= the paper's server CPU) runs the irregular, branch-rich
  request preprocessing — parsing, and the MERCI sub-query memoization
  rewrite (numpy, :class:`MerciIndex`);
* the **device** (= the cc-accelerator APU) runs the memory-bound embedding
  reduction — a wide batched gather+segment-sum, the ``64 outstanding memory
  requests per query`` loop of §IV-C — plus the dense bottom/top MLPs and
  feature interactions.

MERCI (the paper's algorithmic baseline, Fig. 12): rows of each table are
grouped into clusters; sums of frequently co-occurring pairs inside a
cluster are precomputed into a memoization table sized ``memo_ratio`` × the
original. The host rewrites each query's index list, replacing matched pairs
by a single memo row (second member -> a shared zero row), so the device
issues fewer gathers for the same result.

DCNv2 (MLPerf's DLRM-DCNv2, the MLCommons torchrec reference): tables of
different row counts (``table_rows``) with a pooling factor each
(``pooling``) are held as one ``(sum(table_rows), dim)`` array at static
per-table row offsets, and ``interaction="dcn"`` replaces the pairwise dot
interaction with a low-rank cross net of ``cross_layers`` layers of rank
``cross_rank``. Tables may be held in bfloat16 and are pooled in float32
in lookup order; ``matmul_precision`` sets every matmul's precision (MLPerf's
model is float32, so its configuration states ``"highest"``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
I32 = jnp.int32

# Named scopes of the DLRM phases (op metadata only, see ``engine.SCOPES``):
# the embedding pooling, the feature interaction (dot or cross net), and the
# dense and over MLPs.
EMBED = "dlrm.embed"
CROSS = "dlrm.cross"
MLP = "dlrm.mlp"
SCOPES = (EMBED, CROSS, MLP)


class DLRMConfig(NamedTuple):
    num_tables: int = 8
    rows: int = 4096  # rows per table
    dim: int = 64  # embedding dim (paper default)
    lookups: int = 32  # multi-hot lookups per table per query
    dense_features: int = 13
    bottom: tuple = (128, 64)
    top: tuple = (128, 64, 1)
    memo_ratio: float = 0.25
    cluster: int = 4  # rows per MERCI cluster
    # rows of each table; () holds num_tables x rows in one (T, R, D) array,
    # else the tables are one (sum(table_rows), D) array (``offsets``)
    table_rows: tuple = ()
    pooling: tuple = ()  # lookups of each table; () = ``lookups`` each
    interaction: str = "dot"  # "dot" | "dcn" (low-rank cross net)
    cross_layers: int = 3
    cross_rank: int = 512
    matmul_precision: Optional[str] = None  # of every matmul; None = DEFAULT


def ragged(cfg: DLRMConfig) -> bool:
    """Tables held as one (sum(table_rows), D) array."""
    return bool(cfg.table_rows)


def pooling(cfg: DLRMConfig) -> tuple:
    return tuple(cfg.pooling) or (cfg.lookups,) * cfg.num_tables


def offsets(cfg: DLRMConfig) -> np.ndarray:
    """First row of each table in the (sum(table_rows), D) array."""
    return np.concatenate([[0], np.cumsum(cfg.table_rows)[:-1]]).astype(np.int64)


def columns(cfg: DLRMConfig) -> np.ndarray:
    """The table of each id word of a request, ids laid out table by table."""
    return np.repeat(np.arange(cfg.num_tables), pooling(cfg))


def _check(cfg: DLRMConfig):
    for name in ("table_rows", "pooling"):
        given = getattr(cfg, name)
        if given and len(given) != cfg.num_tables:
            raise ValueError(f"{name} needs one entry per table")
    if cfg.pooling and not ragged(cfg):
        raise ValueError("per-table pooling needs per-table table_rows")
    if cfg.interaction not in ("dot", "dcn"):
        raise ValueError(f"unknown interaction {cfg.interaction!r} (dot | dcn)")


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(key, cfg: DLRMConfig, dtype=jnp.float32):
    """Seeded weights, and the app's device counters (``counters``)."""
    _check(cfg)
    nb, nt = len(cfg.bottom) + 1, len(cfg.top)
    ks = jax.random.split(key, 1 + nb + nt)
    shape = ((sum(cfg.table_rows),) if ragged(cfg) else (cfg.num_tables, cfg.rows))
    tables = (jax.random.normal(ks[0], shape + (cfg.dim,), F32) * 0.1).astype(dtype)

    def mlp(keys, dims, d_in):
        layers = []
        for k, d_out in zip(keys, dims):
            w = jax.random.normal(k, (d_in, d_out), F32) / (d_in ** 0.5)
            layers.append({"w": w.astype(dtype), "b": jnp.zeros((d_out,), dtype)})
            d_in = d_out
        return layers

    bottom = mlp(ks[1 : 1 + nb], cfg.bottom + (cfg.dim,), cfg.dense_features)
    out = {"tables": tables, "bottom": bottom}
    if cfg.interaction == "dcn":
        width = (cfg.num_tables + 1) * cfg.dim
        out["cross"] = _init_cross(jax.random.fold_in(key, 1), width,
                                   cfg.cross_layers, cfg.cross_rank, dtype)
        top_in = width
    else:
        top_in = cfg.dim + cfg.num_tables * (cfg.num_tables + 1) // 2  # dots + dense
    out["top"] = mlp(ks[1 + nb :], cfg.top, top_in)
    return {**out, **counters()}


def _init_cross(key, width: int, layers: int, rank: int, dtype=jnp.float32):
    """Cross layers ``{"v": (width, rank), "w": (rank, width), "b": (width,)}``."""
    out = []
    for k in jax.random.split(key, layers):
        kv, kw, kb = jax.random.split(k, 3)
        out.append({
            "v": (jax.random.normal(kv, (width, rank), F32) / width ** 0.5).astype(dtype),
            "w": (jax.random.normal(kw, (rank, width), F32) / rank ** 0.5).astype(dtype),
            "b": (jax.random.normal(kb, (width,), F32) * 0.1).astype(dtype)})
    return out


def counters() -> dict:
    """The app's device counters, at zero: ``samples`` (live inferences
    served) and ``malformed`` (requests NACKed)."""
    return {"samples": jnp.zeros((), I32), "malformed": jnp.zeros((), I32)}


def ids_pooled(state, cfg: DLRMConfig) -> int:
    """Ids pooled for live inferences, counted on the host: every sample
    pools ``sum(pooling)`` ids, so the device counts samples, whose int32
    wraps only after 2^31 of them (33 hours at 18k samples/s)."""
    return int(state["samples"]) * sum(pooling(cfg))


# ---------------------------------------------------------------------------
# Embedding reduction (device hot loop; Pallas kernel target + oracle)
# ---------------------------------------------------------------------------

def embedding_reduce(tables, idx, *, backend: Optional[str] = None):
    """tables: (T, R', D); idx: (B, T, L) int32 -> (B, T, D) f32 sum-pool.

    R' may exceed cfg.rows when a memo extension is appended. ``backend``
    is the kernel dispatch knob (``auto | pallas | ref``); the default
    (None) runs the jnp oracle in :mod:`repro.kernels.ref`, which sums
    lookups sequentially — the same access order as the Pallas kernel's
    per-segment VMEM accumulator.
    """
    from repro.kernels import ops as _ops
    from repro.kernels import ref as _ref

    if backend is None or backend == "ref":
        return _ref.dlrm_embedding_reduce(tables, idx)
    t, r, d = tables.shape
    b, _, l = idx.shape
    _, interpret = _ops.resolve_backend(backend)
    # flatten to the kernel's (table rows, sorted segment ids) layout:
    # segment (b, t) -> b*T + t, non-decreasing in (B, T, L) flatten order
    flat_idx = (idx.astype(jnp.int32) + jnp.arange(t, dtype=jnp.int32)[None, :, None] * r)
    seg = jnp.repeat(jnp.arange(b * t, dtype=jnp.int32), l)
    out = _ops.embedding_reduce(
        tables.reshape(t * r, d), flat_idx.reshape(-1), seg, b * t,
        interpret=interpret,
    )
    return out.reshape(b, t, d)


def pool(tables, ids, cfg: DLRMConfig, *, backend: Optional[str] = None):
    """Ragged sum-pooling: tables (sum(table_rows), D); ids (B, sum(pooling))
    int32, each local to its table and laid out table by table -> (B, T, D)
    f32, each table's lookups summed in lookup order.

    The ids become rows of the one array at static per-table offsets, and
    sample ``b``'s lookups of table ``t`` segment ``b*T + t``, so segment ids
    are non-decreasing in the request's own order, as the kernel needs."""
    from repro.kernels import ops as _ops
    from repro.kernels import ref as _ref

    b, t = ids.shape[0], cfg.num_tables
    col = columns(cfg)
    flat = ids.astype(I32) + jnp.asarray(offsets(cfg)[col], I32)[None, :]
    if backend is None or backend == "ref":
        return _ref.dlrm_pool(tables, flat, pooling(cfg))
    _, interpret = _ops.resolve_backend(backend)
    seg = jnp.arange(b, dtype=I32)[:, None] * t + jnp.asarray(col, I32)[None, :]
    out = _ops.embedding_reduce(tables, flat.reshape(-1), seg.reshape(-1), b * t,
                                interpret=interpret)
    return out.reshape(b, t, -1)


def cross_net(x0, layers, precision=None):
    """DCNv2's low-rank cross net: ``x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l``
    with ``V_l`` (width, rank) and ``W_l`` (rank, width)."""
    x = x0
    for layer in layers:
        y = jnp.dot(jnp.dot(x, layer["v"], precision=precision), layer["w"],
                    precision=precision)
        x = x0 * (y + layer["b"]) + x
    return x


def _mlp_apply(layers, x, final_linear=False, precision=None):
    for i, l in enumerate(layers):
        x = jnp.dot(x, l["w"], precision=precision) + l["b"]
        if not (final_linear and i == len(layers) - 1):
            x = jax.nn.relu(x)
    return x


def forward(params, dense, idx, cfg: DLRMConfig, tables_ext=None, *,
            backend: Optional[str] = None):
    """dense: (B, F); idx: (B, T, L), or (B, sum(pooling)) ids laid out
    table by table when the tables are ragged -> CTR logits (B,).

    ``tables_ext``: optional extended tables (raw ‖ memo ‖ zero-row) when the
    host rewrote idx with MERCI references. ``backend`` routes the embedding
    reduction (the device hot loop) through the Pallas kernel path. Every
    matmul runs at ``cfg.matmul_precision``."""
    tables = tables_ext if tables_ext is not None else params["tables"]
    precision = cfg.matmul_precision
    with jax.named_scope(EMBED):
        if ragged(cfg):
            emb = pool(tables, idx, cfg, backend=backend)  # (B, T, D)
        else:
            emb = embedding_reduce(tables, idx, backend=backend).astype(F32)
    with jax.named_scope(MLP):
        bot = _mlp_apply(params["bottom"], dense.astype(F32), precision=precision)
    with jax.named_scope(CROSS):
        if cfg.interaction == "dcn":
            x0 = jnp.concatenate([bot, emb.reshape(emb.shape[0], -1)], axis=1)
            z = cross_net(x0, params["cross"], precision)
        else:
            feats = jnp.concatenate([bot[:, None, :], emb], axis=1)  # (B, T+1, D)
            inter = jnp.einsum("bmd,bnd->bmn", feats, feats, precision=precision)
            iu, ju = jnp.triu_indices(cfg.num_tables + 1, k=1)
            z = jnp.concatenate([bot, inter[:, iu, ju]], axis=1)  # dense + dots
    with jax.named_scope(MLP):
        return _mlp_apply(params["top"], z, final_linear=True,
                          precision=precision)[:, 0]


# ---------------------------------------------------------------------------
# Request-level interface (engine app): DLRM inference through the rings.
# word0 = op (0 nop / 1 infer), words[1:1+F] = dense features (f32 bit-
# cast), rest = the embedding ids table by table, pooling[t] each (host-
# rewritten when MERCI is on), each checked against its own table's rows.
# Response: word0 = status (1 ok), word1 = CTR logit (f32 bit-cast).
# ---------------------------------------------------------------------------

OP_NOP, OP_INFER = 0, 1


def request_words(cfg: DLRMConfig) -> int:
    return 1 + cfg.dense_features + sum(pooling(cfg))


def app_step(params, payloads, valid, cfg: DLRMConfig, *, tables_ext=None,
             kernel_backend: Optional[str] = "auto"):
    """Engine hook: payloads (B, request_words) int32 -> (params, responses).

    The APU half of the §IV-C collaboration: the embedding reduction (and
    the dense MLPs) run device-side per request batch, through the Pallas
    kernel path when ``kernel_backend`` selects it. ``tables_ext`` carries
    the MERCI-extended tables when the host rewrote the index lists. The
    counters add the live inferences and the NACKs."""
    from repro.core import status as stc

    tables = tables_ext if tables_ext is not None else params["tables"]
    f = cfg.dense_features
    n = sum(pooling(cfg))
    op = payloads[:, 0]
    dense = jax.lax.bitcast_convert_type(payloads[:, 1 : 1 + f], F32)
    raw_idx = payloads[:, 1 + f : 1 + f + n]
    # each id against its own table's rows
    limit = (jnp.asarray(np.asarray(cfg.table_rows)[columns(cfg)], I32)
             if ragged(cfg) else tables.shape[1])
    # payload validation (core/status.py): an unknown opcode or any
    # out-of-range embedding index NACKs as MALFORMED — previously the
    # clip below silently aliased bad indices onto real rows and returned
    # a garbage logit with a success status
    bad = valid & (
        ~((op == OP_NOP) | (op == OP_INFER))
        | ((op == OP_INFER)
           & jnp.any((raw_idx < 0) | (raw_idx >= limit), axis=1))
    )
    idx = jnp.clip(raw_idx, 0, limit - 1)
    if not ragged(cfg):
        idx = idx.reshape(payloads.shape[0], cfg.num_tables, cfg.lookups)
    live = valid & ~bad & (op == OP_INFER)
    logits = forward(params, dense, idx, cfg, tables_ext=tables_ext,
                     backend=kernel_backend)
    logit_bits = jax.lax.bitcast_convert_type(
        jnp.where(live, logits, 0.0).astype(F32), jnp.int32
    )
    status = jnp.where(bad, stc.MALFORMED, live.astype(jnp.int32))
    resp = jnp.zeros_like(payloads)
    resp = resp.at[:, 0].set(status).at[:, 1].set(logit_bits)
    params = {**params,
              "samples": params["samples"] + jnp.sum(live, dtype=I32),
              "malformed": params["malformed"] + jnp.sum(bad, dtype=I32)}
    return params, resp


# ---------------------------------------------------------------------------
# MERCI memoization (host side — the "CPU" of the collaboration)
# ---------------------------------------------------------------------------

class MerciIndex:
    """Per-table pair-memoization built offline from cluster structure.

    Memo entry m of table t holds ``table[t,a] + table[t,b]`` for a chosen
    in-cluster pair (a, b). Queries are rewritten on the host: every matched
    (a, b) pair collapses to one reference at offset ``rows + m``; the freed
    slot points at the shared zero row (offset ``rows + n_memo``)."""

    def __init__(self, cfg: DLRMConfig, seed: int = 0):
        self.cfg = cfg
        rng = np.random.default_rng(seed)
        n_memo = int(cfg.rows * cfg.memo_ratio)
        self.n_memo = n_memo
        # pick pairs within clusters (cluster c = rows [c*k, (c+1)*k))
        k = cfg.cluster
        n_clusters = cfg.rows // k
        pairs = np.zeros((cfg.num_tables, n_memo, 2), np.int32)
        for t in range(cfg.num_tables):
            cl = rng.integers(0, n_clusters, size=n_memo)
            a = rng.integers(0, k, size=n_memo)
            off = 1 + rng.integers(0, k - 1, size=n_memo)
            b = (a + off) % k
            pairs[t, :, 0] = cl * k + np.minimum(a, b)
            pairs[t, :, 1] = cl * k + np.maximum(a, b)
        self.pairs = pairs
        # pair -> memo id lookup per table
        self.lookup = [
            {(int(a), int(b)): m for m, (a, b) in enumerate(pairs[t])}
            for t in range(cfg.num_tables)
        ]

    def build_tables(self, tables) -> jax.Array:
        """(T, R, D) -> (T, R + n_memo + 1, D) with memo sums + zero row."""
        t = np.asarray(tables, np.float32)
        memo = t[np.arange(self.cfg.num_tables)[:, None], self.pairs[..., 0]] + \
            t[np.arange(self.cfg.num_tables)[:, None], self.pairs[..., 1]]
        zero = np.zeros((self.cfg.num_tables, 1, self.cfg.dim), np.float32)
        return jnp.asarray(
            np.concatenate([t, memo, zero], axis=1), tables.dtype
        )

    def rewrite_query(self, idx: np.ndarray) -> tuple[np.ndarray, int]:
        """idx: (B, T, L) raw -> rewritten (B, T, L) into the extended table.
        Returns (new_idx, gathers_saved). Host-side, irregular — numpy."""
        cfg = self.cfg
        b = idx.shape[0]
        out = idx.copy()
        zero_row = cfg.rows + self.n_memo
        saved = 0
        for bi in range(b):
            for t in range(cfg.num_tables):
                row = out[bi, t]
                seen: dict[int, int] = {}
                svals = np.sort(row)
                present = set(int(x) for x in row)
                used = np.zeros(len(row), bool)
                pos_of = {}
                for p, v in enumerate(row):
                    pos_of.setdefault(int(v), []).append(p)
                for (a, bb_), m in self.lookup[t].items():
                    if a in present and bb_ in present and a != bb_:
                        pa = next((p for p in pos_of[a] if not used[p]), None)
                        pb = next((p for p in pos_of[bb_] if not used[p]), None)
                        if pa is None or pb is None:
                            continue
                        out[bi, t, pa] = cfg.rows + m
                        out[bi, t, pb] = zero_row
                        used[pa] = used[pb] = True
                        saved += 1
        return out, saved


def gen_queries(cfg: DLRMConfig, batch: int, merci: Optional[MerciIndex],
                hit_rate: float, rng: np.random.Generator):
    """Synthetic Amazon-Review-style queries: with probability ``hit_rate``
    a lookup slot pair is drawn from a memoized pair (co-occurrence skew)."""
    idx = rng.integers(0, cfg.rows, size=(batch, cfg.num_tables, cfg.lookups))
    if merci is not None and hit_rate > 0:
        n_pairs = cfg.lookups // 2
        for t in range(cfg.num_tables):
            pick = rng.integers(0, merci.n_memo, size=(batch, n_pairs))
            use = rng.random((batch, n_pairs)) < hit_rate
            pa = merci.pairs[t, pick]  # (B, P, 2)
            for p in range(n_pairs):
                sel = use[:, p]
                idx[sel, t, 2 * p] = pa[sel, p, 0]
                idx[sel, t, 2 * p + 1] = pa[sel, p, 1]
    dense = rng.normal(size=(batch, cfg.dense_features)).astype(np.float32)
    return dense, idx.astype(np.int32)
