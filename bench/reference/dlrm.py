"""Plain reference of DLRM-DCNv2 inference (MLPerf's recommendation model,
the MLCommons torchrec ``DLRM_DCN``), as the benchmark checks every answer.

It holds no table: row ``r`` of table ``t`` is a pure function of
``(salt, t, r, column)`` (``table_values``), which the app also uses to fill
the tables on the device, so the reference computes the rows each sample
looks up. The dense weights are seeded draws (``dense_weights``), handed to
the program and kept for the reference. The forward pass follows the
published equations in ``jax.numpy`` float32 at the highest matmul
precision, one block of samples at a time:

* dense arch: ``x = relu(x @ w + b)`` per layer (13-512-256-128);
* sparse arch: per table, its ids' rows added one after another in lookup
  order (``multi_hot_sizes`` a table, 214 a sample);
* cross net: ``x0`` is the dense output followed by the 26 pooled rows
  (3,456 wide); ``x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l``, 3 layers of
  rank 512;
* over arch: 3456-1024-1024-512-256-1, ReLU after all but the last; the
  logit, with the sigmoid left to the client.

A logit is a mismatch when ``|got - want| > ATOL + RTOL * |want|`` (or
either is not finite). Imports nothing of ``src/``.
"""
from __future__ import annotations

import numpy as np

from bench.requests import rng_for

# Tolerance of a logit. Both sides add the same float32 rows in the same
# (lookup) order, so their pooled rows are equal bit for bit, and run every
# matmul at the highest precision; they differ only in how the compiler
# orders each matmul's accumulation over a batch of 256 against a block of
# the reference. On a TPU v5e that gap was one or two ulps of logits of
# about 0.002-0.05, under a tenth of ATOL + RTOL |logit|; the cross net at
# DEFAULT precision, one of table 20's 100 lookups left out, and tables
# held as int8 codes with a scale a row each moved most logits outside it
# (the readings: PERF.md §6).
ATOL, RTOL = 1e-7, 1e-6
BLOCK = 4096  # samples per reference block, at most


def table_values(table, row, salt, dim: int, xp=np):
    """Rows ``row`` of tables ``table`` (broadcast together to shape S) in a
    run salted ``salt`` (a uint32, or a traced one): ``S + (dim,)`` float32
    values ``±(128 + m) * 2^(e - 14)``, with the sign, ``m`` in [0, 127] and
    ``e`` in [0, 3] drawn from a counter hash: every bfloat16 of magnitude in
    [2^-7, 2^-3). Each is exact in bfloat16 and uses its whole 8-bit
    significand, so a table held in fewer bits, such as int8 codes with a
    scale per row, cannot hold them. ``xp`` is numpy or ``jax.numpy``; both
    give the same bits."""
    u = xp.uint32
    t = xp.asarray(table).astype(u)[..., None]
    r = xp.asarray(row).astype(u)[..., None]
    c = xp.arange(dim, dtype=u)
    s = xp.asarray(salt, dtype=u).reshape(1)
    v = (r * u(2654435761) + t * u(2246822519) + c * u(3266489917)
         + s * u(668265263) + u(374761393))
    v = (v ^ (v >> u(15))) * u(2246822519)
    v = (v ^ (v >> u(13))) * u(3266489917)
    v = v ^ (v >> u(16))
    m = ((v >> u(16)) & u(127)).astype(xp.int32)
    e = ((v >> u(23)) & u(3)).astype(xp.int32)
    sign = 1 - 2 * (v >> u(31)).astype(xp.int32)
    k = sign * ((128 + m) << e)
    return k.astype(xp.float32) / xp.float32(16384)


def dense_weights(config: dict, seed: int) -> dict:
    """Seeded float32 weights: ``bottom`` and ``top`` as lists of ``(w, b)``,
    ``cross`` as a list of ``(v, w, b)``."""
    rng = rng_for(seed, 5)

    def linear(d_in, d_out):
        lim = 1 / np.sqrt(d_in)
        return (rng.uniform(-lim, lim, (d_in, d_out)).astype(np.float32),
                rng.uniform(-lim, lim, d_out).astype(np.float32))

    def mlp(d_in, sizes):
        out = []
        for d in sizes:
            out.append(linear(d_in, d))
            d_in = d
        return out

    d = config["embedding_dim"]
    width = d * (1 + len(config["num_embeddings_per_feature"]))
    rank = config["dcn_low_rank_dim"]
    bottom = mlp(config["num_dense_features"], config["dense_arch_layer_sizes"])
    cross = []
    for _ in range(config["dcn_num_layers"]):
        std = np.sqrt(2 / (width + rank))
        cross.append((rng.normal(0, std, (width, rank)).astype(np.float32),
                      rng.normal(0, std, (rank, width)).astype(np.float32),
                      rng.uniform(-1 / np.sqrt(rank), 1 / np.sqrt(rank), width)
                      .astype(np.float32)))
    top = mlp(width, config["over_arch_layer_sizes"])
    return {"bottom": bottom, "cross": cross, "top": top}


class Model:
    """The reference forward pass of one run, jitted for a block of samples."""

    def __init__(self, config: dict, weights: dict, salt: int):
        import jax
        import jax.numpy as jnp

        self.pooling = list(config["multi_hot_sizes"])
        table = np.repeat(np.arange(len(self.pooling)), self.pooling)
        dim = config["embedding_dim"]
        self.weights = jax.tree.map(jnp.asarray, weights)

        def logits(w, dense, ids):  # weights as arguments, not constants
            with jax.default_matmul_precision("highest"):
                x = dense
                for wt, b in w["bottom"]:
                    x = jax.nn.relu(x @ wt + b)
                rows = table_values(table[None, :], ids, salt, dim, xp=jnp)
                pooled, col = [], 0
                for p in self.pooling:
                    acc = rows[:, col]
                    for j in range(col + 1, col + p):
                        acc = acc + rows[:, j]
                    pooled.append(acc)
                    col += p
                x0 = jnp.concatenate([x] + pooled, axis=1)
                x = x0
                for v, wt, b in w["cross"]:
                    x = x0 * ((x @ v) @ wt + b) + x
                for wt, b in w["top"][:-1]:
                    x = jax.nn.relu(x @ wt + b)
                wt, b = w["top"][-1]
                return (x @ wt + b)[:, 0]

        self.fn = jax.jit(logits)

    def logits(self, dense: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """(n, 13) float32, (n, 214) ids -> (n,) logits, ``BLOCK`` at a time."""
        n = len(ids)
        block = min(BLOCK, -(-n // 256) * 256)
        out = np.zeros(n, np.float32)
        for at in range(0, n, block):
            k = min(block, n - at)
            d = np.zeros((block, dense.shape[1]), np.float32)
            i = np.zeros((block, ids.shape[1]), np.int32)
            d[:k], i[:k] = dense[at:at + k], ids[at:at + k]
            out[at:at + k] = np.asarray(self.fn(self.weights, d, i))[:k]
        return out


def mismatches(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Logits outside the tolerance (a non-finite one always is)."""
    return ~(np.abs(got - want) <= ATOL + RTOL * np.abs(want))
