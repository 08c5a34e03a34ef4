"""Plain float32 forward pass of DLRM-DCNv2, MLPerf's recommendation model.

Written from the equations of the MLCommons reference
(github.com/mlcommons/training, ``recommendation_v2/torchrec_dlrm``, torchrec's
``DLRM_DCN``), with no kernel, no flattening of the tables and no engine:

* dense arch: ``x = relu(x @ w + b)`` for each layer;
* sparse arch: for each table, its ids taken (``jnp.take``) and summed one
  after another, in lookup order;
* interaction: ``x0`` is the dense output followed by every pooled row;
  each cross layer computes ``x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l``;
* over arch: ``relu(z @ w + b)`` for every layer but the last, whose output
  is the CTR logit.

Every matmul runs at the highest precision, as the published model is
float32. Departures from the MLCommons reference:

* the tables may be held in bfloat16 (the reference holds float32); each row
  is widened to float32 before it is summed;
* the sigmoid is left to the caller: the result is the logit;
* where a deployment divides a table by rows over several chips, ``tables``
  holds one chip's rows and ``ids`` index into them; the all-to-all that
  would return the pooled rows to the chip serving each sample is left out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def pool(tables, ids, pooling):
    """tables: a (rows_t, D) array per table; ids: (B, sum(pooling)), table
    by table -> a (B, D) float32 sum per table."""
    out, col = [], 0
    for table, p in zip(tables, pooling):
        rows = jnp.take(table, ids[:, col:col + p], axis=0).astype(F32)
        acc = rows[:, 0]
        for l in range(1, p):
            acc = acc + rows[:, l]
        out.append(acc)
        col += p
    return out


def forward(params, tables, dense, ids, pooling):
    """params: ``bottom`` and ``top`` layers ``{"w", "b"}``, ``cross`` layers
    ``{"v", "w", "b"}``; tables: one array per table; dense: (B, F);
    ids: (B, sum(pooling)) -> CTR logits (B,)."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(dense, F32)
        for layer in params["bottom"]:
            x = jax.nn.relu(x @ layer["w"] + layer["b"])
        x0 = jnp.concatenate([x] + pool(tables, ids, pooling), axis=1)
        x = x0
        for layer in params["cross"]:
            x = x0 * ((x @ layer["v"]) @ layer["w"] + layer["b"]) + x
        top = params["top"]
        for layer in top[:-1]:
            x = jax.nn.relu(x @ layer["w"] + layer["b"])
        return (x @ top[-1]["w"] + top[-1]["b"])[:, 0]


def split(flat, table_rows):
    """The one (sum(table_rows), D) array of the program as a list of
    per-table arrays."""
    out, at = [], 0
    for r in table_rows:
        out.append(flat[at:at + r])
        at += r
    return out
