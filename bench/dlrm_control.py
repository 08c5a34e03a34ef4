#!/usr/bin/env python3
"""Run the DLRM cell with a control planted in the program, and print the
run's result, whose ``logit`` check must then fail: the tolerance is tight
enough that a lower precision, or a piece of the model left out, is caught.

    python3 bench/dlrm_control.py --control cross_default --workload dlrm-dcnv2-multihot --seed 11 --seconds 5 --trace 0

* ``cross_default``: the cross net's matmuls at ``precision=DEFAULT`` (on a
  TPU, one pass of bf16 operands) in place of the stated HIGHEST.
* ``drop_lookup``: the last of table 20's 100 lookups of every sample is
  left out of its pool.
* ``int8_tables``: every table row held as int8 codes times a scale of its
  own (a power of two, the smallest that fits the row's largest magnitude
  in 127 steps), as row-wise 8-bit embedding tables store it; the codes
  times the scale fill the bf16 table exactly.

The benchmark's own runs never plant one.
"""
from __future__ import annotations

import sys
from pathlib import Path

CONTROLS = ("cross_default", "drop_lookup", "int8_tables")


def int8_rows(table):
    """``table``'s rows through int8 codes with a power-of-two scale a row."""
    import jax.numpy as jnp

    x = table.astype(jnp.float32)
    top = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30)
    scale = jnp.exp2(jnp.ceil(jnp.log2(top / 127)))
    return (jnp.clip(jnp.round(x / scale), -127, 127) * scale).astype(table.dtype)


def plant(control: str):
    """Install ``control`` in ``repro.core.dlrm`` (in the engine's state
    build, for ``int8_tables``); returns its removal."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import dlrm

    owner = dlrm
    if control == "cross_default":
        name, real = "cross_net", dlrm.cross_net

        def new(x0, layers, precision=None):
            return real(x0, layers, jax.lax.Precision.DEFAULT)
    elif control == "drop_lookup":
        name, real = "pool", dlrm.pool

        def new(tables, ids, cfg, **kw):
            pooling = list(dlrm.pooling(cfg))
            col = int(np.cumsum(pooling)[20]) - 1  # table 20's last id
            pooling[20] -= 1
            return real(tables, jnp.delete(ids, col, axis=1),
                        cfg._replace(pooling=tuple(pooling)), **kw)
    elif control == "int8_tables":
        from repro.core import engine

        # inside the driver's jit of the engine's state, which takes the
        # app's state donated: the 6.78 GB table is rewritten in place
        owner, name, real = engine, "make", engine.make

        def new(ecfg, app):
            return real(ecfg, {**app, "tables": int8_rows(app["tables"])})
    else:
        raise ValueError(f"unknown control {control!r}; one of {CONTROLS}")
    old = getattr(owner, name)
    setattr(owner, name, new)
    return lambda: setattr(owner, name, old)


def main(argv=None, **run_kw) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    i = argv.index("--control")
    control = argv[i + 1]
    del argv[i:i + 2]
    root = Path(__file__).resolve().parents[1]
    for p in (str(root), str(root / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.run import main as run

    remove = plant(control)
    try:
        return run(argv, **run_kw)
    finally:
        remove()


if __name__ == "__main__":
    sys.exit(main())
