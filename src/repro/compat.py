"""Version-portability shims — one home for API spellings call sites share.

The repo runs on the installed jax (pinned in pyproject); this module keeps
the one wrapper call sites use, so a future API move changes one place.
"""
from __future__ import annotations

from typing import Any

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True) -> Any:
    """``jax.shard_map``; ``check_vma=False`` disables the per-output
    varying-manual-axes check for bodies whose outputs are replicated by
    construction."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
