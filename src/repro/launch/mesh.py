"""Production mesh + parallel-context construction.

``make_production_mesh`` is a FUNCTION (module import never touches jax
device state): single-pod v5e-256 as (16, 16) ("data", "model"); multi-pod
as (2, 16, 16) ("pod", "data", "model"). Hardware constants for the
roofline live here too.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType

from repro.configs.base import ModelConfig
from repro.parallel.sharding import ParallelContext

# --- TPU v5e constants (per chip) -----------------------------------------
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link (~3 usable links/chip on a 2D torus slice)
HBM_BYTES = 16 * 2 ** 30
DCN_BW = 25e9  # B/s per host aggregate (cross-pod)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for multi-device CPU tests (needs host-device override)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_context(mesh, cfg: Optional[ModelConfig] = None, *, sp: bool = False,
                 pp_stages: int = 1) -> ParallelContext:
    """Derive the parallel context from the mesh + arch config."""
    axes = list(mesh.axis_names) if mesh is not None else []
    pod = "pod" if "pod" in axes else None
    use_ep = False
    fsdp = False
    if cfg is not None:
        fsdp = cfg.fsdp
        if cfg.is_moe and mesh is not None:
            tp = mesh.shape["model"]
            if cfg.moe_impl == "ep" or (
                cfg.moe_impl == "auto" and cfg.num_experts % tp == 0
            ):
                use_ep = True
    return ParallelContext(
        mesh=mesh,
        data_axes=("data",),
        model_axis="model",
        pod_axis=pod,
        fsdp=fsdp,
        use_ep=use_ep,
        sp=sp,
        pp_stages=pp_stages,
    )
