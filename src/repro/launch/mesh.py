"""Production mesh construction (TPU v5e pods; 512 host devices in the
dry-run). A function, not a module constant — importing this module must
never touch jax device state."""
from __future__ import annotations

import contextlib

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``. JAX makes Explicit
    axes by default, and under them the pipeline executors' indexing of
    stage-sharded values (``outs_all[-1]``) is not implemented; every
    mesh in this repo is built here so the rule lives in one place.
    ``devices``: explicit device list, laid out in exactly that order
    (default: ``jax.make_mesh``'s choice among ``jax.devices()``)."""
    types = (AxisType.Auto,) * len(axes)
    if devices is not None:
        return Mesh(np.asarray(devices).reshape(shape), tuple(axes),
                    axis_types=types)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(*, n_data: int = 2, n_model: int = 2, n_pod: int = 0):
    """Small mesh for CPU tests (requires forced host device count)."""
    if n_pod:
        return make_mesh((n_pod, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))


def mesh_context(mesh):
    """Fresh ``jax.set_mesh`` context per use, a no-op without a mesh
    (serve + dryrun + the serving tier)."""
    if mesh is None:
        return contextlib.nullcontext()
    return jax.set_mesh(mesh)


def make_stage_mesh(n_stages: int, n_replicas: int = 1, *,
                    stage_axis: str = "stage", data_axis: str = "data",
                    devices=None):
    """Mesh for the heterogeneous CNN layer pipeline: one device slot
    per stage, optionally replicated along a leading data axis (the
    stage x data 2-D pipeline — each data row is a full pipeline, the
    batch shards across rows, stage weights replicate only across
    rows). With ``n_replicas == 1`` the mesh stays 1-D so existing
    single-pipeline specs/paths are unchanged.

    ``devices``: explicit device list for the mesh (the serving tier
    carves one disjoint S-device slice per replica worker out of the
    host pool, so two workers never share a stage slot). Must hold
    exactly ``n_stages * n_replicas`` devices; default: the first
    ``n_stages * n_replicas`` of ``jax.devices()``."""
    shape = (n_replicas, n_stages) if n_replicas > 1 else (n_stages,)
    axes = (data_axis, stage_axis) if n_replicas > 1 else (stage_axis,)
    if devices is not None:
        need = n_stages * n_replicas
        if len(devices) != need:
            raise ValueError(f"stage mesh needs exactly {need} devices "
                             f"({n_stages} stages x {n_replicas} "
                             f"replicas), got {len(devices)}")
    return make_mesh(shape, axes, devices=devices)


# TPU v5e hardware constants for the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
CHIP_VMEM = 128 * 1024 * 1024   # ~128 MiB VMEM
CHIP_HBM = 16 * 1024**3         # 16 GiB
