"""Production mesh factory.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep their 1-device view.

Production target: TPU v5e pods, 256 chips each.
  single-pod: (16, 16)   axes ("data", "model")
  multi-pod:  (2, 16, 16) axes ("pod", "data", "model") — 512 chips
"""
from __future__ import annotations

import jax

PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link


def mesh_kwargs(n_axes: int) -> dict:
    """Auto (GSPMD-propagated) sharding on every axis: the plans in
    `distributed.sharding` place arrays by constraint, not by type."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **mesh_kwargs(len(axes)))


def make_elastic_mesh(n_devices: int | None = None, *, model_parallel: int = 16):
    """Largest (data, model) mesh for whatever devices exist — used by the
    elastic-restart path: a checkpoint written on any mesh restores here."""
    devs = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    n = len(devs)
    mp = min(model_parallel, n)
    while n % mp:
        mp -= 1
    return jax.make_mesh((n // mp, mp), ("data", "model"), **mesh_kwargs(2))
