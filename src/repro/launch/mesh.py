"""Mesh construction: production TPU v5e pods and forced-host-device test
meshes (docs/sharding.md).

Every constructor is a FUNCTION, not a module-level constant: importing
this module never touches jax device state (required so smoke tests see
1 CPU device).  Multi-device CPU runs must force the device count through
``XLA_FLAGS`` BEFORE jax initializes — ``forced_host_env`` builds the
subprocess environment tests and benches share for that.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = [
    "make_production_mesh", "make_host_mesh", "forced_host_env",
    "HOST_DEVICE_FLAG", "PEAK_FLOPS_BF16", "HBM_BW", "ICI_BW",
]

# the XLA flag that splits the host CPU into N virtual devices; it must be
# in the environment before jax initializes (see launch/dryrun.py)
HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis typed Auto.  JAX 0.9 defaults new
    meshes to Explicit axes, under which an unannotated gather such as the
    embedding lookup ``params["embed"][tokens]`` raises
    ``ShardingTypeError``; the models rely on GSPMD propagation plus
    ``constrain`` hints, which is the Auto contract."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    """The deployment meshes: (16, 16) ("data","model") single pod, or
    (2, 16, 16) ("pod","data","model") across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int = 1):
    """Small ("data","model") mesh over whatever local devices exist
    (sharding tests / forced-host-device runs).  Axis sizes are clamped to
    the available device count, so the same call works on 1 real CPU
    device and on ``--xla_force_host_platform_device_count=8``."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    data = max(1, min(data, n // model))
    return _auto_mesh((data, model), ("data", "model"))


def forced_host_env(n_devices: int, base: Optional[dict] = None) -> dict:
    """Subprocess environment forcing ``n_devices`` virtual CPU devices.

    The flag only takes effect at jax init, so multi-device CPU tests and
    benches spawn a fresh interpreter with this env (never set it in an
    already-initialized process).  Existing XLA_FLAGS content is preserved.
    """
    env = dict(os.environ if base is None else base)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(HOST_DEVICE_FLAG)]
    flags.append(f"{HOST_DEVICE_FLAG}={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    return env


# Hardware constants for the roofline model (TPU v5e)
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link
