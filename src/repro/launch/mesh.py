"""Production meshes.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model").

Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes. JAX now defaults to
    ``Explicit`` axes, under which every gather and sharding constraint
    must spell out its output sharding; the repo's sharding rules are
    written for the partitioner to propagate (GSPMD-style), so every mesh
    is built here."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2, multi_pod: bool = False):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count
    set by the test itself before jax init)."""
    if multi_pod:
        return auto_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return auto_mesh((n_data, n_model), ("data", "model"))


def make_mesh_from_spec(spec: str):
    """``--mesh`` flag parser: 'DATAxMODEL' ('2x4') or 'PODxDATAxMODEL'
    ('2x2x2'). Needs that many devices — on CPU set
    XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT (or the xla_force_host_platform_
    device_count XLA flag) before jax initializes."""
    dims = tuple(int(x) for x in spec.lower().replace("×", "x").split("x"))
    if len(dims) == 2:
        axes = ("data", "model")
    elif len(dims) == 3:
        axes = ("pod", "data", "model")
    else:
        raise ValueError(f"--mesh wants DATAxMODEL or PODxDATAxMODEL, got "
                         f"{spec!r}")
    need = 1
    for d in dims:
        need *= d
    have = jax.device_count()
    if have < need:
        raise RuntimeError(
            f"mesh {spec} needs {need} devices but only {have} visible — "
            f"on CPU run with XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT={need}")
    return auto_mesh(dims, axes)


# TPU v5e roofline constants (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW_PER_LINK = 50e9          # bytes/s/link
