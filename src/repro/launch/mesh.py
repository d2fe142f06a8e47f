"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state. The production topology is a TPU v5e pod of 16x16 = 256 chips
("data" x "model"); the multi-pod configuration stacks 2 pods on a leading
"pod" axis (2 x 16 x 16 = 512 chips) — the pod axis carries data-parallel /
FSDP traffic (DCI-friendly: gradient reduction only), or pipeline stages
when RunProfile.pipeline is enabled.
"""
from __future__ import annotations

import re
from typing import Tuple

import jax
from jax.sharding import AxisType

_MESH_RE = re.compile(r"^(\d+)x(\d+)$")


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the repo's shard_maps and
    jitted steps leave placement outside a shard_map to the compiler, which
    ``jax.make_mesh``'s default ``Explicit`` axes refuse (an unresolved
    gather sharding, a jit with no mesh context)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has (smoke tests / examples): 1D data mesh."""
    n = len(jax.devices())
    return _auto_mesh((n,), ("data",))


def parse_mesh(spec: str) -> Tuple[int, int]:
    """Parse a ``--mesh DxM`` factorization string ("2x2" -> (2, 2)).
    Rejects anything that is not two positive integers joined by "x"."""
    m = _MESH_RE.match(spec.strip().lower())
    if not m:
        raise ValueError(
            f"--mesh wants DxM (two positive integers, e.g. 4x1, 2x2), "
            f"got {spec!r}")
    data, model = int(m.group(1)), int(m.group(2))
    if data < 1 or model < 1:
        raise ValueError(f"--mesh axes must be >= 1, got {data}x{model}")
    return data, model


def make_host_mesh_2d(data: int, model: int):
    """Factorized ("data", "model") host mesh over the first
    ``data * model`` local devices (DESIGN.md §16): batch rows shard over
    "data", TNN site/columns over "model". Validates the factorization
    against what the host actually has — ``jax.make_mesh`` insists on
    consuming EVERY device, so this builds the raw ``Mesh`` over a prefix
    of ``jax.devices()`` instead, letting e.g. a 2x2 mesh run on a 4- or
    8-device host."""
    import numpy as np
    from jax.sharding import Mesh

    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got {data}x{model}")
    devices = jax.devices()
    need = data * model
    if need > len(devices):
        raise ValueError(
            f"mesh {data}x{model} needs {need} devices but this host has "
            f"{len(devices)} (set TNN_HOST_DEVICES / "
            f"--xla_force_host_platform_device_count before jax imports)")
    grid = np.asarray(devices[:need]).reshape(data, model)
    return Mesh(grid, ("data", "model"))


def describe(mesh) -> str:
    return f"mesh{dict(mesh.shape)} on {len(mesh.devices.flat)} devices"
