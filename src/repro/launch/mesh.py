"""Production mesh definitions.

Axes:
  "data"  — data parallelism == the MTSL client axis (16-way per pod)
  "model" — tensor/expert parallelism (16-way per pod)
  "pod"   — multi-pod outer data axis (2 pods = 512 chips)

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with every axis `Auto`. The round builders place
    client leaves with `with_sharding_constraint`, which accepts only Auto
    axes (jax.make_mesh defaults to Explicit ones)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def num_clients_for(mesh) -> int:
    """MTSL clients = pod * data extent."""
    n = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    return max(n, 1)


# canonical axis order for user-specified meshes (client axes outermost,
# matching make_production_mesh and utils/sharding.DEFAULT_RULES["client"])
_AXIS_ORDER = ("pod", "data", "model")


def parse_mesh_spec(spec: str) -> dict:
    """Parse a launcher mesh spec "data=N[,model=K[,pod=P]]" into an
    axis->size dict. Axis names must come from ("pod","data","model");
    sizes must be positive ints; repeats are rejected. "" -> {} (no mesh).
    """
    out: dict = {}
    spec = spec.strip()
    if not spec:
        return out
    for part in spec.split(","):
        name, eq, val = part.partition("=")
        name = name.strip()
        if name not in _AXIS_ORDER:
            raise ValueError(
                f"unknown mesh axis {name!r} in spec {spec!r}; "
                f"axes: {_AXIS_ORDER}")
        if name in out:
            raise ValueError(f"mesh axis {name!r} repeated in spec {spec!r}")
        if not eq or not val.strip().isdigit() or int(val) < 1:
            raise ValueError(
                f"mesh spec entry {part!r} must be '<axis>=<positive int>'")
        out[name] = int(val)
    return out


def make_mesh_from_spec(spec):
    """Build a Mesh from a "data=N[,model=K[,pod=P]]" spec (string or the
    dict parse_mesh_spec returns). Axes are laid out in the canonical
    ("pod","data","model") order, restricted to the axes named in the spec;
    the size product must not exceed the available device count. None or
    "" -> None (no mesh: the single-device path)."""
    if spec is None:
        return None
    sizes = parse_mesh_spec(spec) if isinstance(spec, str) else dict(spec)
    if not sizes:
        return None
    axes = tuple(a for a in _AXIS_ORDER if a in sizes)
    shape = tuple(sizes[a] for a in axes)
    total = 1
    for s in shape:
        total *= s
    avail = len(jax.devices())
    if total > avail:
        raise ValueError(
            f"mesh spec {sizes} needs {total} devices but only {avail} are "
            "available (force more host CPU devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            "jax initializes)")
    return make_mesh(shape, axes)
