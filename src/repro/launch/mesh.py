"""Production mesh builders (functions, not module constants — importing
this module never touches jax device state).

Every mesh is built with ``Auto`` axis types: the sharding rules
(sharding/specs.py) and activation constraints (sharding/ctx.py) are
GSPMD-style hints that the compiler propagates.  ``jax.make_mesh`` defaults
to ``Explicit`` axes, under which an op whose output sharding cannot be
inferred (the embedding gather of ``P(model, data)`` rows by
data-sharded tokens) is a trace-time error."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis is the
    slow DCI axis and carries only data-parallel gradient reduction."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int = 1):
    """Small mesh for CPU sharding tests (run under
    XLA_FLAGS=--xla_force_host_platform_device_count=N)."""
    return _mesh((data, model), ("data", "model"))


def parse_mesh_spec(spec: str):
    """'data=2,model=4' -> {"data": 2, "model": 4} (axis order preserved)."""
    out = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        name, size = name.strip(), size.strip()
        if name not in ("pod", "data", "model") or not size.isdigit():
            raise ValueError(
                f"bad mesh spec {spec!r}: expected e.g. 'data=2,model=4' "
                f"with axes from (pod, data, model)")
        out[name] = int(size)
    return out


def mesh_from_spec(spec: str):
    """Build a mesh from a '--mesh data=N,model=M' flag value.

    The axis product must equal the visible device count (on CPU, set
    XLA_FLAGS=--xla_force_host_platform_device_count=N before the process
    starts — jax locks the device count at first init)."""
    axes = parse_mesh_spec(spec)
    n = 1
    for s in axes.values():
        n *= s
    have = len(jax.devices())
    if n != have:
        raise ValueError(
            f"mesh {spec!r} needs {n} devices but {have} are visible "
            f"(CPU runs: XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={n})")
    return _mesh(axes.values(), axes)
