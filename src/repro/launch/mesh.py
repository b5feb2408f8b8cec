"""Production mesh builders.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

Defined as functions — importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import).
"""

from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = n // model
    return _mesh((data, model), ("data", "model"))


def parse_mesh_spec(spec: str):
    """'dxm' (e.g. '2x4') → (data, model) ints."""
    parts = spec.lower().split("x")
    if len(parts) != 2:
        raise ValueError(f"mesh spec must be 'DATAxMODEL' (e.g. 2x4), "
                         f"got {spec!r}")
    data, model = (int(p) for p in parts)
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got {spec!r}")
    return data, model


def make_serving_mesh(spec: str):
    """(data, model) mesh for the serving engine from a CLI 'dxm' spec.

    Decode slots shard over ``data``, attention heads over ``model``
    (runtime/server.py).  On a CPU host, fake devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before the
    first jax use — the error message reminds the caller.
    """
    data, model = parse_mesh_spec(spec)
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"mesh {spec} needs {data * model} devices but only {n} are "
            f"visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={data * model} "
            f"before jax initializes")
    return _mesh((data, model), ("data", "model"))
