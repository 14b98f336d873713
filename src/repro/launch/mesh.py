"""Production mesh construction (deliverable e).

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state. The single-pod mesh is
16x16 = 256 chips over ("data", "model"); the multi-pod mesh adds an outer
"pod" axis: 2 pods x 256 = 512 chips. The pod axis is the DCN-connected
outer data-parallel axis (per-pod replica groups; gradients cross pods once
per step), composing data parallelism over ICI within a pod.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    # Auto axes: shardings propagate through GSPMD and shard_map as the
    # constrain()/spec_for() rule tables expect (make_mesh defaults to
    # Explicit axes, which reject with_sharding_constraint on them)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_smoke_mesh(*, data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over however many (CPU) devices exist — used by tests
    and the ``--mesh`` serving path."""
    need = data * model
    have = len(jax.devices())
    if need > have:
        raise RuntimeError(
            f"make_smoke_mesh(data={data}, model={model}) needs {need} "
            f"devices but jax sees {have}. On CPU, emulate host devices "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need} (it must be set in the environment BEFORE jax "
            f"initializes — the multi-device CI lane and "
            f"tests/conftest.py's `mesh` fixture rely on this).")
    return _make_mesh((data, model), ("data", "model"))
