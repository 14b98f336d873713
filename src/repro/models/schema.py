"""Parameter schema: declare params once, derive init / shardings / abstract.

Each model family builds a nested dict of :class:`ParamSpec` (shape + logical
axes + init scale). From the schema we derive:

  * ``init_params``   — materialized arrays (smoke tests, real training),
  * ``abstract_params`` — ShapeDtypeStructs (the dry-run lowers 671B-param
    models without allocating a byte),
  * ``param_pspecs``  — PartitionSpecs via the logical-axis rule table,
  * ``param_shardings`` — NamedShardings for jit in_shardings.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p
from jax.sharding import Mesh, NamedSharding

from repro.distributed.sharding import spec_for


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis per dim
    dtype: jnp.dtype = jnp.float32
    init: str = "normal"              # normal | zeros | ones | embed
    scale: Optional[float] = None     # overrides fan-in scaling

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


Schema = Dict[str, object]  # nested dict of ParamSpec


def _fan_in(shape: Tuple[int, ...]) -> int:
    # last dim is output features by convention; fan-in = prod of the rest
    if len(shape) <= 1:
        return max(1, shape[0] if shape else 1)
    f = 1
    for d in shape[:-1]:
        f *= d
    return f


def _std(spec: ParamSpec) -> float:
    if spec.init == "embed":
        return spec.scale if spec.scale is not None else 1.0
    return spec.scale if spec.scale is not None else 1.0 / math.sqrt(
        _fan_in(spec.shape))


@functools.partial(jax.jit, static_argnums=1)
def init_leaf(key, spec: ParamSpec) -> jax.Array:
    """One leaf's seeded values. Jitted, so its arithmetic is the same
    compiled expression as :func:`init_layer`'s: XLA folds the constants
    of ``normal * std`` in a jitted program, which an op-by-op one would
    not, and the two would differ in the last bit."""
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    return (jax.random.normal(key, spec.shape, jnp.float32) * _std(spec)
            ).astype(spec.dtype)


def _normal_at(key, shape, first) -> jax.Array:
    """The standard normals at flat indices ``first, first + 1, ...`` of a
    larger draw, laid out as ``shape``. Under ``jax_threefry_partitionable``
    (the default) element ``n`` of ``jax.random.normal(key, full_shape)``
    is a function of the key and ``n`` alone — the threefry hash of the
    64-bit counter ``n``, mapped to a float by the steps of
    ``jax.random.uniform`` and ``normal`` — so this reproduces a slice of
    the draw without computing the rest. The draw must have fewer than
    2**32 elements (the counter's high word is then zero)."""
    lo = first + jax.lax.iota(jnp.uint32, math.prod(shape)).reshape(shape)
    b1, b2 = threefry2x32_p.bind(key[0], key[1], jnp.zeros_like(lo), lo)
    one = np.array(1.0, np.float32)
    float_bits = jax.lax.shift_right_logical(b1 ^ b2, jnp.uint32(32 - 23))
    float_bits = float_bits | jnp.uint32(one.view(np.uint32))
    floats = jax.lax.bitcast_convert_type(float_bits, jnp.float32) - one
    lo_f = np.nextafter(np.float32(-1.0), np.float32(0.0))
    u = jax.lax.max(lo_f, floats * (one - lo_f) + lo_f)
    return np.float32(np.sqrt(2)) * jax.lax.erf_inv(u)


@functools.partial(jax.jit, static_argnums=1)
def init_layer(key, spec: ParamSpec, layer) -> jax.Array:
    """``init_leaf(key, spec)[layer]`` for a normal-initialized leaf,
    computed without the other layers' values. ``layer`` may be traced."""
    if math.prod(spec.shape) >= 2 ** 32:
        raise ValueError(f"leaf of {spec.shape} has 2**32 or more elements")
    first = (jnp.asarray(layer, jnp.uint32)
             * jnp.uint32(math.prod(spec.shape[1:])))
    return (_normal_at(key, spec.shape[1:], first) * _std(spec)
            ).astype(spec.dtype)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _map_schema(schema: Schema, fn: Callable[[str, ParamSpec], object],
                prefix: str = "") -> Dict:
    out = {}
    for k, v in schema.items():
        path = f"{prefix}/{k}" if prefix else k
        if _is_spec(v):
            out[k] = fn(path, v)
        else:
            out[k] = _map_schema(v, fn, path)
    return out


def leaf_keys(schema: Schema, key: jax.Array) -> Dict[str, jax.Array]:
    """The PRNG key of every leaf, by path (sorted paths, one split)."""
    leaves = []
    _map_schema(schema, lambda p, s: leaves.append(p) or p)
    return dict(zip(sorted(leaves),
                    jax.random.split(key, max(1, len(leaves)))))


def init_params(schema: Schema, key: jax.Array) -> Dict:
    keys = leaf_keys(schema, key)
    return _map_schema(schema, lambda p, s: init_leaf(keys[p], s))


def abstract_params(schema: Schema) -> Dict:
    return _map_schema(
        schema, lambda p, s: jax.ShapeDtypeStruct(s.shape, s.dtype))


def param_pspecs(schema: Schema, mesh: Mesh) -> Dict:
    return _map_schema(
        schema, lambda p, s: spec_for(s.axes, s.shape, mesh))


def param_shardings(schema: Schema, mesh: Mesh) -> Dict:
    return _map_schema(
        schema,
        lambda p, s: NamedSharding(mesh, spec_for(s.axes, s.shape, mesh)))


def param_count(schema: Schema) -> int:
    total = [0]

    def add(p, s):
        n = 1
        for d in s.shape:
            n *= d
        total[0] += n
        return None

    _map_schema(schema, add)
    return total[0]


def param_bytes(schema: Schema) -> int:
    total = [0]

    def add(p, s):
        n = np.dtype(s.dtype).itemsize
        for d in s.shape:
            n *= d
        total[0] += n
        return None

    _map_schema(schema, add)
    return total[0]
