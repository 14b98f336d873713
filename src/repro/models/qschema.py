"""Abstract SPARQLe-quantized parameter trees (dry-run substrate).

``build_quantized_schema`` mirrors :func:`repro.core.qlinear.
quantize_model_params` at the *schema* level: every quantizable projection
ParamSpec becomes a :class:`SparqleLinear` whose leaves are ParamSpecs for
the int8-container weight, per-output-channel scales, column-importance
mask and clipping constants. From that tree the dry-run derives
ShapeDtypeStructs and NamedShardings without allocating any memory — this
is how a 671B-param served model lowers on a laptop.

``init_served_params`` is the concrete counterpart: it builds the served
tree of seeded random weights leaf by leaf, so that a model whose float
tree would not fit the device never holds more than one float layer slice
of one projection at a time.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.core.qlinear import SparqleLinear, is_quantizable, leaf_plan
from repro.core.quantize import QuantizedTensor
from repro.distributed.sharding import spec_for
from repro.models.schema import (ParamSpec, Schema, _map_schema, init_layer,
                                 init_leaf, leaf_keys)


def _quantize_spec(spec: ParamSpec, path: str) -> SparqleLinear:
    shape, axes = spec.shape, spec.axes
    stacked = bool(axes) and axes[0] == "layers"
    is_expert = "experts" in axes          # routed-expert batched weight
    # identify (prefix dims, K, N): prefix = layer-stack and/or expert dims
    n_prefix = (1 if stacked else 0) + (1 if is_expert else 0)
    assert len(shape) == n_prefix + 2, (path, shape)
    pre_shape, (k, n) = shape[:n_prefix], shape[n_prefix:]
    pre_axes = axes[:n_prefix]
    k_ax, n_ax = axes[n_prefix], axes[n_prefix + 1]
    packed = k % 2 == 0                      # int4 nibbles two-per-byte
    q_shape = pre_shape + ((k // 2 if packed else k), n)
    q = ParamSpec(q_shape, axes, jnp.int8, init="zeros")
    scale = ParamSpec(pre_shape + (1, n), pre_axes + (None, n_ax),
                      jnp.float32, init="ones")
    zero = ParamSpec(pre_shape + (1, n), pre_axes + (None, n_ax),
                     jnp.float32, init="zeros")
    col_mask = ParamSpec(pre_shape + (k,), pre_axes + (k_ax,),
                         jnp.bool_, init="zeros")
    lh_shape = (shape[0],) if stacked else ()
    lh_axes = ("layers",) if stacked else ()
    l = ParamSpec(lh_shape, lh_axes, jnp.float32, init="zeros")
    h = ParamSpec(lh_shape, lh_axes, jnp.float32, init="zeros")
    return SparqleLinear(
        w=QuantizedTensor(q=q, scale=scale, zero=zero, bits=4),
        col_mask=col_mask, l=l, h=h, mode="sparqle", packed=packed)


def build_quantized_schema(schema: Dict[str, Any], w_bits: int = 4,
                           mode: str = "sparqle") -> Dict[str, Any]:
    """Schema tree with quantizable leaves replaced by SparqleLinear-of-spec."""

    def walk(tree, prefix=""):
        out = {}
        for key, v in tree.items():
            path = f"{prefix}/{key}" if prefix else key
            if isinstance(v, dict):
                out[key] = walk(v, path)
            elif isinstance(v, ParamSpec) and is_quantizable(path, _Probe(v)):
                sl = _quantize_spec(v, path)
                sl.w.bits = w_bits
                sl.mode = mode
                out[key] = sl
            else:
                out[key] = v
        return out

    return walk(schema)


class _Probe:
    """Adapter: is_quantizable checks .ndim on array leaves."""

    def __init__(self, spec: ParamSpec):
        self.ndim = len(spec.shape)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def tree_abstract(tree) -> Any:
    """ParamSpec leaves -> ShapeDtypeStruct (works through SparqleLinear)."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        tree, is_leaf=_is_spec)


def tree_shardings(tree, mesh: Mesh) -> Any:
    """ParamSpec leaves -> NamedSharding via the logical-axis rule table."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, spec_for(s.axes, s.shape, mesh)),
        tree, is_leaf=_is_spec)


def init_served_params(schema: Schema, key: jax.Array,
                       **quant_kw) -> Dict[str, Any]:
    """``quantize_model_params(init_params(schema, key), **quant_kw)``,
    built without ever holding the float tree.

    Each quantizable leaf is made in one jitted init -> quantize program;
    a layer-stacked leaf is made one layer slice per call, written into a
    donated stacked buffer, so only the served tree persists on the device.
    A slice's values are those of the whole leaf's random draw
    (``schema.init_layer``), computed without the rest of the draw. Other
    leaves (embeddings, norms, biases) are built as ``init_params`` builds
    them.
    """
    keys = leaf_keys(schema, key)

    def build(path: str, spec: ParamSpec):
        if not is_quantizable(path, _Probe(spec)):
            return init_leaf(keys[path], spec)
        quantize, stacked = leaf_plan(path, len(spec.shape), **quant_kw)
        if not stacked:
            return jax.jit(lambda k: quantize(init_leaf(k, spec)))(
                keys[path])

        def one_layer(k, i):
            return quantize(init_layer(k, spec, i))

        def write(buf, k, i):
            return jax.tree_util.tree_map(
                lambda b, x: jax.lax.dynamic_update_index_in_dim(b, x, i, 0),
                buf, one_layer(k, i))

        n_layers = spec.shape[0]
        buf = jax.tree_util.tree_map(
            lambda a: jnp.zeros((n_layers,) + a.shape, a.dtype),
            jax.eval_shape(one_layer, keys[path], 0))
        write = jax.jit(write, donate_argnums=0)
        for i in range(n_layers):
            buf = write(buf, keys[path], jnp.int32(i))
        return buf

    return _map_schema(schema, build)
