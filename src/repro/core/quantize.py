"""Integer quantization substrate: W4A8 / W2A8 / KV4 (paper §4 model configs).

Symmetric per-output-channel weight quantization (int4 / ternary int2),
per-token (or per-tensor) int8 activation quantization with optional
zero-point adjustment (paper §3.1: shifting non-centered distributions into
the MSB4==0 range), and int4 KV-cache quantization (W4A8KV4 / W2A8KV4).

Quantized payloads are carried in int8 containers at this level; sub-byte
packing is applied downstream where the bytes move — weights via
``qlinear.pack_int4``, the KV cache via ``model._kv_quant``, and the
activation stream via the packed wire format in ``core/packing.py``
(docs/format.md).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """q * scale + zero  ≈  original  (zero is in real units, optional)."""

    q: jax.Array          # int8 container
    scale: jax.Array      # f32, broadcastable to q
    zero: jax.Array       # f32, broadcastable to q (0.0 when symmetric)
    bits: int             # payload width actually used (2, 4, or 8)

    def tree_flatten(self):
        return (self.q, self.scale, self.zero), self.bits

    @classmethod
    def tree_unflatten(cls, bits, children):
        return cls(*children, bits=bits)

    @property
    def shape(self):
        return self.q.shape

    def dequantize(self) -> jax.Array:
        return self.q.astype(jnp.float32) * self.scale + self.zero


def _qrange(bits: int) -> tuple[int, int]:
    return -(2 ** (bits - 1)), 2 ** (bits - 1) - 1


def quantize_weights(w: jax.Array, bits: int = 4, axis: int = -1) -> QuantizedTensor:
    """Symmetric per-channel weight quantization.

    ``axis`` is the *reduction* axis of the matmul the weight participates in;
    scales are computed per output channel (all axes except ``axis`` reduced).
    For bits=2 this is ternary-ish {-2..1} (BitNet W2 carrier).
    """
    lo, hi = _qrange(bits)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    # multiply by the reciprocal: inside jit XLA rewrites a division by a
    # constant this way, so writing it out makes eager and jitted
    # quantization agree bit for bit
    scale = jnp.maximum(amax * (1.0 / hi), 1e-8)
    q = jnp.clip(jnp.round(w / scale), lo, hi).astype(jnp.int8)
    return QuantizedTensor(q=q, scale=scale.astype(jnp.float32),
                           zero=jnp.zeros_like(scale, jnp.float32), bits=bits)


def quantize_activations(
    x: jax.Array,
    bits: int = 8,
    per_token: bool = True,
    zero_point: bool = False,
    amax: Optional[jax.Array] = None,
) -> QuantizedTensor:
    """Int8 activation quantization.

    ``zero_point=True`` applies the paper's zero-point adjustment: shift the
    distribution so its near-zero mass lands in [0, 15] (MSB4==0 range),
    boosting sub-precision sparsity for non-centered activations (e.g. SiLU
    outputs). The shift is in real units; dequantization undoes it exactly.

    ``amax`` overrides the reduction-axis abs-max (broadcastable to the
    keepdims reduction shape). A tensor-parallel caller whose rows are
    sharded over a mesh axis passes the GLOBAL row max (an exact ``pmax``
    of local maxima), so every shard quantizes with the same scale and
    the local int8 planes are exact slices of the unsharded ones.
    """
    lo, hi = _qrange(bits)
    axis = tuple(range(x.ndim - 1, x.ndim)) if per_token else tuple(range(x.ndim))
    if zero_point:
        assert amax is None, "amax override not supported with zero_point"
        # Paper §3.1 zero-point adjustment: shift so the distribution's
        # near-minimum mass lands at q ~ 0, i.e. inside the MSB4==0 range
        # [0, 15]. For SiLU-like activations (bounded slightly below zero,
        # mode near zero) this converts the dense near-zero band into
        # sub-precision-sparse codes, at the cost of using only the
        # non-negative half of the int8 range for the payload.
        xmin = jnp.min(x, axis=axis, keepdims=True)
        xmax = jnp.max(x, axis=axis, keepdims=True)
        scale = jnp.maximum((xmax - xmin) / hi, 1e-8)
        zero = xmin                       # x == xmin -> q == 0
        q = jnp.clip(jnp.round((x - zero) / scale), 0, hi).astype(jnp.int8)
        return QuantizedTensor(q=q, scale=scale.astype(jnp.float32),
                               zero=zero.astype(jnp.float32), bits=bits)
    if amax is None:
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax / hi, 1e-8)
    q = jnp.clip(jnp.round(x / scale), lo, hi).astype(jnp.int8)
    return QuantizedTensor(q=q, scale=scale.astype(jnp.float32),
                           zero=jnp.zeros_like(scale, jnp.float32), bits=bits)


def quantize_kv(kv: jax.Array, bits: int = 4) -> QuantizedTensor:
    """KV-cache quantization (per head-dim-channel scales), KV4 in the paper."""
    return quantize_weights(kv, bits=bits, axis=-1)


def dequantize(t: QuantizedTensor) -> jax.Array:
    return t.dequantize()


def fake_quantize(x: jax.Array, bits: int = 8, per_token: bool = True) -> jax.Array:
    """Quantize-dequantize in one op (QAT-style straight-through in fwd)."""
    return quantize_activations(x, bits=bits, per_token=per_token).dequantize()
