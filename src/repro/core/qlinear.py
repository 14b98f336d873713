"""SPARQLe quantized linear — the paper's technique as a drop-in layer.

``SparqleLinear`` bundles everything a deployed SPARQLe linear needs:
the int4/int2 quantized weight, the precomputed column-importance mask
(paper §3.2 — offline, zero runtime overhead) and the calibrated clipping
constants.  ``linear()`` is the single projection entry point used by every
model family: it dispatches transparently between

  * plain float weights                  (training / float serving),
  * ``SparqleLinear`` in ``sparqle`` mode (dual-pass sub-precision execution),
  * ``SparqleLinear`` in ``dense`` mode   (the paper's W4A8 dense baseline).

``quantize_model_params`` converts a float param tree into its served form
by rewriting projection leaves in place — models need no code changes to
run quantized (the "complementary to quantization" contribution of §1).
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.clipping import apply_clipping, importance_mask_tile_aligned
from repro.core.packing import encode_packed, unpack_planes
from repro.core.quantize import (QuantizedTensor, quantize_activations,
                                 quantize_weights)
from repro.core.sparqle import encode
from repro.distributed.tp import tp_ctx


# Trace-time draft-mode flag (self-speculative decoding): while True, every
# sparqle-mode projection runs LSB4-only — the sparse MSB pass is elided
# from the traced program entirely, so a jitted function traced under
# msb_skip_scope() IS the 1-compute-round draft forward (paper §3.3: the
# full hybrid pass costs 1 + (1 - s) rounds). Read at trace time only; it
# must wrap the whole trace (e.g. the body of a jitted step function),
# not individual calls of an already-compiled one.
_MSB_SKIP = False


@contextlib.contextmanager
def msb_skip_scope(enabled: bool = True):
    """Trace every sparqle projection in LSB4-only (draft) mode."""
    global _MSB_SKIP
    prev = _MSB_SKIP
    _MSB_SKIP = enabled
    try:
        yield
    finally:
        _MSB_SKIP = prev


def msb_skip_active() -> bool:
    return _MSB_SKIP


def pack_int4(q: jax.Array, axis: int = -2) -> jax.Array:
    """Pack two's-complement int4 values two-per-byte along ``axis``.

    The sub-byte wire format the paper's representation implies, applied
    to the static weights: halves the weight HBM stream (the dominant
    decode bytes after the KV cache).
    """
    assert q.shape[axis] % 2 == 0, q.shape
    lo = jnp.take(q, jnp.arange(0, q.shape[axis], 2), axis=axis)
    hi = jnp.take(q, jnp.arange(1, q.shape[axis], 2), axis=axis)
    return jnp.bitwise_or(
        jnp.bitwise_and(lo, 0xF),
        jnp.left_shift(jnp.bitwise_and(hi, 0xF), 4)).astype(jnp.int8)


def int4_planes(q: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The two sign-extended nibble planes of :func:`pack_int4` bytes.

    ``lo`` holds the even rows along the packed axis and ``hi`` the odd
    ones, each the packed array's shape: elementwise shifts, no relayout.
    """
    return jnp.right_shift(jnp.left_shift(q, 4), 4), jnp.right_shift(q, 4)


def unpack_int4(q: jax.Array, axis: int = -2) -> jax.Array:
    """Inverse of :func:`pack_int4` (sign-extending)."""
    lo, hi = int4_planes(q)
    stacked = jnp.stack([lo, hi], axis=axis + 1 if axis >= 0
                        else q.ndim + axis + 1)
    shape = list(q.shape)
    shape[axis] = shape[axis] * 2
    return stacked.reshape(shape)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SparqleLinear:
    """A quantized projection in SPARQLe served form.

    ``w.q`` is (K, N) or batched (E, K, N) for expert weights — stored
    nibble-PACKED along K ((K/2, N)) when ``packed``. ``col_mask`` marks
    the k% least-important activation columns (per expert for batched
    weights); ``l``/``h`` are the calibrated clipping constants.
    Aux (untraced): ``mode`` ('sparqle' | 'dense'), ``packed``, and
    ``wire_format`` ('unpacked' | 'packed') — the latter routes the
    *activation* stream through the packed sub-precision wire format
    (``core/packing.py``) before the dual-pass matmul.
    """

    w: QuantizedTensor
    col_mask: Optional[jax.Array]   # (K,) or (E, K) bool; None = no clipping
    l: Optional[jax.Array]          # scalar f32 (integer-domain)
    h: Optional[jax.Array]
    mode: str = "sparqle"
    packed: bool = False
    wire_format: str = "unpacked"

    def tree_flatten(self):
        return (self.w, self.col_mask, self.l, self.h), (
            self.mode, self.packed, self.wire_format)

    @classmethod
    def tree_unflatten(cls, aux, children):
        aux = aux if isinstance(aux, tuple) else (aux,)
        mode = aux[0]
        packed = aux[1] if len(aux) > 1 else False
        wf = aux[2] if len(aux) > 2 else "unpacked"
        return cls(*children, mode=mode, packed=packed, wire_format=wf)

    def unpacked_q(self) -> jax.Array:
        q = self.w.q.astype(jnp.int8)
        return unpack_int4(q) if self.packed else q

    def dequantize(self) -> jax.Array:
        return self.unpacked_q().astype(jnp.float32) * self.w.scale \
            + self.w.zero

    @property
    def shape(self):
        s = list(self.w.q.shape)
        if self.packed:
            s[-2] *= 2
        return tuple(s)


# The weight operand of the integer dots: an int8 (…, K, N) array, or the
# sign-extended (lo, hi) nibble planes of a packed (…, K/2, N) one.
WeightOperand = Union[jax.Array, Tuple[jax.Array, jax.Array]]


def _int_dot(a: jax.Array, w: WeightOperand, batched: bool) -> jax.Array:
    """int8 (M, K) x (K, N), or batched (E, C, K) x (E, K, N), -> int32.

    For packed planes, row 2i of the unpacked weight is ``lo[i]`` and row
    2i+1 is ``hi[i]``, so ``a @ unpack(W) == a[..., 0::2] @ lo +
    a[..., 1::2] @ hi`` exactly in int32: the K-interleaved int8 weight
    is never built. A row-parallel shard holding packed rows [r, s) holds
    unpacked rows [2r, 2s) and the matching activation columns, so the
    parity split is local to each shard.
    """
    dims = ((((2,), (1,)), ((0,), (0,))) if batched
            else (((1,), (0,)), ((), ())))

    def dot(x, y):
        return jax.lax.dot_general(x, y, dims,
                                   preferred_element_type=jnp.int32)

    if isinstance(w, tuple):
        lo, hi = w
        k = a.shape[-1]
        even, odd = (jax.lax.slice_in_dim(a, p, k, stride=2, axis=a.ndim - 1)
                     for p in (0, 1))
        return dot(even, lo) + dot(odd, hi)
    return dot(a, w)


def _dual_pass_matmul(q: jax.Array, w: WeightOperand, batched: bool,
                      wire_format: str = "unpacked",
                      msb_skip: bool = False) -> jax.Array:
    """int8 SPARQLe activations x int-weights -> int32, dual nibble passes.

    ``wire_format='packed'`` round-trips the activations through the packed
    sub-precision wire format first, making the wire layout — not the dense
    int8 tensor — the source of truth the matmul consumes. The codec is an
    exact inverse pair, so both formats produce bit-identical accumulators.

    ``msb_skip`` drops the sparse pass from the traced program: the result
    is the dense LSB4 contribution alone (equal to dequantizing the LSB
    plane by itself), the draft forward of self-speculative decoding.

    The encode and the two passes run under the named scopes
    ``act_encode``, ``lsb_dot`` and ``msb_dot``, which name their ops in
    the compiled program's metadata (and so in a device trace).
    """
    with jax.named_scope("act_encode"):
        if wire_format == "packed":
            pa = encode_packed(q.reshape(-1, q.shape[-1]))
            planes = unpack_planes(pa)
            lsb = planes.lsb4.reshape(q.shape)
            msb = planes.msb4.reshape(q.shape)
        else:
            act = encode(q)
            lsb, msb = act.lsb4, act.msb4
    with jax.named_scope("lsb_dot"):
        dense = _int_dot(lsb, w, batched)
    if msb_skip:
        return dense
    with jax.named_scope("msb_dot"):
        sparse = _int_dot(msb, w, batched)
    return dense + sparse * 16


def _single_pass_matmul(q: jax.Array, w: WeightOperand,
                        batched: bool) -> jax.Array:
    with jax.named_scope("int8_dot"):
        return _int_dot(q, w, batched)


def linear(x: jax.Array, w, b: Optional[jax.Array] = None, *,
           tp: Optional[str] = None) -> jax.Array:
    """Universal projection: x (..., K) @ w (K, N) [+ b].

    ``w`` may be a float array, a :class:`SparqleLinear`, or (batched expert
    form) x (E, C, K) @ w (E, K, N).

    ``tp="row"`` marks this call site as row-parallel under tensor
    parallelism (``distributed/tp.py``): when a TP trace is active the
    input features and weight K dim are sharded over the model axis, the
    per-token activation scale is taken over the GLOBAL row (exact pmax)
    and the int32 accumulator is reduced with ONE psum before rescaling
    (bias added after, on the replicated output). Inert otherwise —
    single-device traces are unchanged.
    """
    if isinstance(w, SparqleLinear):
        y = _quantized_apply(x, w, tp=tp)
    else:
        y = jax.lax.dot_general(
            x, w.astype(x.dtype),
            (((x.ndim - 1,), (0,)), ((), ())))
        ctx = tp_ctx()
        if tp == "row" and ctx is not None and ctx.ways > 1:
            y = jax.lax.psum(y, ctx.axis)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def expert_linear(x: jax.Array, w, *, tp: Optional[str] = None) -> jax.Array:
    """Batched expert projection: x (E, C, K) @ w (E, K, N).

    ``tp="row"`` as in :func:`linear` (per-expert K dims sharded; one
    int32 psum of the merged accumulator).
    """
    if isinstance(w, SparqleLinear):
        return _quantized_apply(x, w, batched=True, tp=tp)
    y = jnp.einsum("eck,ekn->ecn", x, w.astype(x.dtype))
    ctx = tp_ctx()
    if tp == "row" and ctx is not None and ctx.ways > 1:
        y = jax.lax.psum(y, ctx.axis)
    return y


def _quantized_apply(x: jax.Array, sl: SparqleLinear,
                     batched: bool = False,
                     tp: Optional[str] = None) -> jax.Array:
    """quantize -> clip -> decompose -> dual-pass -> [psum] -> rescale."""
    ctx = tp_ctx()
    row = tp == "row" and ctx is not None and ctx.ways > 1
    orig = x.shape
    k_in = orig[-1]
    if batched:
        x2 = x                                 # (E, C, K)
    else:
        x2 = x.reshape(-1, k_in)               # (M, K)
    if row:
        # global per-token scale: pmax of local row maxima is exact, so
        # each shard's int8 plane is a slice of the unsharded plane
        amax = jax.lax.pmax(
            jnp.max(jnp.abs(x2), axis=-1, keepdims=True), ctx.axis)
        qa = quantize_activations(x2, bits=8, per_token=True, amax=amax)
    else:
        qa = quantize_activations(x2, bits=8, per_token=True)
    q = qa.q
    if sl.col_mask is not None and sl.l is not None:
        mask = sl.col_mask[:, None, :] if batched else sl.col_mask
        q = apply_clipping(q, mask, sl.l, sl.h)
    # a packed weight reaches the dots as its two nibble planes (see
    # _int_dot), so no int8 copy of it is built on the call
    with jax.named_scope("weight_unpack"):
        w = (int4_planes(sl.w.q.astype(jnp.int8)) if sl.packed
             else sl.unpacked_q())
    if sl.mode == "sparqle":
        acc = _dual_pass_matmul(q, w, batched, sl.wire_format,
                                msb_skip=_MSB_SKIP)
    else:
        acc = _single_pass_matmul(q, w, batched)
    if row:
        # ONE reduction per linear: the dual-pass accumulator already
        # merged LSB and shifted-MSB partials, and int32 addition is
        # associative — the psum'd accumulator is bit-identical to the
        # single-device one
        acc = jax.lax.psum(acc, ctx.axis)
    w_scale = sl.w.scale  # (1, N) or (E, 1, N) per-output-channel
    out = acc.astype(jnp.float32) * qa.scale.astype(jnp.float32) \
        * w_scale.reshape((sl.w.q.shape[0], 1, -1) if batched else (1, -1))
    if batched:
        return out.astype(x.dtype)
    return out.reshape(*orig[:-1], sl.w.q.shape[-1]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Offline conversion: float param tree -> SPARQLe served tree
# ---------------------------------------------------------------------------

# param-leaf name patterns eligible for quantization (projection weights);
# norms / embeddings / biases / ssm scalars stay float.
_QUANT_LEAF = re.compile(
    r"(wq|wk|wv|wo|w_gate|w_up|w_down|w_fc|w_proj|w_in|w_out|"
    r"wq_a|wq_b|wkv_a|wkv_b|lm_head|w_shared_gate|w_shared_up|w_shared_down)$")


def is_quantizable(path: str, leaf) -> bool:
    if not hasattr(leaf, "ndim") or leaf.ndim < 2:
        return False
    name = path.rsplit("/", 1)[-1]
    return bool(_QUANT_LEAF.search(name))


def quantize_leaf(
    leaf: jax.Array,
    *,
    w_bits: int = 4,
    k_percent: float = 50.0,
    clip_l: float = -8.0,
    clip_h: float = 23.0,
    mode: str = "sparqle",
    tile_k: int = 128,
    enable_clipping: bool = True,
    pack: bool = True,
    wire_format: str = "unpacked",
) -> SparqleLinear:
    """Quantize one (K, N) or (E, K, N) projection into served form.

    ``pack`` nibble-packs the int4 payload two-per-byte along K (halving
    the stored/streamed weight bytes); disabled automatically for odd K
    or w_bits > 4. ``wire_format='packed'`` additionally routes the
    layer's *activations* through the packed sub-precision wire format.
    """
    if leaf.ndim == 2:
        wq = quantize_weights(leaf, bits=w_bits, axis=0)
        mask = (importance_mask_tile_aligned(leaf, k_percent, tile_k)
                if enable_clipping else None)
    elif leaf.ndim == 3:
        wq = quantize_weights(leaf, bits=w_bits, axis=1)
        if enable_clipping:
            mask = jnp.stack([
                importance_mask_tile_aligned(leaf[e], k_percent, tile_k)
                for e in range(leaf.shape[0])])
        else:
            mask = None
    else:
        raise ValueError(f"unsupported weight rank {leaf.ndim}")
    do_pack = pack and w_bits <= 4 and wq.q.shape[-2] % 2 == 0
    if do_pack:
        wq = QuantizedTensor(q=pack_int4(wq.q), scale=wq.scale,
                             zero=wq.zero, bits=wq.bits)
    return SparqleLinear(
        w=wq,
        col_mask=mask,
        l=jnp.float32(clip_l) if enable_clipping else None,
        h=jnp.float32(clip_h) if enable_clipping else None,
        mode=mode,
        packed=do_pack,
        wire_format=wire_format,
    )


def leaf_plan(
    path: str,
    ndim: int,
    *,
    w_bits: int = 4,
    k_percent: float = 50.0,
    clip_l: float = -8.0,
    clip_h: float = 23.0,
    mode: str = "sparqle",
    enable_clipping: bool = True,
    per_layer_lh: Optional[Dict[str, tuple]] = None,
    tile_k: int = 128,
    wire_format: str = "unpacked",
):
    """How :func:`quantize_model_params` serves the projection at ``path``.

    Returns ``(quantize, stacked)``: ``quantize`` maps one (K, N) or
    (E, K, N) float slice to its :class:`SparqleLinear`, and ``stacked``
    says the leaf carries a leading layer dim that is quantized one slice
    at a time and re-stacked. ``per_layer_lh`` optionally maps path
    prefixes to (l, h) pairs (the Algorithm-1 layerwise constants);
    unmatched paths use the global pair.
    """
    l, h = clip_l, clip_h
    if per_layer_lh:
        for pref, (pl_, ph_) in per_layer_lh.items():
            if path.startswith(pref):
                l, h = pl_, ph_
                break

    def quantize(w):
        return quantize_leaf(
            w, w_bits=w_bits, k_percent=k_percent, clip_l=l, clip_h=h,
            mode=mode, enable_clipping=enable_clipping, tile_k=tile_k,
            wire_format=wire_format)

    # routed-expert weights are (E,K,N)-batched; shared-expert weights
    # (w_shared_*) are plain 2D despite living in moe/
    name = path.rsplit("/", 1)[-1]
    is_expert = (("/moe/" in path or path.startswith("moe/"))
                 and "shared" not in name)
    # leaf ranks: 2 = plain (K,N); 3 = experts (E,K,N) when under a moe/
    # subtree else layer-stacked (L,K,N); 4 = layer-stacked experts
    # (L,E,K,N).
    if ndim == 2 or (ndim == 3 and is_expert):
        return quantize, False
    if ndim in (3, 4):
        return quantize, True
    raise ValueError(f"{path}: rank {ndim}")


def quantize_model_params(
    params: Dict[str, Any],
    **quant_kw,
) -> Dict[str, Any]:
    """Rewrite every projection leaf of a param tree into SPARQLe form.

    Keyword arguments are :func:`leaf_plan`'s (bits, clipping, tile and
    wire-format choices). ``wire_format='packed'`` serves every
    projection's activations through the packed sub-precision wire format.
    """

    def walk(tree, prefix=""):
        out = {}
        for k, v in tree.items():
            path = f"{prefix}/{k}" if prefix else k
            if isinstance(v, dict):
                out[k] = walk(v, path)
            elif is_quantizable(path, v):
                q1, stacked = leaf_plan(path, v.ndim, **quant_kw)
                if stacked:
                    sls = [q1(v[i]) for i in range(v.shape[0])]
                    out[k] = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs), *sls)
                else:
                    out[k] = q1(v)
            else:
                out[k] = v
        return out

    return walk(params)


def packed_projection_counts(params: Dict[str, Any]) -> Dict[str, int]:
    """Served projection slices by the weight path their dots take.

    ``nibble_planes``: packed leaves, whose dots read the two int4 planes
    (:func:`_int_dot`); ``unpacked``: int8 weights (odd K, w_bits > 4).
    A layer-stacked leaf counts once per layer slice, an expert leaf
    once (its experts are one batched dot).
    """
    counts = {"nibble_planes": 0, "unpacked": 0}

    def walk(tree, prefix=""):
        for k, v in tree.items():
            path = f"{prefix}/{k}" if prefix else k
            if isinstance(v, dict):
                walk(v, path)
            elif isinstance(v, SparqleLinear):
                _, stacked = leaf_plan(path, v.w.q.ndim)
                counts["nibble_planes" if v.packed else "unpacked"] += (
                    v.w.q.shape[0] if stacked else 1)

    walk(params)
    return counts
