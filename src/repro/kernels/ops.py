"""Public jit'd wrappers over the Pallas kernels.

``sparqle_linear`` is the framework's quantized-linear entry point. It hides
tile padding, backend selection and the encode step:

  * ``backend='pallas'``  — Pallas kernels (compiled on TPU, interpreted on
    CPU — ``kernels.pallas_call`` decides from the lowering platform);
  * ``backend='xla'``     — the pure-XLA dual-pass path
    (``core.sparse_matmul``), used inside pjit'd distributed graphs.

Both backends implement the identical numerical contract (kernels/ref.py).

``sparqle_linear_sharded`` runs the same kernels under ``shard_map`` with
the weight partitioned on a mesh axis — column-parallel (output channels
sharded; exact by construction) or row-parallel (K sharded; global
per-token scale via an exact pmax, then ONE int32 psum of the merged
dual-pass accumulator before the drain-path rescale, so the result is
bit-identical to the unsharded call). Both wire formats and the
``msb_skip`` draft dispatch shard the same way.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.packing import pack_nibbles
from repro.core.quantize import QuantizedTensor, quantize_activations
from repro.core.sparqle import SparqleActivation, encode, tile_population
from repro.kernels.quant_matmul import quant_matmul
from repro.kernels.sparqle_matmul import (
    DEFAULT_BK, DEFAULT_BM, DEFAULT_BN, sparqle_matmul,
    sparqle_matmul_packed)


def _pad_to(x: jax.Array, mult: tuple) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mult)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def _padded_kernel_call(q, w_q, a_scale, w_scale, *, wire_format, msb_skip,
                        bm, bn, bk, acc_out=False):
    """Encode int8 activations, tile-pad, dispatch the kernel, un-pad.

    Shared by the single-device and shard_map'd entry points, so sharded
    shards run the exact per-tile computation of the unsharded kernel
    (padding contributes zero to the int32 accumulator either way).
    """
    m, _ = q.shape
    n_out = w_q.shape[-1]
    act = encode(q, 1.0)
    lsb = _pad_to(act.lsb4, (bm, bk))
    msb = _pad_to(act.msb4, (bm, bk))
    pbm = _pad_to(act.pbm, (bm, bk))
    wq = _pad_to(w_q.astype(jnp.int8), (bk, bn))
    asc = _pad_to(a_scale.reshape(-1, 1).astype(jnp.float32), (bm, 1))
    wsc = _pad_to(w_scale.reshape(1, -1).astype(jnp.float32), (1, bn))
    pop = tile_population(pbm, bm, bk)
    if wire_format == "packed":
        out = sparqle_matmul_packed(
            pack_nibbles(lsb), pack_nibbles(msb), pop, wq, asc, wsc,
            bm=bm, bn=bn, bk=bk, msb_skip=msb_skip,
            acc_out=acc_out)
    else:
        out = sparqle_matmul(lsb, msb, pop, wq, asc, wsc,
                             bm=bm, bn=bn, bk=bk,
                             msb_skip=msb_skip, acc_out=acc_out)
    return out[:m, :n_out]


def sparqle_linear(
    x: jax.Array,
    w: QuantizedTensor,
    *,
    col_mask: Optional[jax.Array] = None,
    clip_l: Optional[jax.Array] = None,
    clip_h: Optional[jax.Array] = None,
    backend: str = "pallas",
    wire_format: str = "unpacked",
    msb_skip: bool = False,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
) -> jax.Array:
    """Quantize -> (clip) -> decompose -> dual-pass matmul. x: (..., K).

    ``wire_format='packed'`` streams the activation nibble planes in the
    two-per-byte wire layout (``sparqle_matmul_packed`` unpacks in-VMEM);
    bit-exact vs ``'unpacked'`` — same kernel body, half the DMA bytes.
    ``msb_skip`` runs the 1-round LSB4-only draft forward (the sparse MSB
    pass is statically elided from the kernel): the output is what you
    would get dequantizing the LSB plane alone.
    """
    from repro.core.clipping import apply_clipping

    orig = x.shape
    k_in = orig[-1]
    n_out = w.q.shape[-1]
    x2 = x.reshape(-1, k_in)

    qa = quantize_activations(x2, bits=8, per_token=True)
    q = qa.q
    if col_mask is not None and clip_l is not None:
        q = apply_clipping(q, col_mask, clip_l, clip_h)

    assert wire_format in ("unpacked", "packed"), wire_format
    if backend == "xla":
        if wire_format == "packed":
            # the wire layout, not the dense int8 tensor, feeds the matmul
            from repro.core.packing import encode_packed, unpack_planes
            act = unpack_planes(encode_packed(q))
        else:
            act = encode(q, 1.0)
        from repro.core.sparse_matmul import sparqle_matmul_xla
        msb = jnp.zeros_like(act.msb4) if msb_skip else act.msb4
        pbm = jnp.zeros_like(act.pbm) if msb_skip else act.pbm
        out = sparqle_matmul_xla(
            SparqleActivation(act.lsb4, msb, pbm, jnp.float32(1.0)),
            QuantizedTensor(w.q, jnp.ones_like(w.scale), w.zero, w.bits))
        out = out * qa.scale * w.scale.reshape(1, -1)
        return out.reshape(*orig[:-1], n_out).astype(x.dtype)

    # pallas path: pad everything to tile multiples
    out = _padded_kernel_call(q, w.q, qa.scale, w.scale,
                              wire_format=wire_format, msb_skip=msb_skip,
                              bm=bm, bn=bn, bk=bk)
    return out.reshape(*orig[:-1], n_out).astype(x.dtype)


def sparqle_linear_sharded(
    x: jax.Array,
    w: QuantizedTensor,
    *,
    mesh: Mesh,
    axis: str = "model",
    partition: str = "col",
    col_mask: Optional[jax.Array] = None,
    clip_l: Optional[jax.Array] = None,
    clip_h: Optional[jax.Array] = None,
    wire_format: str = "unpacked",
    msb_skip: bool = False,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
) -> jax.Array:
    """:func:`sparqle_linear` with the weight partitioned on a mesh axis.

    ``partition='col'`` shards the output channels: every shard runs the
    unsharded kernel on its (K, N/ways) slice, and the assembled output is
    the exact concatenation — bit-identical to the unsharded call.

    ``partition='row'`` shards K (activations and weight rows): the
    per-token scale comes from an exact ``pmax`` of local row maxima, the
    kernel drains its raw merged int32 accumulator (``acc_out=True`` —
    LSB and shifted-MSB partials already summed per shard), ONE ``psum``
    reduces it across the axis, and the f32 rescale runs on the reduced
    accumulator — also bit-identical, because int32 addition is
    associative. Both wire formats and the ``msb_skip`` draft dispatch
    shard identically. The replicated output is returned.
    """
    from repro.core.clipping import apply_clipping

    assert partition in ("col", "row"), partition
    orig = x.shape
    x2 = x.reshape(-1, orig[-1])
    n_out = w.q.shape[-1]
    has_clip = col_mask is not None and clip_l is not None

    if partition == "col":
        def body(x_l, wq_l, wsc_l, mask):
            qa = quantize_activations(x_l, bits=8, per_token=True)
            q = qa.q
            if has_clip:
                q = apply_clipping(q, mask, clip_l, clip_h)
            return _padded_kernel_call(
                q, wq_l, qa.scale, wsc_l, wire_format=wire_format,
                msb_skip=msb_skip, bm=bm, bn=bn, bk=bk)

        in_specs = (P(), P(None, axis), P(None, axis),
                    P() if has_clip else None)
        out_specs = P(None, axis)
    else:
        def body(x_l, wq_l, wsc, mask):
            amax = jax.lax.pmax(
                jnp.max(jnp.abs(x_l), axis=-1, keepdims=True), axis)
            qa = quantize_activations(x_l, bits=8, per_token=True,
                                      amax=amax)
            q = qa.q
            if has_clip:
                q = apply_clipping(q, mask, clip_l, clip_h)
            acc = _padded_kernel_call(
                q, wq_l, qa.scale, wsc, wire_format=wire_format,
                msb_skip=msb_skip, bm=bm, bn=bn, bk=bk,
                acc_out=True)
            acc = jax.lax.psum(acc, axis)        # ONE reduction, int32
            return (acc.astype(jnp.float32)
                    * qa.scale.reshape(-1, 1).astype(jnp.float32)
                    * wsc.reshape(1, -1).astype(jnp.float32))

        in_specs = (P(None, axis), P(axis, None), P(),
                    P(axis) if has_clip else None)
        out_specs = P()

    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out = fn(x2, w.q.astype(jnp.int8), w.scale.reshape(1, -1),
             col_mask)
    return out.reshape(*orig[:-1], n_out).astype(x.dtype)


def dense_quant_linear(
    x: jax.Array,
    w: QuantizedTensor,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
) -> jax.Array:
    """Baseline dense W4A8 linear (no SPARQLe decomposition)."""
    orig = x.shape
    x2 = x.reshape(-1, orig[-1])
    m = x2.shape[0]
    n_out = w.q.shape[-1]
    qa = quantize_activations(x2, bits=8, per_token=True)
    a = _pad_to(qa.q, (bm, bk))
    wq = _pad_to(w.q.astype(jnp.int8), (bk, bn))
    asc = _pad_to(qa.scale.reshape(-1, 1).astype(jnp.float32), (bm, 1))
    wsc = _pad_to(w.scale.reshape(1, -1).astype(jnp.float32), (1, bn))
    out = quant_matmul(a, wq, asc, wsc, bm=bm, bn=bn, bk=bk)
    out = out[:m, :n_out]
    return out.reshape(*orig[:-1], n_out).astype(x.dtype)
