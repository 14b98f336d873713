"""Pallas TPU kernel: fused drain-path SPARQLe encoder.

The paper's drain phase (§3.3) writes linear-layer outputs back to SRAM
*already in SPARQLe format* (MSB4/LSB4 splitters + sparse encoder beyond the
drain buffer). The TPU-side analogue fuses output quantization with the
LSB4/MSB4/PBM decomposition in one elementwise VPU kernel, so the next layer
reads decomposed planes without a decompress-compute-recompress round trip.

Outputs the per-(bm, bk) tile PBM population counts as well — the metadata
the matmul kernel's ``@pl.when`` skipping consumes.

Two emit layouts:

  * :func:`sparqle_encode` — dense int8 nibble planes (debug/legacy);
  * :func:`sparqle_encode_packed` — the wire-format planes the packed
    matmul consumes: LSB4/MSB4 packed two nibbles per byte and the PBM
    folded into uint32 bitmask words (``core/packing.py`` layout), so the
    drain stream is the compressed format, not dense int8.

Per-token scales are clamped away from zero/denormal before the divide:
an all-zero token (padded prefill rows writing through the null page)
produces ``scale == 0`` and ``x / 0`` would round inf/nan into ±127
garbage; the clamp makes such rows encode exactly to zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.packing import PBM_WORD_BITS, pack_nibbles, pack_pbm
from repro.kernels import pallas_call

# Smallest normal f32: anything below (zero or denormal scales) is treated
# as a degenerate all-zero token and divided by 1 instead.
_MIN_SCALE = float(jnp.finfo(jnp.float32).tiny)


def _quantize(x_ref, scale_ref):
    s = scale_ref[...].astype(jnp.float32)
    s = jnp.where(jnp.abs(s) < _MIN_SCALE, 1.0, s)
    x = x_ref[...].astype(jnp.float32) / s
    q = jnp.clip(jnp.round(x), -128, 127).astype(jnp.int8)
    msb = jnp.right_shift(q, 4)
    lsb = jnp.bitwise_and(q, 0xF)
    return lsb, msb, msb != 0


def _kernel(x_ref, scale_ref, lsb_ref, msb_ref, pbm_ref, pop_ref):
    lsb, msb, pbm = _quantize(x_ref, scale_ref)
    lsb_ref[...] = lsb.astype(jnp.int8)
    msb_ref[...] = msb.astype(jnp.int8)
    pbm_ref[...] = pbm
    pop_ref[0, 0] = jnp.sum(pbm.astype(jnp.int32))


def _kernel_packed(x_ref, scale_ref, lsb_ref, msb_ref, pbm_ref, pop_ref):
    # emit through the codec's own primitives, so the drain stream and
    # the core/packing.py wire layout cannot drift apart
    lsb, msb, pbm = _quantize(x_ref, scale_ref)
    lsb_ref[...] = pack_nibbles(lsb)
    msb_ref[...] = pack_nibbles(msb)
    pbm_ref[...] = pack_pbm(pbm)
    pop_ref[0, 0] = jnp.sum(pbm.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("bm", "bk"))
def sparqle_encode(
    x: jax.Array,       # (M, K) f32/bf16 pre-quantization outputs
    scale: jax.Array,   # (M, 1) f32 per-token scales
    *,
    bm: int = 128,
    bk: int = 128,
):
    """Returns (lsb4, msb4, pbm, tile_pop) with tile_pop (M/bm, K/bk)."""
    m, k = x.shape
    assert m % bm == 0 and k % bk == 0, (x.shape, bm, bk)
    grid = (m // bm, k // bk)
    return pallas_call(
        _kernel,
        name="sparqle_encode",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.int8),
            jax.ShapeDtypeStruct((m, k), jnp.int8),
            jax.ShapeDtypeStruct((m, k), jnp.bool_),
            jax.ShapeDtypeStruct((m // bm, k // bk), jnp.int32),
        ],
    )(x, scale)


@functools.partial(jax.jit, static_argnames=("bm", "bk"))
def sparqle_encode_packed(
    x: jax.Array,       # (M, K) f32/bf16 pre-quantization outputs
    scale: jax.Array,   # (M, 1) f32 per-token scales
    *,
    bm: int = 128,
    bk: int = 128,
):
    """Wire-format drain: (lsb4_packed (M, K/2), msb4_packed (M, K/2),
    pbm_words (M, K/32), tile_pop (M/bm, K/bk)).

    ``bk`` must be a multiple of 32 so PBM words never straddle tiles.
    Bit-exact with ``core.packing`` on the quantized values: unpacking the
    emitted planes reproduces ``sparqle_encode``'s planes exactly.
    """
    m, k = x.shape
    assert m % bm == 0 and k % bk == 0, (x.shape, bm, bk)
    assert bk % PBM_WORD_BITS == 0, bk
    grid = (m // bm, k // bk)
    hbk = bk // 2
    nw = bk // PBM_WORD_BITS
    return pallas_call(
        _kernel_packed,
        name="sparqle_encode_packed",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, hbk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, hbk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, nw), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k // 2), jnp.int8),
            jax.ShapeDtypeStruct((m, k // 2), jnp.int8),
            jax.ShapeDtypeStruct((m, k // PBM_WORD_BITS), jnp.uint32),
            jax.ShapeDtypeStruct((m // bm, k // bk), jnp.int32),
        ],
    )(x, scale)
