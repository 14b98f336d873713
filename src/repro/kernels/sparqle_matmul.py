"""Pallas TPU kernel: SPARQLe dual-pass matmul with PBM tile skipping.

TPU adaptation of the paper's hybrid PE array (§3.3, DESIGN.md §2):

  * the accelerator's *dense LSB4 pass* is one MXU matmul per (bm,bk,bn)
    tile over the LSB4 plane;
  * the *sparse MSB4 pass* is a second MXU matmul over the MSB4 plane,
    predicated per K-tile with ``@pl.when(tile_pop > 0)`` — the TPU-granular
    equivalent of the paper's PBM-gated operand dispatch (a 128x128 systolic
    array cannot gate individual operands, so sub-precision sparsity is
    exploited at VMEM-tile granularity; the paper's column-wise clipping is
    what clusters MSB4 zeros into skippable tiles — see
    ``clipping.importance_mask_tile_aligned``);
  * shift-by-4 accumulation into the int32 accumulator = the paper's OFRF
    accumulation of left-shifted sparse partial sums;
  * per-token activation scales and per-channel weight scales applied at
    drain time (the paper's drain-path SFU requantization).

Two operand layouts share one kernel body (``_tile_body``), so they are
bit-exact by construction:

  * :func:`sparqle_matmul` — dense nibble planes in int8 containers
    (one byte per nibble; the debug/legacy layout);
  * :func:`sparqle_matmul_packed` — nibble planes packed two-per-byte
    (``core.packing.pack_nibbles``), unpacked in-VMEM right after the DMA.
    This is the wire-format hot path: the activation blocks the grid
    streams from HBM are half the bytes of the unpacked variant.

Int4 *weights* travel in int8 containers here: ``jnp.int4`` is not fully
supported by the CPU/interpret path used for validation. On real TPU the
MXU consumes int8 natively; weight packing is handled upstream
(``qlinear.pack_int4``) and unpacked before the kernel call.

Grid: (M/bm, N/bn, K/bk), K innermost (``arbitrary``), output-stationary
accumulator scratch in VMEM. ``tile_pop`` — the per-(M-tile, K-tile) PBM
population count from ``core.sparqle.tile_population`` — is delivered as a
(1,1) block (SMEM-resident scalar on real TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import unpack_nibbles
from repro.kernels import pallas_call


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128


def _tile_body(pop, lsb, msb_fn, w, acc_ref, *, msb_skip: bool = False):
    """Shared dual-pass accumulation for one (bm, bk, bn) tile.

    ``lsb`` is the UNPACKED (bm, bk) int8 LSB4 plane; ``msb_fn`` is a
    thunk producing the unpacked MSB4 plane — a thunk so the guarded
    branch below is what reads (and, for the packed layout, unpacks) the
    sparse plane: pop == 0 tiles skip that work entirely. Both entry
    kernels normalize their operand layout this way, which is what keeps
    the packed and unpacked paths bit-exact.

    ``msb_skip`` statically elides the sparse pass altogether: the traced
    program contains only the dense LSB4 matmul, so the result is the
    LSB4 plane's contribution alone — the 1-compute-round draft forward
    of the self-speculative decode path (vs 1 + (1 - s) rounds for the
    full hybrid pass, paper §3.3).
    """
    # ---- dense pass: LSB4 (always executes) ----
    acc_ref[...] += jax.lax.dot_general(
        lsb, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    if msb_skip:
        return

    # ---- sparse pass: MSB4, skipped when this (m,k) tile has no PBM bits
    @pl.when(pop > 0)
    def _sparse():
        acc_ref[...] += (
            jax.lax.dot_general(
                msb_fn(), w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            << 4)


def _drain(k, n_k, acc_ref, out_ref, ascale_ref, wscale_ref,
           acc_out: bool = False):
    @pl.when(k == n_k - 1)
    def _():
        if acc_out:
            # tensor-parallel drain: emit the raw merged int32 accumulator
            # (LSB + shifted MSB already summed). The caller psums it ONCE
            # across the model axis — int32 addition is associative, so
            # the reduced accumulator is bit-identical to a single-device
            # run — and applies the f32 rescale after the reduction.
            out_ref[...] = acc_ref[...]
        else:
            out_ref[...] = (
                acc_ref[...].astype(jnp.float32)
                * ascale_ref[...].astype(jnp.float32)
                * wscale_ref[...].astype(jnp.float32))


def _kernel(pop_ref, lsb_ref, msb_ref, w_ref, ascale_ref, wscale_ref,
            out_ref, acc_ref, *, n_k: int, acc_out: bool = False):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _tile_body(pop_ref[0, 0], lsb_ref[...].astype(jnp.int8),
               lambda: msb_ref[...].astype(jnp.int8),
               w_ref[...].astype(jnp.int8), acc_ref)
    _drain(k, n_k, acc_ref, out_ref, ascale_ref, wscale_ref, acc_out)


def _kernel_packed(pop_ref, lsbp_ref, msbp_ref, w_ref, ascale_ref,
                   wscale_ref, out_ref, acc_ref, *, n_k: int,
                   acc_out: bool = False):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # in-VMEM unpack of the half-width packed blocks (the DMA moved bk/2
    # bytes per row; the MXU still sees full (bm, bk) nibble planes) —
    # the codec's own unpack primitive, so kernel and wire layout cannot
    # drift apart; the MSB unpack happens inside the pop > 0 guard
    lsb = unpack_nibbles(lsbp_ref[...], signed=False)
    _tile_body(pop_ref[0, 0], lsb,
               lambda: unpack_nibbles(msbp_ref[...], signed=True),
               w_ref[...].astype(jnp.int8), acc_ref)
    _drain(k, n_k, acc_ref, out_ref, ascale_ref, wscale_ref, acc_out)


def _kernel_draft(lsb_ref, w_ref, ascale_ref, wscale_ref, out_ref,
                  acc_ref, *, n_k: int, acc_out: bool = False):
    """LSB4-only draft entry: the MSB plane and the PBM populations are
    not operands at all, so the grid streams HALF the (unpacked)
    activation bytes — the wire saving the cost model credits the draft
    (``costmodel.linear_cost(lsb_only=True)``), not just elided MACs."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _tile_body(0, lsb_ref[...].astype(jnp.int8), None,
               w_ref[...].astype(jnp.int8), acc_ref, msb_skip=True)
    _drain(k, n_k, acc_ref, out_ref, ascale_ref, wscale_ref, acc_out)


def _kernel_packed_draft(lsbp_ref, w_ref, ascale_ref, wscale_ref, out_ref,
                         acc_ref, *, n_k: int, acc_out: bool = False):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lsb = unpack_nibbles(lsbp_ref[...], signed=False)
    _tile_body(0, lsb, None, w_ref[...].astype(jnp.int8), acc_ref,
               msb_skip=True)
    _drain(k, n_k, acc_ref, out_ref, ascale_ref, wscale_ref, acc_out)


def _call(kernel, grid, act_specs, act_args, w, act_scale, w_scale,
          tile_pop, m, n, bm, bn, bk, n_k, msb_skip=False,
          draft_kernel=None, acc_out=False):
    if msb_skip:
        # draft dispatch: ONLY the LSB plane is an operand — the MSB
        # plane and PBM populations never enter the grid's DMA stream
        kernel, in_specs = draft_kernel, [act_specs[0]]
        args = (act_args[0], w, act_scale, w_scale)
    else:
        in_specs = [
            pl.BlockSpec((1, 1), lambda i, j, kk: (i, kk)),        # tile_pop
            *act_specs,                                            # lsb, msb
        ]
        args = (tile_pop, *act_args, w, act_scale, w_scale)
    return pallas_call(
        functools.partial(kernel, n_k=n_k, acc_out=acc_out),
        name="sparqle_matmul_lsb" if msb_skip else "sparqle_matmul",
        grid=grid,
        in_specs=[
            *in_specs,
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),      # w
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),        # act_scale
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),        # w_scale
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (m, n), jnp.int32 if acc_out else jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "msb_skip", "acc_out"))
def sparqle_matmul(
    lsb4: jax.Array,       # (M, K) int8 in [0, 15]
    msb4: jax.Array,       # (M, K) int8 in [-8, 7]
    tile_pop: jax.Array,   # (M/bm, K/bk) int32 PBM population per tile
    w: jax.Array,          # (K, N) int8 (int4 payload)
    act_scale: jax.Array,  # (M, 1) f32
    w_scale: jax.Array,    # (1, N) f32
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    msb_skip: bool = False,
    acc_out: bool = False,
) -> jax.Array:
    """``acc_out`` emits the raw merged int32 accumulator instead of the
    rescaled f32 output (scale operands are ignored) — the operand a
    K-sharded tensor-parallel caller reduces with a single psum before
    applying the drain-path rescale (``ops.sparqle_linear_sharded``)."""
    m, k = lsb4.shape
    k2, n = w.shape
    assert k == k2, (lsb4.shape, w.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"operands must be tile-aligned: {(m, k, n)} vs {(bm, bk, bn)}")
    assert tile_pop.shape == (m // bm, k // bk), tile_pop.shape

    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    act_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),      # lsb4
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),      # msb4
    ]
    return _call(_kernel, grid, act_specs, (lsb4, msb4), w, act_scale,
                 w_scale, tile_pop, m, n, bm, bn, bk, n_k,
                 msb_skip=msb_skip, draft_kernel=_kernel_draft,
                 acc_out=acc_out)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "msb_skip", "acc_out"))
def sparqle_matmul_packed(
    lsb4_packed: jax.Array,  # (M, K/2) int8 — two LSB nibbles per byte
    msb4_packed: jax.Array,  # (M, K/2) int8 — two MSB nibbles per byte
    tile_pop: jax.Array,     # (M/bm, K/bk) int32 PBM population per tile
    w: jax.Array,            # (K, N) int8 (int4 payload)
    act_scale: jax.Array,    # (M, 1) f32
    w_scale: jax.Array,      # (1, N) f32
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    msb_skip: bool = False,
    acc_out: bool = False,
) -> jax.Array:
    """Wire-format variant of :func:`sparqle_matmul`.

    Activation planes arrive packed two-per-byte (half the DMA bytes) and
    are unpacked in VMEM; the accumulation body is shared, so outputs are
    bit-exact vs the unpacked kernel on identical logical operands.

    ``msb_skip`` dispatches the LSB4-only draft kernel: the ``msb4`` /
    ``tile_pop`` arguments are accepted for signature parity but are NOT
    operands of the pallas_call — the draft grid streams only the LSB
    plane plus weights/scales. ``acc_out`` as in :func:`sparqle_matmul`.
    """
    m, kh = lsb4_packed.shape
    k = kh * 2
    k2, n = w.shape
    assert k == k2, (lsb4_packed.shape, w.shape)
    assert msb4_packed.shape == (m, kh), msb4_packed.shape
    assert bk % 2 == 0, bk
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"operands must be tile-aligned: {(m, k, n)} vs {(bm, bk, bn)}")
    assert tile_pop.shape == (m // bm, k // bk), tile_pop.shape

    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    hbk = bk // 2
    act_specs = [
        pl.BlockSpec((bm, hbk), lambda i, j, kk: (i, kk)),     # lsb4 packed
        pl.BlockSpec((bm, hbk), lambda i, j, kk: (i, kk)),     # msb4 packed
    ]
    return _call(_kernel_packed, grid, act_specs,
                 (lsb4_packed, msb4_packed), w, act_scale, w_scale,
                 tile_pop, m, n, bm, bn, bk, n_k,
                 msb_skip=msb_skip, draft_kernel=_kernel_packed_draft,
                 acc_out=acc_out)
