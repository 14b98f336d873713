"""Pallas TPU kernel: fused decode attention over a packed-KV4 cache.

The serving hot loop after the §Perf tuning is decode attention streaming
the quantized KV cache (EXPERIMENTS.md Cell A: memory-bound at the
weights+cache stream). This kernel keeps the cache in its wire format end
to end: int4 nibbles packed two-per-byte are DMA'd into VMEM, unpacked and
dequantized in-register, and consumed by a blockwise online-softmax
attention — the cache never exists in HBM at bf16 width, which is what
halves the dominant decode stream (the XLA path materializes the
dequantized cache between ops unless fusion cooperates; the kernel makes
the fusion structural).

Layout: one grid step handles one sequence and one cache block of ``bs``
tokens (innermost grid axis, 'arbitrary'). The block spans every KV head
— (1, bs, KVH, hd//2) — so its last two dims equal the array's, which is
what the TPU's (8, 128) tiling admits; the body walks the heads in a
static loop, each head running the same (G, hd) x (bs, hd) online-softmax
update on its own running max/denominator/accumulator in VMEM scratch
(the flash-decoding structure re-tiled for VMEM). A head's arithmetic
never touches another head's values, so its bits do not depend on how
many heads share the block (KV-head sharding is exact). Positions and
block tables are scalar-prefetched into SMEM.

The grid spans the whole cache axis (the block table's width), but only
the first :func:`context_pages` blocks of a sequence hold positions
``<= pos``. A block past them is wholly masked: its scores are all
``NEG_INF``, so its softmax weights are 0 and the running max,
denominator and accumulator come out of it unchanged. The scan therefore
skips such a block: the shared body does no work for it, and the paged
index maps name the sequence's last context page in its place, so the
pipeline sees an unchanged block index and issues no copy. The skip is
exact — a block holding ``pos`` is never skipped, and block 0 always
holds position 0 — and whatever a skipped block holds (even NaN) cannot
reach the output.

Three entry points share the same kernel body (``_scan_step``):

  * :func:`kv4_decode_attention`        — contiguous (B, S, KVH, …) cache;
  * :func:`kv4_paged_decode_attention`  — a paged pool (P, page, KVH, …)
    walked through a per-sequence block table (scalar-prefetched so the
    page index feeds the DMA index map). Because the body, block shapes
    and accumulation order are identical, the paged variant is bit-exact
    against the contiguous one when the pages tile the same cache.
  * :func:`kv4_paged_verify_attention`  — the multi-token (q > 1) variant
    for self-speculative verification: T window tokens per sequence, each
    causally masked to its own absolute position ``pos + t``. The window
    axis is a *grid* dimension, so every (b, t) cell runs the exact
    single-token computation (same block shapes, same dot shapes, same
    accumulation order) — bit-exact against a loop of T single-token
    paged decode calls by construction.

A fourth entry point, :func:`kv_tiered_paged_decode_attention`, extends
the paged decode variant with the KV2 precision-ladder read path: a
second scalar-prefetched table carries a per-page tier id, the index maps
route each grid step's DMA to the KV4 or KV2 slab accordingly, and the
body selects the dequantized block by tier — bit-exact against
``kv4_paged_decode_attention`` whenever every page is tier 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call

NEG_INF = -2.0e38


def context_pages(pos, page_size: int):
    """How many cache blocks of ``page_size`` tokens hold the positions
    ``0..pos``: the blocks the scan attends over. Every block from this
    one on lies wholly past ``pos`` and is skipped. Works on Python ints,
    NumPy arrays and traced scalars alike, so the kernels' index maps and
    the engine's page counts share the one rule."""
    return pos // page_size + 1


def _context_col(isb, pos, page_size: int):
    """Block-table column a grid step at column ``isb`` reads: ``isb``
    itself, or the sequence's last context page once ``isb`` is past it
    (an unchanged block index, so the pipeline copies nothing)."""
    return jnp.minimum(isb, context_pages(pos, page_size) - 1)


def _dequant(q_ref, s_ref, h: int, width: int) -> jax.Array:
    """KV head ``h`` of a packed cache block -> (bs, hd) f32.

    ``q_ref`` is a (1, bs, KVH, hd*width//8) int8 block whose bytes hold
    ``8 // width`` two's-complement fields, lowest field first; ``s_ref``
    the (1, bs, KVH) per-token-head scales. Fields are widened to int32
    before the shifts and the interleave (Mosaic lays int32 out natively).
    """
    x = q_ref[0, :, h, :].astype(jnp.int32)
    fields = [jnp.right_shift(jnp.left_shift(x, 32 - width * (f + 1)),
                              32 - width)
              for f in range(8 // width)]
    x_int = jnp.stack(fields, axis=-1).reshape(x.shape[0], -1)
    return x_int.astype(jnp.float32) * s_ref[0, :, pl.ds(h, 1)]


def _scan_step(pos, s_idx, q_of, kv_of, out_ref, m_ref, l_ref, acc_ref,
               *, n_s: int, bs: int, scale: float):
    """One cache-block step of the online-softmax scan, for every KV head.

    ``pos`` is the query's absolute position (a scalar) and ``s_idx`` the
    block's place along the cache axis; ``q_of(h)`` gives head ``h``'s
    (G, hd) query rows and ``kv_of(h)`` its dequantized (bs, hd) k and v.
    Every entry point maps its own grid and layout onto these, so the f32
    computation (and therefore the bits) is identical across them. The
    init and the drain run at every sequence's first and last step; the
    attention between them only for blocks that hold a position <= pos."""
    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a block wholly past pos would leave m, l and acc bit for bit as
    # they are (all scores NEG_INF: p = 0, corr = 1), so it is skipped
    @pl.when(s_idx < context_pages(pos, bs))
    def _attend():
        # causal validity: absolute cache position <= pos
        j = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        for h in range(acc_ref.shape[0]):
            k, v = kv_of(h)
            q = q_of(h).astype(jnp.float32)               # (G, hd)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(j <= pos, s, NEG_INF)           # (G, bs)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(s_idx == n_s - 1)
    def _drain():
        out_ref[...] = (acc_ref[...] /
                        jnp.maximum(l_ref[...], 1e-30)).astype(
                            out_ref.dtype).reshape(out_ref.shape)


def _kv4(kq_ref, ks_ref, vq_ref, vs_ref):
    return lambda h: (_dequant(kq_ref, ks_ref, h, 4),
                      _dequant(vq_ref, vs_ref, h, 4))


def _scratch(kvh: int, g: int, hd: int):
    return [
        pltpu.VMEM((kvh, g, 1), jnp.float32),    # running max
        pltpu.VMEM((kvh, g, 1), jnp.float32),    # running denominator
        pltpu.VMEM((kvh, g, hd), jnp.float32),   # output accumulator
    ]


def _kernel(pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, out_ref,
            m_ref, l_ref, acc_ref, *, n_s: int, bs: int, scale: float):
    _scan_step(pos_ref[pl.program_id(0)], pl.program_id(1),
               lambda h: q_ref[0, h], _kv4(kq_ref, ks_ref, vq_ref, vs_ref),
               out_ref, m_ref, l_ref, acc_ref, n_s=n_s, bs=bs, scale=scale)


@functools.partial(jax.jit, static_argnames=("bs",))
def kv4_decode_attention(
    q: jax.Array,       # (B, KVH, G, hd) — grouped query heads
    k_q: jax.Array,     # (B, S, KVH, hd//2) int8, packed nibbles
    k_s: jax.Array,     # (B, S, KVH) f32 per-token-head scales
    v_q: jax.Array,     # (B, S, KVH, hd//2) int8
    v_s: jax.Array,     # (B, S, KVH) f32
    pos: jax.Array,     # (B,) int32 — current position (inclusive)
    *,
    bs: int = 512,
) -> jax.Array:
    """Returns (B, KVH, G, hd) attention output. Cache stays packed-int4
    in HBM; unpack+dequant are fused into the attention block scan."""
    b, kvh, g, hd = q.shape
    _, s, _, hdp = k_q.shape
    assert hdp * 2 == hd, (hd, hdp)
    assert s % bs == 0, (s, bs)
    n_s = s // bs

    kv = pl.BlockSpec((1, bs, kvh, hdp), lambda ib, isb, p: (ib, isb, 0, 0))
    sc = pl.BlockSpec((1, bs, kvh), lambda ib, isb, p: (ib, isb, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_s),
        in_specs=[
            pl.BlockSpec((1, kvh, g, hd), lambda ib, isb, p: (ib, 0, 0, 0)),
            kv, sc, kv, sc,
        ],
        out_specs=pl.BlockSpec((1, kvh, g, hd),
                               lambda ib, isb, p: (ib, 0, 0, 0)),
        scratch_shapes=_scratch(kvh, g, hd),
    )
    return pallas_call(
        functools.partial(_kernel, n_s=n_s, bs=bs, scale=hd ** -0.5),
        name="kv4_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(pos, q, k_q, k_s, v_q, v_s)


def _page_specs(ps: int, kvh: int, width: int, page_of):
    """Block specs of one page's packed values and scales, all KV heads;
    ``page_of(*grid_idx, *prefetch_refs)`` names the physical page."""
    kv = pl.BlockSpec((1, ps, kvh, width),
                      lambda *a: (page_of(*a), 0, 0, 0))
    sc = pl.BlockSpec((1, ps, kvh), lambda *a: (page_of(*a), 0, 0))
    return [kv, sc, kv, sc]


def _paged_kernel(bt_ref, pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                  out_ref, m_ref, l_ref, acc_ref, *, n_s, bs, scale):
    # the block table only drives the index maps; the body is the shared
    # flash-decoding step (bit-exact with the contiguous layout)
    del bt_ref
    _kernel(pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, out_ref,
            m_ref, l_ref, acc_ref, n_s=n_s, bs=bs, scale=scale)


@jax.jit
def kv4_paged_decode_attention(
    q: jax.Array,             # (B, KVH, G, hd) — grouped query heads
    k_pages: jax.Array,       # (P, ps, KVH, hd//2) int8, packed nibbles
    k_scale_pages: jax.Array, # (P, ps, KVH) f32 per-token-head scales
    v_pages: jax.Array,       # (P, ps, KVH, hd//2) int8
    v_scale_pages: jax.Array, # (P, ps, KVH) f32
    block_tables: jax.Array,  # (B, Pmax) int32 — seq-order page ids
    pos: jax.Array,           # (B,) int32 — current position (inclusive)
) -> jax.Array:
    """Decode attention over a *paged* packed-KV4 pool.

    ``block_tables[b, i]`` names the physical page holding sequence ``b``'s
    tokens ``[i*ps, (i+1)*ps)``. Entries past the sequence's last context
    page (:func:`context_pages`) may point anywhere, and are never read:
    those grid steps re-name the last context page, so no copy is issued,
    and the body skips them. The table is scalar-prefetched (flattened, so
    SMEM holds no padded 2-D array) and page ids feed the DMA index maps —
    the pool is read only at a sequence's context pages, one page per
    grid step, in wire format.
    """
    b, kvh, g, hd = q.shape
    _, ps, _, hdp = k_pages.shape
    _, n_s = block_tables.shape
    assert hdp * 2 == hd, (hd, hdp)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_s),
        in_specs=[
            pl.BlockSpec((1, kvh, g, hd),
                         lambda ib, isb, bt, p: (ib, 0, 0, 0)),
            *_page_specs(ps, kvh, hdp, lambda ib, isb, bt, p: bt[
                ib * n_s + _context_col(isb, p[ib], ps)]),
        ],
        out_specs=pl.BlockSpec((1, kvh, g, hd),
                               lambda ib, isb, bt, p: (ib, 0, 0, 0)),
        scratch_shapes=_scratch(kvh, g, hd),
    )
    return pallas_call(
        functools.partial(_paged_kernel, n_s=n_s, bs=ps, scale=hd ** -0.5),
        name="kv4_paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(block_tables.reshape(-1), pos, q, k_pages, k_scale_pages, v_pages,
      v_scale_pages)


def _paged_verify_kernel(bt_ref, pos_ref, q_ref, kq_ref, ks_ref, vq_ref,
                         vs_ref, out_ref, m_ref, l_ref, acc_ref, *, n_s,
                         bs, scale):
    # grid = (B, T, n_s): window token t's query position is pos + t;
    # everything else is the shared single-token flash step, so cell
    # (b, t) computes exactly what a single-token decode at pos + t
    # would (bit-exact vs a loop of kv4_paged_decode_attention calls)
    del bt_ref
    _scan_step(pos_ref[pl.program_id(0)] + pl.program_id(1),
               pl.program_id(2), lambda h: q_ref[0, 0, h],
               _kv4(kq_ref, ks_ref, vq_ref, vs_ref), out_ref, m_ref, l_ref,
               acc_ref, n_s=n_s, bs=bs, scale=scale)


@jax.jit
def kv4_paged_verify_attention(
    q: jax.Array,             # (B, T, KVH, G, hd) — T window tokens/seq
    k_pages: jax.Array,       # (P, ps, KVH, hd//2) int8, packed nibbles
    k_scale_pages: jax.Array, # (P, ps, KVH) f32 per-token-head scales
    v_pages: jax.Array,       # (P, ps, KVH, hd//2) int8
    v_scale_pages: jax.Array, # (P, ps, KVH) f32
    block_tables: jax.Array,  # (B, Pmax) int32 — seq-order page ids
    pos: jax.Array,           # (B,) int32 — position of window token 0
) -> jax.Array:
    """Multi-token decode attention for speculative verification.

    Scores a whole draft window in one batched call: window token ``t``
    of sequence ``b`` sits at absolute position ``pos[b] + t`` and
    attends to cache positions ``<= pos[b] + t`` (so it sees the other
    window tokens' K/V — the caller writes the window's K/V into the
    pages *before* this call — but never its own future). Returns
    (B, T, KVH, G, hd).

    The window axis is a grid dimension, not a wider query block: each
    (b, t) grid cell replays the single-token kernel body with the same
    block and dot shapes, which makes the output bit-exact against T
    sequential :func:`kv4_paged_decode_attention` calls.
    """
    b, t, kvh, g, hd = q.shape
    _, ps, _, hdp = k_pages.shape
    _, n_s = block_tables.shape
    assert hdp * 2 == hd, (hd, hdp)

    def q_map(ib, it, isb, bt, p):
        return (ib, it, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, t, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, kvh, g, hd), q_map),
            *_page_specs(ps, kvh, hdp, lambda ib, it, isb, bt, p: bt[
                ib * n_s + _context_col(isb, p[ib] + it, ps)]),
        ],
        out_specs=pl.BlockSpec((1, 1, kvh, g, hd), q_map),
        scratch_shapes=_scratch(kvh, g, hd),
    )
    return pallas_call(
        functools.partial(_paged_verify_kernel, n_s=n_s, bs=ps,
                          scale=hd ** -0.5),
        name="kv4_paged_verify_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, kvh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(block_tables.reshape(-1), pos, q, k_pages, k_scale_pages, v_pages,
      v_scale_pages)


def _tiered_paged_kernel(bt_ref, tt_ref, pos_ref, q_ref, kq_ref, ks_ref,
                         vq_ref, vs_ref, k2q_ref, k2s_ref, v2q_ref, v2s_ref,
                         out_ref, m_ref, l_ref, acc_ref, *, n_s, bs, scale):
    # per-page tier routing: the index maps already DMA'd the right slab
    # block (the other slab's block is its null page); the body dequantizes
    # both candidates and selects by the prefetched tier id. On a tier-0
    # page the selected f32 values are elementwise identical to what the
    # KV4 kernel computes, so the shared scan step produces identical bits.
    del bt_ref
    ib, isb = pl.program_id(0), pl.program_id(1)
    demoted = tt_ref[ib * n_s + isb] == 1

    def kv_of(h):
        k = jnp.where(demoted, _dequant(k2q_ref, k2s_ref, h, 2),
                      _dequant(kq_ref, ks_ref, h, 4))
        v = jnp.where(demoted, _dequant(v2q_ref, v2s_ref, h, 2),
                      _dequant(vq_ref, vs_ref, h, 4))
        return k, v

    _scan_step(pos_ref[ib], isb, lambda h: q_ref[0, h], kv_of, out_ref,
               m_ref, l_ref, acc_ref, n_s=n_s, bs=bs, scale=scale)


@jax.jit
def kv_tiered_paged_decode_attention(
    q: jax.Array,              # (B, KVH, G, hd) — grouped query heads
    k_pages: jax.Array,        # (P, ps, KVH, hd//2) int8, packed nibbles
    k_scale_pages: jax.Array,  # (P, ps, KVH) f32 per-token-head scales
    v_pages: jax.Array,        # (P, ps, KVH, hd//2) int8
    v_scale_pages: jax.Array,  # (P, ps, KVH) f32
    k2_pages: jax.Array,       # (P2, ps, KVH, hd//4) int8, 2-bit fields
    k2_scale_pages: jax.Array,  # (P2, ps, KVH) f32
    v2_pages: jax.Array,       # (P2, ps, KVH, hd//4) int8
    v2_scale_pages: jax.Array,  # (P2, ps, KVH) f32
    block_tables: jax.Array,   # (B, Pmax) int32 — seq-order page ids
    tier_tables: jax.Array,    # (B, Pmax) int32 — per-page tier (0/1)
    pos: jax.Array,            # (B,) int32 — current position (inclusive)
) -> jax.Array:
    """Mixed-tier decode attention over the KV4 + KV2 page slabs.

    The precision ladder's read path: ``tier_tables[b, i]`` says which
    slab ``block_tables[b, i]`` indexes — 0 for the packed-int4 pool,
    1 for the packed-int2 (demoted) pool. Both tables are scalar-
    prefetched; each grid step DMAs one page from the slab the tier
    selects (the other slab contributes only its reserved null page 0)
    and the body picks the dequantized block by tier id. Undemoted
    pages therefore flow through the exact f32 computation of
    :func:`kv4_paged_decode_attention` — an all-tier-0 call is bit-exact
    against it — while demoted pages stream at int2 width with their
    original scales (clamp error bound in docs/format.md).
    """
    b, kvh, g, hd = q.shape
    _, ps, _, hdp = k_pages.shape
    _, n_s = block_tables.shape
    assert hdp * 2 == hd, (hd, hdp)
    assert k2_pages.shape[-1] * 4 == hd, (hd, k2_pages.shape)

    def kv4_page(ib, isb, bt, tt, p):
        i = ib * n_s + _context_col(isb, p[ib], ps)
        return jnp.where(tt[i] == 1, 0, bt[i])

    def kv2_page(ib, isb, bt, tt, p):
        i = ib * n_s + _context_col(isb, p[ib], ps)
        return jnp.where(tt[i] == 1, bt[i], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_s),
        in_specs=[
            pl.BlockSpec((1, kvh, g, hd),
                         lambda ib, isb, bt, tt, p: (ib, 0, 0, 0)),
            *_page_specs(ps, kvh, hdp, kv4_page),
            *_page_specs(ps, kvh, hd // 4, kv2_page),
        ],
        out_specs=pl.BlockSpec((1, kvh, g, hd),
                               lambda ib, isb, bt, tt, p: (ib, 0, 0, 0)),
        scratch_shapes=_scratch(kvh, g, hd),
    )
    return pallas_call(
        functools.partial(_tiered_paged_kernel, n_s=n_s, bs=ps,
                          scale=hd ** -0.5),
        name="kv_tiered_paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(block_tables.reshape(-1), tier_tables.reshape(-1), pos, q, k_pages,
      k_scale_pages, v_pages, v_scale_pages, k2_pages, k2_scale_pages,
      v2_pages, v2_scale_pages)
