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
— (bs, KVH, hd//2) in VMEM, token-major like the cache — and the body
walks the heads in a static loop, each head running the same (G, hd) x
(bs, hd) online-softmax update on its own running max/denominator/
accumulator in VMEM scratch (the flash-decoding structure re-tiled for
VMEM). A head's arithmetic never touches another head's values, so its
bits do not depend on how many heads share the block (KV-head sharding
is exact). Positions and block tables are scalar-prefetched into SMEM.

The contiguous kernel takes ``bs`` from its caller. A paged block is
:func:`block_pages` table columns of one sequence — about
``BLOCK_TOKENS`` tokens — whose pages lie anywhere in the pool, so no one
block of the pool covers it: each page slot of the block is an operand
of its own, a (1, page, KVH, …) block of the pool whose index map reads
the slot's page id from the block table, and the Pallas pipeline copies
every slot's page and prefetches the next grid step's. The body joins
the slots' rows into one (bs, hd) block per head. A table width the
block does not divide leaves the last block short: its missing columns
hold no context and are masked like any position past ``pos``.

Only the first :func:`context_pages` pages of a sequence hold positions
``<= pos``, and so only the first ``context_pages(pos, bs)`` blocks. The
scan skips every block past them: the shared body does no work for it
(all its scores would be ``NEG_INF``, leaving the running max,
denominator and accumulator bit for bit as they are), and each page
slot's index map names, in place of a column past the context, the
last context column that slot read (:func:`_page_col`) — an unchanged
block index, so the pipeline issues no copy. In the last context block
the slots past the context therefore hold a page read earlier; the body
masks the values of every row past ``pos`` to zero as well as its
score, so nothing there (even NaN) reaches the output. The skip is
exact: a block holding ``pos`` is never skipped, and block 0 always
holds position 0.

Three entry points share the same kernel body (``_scan_step``):

  * :func:`kv4_decode_attention`        — contiguous (B, S, KVH, …) cache;
  * :func:`kv4_paged_decode_attention`  — a paged pool (P, page, KVH, …)
    walked through a per-sequence block table. Because the body, block
    shapes and accumulation order are identical, the paged variant is
    bit-exact against the contiguous one at ``bs = block_pages * page``
    when the pages tile the same cache.
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
route each page slot's DMA to the KV4 or KV2 slab accordingly, and the
body selects each page's dequantized rows by tier — bit-exact against
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
# tokens a paged grid step attends over: a block of 8 16-token pages
BLOCK_TOKENS = 128


def context_pages(pos, page_size: int):
    """How many cache blocks of ``page_size`` tokens hold the positions
    ``0..pos``: the blocks the scan attends over. Every block from this
    one on lies wholly past ``pos`` and is skipped. Works on Python ints,
    NumPy arrays and traced scalars alike, so the kernels and the
    engine's page counts share the one rule."""
    return pos // page_size + 1


def block_pages(page_size: int, n_cols: int) -> int:
    """Table columns (pages) one grid step of a paged kernel gathers:
    ``BLOCK_TOKENS`` tokens' worth, at least one and at most the table's
    width ``n_cols``."""
    return max(1, min(BLOCK_TOKENS // page_size, n_cols))


def _dequant(q_refs, s_refs, h: int, width: int) -> jax.Array:
    """KV head ``h`` of a packed cache block -> (bs, hd) f32.

    The block is the rows of ``q_refs`` in order (one ref, or one per
    page): each a (1, rows, KVH, hd*width//8) int8 block whose bytes hold
    ``8 // width`` two's-complement fields, lowest field first; ``s_refs``
    the matching (1, rows, KVH) per-token-head scales. Fields are widened
    to int32 before the shifts and the interleave (Mosaic lays int32 out
    natively).
    """
    x = jnp.concatenate([r[0, :, h, :].astype(jnp.int32) for r in q_refs])
    fields = [jnp.right_shift(jnp.left_shift(x, 32 - width * (f + 1)),
                              32 - width)
              for f in range(8 // width)]
    scales = jnp.concatenate([r[0, :, pl.ds(h, 1)] for r in s_refs])
    return _interleave(fields) * scales


def _interleave(fields) -> jax.Array:
    """(rows, w) int32 fields, lowest first -> (rows, F*w) f32 whose
    column ``F*i + f`` is ``fields[f][:, i]``.

    The lane interleave is done on the MXU: field ``f`` times the 0/1
    matrix that sends column ``i`` to column ``F*i + f``. The fields are
    small integers, exact in bf16, and each output column sums one
    nonzero product, so the result is exact; a lane shuffle costs Mosaic
    work in every row of the block instead.
    """
    n_f, w = len(fields), fields[0].shape[1]
    src = jax.lax.broadcasted_iota(jnp.int32, (w, n_f * w), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (w, n_f * w), 1)
    out = None
    for f, x in enumerate(fields):
        y = jax.lax.dot_general(
            x.astype(jnp.bfloat16), (dst == n_f * src + f).astype(
                jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out = y if out is None else out + y
    return out


def _scan_step(pos, s_idx, q_of, kv_of, out_ref, m_ref, l_ref, acc_ref,
               *, n_s: int, bs: int, scale: float):
    """One cache-block step of the online-softmax scan, for every KV head.

    ``pos`` is the query's absolute position (a scalar) and ``s_idx`` the
    block's place along the cache axis; ``q_of(h)`` gives head ``h``'s
    (G, hd) query rows and ``kv_of(h)`` its dequantized (bs, hd) k and v.
    Every entry point maps its own grid and layout onto these, so the f32
    computation (and therefore the bits) is identical across them. The
    init and the drain run at every sequence's first and last step; the
    attention between them only for blocks that hold a position <= pos.
    Rows past ``pos`` count for nothing whatever they hold: their scores
    are ``NEG_INF`` and their values zero."""
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
        j_row = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        for h in range(acc_ref.shape[0]):
            k, v = kv_of(h)
            v = jnp.where(j_row <= pos, v, 0.0)
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


def _kv4(kq_refs, ks_refs, vq_refs, vs_refs):
    return lambda h: (_dequant(kq_refs, ks_refs, h, 4),
                      _dequant(vq_refs, vs_refs, h, 4))


def _scratch(kvh: int, g: int, hd: int):
    return [
        pltpu.VMEM((kvh, g, 1), jnp.float32),    # running max
        pltpu.VMEM((kvh, g, 1), jnp.float32),    # running denominator
        pltpu.VMEM((kvh, g, hd), jnp.float32),   # output accumulator
    ]


def _kernel(pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, out_ref,
            m_ref, l_ref, acc_ref, *, n_s: int, bs: int, scale: float):
    _scan_step(pos_ref[pl.program_id(0)], pl.program_id(1),
               lambda h: q_ref[0, h],
               _kv4([kq_ref], [ks_ref], [vq_ref], [vs_ref]),
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


# ---------------------------------------------------------------------------
# paged kernels: a block of table columns, one pipelined operand per page
# ---------------------------------------------------------------------------

def _page_col(blk, j: int, n: int, last):
    """Table column that page slot ``j`` of block ``blk`` reads, where
    ``last`` is the sequence's last context column: ``blk*n + j`` while
    that is a context column; past it, the slot's last context column
    (``last`` itself for a slot that has none), so the slot's page index
    stays unchanged and the pipeline copies nothing."""
    k = jnp.maximum(last - j, 0) // n
    return jnp.where(j <= last, jnp.minimum(blk, k) * n + j, last)


def _page_specs(n: int, ps: int, kvh: int, width: int, page_of):
    """Block specs of a block's ``n`` pages of packed values and scales,
    all KV heads, page by page (k values, k scales, v values, v scales);
    ``page_of(j, *grid_idx, *prefetch_refs)`` names page slot ``j``'s
    physical page."""
    def slots(shape):
        return [pl.BlockSpec(shape, lambda *a, j=j: (page_of(j, *a),) +
                             (0,) * (len(shape) - 1)) for j in range(n)]
    return 2 * (slots((1, ps, kvh, width)) + slots((1, ps, kvh)))


def _split(refs, n: int):
    """Page operands in ``_page_specs`` order -> lists of ``n`` refs each
    (k values, k scales, v values, v scales), and the refs after them."""
    return [refs[i * n:(i + 1) * n] for i in range(4)], refs[4 * n:]


def _paged_call(kernel, name, q, slabs, tables, pos, *, page_of,
                semantics):
    """Launch a paged kernel over ``q``'s leading axes (sequence, then
    window token for verify) and the blocks of ``tables``' columns,
    :func:`block_pages` columns a grid step. Each page of a block is a
    pipelined operand of each array of ``slabs`` (tuples of k values, k
    scales, v values, v scales); its page id is ``page_of(slab, entry,
    tables)``, ``entry`` being the flat table index of the column the
    slot reads (:func:`_page_col`). ``tables`` and ``pos`` are
    scalar-prefetched (tables flattened, so SMEM holds no padded 2-D
    array); a window token's query position is ``pos`` plus its index."""
    kvh, g, hd = q.shape[-3:]
    lead = q.shape[:q.ndim - 3]
    ps = slabs[0][0].shape[1]
    n_s = tables[0].shape[1]
    n = block_pages(ps, n_s)
    n_b = -(-n_s // n)

    def slot_page(slab):
        def page(j, *a):
            idx, blk, refs = a[:len(lead)], a[len(lead)], a[len(lead) + 1:]
            last = context_pages(refs[-1][idx[0]] + sum(idx[1:]), ps) - 1
            return page_of(slab, idx[0] * n_s + _page_col(blk, j, n, last),
                           refs[:-1])
        return page

    def q_map(*a):
        return a[:len(lead)] + (0, 0, 0)

    q_block = (1,) * len(lead) + (kvh, g, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(tables) + 1,
        grid=lead + (n_b,),
        in_specs=[pl.BlockSpec(q_block, q_map)] + [
            spec for i, slab in enumerate(slabs)
            for spec in _page_specs(n, ps, kvh, slab[0].shape[-1],
                                    slot_page(i))],
        out_specs=pl.BlockSpec(q_block, q_map),
        scratch_shapes=_scratch(kvh, g, hd),
    )
    return pallas_call(
        functools.partial(kernel, n_s=n_s, n=n, bs=n * ps, n_b=n_b,
                          scale=hd ** -0.5),
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
    )(*(t.reshape(-1) for t in tables), pos, q,
      *(a for slab in slabs for a in slab for _ in range(n)))


def _table_page(slab, entry, tables):
    """A KV4 page slot's page: the block table's entry."""
    del slab
    return tables[0][entry]


def _paged_kernel(bt_ref, pos_ref, q_ref, *refs, n_s, n, bs, n_b, scale,
                  verify=False):
    # grid = (B, n_b), or (B, T, n_b) for verify, where window token t's
    # query position is pos + t: each (b, t) cell then computes exactly
    # what a single-token decode at pos + t would (bit-exact vs a loop of
    # kv4_paged_decode_attention calls). The block table only drives the
    # index maps; the body is the shared flash-decoding step (bit-exact
    # with the contiguous layout at the same block size).
    del bt_ref, n_s
    ib = pl.program_id(0)
    pages, (out_ref, m_ref, l_ref, acc_ref) = _split(refs, n)
    if verify:
        pos, blk = pos_ref[ib] + pl.program_id(1), pl.program_id(2)
        q_of = lambda h: q_ref[0, 0, h]  # noqa: E731
    else:
        pos, blk = pos_ref[ib], pl.program_id(1)
        q_of = lambda h: q_ref[0, h]  # noqa: E731
    _scan_step(pos, blk, q_of, _kv4(*pages), out_ref, m_ref, l_ref, acc_ref,
               n_s=n_b, bs=bs, scale=scale)


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
    page (:func:`context_pages`) may point anywhere, and are never read.
    The table is scalar-prefetched and page ids feed the DMA index maps —
    the pool is read only at a sequence's context pages, in wire format,
    :func:`block_pages` table columns per grid step.
    """
    assert k_pages.shape[-1] * 2 == q.shape[-1], (q.shape, k_pages.shape)
    return _paged_call(
        _paged_kernel, "kv4_paged_decode_attention", q,
        [(k_pages, k_scale_pages, v_pages, v_scale_pages)], [block_tables],
        pos, page_of=_table_page, semantics=("parallel", "arbitrary"))


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
    assert k_pages.shape[-1] * 2 == q.shape[-1], (q.shape, k_pages.shape)
    return _paged_call(
        functools.partial(_paged_kernel, verify=True),
        "kv4_paged_verify_attention", q,
        [(k_pages, k_scale_pages, v_pages, v_scale_pages)], [block_tables],
        pos, page_of=_table_page,
        semantics=("parallel", "parallel", "arbitrary"))


def _tiered_paged_kernel(bt_ref, tt_ref, pos_ref, q_ref, *refs, n_s, n, bs,
                         n_b, scale):
    # per-page tier routing: the index maps already DMA'd the right slab's
    # page into each slot (the other slab's slot holds its null page); the
    # body dequantizes both candidates and selects each page's by the
    # prefetched tier id. On a tier-0 page the selected f32 values are
    # elementwise identical to what the KV4 kernel computes, so the
    # shared scan step produces identical bits.
    del bt_ref
    ib, blk = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[ib]
    kv4, rest = _split(refs, n)
    kv2, (out_ref, m_ref, l_ref, acc_ref) = _split(rest, n)
    last = context_pages(pos, bs // n) - 1
    demoted = [tt_ref[ib * n_s + _page_col(blk, j, n, last)] == 1
               for j in range(n)]

    def pick(q4, s4, q2, s2, h):
        return jnp.concatenate([
            jnp.where(demoted[j], _dequant([q2[j]], [s2[j]], h, 2),
                      _dequant([q4[j]], [s4[j]], h, 4)) for j in range(n)])

    def kv_of(h):
        return (pick(kv4[0], kv4[1], kv2[0], kv2[1], h),
                pick(kv4[2], kv4[3], kv2[2], kv2[3], h))

    _scan_step(pos, blk, lambda h: q_ref[0, h], kv_of, out_ref, m_ref,
               l_ref, acc_ref, n_s=n_b, bs=bs, scale=scale)


def _tiered_page(slab, entry, tables):
    """A tiered page slot's page in slab ``slab`` (0 KV4, 1 KV2): the
    table's entry where the tier names that slab, else its null page 0."""
    bt, tt = tables
    return jnp.where(tt[entry] == slab, bt[entry], 0)


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
    prefetched; each page slot DMAs its page from the slab the tier
    selects (the other slab contributes only its reserved null page 0)
    and the body picks the dequantized page by tier id. Undemoted
    pages therefore flow through the exact f32 computation of
    :func:`kv4_paged_decode_attention` — an all-tier-0 call is bit-exact
    against it — while demoted pages stream at int2 width with their
    original scales (clamp error bound in docs/format.md).
    """
    hd = q.shape[-1]
    assert k_pages.shape[-1] * 2 == hd, (hd, k_pages.shape)
    assert k2_pages.shape[-1] * 4 == hd, (hd, k2_pages.shape)
    return _paged_call(
        _tiered_paged_kernel, "kv_tiered_paged_decode_attention", q,
        [(k_pages, k_scale_pages, v_pages, v_scale_pages),
         (k2_pages, k2_scale_pages, v2_pages, v2_scale_pages)],
        [block_tables, tier_tables], pos, page_of=_tiered_page,
        semantics=("parallel", "arbitrary"))
