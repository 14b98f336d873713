"""Serving subsystem: pool invariants, paged-kernel exactness, and
engine-vs-legacy token equivalence under continuous batching."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.qlinear import quantize_model_params
from repro.models import model as M
from repro.models.schema import init_params
from repro.models.schema_builder import build_schema
from repro.serving import (Engine, PagedKVPool, PoolConfig, SamplingParams,
                           Scheduler, SchedulerConfig)

# float32 compute so the engine's f32 attention paths and the legacy bf16-
# free path agree to fp rounding — greedy tokens then match exactly.
CFG = ModelConfig(name="tiny-serve", family="transformer", n_layers=2,
                  d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                  d_ff=64, vocab=128, dtype="float32")


@pytest.fixture(scope="module")
def fparams():
    return init_params(build_schema(CFG), jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def qparams(fparams):
    return quantize_model_params(
        fparams, w_bits=4, k_percent=50.0, clip_l=-8.0, clip_h=23.0,
        mode="sparqle", enable_clipping=True, tile_k=16)


def _legacy_greedy(qp, prompt, gen):
    toks = jnp.asarray(prompt, jnp.int32)[None]
    plen = toks.shape[1]
    logits, cache = M.prefill(CFG, qp, {"tokens": toks}, max_len=plen + gen)
    out = [int(jnp.argmax(logits, -1)[0])]
    for i in range(gen - 1):
        pos = jnp.full((1,), plen + i, jnp.int32)
        lg, cache = M.decode_step(CFG, qp, cache,
                                  jnp.asarray([out[-1]], jnp.int32), pos)
        out.append(int(jnp.argmax(lg, -1)[0]))
    return out


# ---------------------------------------------------------------------------
# page pool
# ---------------------------------------------------------------------------

def test_pool_alloc_free_invariants():
    pool = PagedKVPool(CFG, PoolConfig(n_pages=8, page_size=4))
    assert pool.n_usable_pages == 7            # page 0 reserved (null page)
    a = pool.allocate(3, owner="a")
    b = pool.allocate(4, owner="b")
    assert 0 not in a + b                      # null page never handed out
    assert len(set(a + b)) == 7                # no page handed out twice
    assert pool.allocate(1, owner="c") is None  # exhausted: no partial grab
    assert pool.num_free == 0
    freed = pool.release("a")
    assert sorted(freed) == sorted(a)
    assert pool.num_free == 3
    c = pool.allocate(3, owner="c")
    assert sorted(c) == sorted(a)              # recycled
    assert pool.release("missing") == []       # idempotent


def test_pool_eviction_hook_fires():
    pool = PagedKVPool(CFG, PoolConfig(n_pages=8, page_size=4))
    pages = pool.allocate(2, owner=7)
    seen = []
    pool.on_evict = lambda owner, pgs: seen.append((owner, list(pgs)))
    evicted = pool.evict(7)
    assert evicted == pages and seen == [(7, pages)]
    assert pool.evictions == 1 and pool.num_free == 7


def test_pool_evict_unknown_owner_is_noop():
    """Evicting an owner that holds no pages must not bump the eviction
    counter or fire the hook (scheduler churn can retry a preemption
    after the victim already released)."""
    pool = PagedKVPool(CFG, PoolConfig(n_pages=8, page_size=4))
    fired = []
    pool.on_evict = lambda owner, pgs: fired.append(owner)
    assert pool.evict("never-allocated") == []
    assert pool.evictions == 0 and fired == []
    # a real eviction still counts
    pool.allocate(2, owner="a")
    pool.evict("a")
    assert pool.evictions == 1 and fired == ["a"]
    # ... and evicting the same owner again is a no-op
    assert pool.evict("a") == []
    assert pool.evictions == 1 and fired == ["a"]


def test_pool_zero_page_allocate_no_phantom_owner():
    """allocate(0, owner) must not create an ownership entry: release()
    and evict() treat map presence as 'holds pages', so a phantom entry
    drifts the ownership map under scheduler churn."""
    pool = PagedKVPool(CFG, PoolConfig(n_pages=8, page_size=4))
    assert pool.allocate(0, owner="ghost") == []
    assert "ghost" not in pool._owned
    assert pool.pages_of("ghost") == []
    assert pool.evict("ghost") == [] and pool.evictions == 0
    # zero-grab on an EXISTING owner leaves its pages untouched
    pages = pool.allocate(2, owner="real")
    assert pool.allocate(0, owner="real") == []
    assert pool.pages_of("real") == pages


def test_pool_msb_sparsity_all_16_nibble_values():
    """Regression for the signed-nibble criterion: sub-precision nibbles
    are exactly those in [KV2_LOW, KV2_HIGH] = [-2, 1] (signed int2
    range). The old arithmetic-shift test (nib >> 2 == 0) wrongly
    excluded -2 and -1 (and counted 2 and 3, which need 3 signed bits)."""
    from repro.serving.kv_pool import KV2_LOW, KV2_HIGH
    assert (KV2_LOW, KV2_HIGH) == (-2, 1)
    for v in range(-8, 8):
        pool = PagedKVPool(CFG, PoolConfig(n_pages=4, page_size=4))
        byte = np.uint8((v & 0xF) | ((v & 0xF) << 4)).astype(np.int8)
        pool.state = jax.tree_util.tree_map(
            lambda a: (a.at[:, 1].set(byte) if a.dtype == jnp.int8 else a),
            pool.state)
        s = pool.page_msb_sparsity([1])
        expected = 1.0 if KV2_LOW <= v <= KV2_HIGH else 0.0
        np.testing.assert_allclose(s, [expected], err_msg=f"nibble {v}")


def test_pool_msb_sparsity_mixed_nibbles_fraction():
    """A page holding every int4 value equally often reports 4/16."""
    pool = PagedKVPool(CFG, PoolConfig(n_pages=4, page_size=4))
    nibbles = np.arange(-8, 8, dtype=np.int8)          # all 16 values
    seq = np.tile(nibbles, 4)                          # 64 nibbles/page leaf
    packed = ((seq[0::2] & 0xF) | ((seq[1::2] & 0xF) << 4)).astype(np.int8)
    page = jnp.asarray(packed.reshape(4, 2, 4))        # (ps, kvh, hd/2)
    pool.state = jax.tree_util.tree_map(
        lambda a: (a.at[:, 1].set(page) if a.dtype == jnp.int8 else a),
        pool.state)
    np.testing.assert_allclose(pool.page_msb_sparsity([1]), [4 / 16])


def test_pool_msb_sparsity_telemetry():
    pool = PagedKVPool(CFG, PoolConfig(n_pages=4, page_size=4))
    # zero-initialized nibbles are all sub-precision (value 0)
    s = pool.page_msb_sparsity([1, 2])
    np.testing.assert_allclose(s, [1.0, 1.0])
    # 0x77 -> both nibbles 7: high two bits nonzero everywhere on page 2
    full = jax.tree_util.tree_map(
        lambda a: (a.at[:, 2].set(0x77) if a.dtype == jnp.int8 else a),
        pool.state)
    pool.state = full
    s = pool.page_msb_sparsity([1, 2])
    np.testing.assert_allclose(s, [1.0, 0.0])


# ---------------------------------------------------------------------------
# paged kernel vs contiguous kernel
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("b,s,kvh,g,hd,ps", [
    (2, 256, 2, 4, 32, 64), (1, 256, 1, 2, 16, 128),
    # 20 columns of 16 tokens: blocks of 8, 8 and a short 4
    (2, 320, 2, 4, 32, 16),
])
def test_paged_kernel_bitexact_vs_contiguous(b, s, kvh, g, hd, ps):
    """Walking a (shuffled) block table must reproduce the contiguous
    kernel at ``bs = block_pages * ps`` bit for bit — same body, same
    block shapes, same order. Where the blocks overrun the table, the
    contiguous cache runs on with NaN-scaled tokens, which the paged
    kernel's short last block never holds: both mask them."""
    from repro.kernels.kv_attention import (block_pages,
                                            kv4_decode_attention,
                                            kv4_paged_decode_attention)
    n_per = s // ps
    bs = block_pages(ps, n_per) * ps
    s_pad = -(-s // bs) * bs
    q = jax.random.normal(jax.random.PRNGKey(0), (b, kvh, g, hd))
    kq = jax.random.randint(jax.random.PRNGKey(1), (b, s_pad, kvh, hd // 2),
                            -128, 128, jnp.int8)
    vq = jax.random.randint(jax.random.PRNGKey(2), (b, s_pad, kvh, hd // 2),
                            -128, 128, jnp.int8)
    ks = jax.random.uniform(jax.random.PRNGKey(3), (b, s_pad, kvh),
                            minval=0.1, maxval=1.0).at[:, s:].set(jnp.nan)
    vs = jax.random.uniform(jax.random.PRNGKey(4), (b, s_pad, kvh),
                            minval=0.1, maxval=1.0).at[:, s:].set(jnp.nan)
    pos = jax.random.randint(jax.random.PRNGKey(5), (b,), 1, s, jnp.int32)
    ref = kv4_decode_attention(q, kq, ks, vq, vs, pos, bs=bs)
    assert np.isfinite(np.asarray(ref)).all()

    n_pages = b * n_per + 1
    perm = np.random.RandomState(0).permutation(b * n_per) + 1
    kp = np.zeros((n_pages, ps, kvh, hd // 2), np.int8)
    vp = np.zeros_like(kp)
    ksp = np.zeros((n_pages, ps, kvh), np.float32)
    vsp = np.zeros_like(ksp)
    bt = np.zeros((b, n_per), np.int32)
    for i in range(b):
        for j in range(n_per):
            pid = int(perm[i * n_per + j])
            bt[i, j] = pid
            sl = slice(j * ps, (j + 1) * ps)
            kp[pid], vp[pid] = kq[i, sl], vq[i, sl]
            ksp[pid], vsp[pid] = ks[i, sl], vs[i, sl]
    out = kv4_paged_decode_attention(
        q, jnp.asarray(kp), jnp.asarray(ksp), jnp.asarray(vp),
        jnp.asarray(vsp), jnp.asarray(bt), pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_block_pages_follows_page_size_and_table_width():
    """About 128 tokens a block, at least one page, at most the table."""
    from repro.kernels.kv_attention import block_pages
    assert [block_pages(ps, 192) for ps in (8, 16, 32, 128, 256)] == \
        [16, 8, 4, 1, 1]
    assert [block_pages(16, n) for n in (1, 3, 8, 9)] == [1, 3, 8, 8]


def test_context_pages_at_page_boundaries():
    """Blocks holding positions 0..pos: a new one starts at each multiple
    of the page size; the rule is the same on ints and arrays."""
    from repro.kernels.kv_attention import context_pages
    ps = 16
    assert [context_pages(p, ps) for p in (0, 1, ps - 1, ps, ps + 1,
                                           2 * ps - 1, 2 * ps)] == \
        [1, 1, 1, 2, 2, 2, 3]
    np.testing.assert_array_equal(
        context_pages(np.array([0, ps - 1, ps, 191 * ps - 1], np.int32), ps),
        [1, 1, 2, 191])


# (b, g, kvh, hd, ps, n): an n-column table, in blocks of 8, 8 and a
# short 4 columns; the case names row 0's position ("inactive": an
# all-null row at pos 0, as the engine pads)
SKIP_SHAPE = (2, 2, 2, 32, 16, 20)
SKIP_CASES = {"first": 0, "page_end": 15, "page_start": 16, "mid_page": 40,
              "block_last_page": 120, "block_first_page": 130,
              "next_block": 256, "table_end": 319, "inactive": 0}


def _kv_cache(b, s, kvh, hd, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    kq = jax.random.randint(keys[0], (b, s, kvh, hd // 2), -128, 128,
                            jnp.int8)
    vq = jax.random.randint(keys[1], (b, s, kvh, hd // 2), -128, 128,
                            jnp.int8)
    ks = jax.random.uniform(keys[2], (b, s, kvh), minval=0.1, maxval=1.0)
    vs = jax.random.uniform(keys[3], (b, s, kvh), minval=0.1, maxval=1.0)
    return [np.array(a) for a in (kq, ks, vq, vs)]


def _paged(cache, ps, tables):
    """Pool pages laid out from a contiguous cache: page ``1 + i*n + j``
    holds row ``i``'s tokens [j*ps, (j+1)*ps); page 0 is null (zeros)
    and the last page is all-NaN scales. Returns the pool and clean
    tables (row i walks its own pages, or page 0 where ``tables[i]`` is
    0)."""
    b, s = cache[0].shape[:2]
    n = s // ps
    pool = [np.zeros((b * n + 2, ps) + a.shape[2:], a.dtype)
            for a in cache]
    for i in range(b):
        for j in range(n):
            for dst, src in zip(pool, cache):
                dst[1 + i * n + j] = src[i, j * ps:(j + 1) * ps]
    for k in (1, 3):                        # k and v scales of the NaN page
        pool[k][-1] = np.nan
    bt = np.array([(1 + i * n + np.arange(n)) * tables[i]
                   for i in range(b)], np.int32)
    return pool, bt


@pytest.mark.parametrize("case", sorted(SKIP_CASES))
def test_paged_kernel_skips_pages_past_context(case):
    """Table columns past a sequence's last context page are never read:
    pointed at a page whose scales are NaN (which would poison the
    accumulator through p = 0 x NaN if the page were attended over),
    the output equals the clean table's bit for bit — whether the column
    lies inside the last context block or past it. The contiguous
    kernel, which copies whole blocks, masks the NaN rows of its last
    context block and skips the blocks past it; and the paged kernel
    still equals it at the same block size."""
    from repro.kernels.kv_attention import (block_pages, context_pages,
                                            kv4_decode_attention,
                                            kv4_paged_decode_attention)
    b, g, kvh, hd, ps, n = SKIP_SHAPE
    bs = block_pages(ps, n) * ps
    s = -(-n * ps // bs) * bs                 # the blocks overrun the table
    q = jax.random.normal(jax.random.PRNGKey(9), (b, kvh, g, hd))
    pos = np.array([SKIP_CASES[case], 77], np.int32)
    cache = _kv_cache(b, s, kvh, hd)
    pool, bt = _paged([a[:, :n * ps] for a in cache], ps,
                      [0 if case == "inactive" else 1, 1])
    dirty = bt.copy()
    for i in range(b):
        dirty[i, context_pages(pos[i], ps):] = pool[0].shape[0] - 1
    out = {name: np.asarray(kv4_paged_decode_attention(
        q, *map(jnp.asarray, pool), jnp.asarray(table), jnp.asarray(pos)))
        for name, table in (("clean", bt), ("dirty", dirty))}
    assert np.isfinite(out["clean"]).all()
    np.testing.assert_array_equal(out["dirty"], out["clean"])

    if case == "inactive":
        return
    nan_tail = [a.copy() for a in cache]
    for i in range(b):
        for k in (1, 3):
            nan_tail[k][i, context_pages(pos[i], ps) * ps:] = np.nan
    contiguous = {name: np.asarray(kv4_decode_attention(
        q, *map(jnp.asarray, c), jnp.asarray(pos), bs=bs))
        for name, c in (("clean", cache), ("dirty", nan_tail))}
    np.testing.assert_array_equal(contiguous["dirty"], contiguous["clean"])
    np.testing.assert_array_equal(out["clean"], contiguous["clean"])


# ---------------------------------------------------------------------------
# engine vs legacy
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_matches_legacy_8_staggered_requests(qparams):
    """8 staggered requests of different lengths through the continuous-
    batching engine produce the same greedy tokens as the legacy
    fixed-batch path (whole-prompt prefill chunks; 4 decode slots force
    backfill)."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, CFG.vocab, size=n).tolist()
               for n in (12, 20, 5, 30, 9, 17, 26, 14)]
    gen = 6
    eng = Engine(CFG, qparams,
                 pool_config=PoolConfig(n_pages=64, page_size=8),
                 sched_config=SchedulerConfig(
                     max_decode_batch=4, token_budget=256,
                     prefill_chunk=32, max_pages_per_seq=8))
    handles = []
    for p in prompts:                      # staggered: step between submits
        handles.append(eng.submit(p, SamplingParams(max_new_tokens=gen)))
        eng.step()
    eng.run()
    for h, p in zip(handles, prompts):
        assert h.done and h.n_generated == gen
        assert h.out_tokens == _legacy_greedy(qparams, p, gen), h.rid
        st = h.stats()
        assert np.isfinite(st["ttft_s"]) and np.isfinite(st["tpot_s"])
        assert 0.0 <= st["act_sparsity"] <= 1.0
        # measured wire-format accounting rides along per request
        assert np.isfinite(st["act_wire_bytes_per_token"])
        assert st["act_wire_bytes_per_token"] > 0
        assert np.isfinite(st["act_wire_compression_pct"])
    # backfilled slots: 8 requests through 4 slots, everything released
    assert eng.pool.num_free == eng.pool.n_usable_pages
    # ... and per-layer in aggregate: one entry per transformer layer
    agg = eng.aggregate_stats()
    assert len(agg["layer_wire_bytes_per_token"]) == CFG.n_layers
    assert all(b > 0 for b in agg["layer_wire_bytes_per_token"])
    # dense baseline per layer-input row is d_model bytes
    assert all(abs(d - CFG.d_model) < 1e-6
               for d in agg["layer_dense_bytes_per_token"])
    assert agg["wire_bytes_total"] > 0


@pytest.mark.slow
def test_engine_packed_wire_format_matches_unpacked(fparams, qparams):
    """Serving with wire_format='packed' (activations round-trip the
    packed codec before every projection) produces the same greedy tokens
    as the unpacked path — the codec is exact, so the format change is
    invisible to the math."""
    qp_packed = quantize_model_params(
        fparams, w_bits=4, k_percent=50.0, clip_l=-8.0, clip_h=23.0,
        mode="sparqle", enable_clipping=True, tile_k=16,
        wire_format="packed")
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, CFG.vocab, size=n).tolist() for n in (11, 18)]
    outs = []
    for qp in (qparams, qp_packed):
        eng = Engine(CFG, qp,
                     pool_config=PoolConfig(n_pages=16, page_size=8),
                     sched_config=SchedulerConfig(
                         max_decode_batch=2, token_budget=64,
                         prefill_chunk=32, max_pages_per_seq=8))
        hs = [eng.submit(p, SamplingParams(max_new_tokens=5))
              for p in prompts]
        eng.run()
        outs.append([h.out_tokens for h in hs])
    assert outs[0] == outs[1]


@pytest.mark.slow
def test_engine_chunked_prefill_completes(qparams):
    """A prompt longer than the chunk is prefilled across several steps
    (interleaving with decodes) and still completes."""
    rng = np.random.RandomState(3)
    long_p = rng.randint(0, CFG.vocab, size=40).tolist()
    short_p = rng.randint(0, CFG.vocab, size=6).tolist()
    eng = Engine(CFG, qparams,
                 pool_config=PoolConfig(n_pages=32, page_size=8),
                 sched_config=SchedulerConfig(
                     max_decode_batch=2, token_budget=16,
                     prefill_chunk=16, max_pages_per_seq=8))
    h1 = eng.submit(long_p, SamplingParams(max_new_tokens=4))
    h2 = eng.submit(short_p, SamplingParams(max_new_tokens=4))
    eng.run()
    assert h1.n_generated == 4 and h2.n_generated == 4
    # 40-token prompt at chunk 16 needs >= 3 prefill steps
    assert eng.steps >= 3


@pytest.mark.slow
def test_prefill_chunk_boundary_mask_oracle(qparams):
    """The past/chunk attention boundary of _attn_prefill_chunk_paged,
    checked against an independent naive reference with *exactly
    representable* past K/V (int4 values, unit scales) so quantization
    contributes zero error. start=6 with page_size=4 puts the boundary
    mid-page — the off-by-one hot spot."""
    from repro.core.qlinear import linear
    from repro.models.stages import build_stages
    p0 = jax.tree_util.tree_map(lambda a: a[0], qparams["stages"]["s0"]["p0"])
    ld = build_stages(CFG)[0].period[0]
    kvh, H, hd, d = CFG.n_kv_heads, CFG.n_heads, CFG.hd, CFG.d_model
    ps, n_pages = 4, 4
    start, c = 6, 5
    rs = np.random.RandomState(0)
    past_int = rs.randint(-8, 8, size=(n_pages, ps, kvh, hd)).astype(np.int8)

    def pack(v):
        return jnp.asarray(((v[..., 0::2] & 0xF) |
                            ((v[..., 1::2] & 0xF) << 4)).astype(np.int8))

    pool = {"k_q": pack(past_int), "k_s": jnp.ones((n_pages, ps, kvh)),
            "v_q": pack(past_int[::-1]),
            "v_s": jnp.ones((n_pages, ps, kvh))}
    bt = jnp.arange(n_pages, dtype=jnp.int32)[None]
    x = jnp.asarray(rs.randn(1, c, d), jnp.float32)
    out, _ = M._attn_prefill_chunk_paged(
        CFG, ld, p0, x, pool, bt, jnp.asarray(start, jnp.int32),
        jnp.asarray(c, jnp.int32))

    h = M._norm(CFG, p0["ln"], x)
    q, k, v = M._attn_qkv(CFG, p0, h, start + jnp.arange(c), CFG.rope_theta)
    past_k = jnp.asarray(past_int.reshape(-1, kvh, hd), jnp.float32)
    past_v = jnp.asarray(past_int[::-1].reshape(-1, kvh, hd), jnp.float32)
    k_ctx = jnp.concatenate([past_k[:start], k[0].astype(jnp.float32)], 0)
    v_ctx = jnp.concatenate([past_v[:start], v[0].astype(jnp.float32)], 0)
    qg = q[0].reshape(c, kvh, H // kvh, hd).astype(jnp.float32)
    s = jnp.einsum("ikgd,jkd->kgij", qg, k_ctx) * hd ** -0.5
    allow = (jnp.arange(start + c)[None, :] <=
             start + jnp.arange(c)[:, None])
    s = jnp.where(allow[None, None], s, M.NEG_INF)
    o = jnp.einsum("kgij,jkd->ikgd", jax.nn.softmax(s, -1), v_ctx)
    ref = linear(o.reshape(1, c, H * hd).astype(x.dtype), p0["wo"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_chunked_prefill_pool_writes_chunk_invariant(qparams):
    """Quantize-on-write must not depend on chunking: after prefilling the
    same prompt in 1, 2, or 5 chunks, the first layer's page contents are
    bit-identical (layer-0 K/V depend only on embeddings) and the final
    logits drift only by the quantized-past perturbation, not O(1)."""
    from repro.serving.kv_pool import PagedKVPool, PoolConfig
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, CFG.vocab, size=36)

    def run(chunks):
        pool = PagedKVPool(CFG, PoolConfig(n_pages=8, page_size=8))
        pages = pool.allocate(5, "r")
        bt = np.zeros((1, 8), np.int32)
        bt[0, :5] = pages
        state, start = pool.state, 0
        for c in chunks:
            toks = jnp.asarray(prompt[start:start + c], jnp.int32)[None]
            lg, state, _ = M.prefill_chunk_paged(
                CFG, qparams, state, toks, jnp.asarray(start, jnp.int32),
                jnp.asarray(c, jnp.int32), jnp.asarray(bt))
            start += c
        return np.asarray(lg[0]), state, pages

    lg1, st1, pg1 = run([36])
    i1 = np.asarray(pg1)
    for chunks in ([24, 12], [8, 8, 8, 8, 4]):
        lgN, stN, pgN = run(chunks)
        iN = np.asarray(pgN)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a[0][i1]), np.asarray(b[0][iN])), st1, stN)
        assert np.abs(lg1 - lgN).max() < 0.05, chunks


@pytest.mark.slow
def test_engine_preemption_under_page_pressure(qparams):
    """A pool too small for the working set preempts (evicts + recomputes)
    rather than deadlocking, and every request still finishes."""
    rng = np.random.RandomState(1)
    eng = Engine(CFG, qparams,
                 pool_config=PoolConfig(n_pages=10, page_size=4),
                 sched_config=SchedulerConfig(
                     max_decode_batch=4, token_budget=64,
                     prefill_chunk=16, max_pages_per_seq=8))
    hs = [eng.submit(rng.randint(0, CFG.vocab, size=14).tolist(),
                     SamplingParams(max_new_tokens=10)) for _ in range(4)]
    eng.run()
    assert all(h.n_generated == 10 for h in hs)
    assert eng.pool.evictions > 0
    assert sum(h.stats()["preemptions"] for h in hs) > 0


@pytest.mark.slow
def test_engine_stream_and_temperature(qparams):
    """stream() yields tokens as they are produced; temperature sampling
    is seeded and in-vocab."""
    rng = np.random.RandomState(2)
    eng = Engine(CFG, qparams,
                 pool_config=PoolConfig(n_pages=16, page_size=8),
                 sched_config=SchedulerConfig(max_decode_batch=2,
                                              token_budget=64,
                                              prefill_chunk=16,
                                              max_pages_per_seq=4))
    h = eng.submit(rng.randint(0, CFG.vocab, size=10).tolist(),
                   SamplingParams(max_new_tokens=5, temperature=0.8, seed=3))
    got = list(eng.stream(h))
    assert got == h.out_tokens and len(got) == 5
    assert all(0 <= t < CFG.vocab for t in got)


@pytest.mark.parametrize("entry", ["decode", "verify", "prefill"])
def test_decode_paged_telemetry_covers_every_sublayer(entry):
    """Per-layer telemetry must have one entry per LAYER, not per scanned
    period: a GQA MoE config with moe_every=2 (period length 2) passes
    check_paged_support and must still report n_layers wire-byte rows —
    from each paged entry point (decode; verify of a 3-token window; one
    4-token prefill chunk), since all three share the layer scan."""
    cfg = ModelConfig(name="tiny-moe-serve", family="moe", n_layers=4,
                      d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                      d_ff=64, vocab=64, dtype="float32", n_experts=4,
                      top_k=2, moe_every=2, moe_d_ff=32,
                      router_type="softmax")
    M.check_paged_support(cfg)
    from repro.serving.kv_pool import PagedKVPool, PoolConfig
    params = init_params(build_schema(cfg), jax.random.PRNGKey(1))
    pool = PagedKVPool(cfg, PoolConfig(n_pages=4, page_size=4))
    pos = jnp.zeros((2,), jnp.int32)
    tables = jnp.zeros((2, 2), jnp.int32)
    if entry == "decode":
        _, _, tel = M.decode_step_paged(cfg, params, pool.state,
                                        jnp.zeros((2,), jnp.int32), pos,
                                        tables)
        rows, n_tok = (cfg.n_layers, 2), 1
    elif entry == "verify":            # bytes summed over the window
        _, _, tel = M.verify_window_paged(cfg, params, pool.state,
                                          jnp.zeros((2, 3), jnp.int32),
                                          pos, tables)
        rows, n_tok = (cfg.n_layers, 2), 3
    else:                              # bytes summed over valid tokens
        _, _, tel = M.prefill_chunk_paged(
            cfg, params, pool.state, jnp.zeros((1, 4), jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(4, jnp.int32),
            tables[:1])
        rows, n_tok = (cfg.n_layers,), 4
    assert tel["layer_wire_bytes"].shape == rows
    assert tel["layer_sparsity"].shape == rows
    np.testing.assert_allclose(np.asarray(tel["layer_dense_bytes"]),
                               np.full(rows, cfg.d_model * n_tok))


def test_scheduler_token_budget_and_fcfs():
    """Pure-host scheduler check: decode tokens come off the budget first,
    prefill chunks are FCFS and stop at the budget."""
    pool = PagedKVPool(CFG, PoolConfig(n_pages=32, page_size=4))
    sched = Scheduler(pool, SchedulerConfig(max_decode_batch=4,
                                            token_budget=10,
                                            prefill_chunk=8,
                                            max_pages_per_seq=8))
    a = sched.submit([1] * 20, SamplingParams(max_new_tokens=4), 0.0)
    b = sched.submit([2] * 20, SamplingParams(max_new_tokens=4), 1.0)
    plan = sched.schedule()
    assert plan.decode == []
    # budget 10 -> one 8-token chunk for the FCFS head; b waits until a's
    # prompt is fully scheduled (strict FCFS, no head-of-line skip)
    assert [(r.rid, start, n) for r, start, n in plan.prefill] == \
        [(a.rid, 0, 8)]
    assert b.prefilled == 0
    # unsubmittable request: longer than the block table allows
    with pytest.raises(ValueError):
        sched.submit([0] * 100, SamplingParams(max_new_tokens=1), 2.0)


def test_scheduler_preempted_victim_leaves_decode_plan():
    """When the YOUNGEST running request hits page pressure, the chosen
    victim is an OLDER request that would already sit in the decode list —
    it must be dropped from the plan (decoding it against evicted pages
    would append a garbage token to its output)."""
    pool = PagedKVPool(CFG, PoolConfig(n_pages=4, page_size=4))
    sched = Scheduler(pool, SchedulerConfig(max_decode_batch=2,
                                            token_budget=16,
                                            prefill_chunk=8,
                                            max_pages_per_seq=4))
    a = sched.submit([1] * 3, SamplingParams(max_new_tokens=8), 0.0)
    b = sched.submit([2] * 8, SamplingParams(max_new_tokens=4), 1.0)
    for r, n_pages in ((a, 1), (b, 2)):      # simulate finished prefills
        pool.allocate(n_pages, r.rid)
        r.prefilled = len(r.context)
        r.slot = sched._free_slots.pop(0)
        r.context.append(9)
        r.out_tokens.append(9)
        sched.to_running(r)
    # b's next decode (pos 8) needs a 3rd page; pool is empty -> the only
    # victim is a (older, otherwise already decodable)
    plan = sched.schedule()
    assert plan.decode == [b]
    assert a.preemptions == 1 and a.prefilled == 0
    # the page a freed went straight to b's decode growth, so a's
    # recompute prefill cannot start this step — it waits, pageless
    assert plan.prefill == []
    assert pool.pages_of(a.rid) == [] and a in sched.waiting


def test_submit_rejects_zero_max_new_tokens():
    pool = PagedKVPool(CFG, PoolConfig(n_pages=8, page_size=4))
    sched = Scheduler(pool, SchedulerConfig())
    with pytest.raises(ValueError):
        sched.submit([1, 2], SamplingParams(max_new_tokens=0), 0.0)


def test_pool_schema_abstract_matches_live_state():
    """The dry-run plumbing (steps.pool_abstract_and_shardings) must
    mirror the pool state the engine actually materializes."""
    from repro.launch import steps as S
    from repro.launch.mesh import make_smoke_mesh
    from repro.serving.kv_pool import PoolConfig, init_pool_state
    abs_, shard = S.pool_abstract_and_shardings(CFG, 8, 4,
                                                make_smoke_mesh())
    state = init_pool_state(CFG, PoolConfig(n_pages=8, page_size=4))

    def check(live, spec):
        assert live.shape == spec.shape and live.dtype == spec.dtype
    jax.tree_util.tree_map(check, state, abs_)
    assert (jax.tree_util.tree_structure(shard) ==
            jax.tree_util.tree_structure(abs_))


def test_paged_support_validation():
    with pytest.raises(NotImplementedError):
        M.check_paged_support(CFG.replace(kv_bits=8))
    ssm = ModelConfig(name="mamba2-x", family="ssm", n_layers=2,
                      d_model=64, vocab=128, ssm_state=16, ssm_heads=2,
                      ssm_head_dim=64)
    with pytest.raises(NotImplementedError):
        M.check_paged_support(ssm)
