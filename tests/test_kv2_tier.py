"""KV2 precision ladder: tier re-codecs, pool ladder bookkeeping, the
tiered paged kernel, and engine-level equivalence (docs/serving.md
§precision ladder, docs/format.md §KV2 tier)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # degrade to seeded fixed examples
    from _hypothesis_fallback import given, settings, st

from repro.configs.base import ModelConfig
from repro.core.costmodel import HardwareConfig
from repro.core.qlinear import quantize_model_params
from repro.models.schema import init_params
from repro.models.schema_builder import build_schema
from repro.serving import (Engine, PagedKVPool, PoolConfig, SamplingParams,
                           SchedulerConfig)
from repro.serving import tiering
from repro.serving.kv_pool import KV2_LOW, KV2_HIGH

# a CPU has no entry in costmodel.DEVICE_PEAKS: pass roofline peaks
CPU_PEAKS = HardwareConfig()

CFG = ModelConfig(name="tiny-kv2", family="transformer", n_layers=2,
                  d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                  d_ff=64, vocab=128, dtype="float32")


@pytest.fixture(scope="module")
def qparams():
    fp = init_params(build_schema(CFG), jax.random.PRNGKey(0))
    return quantize_model_params(
        fp, w_bits=4, k_percent=50.0, clip_l=-8.0, clip_h=23.0,
        mode="sparqle", enable_clipping=True, tile_k=16)


# ---------------------------------------------------------------------------
# tier re-codecs (serving/tiering.py)
# ---------------------------------------------------------------------------

def _flat_state(nib, scale, *, kv2_pages=3):
    """Minimal single-layer-group pool state holding ``nib`` (int4 values,
    shape (ps, kvh, hd)) packed into KV4 page 1, plus an empty KV2 slab."""
    from repro.core.packing import pack_plane
    ps, kvh, hd = nib.shape
    k_q = jnp.zeros((1, 2, ps, kvh, hd // 2), jnp.int8)
    k_q = k_q.at[:, 1].set(pack_plane(jnp.asarray(nib), width=4)[None])
    k_s = jnp.ones((1, 2, ps, kvh), jnp.float32).at[:, 1].set(scale)
    return {
        "k_q": k_q, "k_s": k_s,
        "v_q": k_q, "v_s": k_s,
        "k2_q": jnp.zeros((1, kv2_pages, ps, kvh, hd // 4), jnp.int8),
        "k2_s": jnp.ones((1, kv2_pages, ps, kvh), jnp.float32),
        "v2_q": jnp.zeros((1, kv2_pages, ps, kvh, hd // 4), jnp.int8),
        "v2_s": jnp.ones((1, kv2_pages, ps, kvh), jnp.float32),
    }


@pytest.mark.property
@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([True, False]))
def test_demote_promote_roundtrip_property(seed, in_band):
    """demote -> promote is the identity on in-band pages; out-of-band
    nibbles clamp to the nearest int2 band edge with integer error at
    most 6 (dequantized: at most 6 * scale) — the documented bound."""
    from repro.core.packing import unpack_plane
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    lo, hi = (KV2_LOW, KV2_HIGH + 1) if in_band else (-8, 8)
    nib = jax.random.randint(k1, (4, 1, 8), lo, hi, dtype=jnp.int8)
    scale = jax.random.uniform(k2, (4, 1), minval=0.1, maxval=2.0)
    state = _flat_state(nib, scale)
    state = tiering.demote_page(state, jnp.int32(1), jnp.int32(2))
    # KV2 slab now holds the clamped nibbles at the untouched scale
    got2 = unpack_plane(state["k2_q"][0, 2], width=2, signed=True)
    expect = np.clip(np.asarray(nib), KV2_LOW, KV2_HIGH)
    np.testing.assert_array_equal(np.asarray(got2), expect)
    np.testing.assert_array_equal(np.asarray(state["k2_s"][0, 2]),
                                  np.asarray(scale))
    err = np.abs(np.asarray(nib, np.int32) - expect)
    assert err.max() <= 6
    if in_band:
        assert err.max() == 0
    # promote back into a fresh KV4 page: exact image of the clamp
    state = tiering.promote_page(state, jnp.int32(2), jnp.int32(0))
    got4 = unpack_plane(state["k_q"][0, 0], width=4, signed=True)
    np.testing.assert_array_equal(np.asarray(got4), expect)
    np.testing.assert_array_equal(np.asarray(state["k_s"][0, 0]),
                                  np.asarray(scale))


# ---------------------------------------------------------------------------
# pool ladder bookkeeping (serving/kv_pool.py)
# ---------------------------------------------------------------------------

def _pool(**kw):
    cfg = dict(n_pages=8, page_size=4, kv2_pages=4,
               demote_min_sparsity=0.0, demote_after_steps=1)
    cfg.update(kw)
    return PagedKVPool(CFG, PoolConfig(**cfg))


def test_pool_demote_promote_bookkeeping():
    pool = _pool()
    pool.allocate(3, owner="a")
    pool.set_demotable(["a"])
    pool.tick(); pool.tick()
    assert pool.demote_cold() == 2          # frontier page protected
    assert pool.tiers_of("a") == [1, 1, 0]
    assert pool.demotions == 2 and pool.kv2_used == 2
    assert pool.kv_bytes_saved() > 0
    assert pool.kv_bytes_reclaimed == pool.kv_bytes_saved()
    # touch promotes back (exact) and frees the KV2 pages
    pool.touch("a", 0, 1)
    assert pool.tiers_of("a") == [0, 0, 0]
    assert pool.promotions == 2 and pool.kv2_used == 0
    assert pool.kv_bytes_saved() == 0
    assert pool.tier_stats_of("a") == {"demotions": 2, "promotions": 2}


def test_pool_demote_requires_demotable_owner():
    pool = _pool()
    pool.allocate(3, owner="a")
    pool.tick(); pool.tick()
    assert pool.demote_cold() == 0          # not in the demotable set
    pool.set_demotable(["a"])
    assert pool.demote_cold() == 2
    pool.release("a")                       # release purges the set too
    pool.allocate(3, owner="a")
    pool.tick(); pool.tick()
    assert pool.demote_cold() == 0


def test_pool_release_routes_pages_to_their_tiers():
    pool = _pool()
    pool.allocate(3, owner="a")
    pool.set_demotable(["a"])
    pool.tick()
    pool.demote_cold()
    free4, free2 = pool.num_free, pool.kv2_free
    pool.release("a")
    assert pool.num_free == free4 + 1       # one KV4 page was still held
    assert pool.kv2_free == free2 + 2       # two KV2 pages returned
    assert pool.kv2_used == 0


def test_pool_demote_for_pressure_ignores_sparsity():
    pool = _pool(demote_min_sparsity=1.1)   # cold sweep can never fire
    pool.allocate(3, owner="a")
    pool.set_demotable(["a"])
    pool.tick()
    assert pool.demote_cold() == 0
    assert pool.demote_for_pressure(0, n=2) == 2
    assert pool.tiers_of("a") == [1, 1, 0]


def test_pool_disarmed_ladder_is_inert():
    pool = _pool(kv2_pages=0)
    pool.allocate(2, owner="a")
    pool.set_demotable(["a"])
    pool.tick(); pool.tick()
    assert not pool.kv2_armed
    assert pool.demote_cold() == 0 and pool.demote_for_pressure(0) == 0
    assert pool.kv_bytes_saved() == 0 and pool.kv2_used == 0


def test_pool_kv2_rejects_sharded_and_tiny_slabs():
    with pytest.raises(NotImplementedError):
        PagedKVPool(CFG, PoolConfig(n_pages=8, page_size=4, kv2_pages=4),
                    n_shards=2)
    with pytest.raises(ValueError):
        PagedKVPool(CFG, PoolConfig(n_pages=8, page_size=4, kv2_pages=1))


# ---------------------------------------------------------------------------
# tiered paged kernel (kernels/kv_attention.py)
# ---------------------------------------------------------------------------

def _paged_inputs(seed, b=2, s=256, kvh=2, g=4, hd=32, ps=64):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (b, kvh, g, hd))
    n_per = s // ps
    n_pages = b * n_per + 1
    kp = jax.random.randint(keys[1], (n_pages, ps, kvh, hd // 2),
                            -128, 128, jnp.int8)
    vp = jax.random.randint(keys[2], (n_pages, ps, kvh, hd // 2),
                            -128, 128, jnp.int8)
    ksp = jax.random.uniform(keys[3], (n_pages, ps, kvh),
                             minval=0.1, maxval=1.0)
    vsp = jax.random.uniform(keys[4], (n_pages, ps, kvh),
                             minval=0.1, maxval=1.0)
    bt = jnp.arange(1, b * n_per + 1, dtype=jnp.int32).reshape(b, n_per)
    pos = jax.random.randint(keys[5], (b,), s // 2, s, jnp.int32)
    return q, kp, ksp, vp, vsp, bt, pos


def test_tiered_kernel_bitexact_on_all_kv4():
    """With every tier id 0 the tiered kernel must reproduce the KV4
    kernel bit for bit — same dequant, same flash core, same order."""
    from repro.kernels.kv_attention import (kv4_paged_decode_attention,
                                            kv_tiered_paged_decode_attention)
    q, kp, ksp, vp, vsp, bt, pos = _paged_inputs(0)
    k2 = jnp.zeros((2,) + kp.shape[1:-1] + (kp.shape[-1] // 2,), jnp.int8)
    s2 = jnp.ones((2,) + ksp.shape[1:], jnp.float32)
    ref = kv4_paged_decode_attention(q, kp, ksp, vp, vsp, bt, pos)
    out = kv_tiered_paged_decode_attention(
        q, kp, ksp, vp, vsp, k2, s2, k2, s2, bt,
        jnp.zeros_like(bt), pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_tiered_kernel_demoted_page_equals_clamped_kv4():
    """A demoted page must read back exactly as its clamp image: the
    tiered kernel over {page demoted to KV2} equals the KV4 kernel over
    {page contents clamped to the int2 band} bit for bit (the dequant
    of both slabs yields elementwise-identical f32)."""
    from repro.core.packing import pack_plane, unpack_plane
    from repro.kernels.kv_attention import (kv4_paged_decode_attention,
                                            kv_tiered_paged_decode_attention)
    q, kp, ksp, vp, vsp, bt, pos = _paged_inputs(1)
    victim = int(bt[0, 0])                  # demote batch 0's first page

    def clamp_page(qp):
        nib = unpack_plane(qp[victim], width=4, signed=True)
        return qp.at[victim].set(
            pack_plane(jnp.clip(nib, KV2_LOW, KV2_HIGH), width=4))

    ref = kv4_paged_decode_attention(
        q, clamp_page(kp), ksp, clamp_page(vp), vsp, bt, pos)

    def demote_into(qp, slab_shape):
        nib = unpack_plane(qp[victim], width=4, signed=True)
        slab = jnp.zeros(slab_shape, jnp.int8)
        return slab.at[1].set(
            pack_plane(jnp.clip(nib, KV2_LOW, KV2_HIGH), width=2))

    shape2 = (2,) + kp.shape[1:-1] + (kp.shape[-1] // 2,)
    k2, v2 = demote_into(kp, shape2), demote_into(vp, shape2)
    s2k = jnp.ones((2,) + ksp.shape[1:], jnp.float32).at[1].set(ksp[victim])
    s2v = jnp.ones((2,) + vsp.shape[1:], jnp.float32).at[1].set(vsp[victim])
    tt = jnp.zeros_like(bt).at[0, 0].set(1)
    # the demoted block-table slot points at KV2 page 1; the KV4 id is
    # dead (the engine routes via tier ids, the kernel masks to null)
    out = kv_tiered_paged_decode_attention(
        q, kp, ksp, vp, vsp, k2, s2k, v2, s2v,
        bt.at[0, 0].set(1), tt, pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))



def test_tiered_kernel_skips_pages_past_context():
    """A demoted page inside the context, and table columns past each
    sequence's last context page pointed at NaN-scaled pages of both
    slabs (tier 0 and tier 1, in turn): the output equals that of the
    clean table (tail at the null page) bit for bit, so neither slab is
    read past the context."""
    from repro.kernels.kv_attention import (context_pages,
                                            kv_tiered_paged_decode_attention)
    ps = 32
    q, kp, ksp, vp, vsp, bt, _ = _paged_inputs(2, s=256, ps=ps)
    pos = np.array([100, 37], np.int32)     # 4 and 2 context pages
    n_ctx = context_pages(pos, ps)
    bt = np.array(bt)
    nan4 = int(bt[1, -1])                   # past row 1's context
    ksp, vsp = ksp.at[nan4].set(jnp.nan), vsp.at[nan4].set(jnp.nan)
    shape2 = (3,) + kp.shape[1:-1] + (kp.shape[-1] // 2,)
    k2 = jax.random.randint(jax.random.PRNGKey(5), shape2, -128, 128,
                            jnp.int8)
    v2 = jax.random.randint(jax.random.PRNGKey(6), shape2, -128, 128,
                            jnp.int8)
    s2 = jnp.full(shape2[:3], 0.5, jnp.float32).at[2].set(jnp.nan)
    tt = np.zeros_like(bt)
    bt[0, 1], tt[0, 1] = 1, 1               # demoted: KV2 page 1
    clean_bt, dirty_bt = bt.copy(), bt.copy()
    dirty_tt = tt.copy()
    for i in range(bt.shape[0]):
        cols = np.arange(n_ctx[i], bt.shape[1])
        clean_bt[i, cols] = 0
        dirty_bt[i, cols] = nan4
        dirty_bt[i, cols[::2]] = 2          # NaN-scaled KV2 page
        dirty_tt[i, cols[::2]] = 1

    def run(table, tiers):
        return np.asarray(kv_tiered_paged_decode_attention(
            q, kp, ksp, vp, vsp, k2, s2, v2, s2, jnp.asarray(table),
            jnp.asarray(tiers), jnp.asarray(pos)))

    clean = run(clean_bt, tt)
    assert np.isfinite(clean).all()
    np.testing.assert_array_equal(run(dirty_bt, dirty_tt), clean)


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

def _run_engine(qp, pool_cfg, gen=24):
    eng = Engine(CFG, qp, pool_config=pool_cfg,
                 sched_config=SchedulerConfig(
                     max_decode_batch=2, token_budget=32, prefill_chunk=8,
                     max_pages_per_seq=16))
    hs = [eng.submit(p, SamplingParams(max_new_tokens=gen))
          for p in ([1, 2, 3, 4, 5], [7, 8, 9])]
    eng.run()
    return eng, [h.out_tokens for h in hs], hs


@pytest.mark.slow
def test_engine_kv2_no_demotion_streams_bitexact(qparams):
    """An armed ladder that never demotes must be invisible: greedy
    streams byte-identical to the base engine (acceptance criterion)."""
    _, base, _ = _run_engine(qparams, PoolConfig(n_pages=32, page_size=4))
    eng, toks, _ = _run_engine(
        qparams, PoolConfig(n_pages=32, page_size=4, kv2_pages=8,
                            demote_after_steps=10**9))
    assert toks == base
    assert eng.pool.demotions == 0
    agg = eng.aggregate_stats()
    assert agg["pool_demotions"] == 0 and agg["kv_bytes_reclaimed"] == 0


@pytest.mark.slow
def test_engine_kv2_cold_sweep_demotes_and_accounts(qparams):
    eng, toks, hs = _run_engine(
        qparams, PoolConfig(n_pages=32, page_size=4, kv2_pages=8,
                            demote_after_steps=1, demote_min_sparsity=0.0))
    assert all(len(t) == 24 for t in toks)  # generation completed
    assert eng.pool.demotions > 0
    agg = eng.aggregate_stats()
    assert agg["pool_demotions"] == eng.pool.demotions
    assert agg["kv_bytes_reclaimed"] > 0
    assert sum(h.stats()["kv_demotions"] for h in hs) == eng.pool.demotions
    snap = eng.metrics_snapshot()
    assert "serving_pool_demotions_total" in snap
    assert "serving_pool_kv2_pages_used" in snap


@pytest.mark.slow
def test_engine_kv2_pressure_rung_prevents_eviction(qparams):
    """Under page pressure the ladder demotes before anyone is preempted:
    the tight pool that forces the base engine to evict drains without
    a single eviction when KV2 pages absorb the pressure."""
    base, _, _ = _run_engine(qparams, PoolConfig(n_pages=12, page_size=4))
    eng, toks, _ = _run_engine(
        qparams, PoolConfig(n_pages=12, page_size=4, kv2_pages=12,
                            demote_after_steps=10**9))  # pressure rung only
    assert base.pool.evictions > 0
    assert eng.pool.evictions == 0
    assert eng.pool.demotions > 0
    assert all(len(t) == 24 for t in toks)


def test_spec_engine_rejects_kv2(qparams):
    from repro.serving.spec_decode import SpecConfig, SpeculativeEngine
    with pytest.raises(NotImplementedError):
        SpeculativeEngine(CFG, qparams, spec=SpecConfig(gamma=2),
                          pool_config=PoolConfig(n_pages=32, page_size=4,
                                                 kv2_pages=8))


def test_attribute_steps_covers_tiered_decode(qparams):
    """Attribution must lower the kv2 decode step (its extra tier-table
    aval included) without error and register the decode phase."""
    eng, _, _ = _run_engine(
        qparams, PoolConfig(n_pages=32, page_size=4, kv2_pages=8,
                            demote_after_steps=10**9), gen=2)
    attr = eng.attribute_steps(hw=CPU_PEAKS)
    assert "decode" in attr.phases()
