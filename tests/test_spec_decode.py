"""Self-speculative decoding: pool truncation, msb_skip draft kernels,
multi-token verify bit-exactness, and spec-engine == base-engine token
equivalence at temperature 0 (plus rejection-sampling termination)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import packing as packing_lib
from repro.core.qlinear import (_dual_pass_matmul, msb_skip_active,
                                msb_skip_scope, quantize_model_params)
from repro.core.sparqle import encode, tile_population
from repro.models import model as M
from repro.models.schema import init_params
from repro.models.schema_builder import build_schema
from repro.serving import (Engine, PagedKVPool, PoolConfig, SamplingParams,
                           Scheduler, SchedulerConfig, SpecConfig,
                           SpeculativeEngine)

CFG = ModelConfig(name="tiny-serve", family="transformer", n_layers=2,
                  d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                  d_ff=64, vocab=128, dtype="float32")

# a second paged-supported config with a different scanned period (MoE
# every 2nd layer) — the "≥ 2 model configs" of the acceptance criteria
CFG_MOE = ModelConfig(name="tiny-moe-serve", family="moe", n_layers=4,
                      d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                      d_ff=64, vocab=64, dtype="float32", n_experts=4,
                      top_k=2, moe_every=2, moe_d_ff=32,
                      router_type="softmax")


def _qparams(cfg, seed=0):
    fp = init_params(build_schema(cfg), jax.random.PRNGKey(seed))
    return quantize_model_params(
        fp, w_bits=4, k_percent=50.0, clip_l=-8.0, clip_h=23.0,
        mode="sparqle", enable_clipping=True, tile_k=16)


@pytest.fixture(scope="module")
def qparams():
    return _qparams(CFG)


# ---------------------------------------------------------------------------
# PagedKVPool.truncate
# ---------------------------------------------------------------------------

def test_truncate_page_boundary_and_mid_page():
    pool = PagedKVPool(CFG, PoolConfig(n_pages=8, page_size=4))
    pages = pool.allocate(5, "r")                    # covers 20 tokens
    # page boundary: 8 tokens -> keep exactly 2 pages
    freed = pool.truncate("r", 8)
    assert freed == pages[2:]
    assert pool.pages_of("r") == pages[:2]
    # mid-page: 5 tokens -> a partially-filled page 2 is kept whole
    pool.allocate(3, "r")
    assert len(pool.pages_of("r")) == 5
    kept_before = pool.pages_of("r")
    freed = pool.truncate("r", 5)
    assert freed == kept_before[2:]
    assert pool.pages_of("r") == kept_before[:2]
    # truncating past the held range is a no-op
    assert pool.truncate("r", 100) == []
    assert pool.pages_of("r") == kept_before[:2]


def test_truncate_preserves_ownership_and_eviction_counters():
    pool = PagedKVPool(CFG, PoolConfig(n_pages=8, page_size=4))
    fired = []
    pool.on_evict = lambda owner, pgs: fired.append(owner)
    a = pool.allocate(3, "a")
    pool.allocate(2, "b")
    freed = pool.truncate("a", 4)                    # keep 1 page of a
    assert freed == a[1:]
    assert pool.evictions == 0 and fired == []       # not an eviction
    assert pool.pages_of("a") == a[:1]               # prefix order kept
    assert len(pool.pages_of("b")) == 2              # b untouched
    # freed pages are back in the free pool (FIFO: grab everything)
    c = pool.allocate(pool.num_free, "c")
    assert set(a[1:]) <= set(c)


def test_truncate_to_zero_removes_ownership_entry():
    pool = PagedKVPool(CFG, PoolConfig(n_pages=8, page_size=4))
    pages = pool.allocate(2, "r")
    assert pool.truncate("r", 0) == pages
    assert "r" not in pool._owned                    # no phantom owner
    assert pool.evict("r") == [] and pool.evictions == 0
    # unknown owner / negative count
    assert pool.truncate("ghost", 4) == []
    with pytest.raises(ValueError):
        pool.truncate("r", -1)


# ---------------------------------------------------------------------------
# msb_skip draft matmul == dequantizing the LSB plane alone
# ---------------------------------------------------------------------------

def test_msb_skip_matmul_exhaustive_nibbles():
    """All 256 int8 values through both kernel layouts with msb_skip: the
    output must equal the LSB4 plane's contribution alone (the
    acceptance-criterion sweep for the draft path)."""
    from repro.kernels.sparqle_matmul import (sparqle_matmul,
                                              sparqle_matmul_packed)
    x = jnp.arange(-128, 128, dtype=jnp.int8).reshape(2, 128).repeat(64, 0)
    w = jax.random.randint(jax.random.PRNGKey(1), (128, 128), -8, 8,
                           dtype=jnp.int8)
    asc = jnp.ones((128, 1)); wsc = jnp.ones((1, 128))
    a = encode(x)
    pop = tile_population(a.pbm, 128, 128)
    # oracle: dequantized LSB plane (values 0..15) times the weights
    ref = jnp.dot(a.lsb4.astype(jnp.int32),
                  w.astype(jnp.int32)).astype(jnp.float32)
    out = sparqle_matmul(a.lsb4, a.msb4, pop, w, asc, wsc, msb_skip=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    outp = sparqle_matmul_packed(
        packing_lib.pack_nibbles(a.lsb4), packing_lib.pack_nibbles(a.msb4),
        pop, w, asc, wsc, msb_skip=True)
    np.testing.assert_array_equal(np.asarray(outp), np.asarray(ref))


def test_msb_skip_ops_linear_and_xla_backend():
    from repro.core.quantize import quantize_weights
    from repro.kernels.ops import sparqle_linear
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 192))
    w = quantize_weights(
        jax.random.normal(jax.random.PRNGKey(3), (192, 96)) * 0.1,
        bits=4, axis=0)
    a = sparqle_linear(x, w, backend="pallas", msb_skip=True)
    b = sparqle_linear(x, w, backend="xla", msb_skip=True)
    full = sparqle_linear(x, w, backend="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5)
    assert np.abs(np.asarray(a) - np.asarray(full)).max() > 0


def test_msb_skip_scope_drives_dual_pass():
    """qlinear's trace-time scope: inside the scope the dual-pass matmul
    returns the dense LSB4 contribution alone (both wire formats)."""
    x = jnp.arange(-128, 128, dtype=jnp.int8).reshape(4, 64)
    w = jax.random.randint(jax.random.PRNGKey(5), (64, 32), -8, 8,
                           dtype=jnp.int8)
    a = encode(x)
    lsb_ref = jnp.dot(a.lsb4.astype(jnp.int32), w.astype(jnp.int32))
    assert not msb_skip_active()
    with msb_skip_scope():
        assert msb_skip_active()
        for wf in ("unpacked", "packed"):
            out = _dual_pass_matmul(x, w, batched=False, wire_format=wf,
                                    msb_skip=True)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(lsb_ref))
    assert not msb_skip_active()


# ---------------------------------------------------------------------------
# multi-token verify attention == loop of single-token paged decodes
# ---------------------------------------------------------------------------

def test_verify_attention_bitexact_vs_single_token_loop():
    from repro.kernels.kv_attention import (kv4_paged_decode_attention,
                                            kv4_paged_verify_attention)
    b, s, kvh, g, hd, ps, t = 2, 64, 2, 4, 32, 16, 3
    kq = jax.random.randint(jax.random.PRNGKey(1), (b, s, kvh, hd // 2),
                            -128, 128, jnp.int8)
    vq = jax.random.randint(jax.random.PRNGKey(2), (b, s, kvh, hd // 2),
                            -128, 128, jnp.int8)
    ks = jax.random.uniform(jax.random.PRNGKey(3), (b, s, kvh),
                            minval=0.1, maxval=1.0)
    vs = jax.random.uniform(jax.random.PRNGKey(4), (b, s, kvh),
                            minval=0.1, maxval=1.0)
    pos = jnp.asarray([5, 40], jnp.int32)
    n_per = s // ps
    # shuffled physical pages
    perm = np.random.RandomState(0).permutation(b * n_per) + 1
    kp = np.zeros((b * n_per + 1, ps, kvh, hd // 2), np.int8)
    vp = np.zeros_like(kp)
    ksp = np.zeros((b * n_per + 1, ps, kvh), np.float32)
    vsp = np.zeros_like(ksp)
    bt = np.zeros((b, n_per), np.int32)
    for i in range(b):
        for j in range(n_per):
            pid = int(perm[i * n_per + j])
            bt[i, j] = pid
            sl = slice(j * ps, (j + 1) * ps)
            kp[pid], vp[pid] = kq[i, sl], vq[i, sl]
            ksp[pid], vsp[pid] = ks[i, sl], vs[i, sl]
    args = (jnp.asarray(kp), jnp.asarray(ksp), jnp.asarray(vp),
            jnp.asarray(vsp), jnp.asarray(bt))
    qT = jax.random.normal(jax.random.PRNGKey(7), (b, t, kvh, g, hd))
    out = kv4_paged_verify_attention(qT, *args, pos)
    for i in range(t):
        single = kv4_paged_decode_attention(qT[:, i], *args, pos + i)
        np.testing.assert_array_equal(np.asarray(out[:, i]),
                                      np.asarray(single))



def test_verify_attention_skips_pages_past_each_window_token():
    """A window that crosses a page boundary (positions 14, 15, 16 with
    16-token pages): table columns past the window's last context page
    point at a page whose scales are NaN, and the output equals the clean
    table's bit for bit, so no window token reads them."""
    from repro.kernels.kv_attention import (context_pages,
                                            kv4_paged_verify_attention)
    b, s, kvh, g, hd, ps, t = 2, 64, 2, 4, 32, 16, 3
    n_per = s // ps
    n_pages = b * n_per + 2                  # null page 0, NaN page last
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    kp = jax.random.randint(keys[0], (n_pages, ps, kvh, hd // 2), -128,
                            128, jnp.int8)
    vp = jax.random.randint(keys[1], (n_pages, ps, kvh, hd // 2), -128,
                            128, jnp.int8)
    ksp = jax.random.uniform(keys[2], (n_pages, ps, kvh), minval=0.1,
                             maxval=1.0).at[-1].set(jnp.nan)
    vsp = jax.random.uniform(keys[3], (n_pages, ps, kvh), minval=0.1,
                             maxval=1.0).at[-1].set(jnp.nan)
    bt = np.arange(1, b * n_per + 1, dtype=np.int32).reshape(b, n_per)
    pos = np.array([14, 30], np.int32)
    dirty = bt.copy()
    for i in range(b):
        dirty[i, context_pages(pos[i] + t - 1, ps):] = n_pages - 1
    qT = jax.random.normal(keys[4], (b, t, kvh, g, hd))
    clean, out = (np.asarray(kv4_paged_verify_attention(
        qT, kp, ksp, vp, vsp, jnp.asarray(table), jnp.asarray(pos)))
        for table in (bt, dirty))
    assert np.isfinite(clean).all()
    np.testing.assert_array_equal(out, clean)

@pytest.mark.slow
@pytest.mark.parametrize("cfg", [
    CFG,
    # tight expert capacity: routed-MoE drops depend on the flat token
    # count, so this would diverge if the verify window batched all B*T
    # tokens into one dispatch instead of one call per window position
    CFG_MOE.replace(capacity_factor=0.5),
], ids=["dense", "moe-tight-capacity"])
def test_verify_window_paged_equals_decode_loop(cfg):
    """The full model-level verify window — logits AND written pool state —
    must reproduce a loop of single-token paged decode steps."""
    qp = _qparams(cfg)
    pool = PagedKVPool(cfg, PoolConfig(n_pages=8, page_size=4))
    pages = pool.allocate(4, "r")
    bt = np.zeros((2, 6), np.int32)
    bt[0, :4] = pages
    bt = jnp.asarray(bt)
    prompt = np.random.RandomState(0).randint(0, cfg.vocab, size=5)
    toks = jnp.asarray(prompt, jnp.int32)[None]
    lg, st, _ = M.prefill_chunk_paged(
        cfg, qp, pool.state, jnp.pad(toks, ((0, 0), (0, 3))),
        jnp.asarray(0, jnp.int32), jnp.asarray(5, jnp.int32), bt[:1])
    window = jnp.asarray([[int(jnp.argmax(lg, -1)[0]), 17, 42],
                          [3, 1, 4]], jnp.int32)
    pos = jnp.asarray([5, 0], jnp.int32)
    vlg, vstate, vtel = M.verify_window_paged(cfg, qp, st, window, pos, bt)
    st2 = st
    for t in range(3):
        lg1, st2, _ = M.decode_step_paged(cfg, qp, st2, window[:, t],
                                          pos + t, bt)
        np.testing.assert_array_equal(np.asarray(vlg[:, t]),
                                      np.asarray(lg1))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        vstate, st2)
    assert vtel["layer_wire_bytes"].shape == (cfg.n_layers, 2)


# ---------------------------------------------------------------------------
# scheduler accounting for draft windows
# ---------------------------------------------------------------------------

def test_scheduler_spec_budget_and_lookahead():
    """A speculative decode slot burns 2γ+1 budget tokens, pages grow to
    cover the draft window, and admission reserves the lookahead."""
    pool = PagedKVPool(CFG, PoolConfig(n_pages=32, page_size=4))
    sched = Scheduler(pool, SchedulerConfig(
        max_decode_batch=4, token_budget=10, prefill_chunk=8,
        max_pages_per_seq=8, decode_tokens_per_slot=5, decode_lookahead=2))
    a = sched.submit([1] * 4, SamplingParams(max_new_tokens=4), 0.0)
    pool.allocate(1, a.rid)
    a.prefilled = len(a.context)
    a.slot = sched._free_slots.pop(0)
    a.context.append(9)
    a.out_tokens.append(9)
    sched.to_running(a)
    b = sched.submit([2] * 20, SamplingParams(max_new_tokens=4), 1.0)
    plan = sched.schedule()
    assert plan.decode == [a]
    # pages cover pos + 1 + lookahead = 4 + 1 + 2 = 7 tokens -> 2 pages
    assert len(pool.pages_of(a.rid)) == 2
    # budget 10 - 1 slot * 5 = 5 -> b's chunk is capped at 5, not 8
    assert [(r.rid, start, n) for r, start, n in plan.prefill] == \
        [(b.rid, 0, 5)]
    # admission capacity reserves the lookahead: 8 pages * 4 = 32 slots;
    # 30 + 4 + lookahead 2 > 32 must be rejected
    with pytest.raises(ValueError):
        sched.submit([0] * 30, SamplingParams(max_new_tokens=4), 2.0)


# ---------------------------------------------------------------------------
# speculative engine vs base engine
# ---------------------------------------------------------------------------

def _run_engines(cfg, qp, prompts, gen, gamma, temperature=0.0):
    def mk(spec):
        kw = dict(
            pool_config=PoolConfig(n_pages=32, page_size=4),
            sched_config=SchedulerConfig(max_decode_batch=4,
                                         token_budget=64, prefill_chunk=32,
                                         max_pages_per_seq=16))
        if spec:
            return SpeculativeEngine(cfg, qp, spec=SpecConfig(gamma=gamma),
                                     **kw)
        return Engine(cfg, qp, **kw)

    outs = []
    for spec in (False, True):
        eng = mk(spec)
        hs = [eng.submit(p, SamplingParams(max_new_tokens=gen,
                                           temperature=temperature))
              for p in prompts]
        eng.run()
        outs.append((eng, hs))
    return outs


@pytest.mark.slow
@pytest.mark.parametrize("cfg,seed", [(CFG, 0), (CFG_MOE, 1)])
def test_spec_engine_greedy_matches_base_engine(cfg, seed):
    """Temperature-0 speculative decoding is byte-identical to the
    non-speculative engine across two model configs (the correctness
    anchor of the subsystem)."""
    qp = _qparams(cfg, seed)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab, size=n).tolist()
               for n in (12, 7, 19)]
    (base, base_hs), (spec, spec_hs) = _run_engines(cfg, qp, prompts,
                                                    gen=8, gamma=2)
    for hb, hs in zip(base_hs, spec_hs):
        assert hb.out_tokens == hs.out_tokens
        assert hs.n_generated == 8
        st = hs.stats()
        assert st["spec_tokens_per_step"] >= 1.0
        assert 0.0 <= st["spec_acceptance_rate"] <= 1.0
    # everything released after the speculative windows + truncations
    assert spec.pool.num_free == spec.pool.n_usable_pages
    agg = spec.aggregate_stats()
    assert agg["spec_gamma"] == 2
    assert agg["spec_tokens_per_step"] >= 1.0


@pytest.mark.slow
def test_spec_engine_rejection_sampling_terminates(qparams):
    """Temperature > 0 exercises the rejection-sampling acceptance path:
    every request terminates with exact n_generated accounting and sane
    draft bookkeeping."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, CFG.vocab, size=n).tolist() for n in (10, 5)]
    gen = 7
    (_, base_hs), (spec, spec_hs) = _run_engines(
        CFG, qparams, prompts, gen=gen, gamma=2, temperature=0.8)
    for h in spec_hs:
        assert h.done and h.n_generated == gen
        assert all(0 <= t < CFG.vocab for t in h.out_tokens)
        st = h.stats()
        assert 0.0 <= st["spec_acceptance_rate"] <= 1.0
        assert st["spec_tokens_per_step"] >= 1.0
        # every generated token after the prefill one came from a cycle
        assert h.spec_emitted == gen - 1
        assert h.draft_accepted <= h.draft_proposed
    assert spec.pool.num_free == spec.pool.n_usable_pages


@pytest.mark.slow
def test_spec_engine_draft_friendly_acceptance_band():
    """On the bench's draft-friendly model the LSB4-only draft is a real
    predictor: acceptance strictly inside (0, 1) and > 1 token per cycle,
    while the greedy stream still matches the non-speculative engine —
    i.e. the draft is genuinely sub-precision, not silently full."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    import bench_serving as B
    cfg = B.BENCH_CFG
    fp = B.draft_friendly_params(cfg, seed=0)
    qp = quantize_model_params(
        fp, w_bits=4, k_percent=50.0, clip_l=-8.0, clip_h=23.0,
        mode="sparqle", enable_clipping=True, tile_k=16)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, size=int(n)).tolist()
               for n in rng.randint(8, 24, 6)]
    (_, base_hs), (spec, spec_hs) = _run_engines(cfg, qp, prompts,
                                                 gen=10, gamma=2)
    for hb, hs in zip(base_hs, spec_hs):
        assert hb.out_tokens == hs.out_tokens
    agg = spec.aggregate_stats()
    assert 0.0 < agg["spec_acceptance_rate"] < 1.0
    assert agg["spec_tokens_per_step"] > 1.0


# ---------------------------------------------------------------------------
# cost model: speculative rounds
# ---------------------------------------------------------------------------

def test_costmodel_expected_tokens_and_draft_rounds():
    from repro.core.costmodel import (PAPER_MODELS, breakeven_acceptance,
                                      evaluate_speculative,
                                      expected_tokens_per_step)
    assert expected_tokens_per_step(0.0, 3) == 1.0
    assert expected_tokens_per_step(1.0, 3) == 4.0
    np.testing.assert_allclose(expected_tokens_per_step(0.5, 3), 1.875)
    with pytest.raises(ValueError):
        expected_tokens_per_step(1.5, 2)

    m = PAPER_MODELS["llama2-7b"]
    r = evaluate_speculative(m, 0.47, 2, 0.8)
    # the draft forward is 1 round vs 1 + (1 - s): strictly fewer MACs
    # (aggregate over the decode stack; act-act attention ops identical)
    assert r.draft_step.compute_macs < r.baseline_step.compute_macs
    # ... and strictly fewer streamed activation bytes
    assert r.draft_step.load_bytes < r.baseline_step.load_bytes
    # on a single eligible linear the ratio is exactly 1 / (2 - s)
    from repro.core.costmodel import HardwareConfig, LinearShape, linear_cost
    shape = LinearShape("l", 16, 4096, 4096, 4, 0.47)
    hw = HardwareConfig()
    full = linear_cost(shape, hw, sparqle=True)
    draft = linear_cost(shape, hw, sparqle=True, lsb_only=True)
    np.testing.assert_allclose(draft.compute_macs / full.compute_macs,
                               1.0 / (2.0 - 0.47))
    # E[tokens] amortization: speedup strictly increases with alpha
    speedups = [evaluate_speculative(m, 0.47, 2, a).tpot_speedup
                for a in (0.0, 0.5, 0.9)]
    assert speedups[0] < speedups[1] < speedups[2]
    # under the §4 restreaming dataflow the draft still pays the full
    # weight stream: at the paper's operating point γ-drafting cannot
    # win TPOT at any acceptance rate — the model says so honestly
    assert breakeven_acceptance(m, 0.47, 2) == float("inf")
