"""Serving-engine benchmark: continuous batching under a Poisson trace.

Drives the paged-pool engine with a Poisson request-arrival process
(exponential inter-arrival gaps, mixed prompt/generation lengths) and
reports the serving quantities the paper's system story turns on:
generation throughput, TTFT and TPOT distributions, achieved decode-time
MSB4 sub-precision sparsity, and pool pressure (evictions). Timings are
CPU interpret-mode — structural comparison only, not TPU numbers.

Arrivals are *step-indexed* (a request arrives before engine step
``ceil(t / step_dt)``), so for a fixed ``--seed`` the admission order,
the batch composition of every step, and therefore every token stream
are exactly reproducible run to run — wall-clock only feeds the timing
metrics.

A KV2 precision-ladder section always rides along (prefix
``serving_kv2``): a long-context trace on the wide-head ``KV2_CFG`` run
with the ladder disarmed, armed-but-idle (stream must match the
disarmed run byte for byte — ``serving_kv2/tokens_match_no_demotion``
is a hard invariant), and with an aggressive cold sweep, reporting
demotion/promotion counts and the peak fraction of KV HBM reclaimed
(``serving_kv2/hbm_reclaimed_pct``, floored at 25% by the bench).

``--spec-gamma N`` additionally runs the self-speculative engine
(``serving/spec_decode.py``: γ LSB4-only draft steps + one batched
full-precision verify) over the SAME trace and model, reporting draft
acceptance rate, mean emitted tokens per draft+verify cycle, and TPOT
for both engines — at temperature 0 the two token streams must be
byte-identical (``serving/spec_tokens_match``).

The bench model is *draft-friendly* (``draft_friendly_params``): a
non-negative residual stream with a scale-carrier dimension whose weight
rows are zeroed, so most activations are genuinely sub-precision and the
LSB4-only draft is a good-but-imperfect predictor — acceptance lands
strictly between 0 and 1 instead of the ~1/vocab chance agreement an
unstructured random init gives the draft.

    PYTHONPATH=src python -m benchmarks.bench_serving          # smoke
    PYTHONPATH=src python -m benchmarks.bench_serving --requests 16
    PYTHONPATH=src python -m benchmarks.bench_serving --spec-gamma 2
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.costmodel import HardwareConfig
from repro.core.qlinear import quantize_model_params
from repro.launch.runtime import use_compile_cache
from repro.models.schema import init_params
from repro.models.schema_builder import build_schema
from repro.serving import (Engine, PoolConfig, SamplingParams,
                           SchedulerConfig, SpecConfig, SpeculativeEngine)

# this bench runs on CPU, which has no entry in costmodel.DEVICE_PEAKS:
# its roofline ratios are taken against the v5e reference peaks, named
# as such in the emitted labels
BENCH_PEAKS = HardwareConfig()

# 8 q-heads / 4 kv-heads so the same bench model shards up to 4-way on
# the model axis (--mesh): n_kv_heads, d_ff and vocab all divide
BENCH_CFG = ModelConfig(
    name="bench-serve-2l", family="transformer", n_layers=2, d_model=64,
    n_heads=8, n_kv_heads=4, head_dim=8, d_ff=128, vocab=64,
    rope_theta=10_000.0, dtype="float32")

# KV2 precision-ladder section: a wide-head long-context variant. The
# per-page HBM split is what matters here — at head_dim=32 the packed
# nibbles dominate the f32 scales (KV4 page = 20 bytes/token-head vs
# KV2 = 12), so a demoted page reclaims 40% of its bytes, vs only 25%
# at BENCH_CFG's head_dim=8 where scales are half the page.
KV2_CFG = ModelConfig(
    name="bench-kv2-2l", family="transformer", n_layers=2, d_model=64,
    n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128, vocab=64,
    rope_theta=10_000.0, dtype="float32")

STEP_DT = 0.05          # virtual seconds per engine step (admission clock)


def draft_friendly_params(cfg: ModelConfig, seed: int = 0,
                          n_spikes: int = 12, spike_lo: float = 0.12,
                          spike_hi: float = 0.9):
    """Float params whose activations are genuinely sub-precision sparse.

    Construction (per layer): the residual stream is kept NON-NEGATIVE
    (positive embeddings; positive wv/wo/w_gate/w_up/w_down so attention
    and SwiGLU outputs stay positive), and hidden dim 0 is a *scale
    carrier* — a large constant that pins every per-token int8
    quantization scale. Every weight matrix's row 0 is zeroed, so the
    carrier's (always nonzero) MSB nibble contributes nothing to any
    projection. The embedding-dominated layer-0 stream is then genuinely
    sub-precision (~0.88 measured) and the draft near-exact there; the
    ``n_spikes`` spike dims per token in [spike_lo, spike_hi] plus the
    attention-mixed deeper streams give the draft real MSB mass to drop.
    Tuning the spike density sets the measured draft acceptance rate
    strictly inside (0, 1) — the machinery the bench measures, well
    above the ~1/vocab chance floor an unstructured init gives.
    """
    rng = np.random.RandomState(seed)
    params = init_params(build_schema(cfg), jax.random.PRNGKey(seed))
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab
    kvd = cfg.n_kv_heads * cfg.hd

    def pos(shape, s):
        return jnp.asarray(np.abs(rng.randn(*shape)) * s, jnp.float32)

    def anysign(shape, s):
        return jnp.asarray(rng.randn(*shape) * s, jnp.float32)

    def carrier_col(w, s=0.3):
        col = jnp.asarray(np.abs(rng.randn(w.shape[0] - 1)) * s, jnp.float32)
        return w.at[0].set(0.0).at[1:, 0].set(col)

    emb = np.abs(rng.randn(v, d)) * 0.05
    for t in range(v):
        dims = rng.choice(np.arange(1, d), size=n_spikes, replace=False)
        emb[t, dims] = rng.uniform(spike_lo, spike_hi, size=n_spikes)
    emb[:, 0] = 1.0
    params["embed"]["table"] = jnp.asarray(emb, jnp.float32)

    def fix_stage(p):
        out = dict(p)
        n_l = p["wq"].shape[0]

        def rep(maker):
            return jnp.stack([maker() for _ in range(n_l)])

        out["wq"] = rep(lambda: anysign((d, d), 0.1).at[0].set(0.0))
        out["wk"] = rep(lambda: anysign((d, kvd), 0.1).at[0].set(0.0))
        out["wv"] = rep(lambda: carrier_col(pos((d, kvd), 0.02)))
        out["wo"] = rep(lambda: pos((d, d), 0.01).at[0].set(0.0))
        out["w_gate"] = rep(lambda: carrier_col(pos((d, f), 0.02)))
        out["w_up"] = rep(lambda: carrier_col(pos((d, f), 0.02)))
        out["w_down"] = rep(lambda: pos((f, d), 0.01).at[0].set(0.0))
        return out

    for stage in params["stages"].values():
        for pk, p in stage.items():
            stage[pk] = fix_stage(p)
    params["lm_head"] = anysign((d, v), 1.0).at[0].set(0.0)
    return params


def _poisson_trace(rng: np.random.Generator, n: int, rate_hz: float):
    """[(arrival_step, prompt, max_new), ...] sorted by arrival."""
    t = 0.0
    out = []
    for _ in range(n):
        t += rng.exponential(1.0 / rate_hz)
        plen = int(rng.integers(8, 48))
        gen = int(rng.integers(4, 12))
        out.append((int(np.ceil(t / STEP_DT)),
                    rng.integers(0, BENCH_CFG.vocab, plen).tolist(), gen))
    return out


def _drive(eng, trace):
    """Step-indexed open loop: deterministic admission, wall-clock stats."""
    handles = []
    i = 0
    t0 = time.monotonic()
    step = 0
    while i < len(trace) or eng.sched.has_work():
        while i < len(trace) and trace[i][0] <= step:
            _, prompt, gen = trace[i]
            handles.append(eng.submit(
                prompt, SamplingParams(max_new_tokens=gen)))
            i += 1
        if eng.sched.has_work():
            eng.step()
        step += 1
    return handles, time.monotonic() - t0


def _drive_kv2(eng, trace):
    """_drive plus per-step tracking of the peak fraction of KV HBM
    bytes reclaimed by demotion: pool.kv_bytes_saved() over what the
    held pages would cost all-KV4 (saved + held-at-current-tier)."""
    handles = []
    i = 0
    t0 = time.monotonic()
    step = 0
    peak = 0.0
    while i < len(trace) or eng.sched.has_work():
        while i < len(trace) and trace[i][0] <= step:
            _, prompt, gen = trace[i]
            handles.append(eng.submit(
                prompt, SamplingParams(max_new_tokens=gen)))
            i += 1
        if eng.sched.has_work():
            eng.step()
            saved = eng.pool.kv_bytes_saved()
            if saved:
                peak = max(peak,
                           saved / (saved + eng.pool.kv_bytes_held()))
        step += 1
    return handles, time.monotonic() - t0, peak


def _run_kv2_ladder(emit, engines, seed: int):
    """KV2 precision-ladder section (docs/serving.md §precision ladder):
    a long-context trace on the wide-head ``KV2_CFG``, run three ways —
    ladder disarmed, armed-but-never-demoting (streams must match the
    disarmed run byte for byte), and an aggressive cold sweep
    (``demote_after_steps=1``, sparsity floor disabled) measuring how
    much KV HBM demotion reclaims. The trace fits the pool, so the
    pressure rung never fires and every counter is deterministic."""
    params = draft_friendly_params(KV2_CFG, seed=seed)
    qparams = quantize_model_params(
        params, w_bits=4, k_percent=50.0, clip_l=-8.0, clip_h=23.0,
        mode="sparqle", enable_clipping=True, tile_k=16)
    rng = np.random.default_rng(seed + 1)
    t = 0.0
    trace = []
    for _ in range(3):
        t += rng.exponential(1.0)
        plen = int(rng.integers(48, 64))
        gen = int(rng.integers(24, 32))
        trace.append((int(np.ceil(t / STEP_DT)),
                      rng.integers(0, KV2_CFG.vocab, plen).tolist(), gen))
    sched = SchedulerConfig(max_decode_batch=4, token_budget=96,
                            prefill_chunk=32, max_pages_per_seq=12)

    def make(kv2_pages: int, **kw):
        eng = Engine(KV2_CFG, qparams,
                     pool_config=PoolConfig(n_pages=24, page_size=16,
                                            kv2_pages=kv2_pages, **kw),
                     sched_config=sched)
        eng.attribute_steps(hw=BENCH_PEAKS)
        return eng

    base = make(0)
    base_handles, _, _ = _drive_kv2(base, trace)
    nod = make(24, demote_after_steps=10**9)
    nod_handles, _, _ = _drive_kv2(nod, trace)
    match = (nod.pool.demotions == 0 and
             all(hb.out_tokens == hn.out_tokens
                 for hb, hn in zip(base_handles, nod_handles)))
    emit("serving_kv2/tokens_match_no_demotion", int(match),
         "armed-but-idle ladder greedy stream byte-identical to the "
         "disarmed engine (and genuinely demoted nothing)")

    eng = make(24, demote_after_steps=1, demote_min_sparsity=0.0)
    engines["serving_kv2"] = eng
    handles, wall, peak = _drive_kv2(eng, trace)
    _report(emit, "serving_kv2", handles, wall, eng)
    agg = eng.aggregate_stats()
    emit("serving_kv2/demotions", agg["pool_demotions"],
         "pages re-encoded KV4 -> KV2 (cold sweep)")
    emit("serving_kv2/promotions", agg["pool_promotions"],
         "demoted pages promoted back on touch")
    emit("serving_kv2/kv_bytes_reclaimed", agg["kv_bytes_reclaimed"],
         "cumulative KV HBM bytes freed by demotion events")
    emit("serving_kv2/hbm_reclaimed_pct", peak * 100.0,
         "peak % of held KV HBM reclaimed by demotion (vs all-KV4)")


def _make_engine(cfg, qparams, spec_gamma: int, mesh=None, slos=None):
    pool = PoolConfig(n_pages=48, page_size=16)
    sched = SchedulerConfig(max_decode_batch=8, token_budget=96,
                            prefill_chunk=32, max_pages_per_seq=8)
    if spec_gamma > 0:
        eng = SpeculativeEngine(cfg, qparams, pool_config=pool,
                                sched_config=sched,
                                spec=SpecConfig(gamma=spec_gamma),
                                mesh=mesh, slos=slos)
    else:
        eng = Engine(cfg, qparams, pool_config=pool, sched_config=sched,
                     mesh=mesh, slos=slos)
    # attribute at warm-up, before the driven trace: the compiled-HLO
    # costs feed the roofline/drift joins that _report reads back
    eng.attribute_steps(hw=BENCH_PEAKS)
    return eng


def _report(emit, prefix, handles, wall, eng):
    agg = eng.aggregate_stats()
    stats = [h.stats() for h in handles]
    n_tok = sum(s["n_generated"] for s in stats)
    ttft = np.array([s["ttft_s"] for s in stats])
    tpot = np.array([s["tpot_s"] for s in stats])
    tpot = tpot[np.isfinite(tpot)]
    spars = np.array([s["act_sparsity"] for s in stats])
    emit(f"{prefix}/requests", len(handles), "Poisson trace")
    emit(f"{prefix}/gen_tokens", n_tok, "total generated")
    emit(f"{prefix}/throughput_tok_s", n_tok / wall, "CPU interpret")
    emit(f"{prefix}/ttft_mean_ms", float(ttft.mean() * 1e3),
         "arrival->1st tok")
    emit(f"{prefix}/ttft_p95_ms", float(np.percentile(ttft, 95) * 1e3), "")
    emit(f"{prefix}/tpot_mean_ms", float(tpot.mean() * 1e3),
         "inter-token latency")
    # histogram-estimated percentiles from the metrics registry — the
    # same numbers a production scrape would see (bucket-interpolated,
    # so coarser than the exact per-request arrays above)
    r = eng.obs.registry
    for hname, key in (("serving_ttft_seconds", "ttft"),
                       ("serving_tpot_seconds", "tpot")):
        hist = r.get(hname)
        for q in (50, 99):
            p = hist.percentile(q)
            emit(f"{prefix}/{key}_p{q}_ms", float(p * 1e3),
                 f"registry histogram estimate, {hist.count()} samples")
    emit(f"{prefix}/act_sparsity_pct", float(spars.mean() * 100),
         "decode-time MSB4 sub-precision sparsity")
    if "wire_compression_pct" in agg:
        emit(f"{prefix}/wire_compression_pct", agg["wire_compression_pct"],
             "MEASURED packed-wire activation bytes saved vs dense int8")
        emit(f"{prefix}/wire_bytes_per_token",
             float(sum(agg["layer_wire_bytes_per_token"])),
             "measured bytes/token, inter-layer hidden stream, all layers")
    emit(f"{prefix}/engine_steps", agg["steps"], "continuous-batching steps")
    emit(f"{prefix}/pool_evictions", agg["pool_evictions"],
         "preemptions under page pressure")
    # compiled-HLO attribution joined with measured step times
    # (aggregate_stats above refreshed the gauges, so these are current)
    if eng._attr is not None:
        for phase in eng._attr.phases():
            emit(f"{prefix}/attr_{phase}_flops_per_step",
                 r.value("serving_step_attr_flops", phase=phase),
                 "dot FLOPs per engine step, compiled HLO")
            emit(f"{prefix}/attr_{phase}_hbm_bytes_per_step",
                 r.value("serving_step_attr_hbm_bytes", phase=phase),
                 "operand+result bytes per engine step, compiled HLO")
            emit(f"{prefix}/roofline_{phase}_compute_util",
                 r.value("serving_roofline_compute_util_ratio",
                         phase=phase),
                 "achieved FLOP/s vs v5e reference peak_flops")
            emit(f"{prefix}/roofline_{phase}_memory_util",
                 r.value("serving_roofline_memory_util_ratio",
                         phase=phase),
                 "achieved HBM bytes/s vs v5e reference hbm_bw")
            emit(f"{prefix}/drift_{phase}_latency_ratio",
                 r.value("serving_costmodel_latency_drift_ratio",
                         phase=phase),
                 "measured step s / costmodel.phase_cost prediction")
        emit(f"{prefix}/drift_wire_ratio",
             r.value("serving_costmodel_wire_drift_ratio"),
             "measured wire bytes/token / Eq.1 prediction (~1.0)")
    if eng.slo is not None:
        emit(f"{prefix}/slo_violations",
             sum(eng.slo.violations().values()),
             "edge-triggered SLO violation events across all SLOs")
    return float(tpot.mean() * 1e3) if len(tpot) else float("nan")


def run(emit, n_requests: int = 8, rate_hz: float = 2.0, seed: int = 0,
        spec_gamma: int = 0, mesh=None, slos=None):
    """Run the bench; returns {prefix: engine} for artifact export.

    ``slos`` — list of ``repro.obs.slo.SLO``; every engine gets its own
    monitor (fresh windows), and each prefix reports its violation
    count. SLO objects are stateless declarations, safe to share.
    """
    cfg = BENCH_CFG
    params = draft_friendly_params(cfg, seed=seed)
    qparams = quantize_model_params(
        params, w_bits=4, k_percent=50.0, clip_l=-8.0, clip_h=23.0,
        mode="sparqle", enable_clipping=True, tile_k=16)
    trace = _poisson_trace(np.random.default_rng(seed), n_requests, rate_hz)

    engines = {}
    eng = _make_engine(cfg, qparams, 0, slos=slos)
    engines["serving"] = eng
    handles, wall = _drive(eng, trace)
    base_tpot = _report(emit, "serving", handles, wall, eng)

    # KV2 precision ladder: its own long-context config + trace
    # (unsharded by design — the ladder's host bookkeeping is single-pool)
    _run_kv2_ladder(emit, engines, seed)

    jmesh = None
    if mesh is not None:
        from repro.launch.mesh import make_smoke_mesh
        jmesh = make_smoke_mesh(data=mesh[0], model=mesh[1])
        meng = _make_engine(cfg, qparams, 0, mesh=jmesh, slos=slos)
        engines["serving_mesh"] = meng
        mesh_handles, mesh_wall = _drive(meng, trace)
        _report(emit, "serving_mesh", mesh_handles, mesh_wall, meng)
        match = all(hb.out_tokens == hm.out_tokens
                    for hb, hm in zip(handles, mesh_handles))
        emit("serving_mesh/tokens_match_single_device", int(match),
             f"sharded {mesh[0]}x{mesh[1]} greedy stream byte-identical "
             f"to the single-device engine")

    if spec_gamma <= 0:
        return engines
    spec_eng = _make_engine(cfg, qparams, spec_gamma, mesh=jmesh,
                            slos=slos)
    engines["serving_spec"] = spec_eng
    spec_handles, spec_wall = _drive(spec_eng, trace)
    agg = spec_eng.aggregate_stats()
    spec_tpot = _report(emit, "serving_spec", spec_handles, spec_wall,
                        spec_eng)
    emit("serving_spec/gamma", spec_gamma, "draft tokens per verify cycle")
    emit("serving_spec/acceptance_rate",
         agg.get("spec_acceptance_rate", float("nan")),
         "LSB4-only drafts accepted by full-precision verify")
    emit("serving_spec/tokens_per_step",
         agg.get("spec_tokens_per_step", float("nan")),
         "emitted tokens per draft+verify cycle (incl. correction)")
    emit("serving_spec/tpot_vs_base",
         spec_tpot / base_tpot if base_tpot else float("nan"),
         "spec TPOT / baseline TPOT on the same trace (<1 = faster)")
    match = all(hb.out_tokens == hs.out_tokens
                for hb, hs in zip(handles, spec_handles))
    emit("serving_spec/tokens_match_baseline", int(match),
         "greedy spec stream byte-identical to non-speculative engine")
    return engines


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="Poisson arrival rate (req/s of virtual time)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spec-gamma", type=int, default=0,
                    help="also run the self-speculative engine with this "
                         "draft window on the same trace")
    ap.add_argument("--mesh", default="",
                    help="DATA,MODEL: also run the mesh-sharded engine "
                         "on the same trace and assert its greedy stream "
                         "matches the single-device engine (needs "
                         "data*model jax devices; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--slo", default="",
                    help="comma-separated SLO specs watched by every "
                         "engine (e.g. 'ttft:p95<5,queue_depth:p50<16'); "
                         "each prefix reports its violation count as "
                         "<prefix>/slo_violations")
    ap.add_argument("--slo-fail", action="store_true",
                    help="exit nonzero if any SLO fired on any engine")
    ap.add_argument("--history", default="",
                    help="append this run's provenance-stamped result to "
                         "the given perf-history JSONL (benchmarks/"
                         "perf_history.py schema)")
    ap.add_argument("--json", default="",
                    help="also write {meta, metrics} to this path — the "
                         "machine-readable result the CI regression gate "
                         "compares against benchmarks/baselines/"
                         "serving.json (benchmarks/check_regression.py)")
    ap.add_argument("--metrics-out", default="",
                    help="write each engine's metrics-registry snapshot "
                         "(JSON, {prefix: snapshot}) to this path")
    ap.add_argument("--trace-out", default="",
                    help="write the base engine's Chrome trace-event "
                         "JSON here — load in Perfetto / chrome://tracing")
    args = ap.parse_args()
    use_compile_cache()
    mesh = None
    if args.mesh:
        d, m = (int(v) for v in args.mesh.split(","))
        mesh = (d, m)

    records = {}

    def emit(name, value, desc):
        records[name] = float(value)
        print(f"{name},{value:.6g},{desc}", flush=True)

    from repro.obs.slo import parse_slo_list
    slos = parse_slo_list(args.slo)

    engines = run(emit, n_requests=args.requests, rate_hz=args.rate,
                  seed=args.seed, spec_gamma=args.spec_gamma, mesh=mesh,
                  slos=slos)

    for pfx, eng in engines.items():
        if eng.slo is None:
            continue
        for rep in eng.slo.report():
            state = "VIOLATING" if rep["violating"] else "ok"
            print(f"# {pfx} SLO {rep['slo']}: p{rep['percentile']:g} = "
                  f"{rep['value']:.4g} {rep['unit']} (target "
                  f"{rep['target']:g}) [{state}], "
                  f"{rep['violations']} violation(s)", flush=True)

    # stream-match metrics are hard invariants, not observations: the CI
    # smoke steps rely on a nonzero exit when equivalence breaks
    broken = [k for k, v in records.items()
              if k.endswith(("tokens_match_baseline",
                             "tokens_match_single_device",
                             "tokens_match_no_demotion")) and v != 1.0]
    # the ladder must genuinely reclaim KV HBM on the long-context
    # config — a silent demotion-policy regression fails the bench
    reclaimed = records.get("serving_kv2/hbm_reclaimed_pct")
    if reclaimed is not None and reclaimed < 25.0:
        broken.append(
            f"serving_kv2/hbm_reclaimed_pct={reclaimed:.1f} < 25")

    payload = None
    if args.json or args.history:
        from benchmarks.common import provenance_meta
        payload = {
            "meta": {"bench": "bench_serving", "config": BENCH_CFG.name,
                     "requests": args.requests, "rate_hz": args.rate,
                     "seed": args.seed, "spec_gamma": args.spec_gamma,
                     "mesh": list(mesh) if mesh else None,
                     "slo": args.slo or None,
                     **provenance_meta(BENCH_CFG)},
            "metrics": records,
        }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.json}", flush=True)
    if args.history:
        from benchmarks.perf_history import append_record
        append_record(args.history, payload)
        print(f"appended to {args.history}", flush=True)

    if args.metrics_out:
        snaps = {pfx: eng.metrics_snapshot()
                 for pfx, eng in engines.items()}
        with open(args.metrics_out, "w") as f:
            json.dump(snaps, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.metrics_out}", flush=True)
    if args.trace_out:
        engines["serving"].obs.tracer.export_chrome(args.trace_out)
        print(f"wrote {args.trace_out}", flush=True)

    if broken:
        raise SystemExit(f"token-stream equivalence FAILED: {broken}")
    if args.slo_fail:
        fired = {pfx: eng.slo.violations()
                 for pfx, eng in engines.items()
                 if eng.slo is not None
                 and any(eng.slo.violations().values())}
        if fired:
            raise SystemExit(f"SLO violations: {fired}")


if __name__ == "__main__":
    main()
