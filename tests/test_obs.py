"""Observability stack: metrics registry, tracer, engine instrumentation.

Fast tests cover the pure ``repro.obs`` machinery (registry semantics,
histogram math, Chrome-trace export, artifact validators) plus the
fault-loop registry wiring and the NaN-guard edges of ``Request.stats``.
The ``@pytest.mark.slow`` tests drive a real (tiny) engine with an
injected deterministic clock and pin the schema contracts downstream
tooling depends on: ``aggregate_stats()`` / ``Request.stats()`` /
registry-snapshot key sets, latency-histogram consistency with the
mean-based per-request stats, and Perfetto validity of the exported
trace.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.qlinear import quantize_model_params
from repro.models.schema import init_params
from repro.models.schema_builder import build_schema
from repro.obs import (DEFAULT_LATENCY_BUCKETS, MetricsRegistry,
                       Observability, Tracer)
from repro.obs.validate import validate_chrome_trace, validate_snapshot
from repro.serving import (Engine, SamplingParams, SpecConfig,
                           SpeculativeEngine)
from repro.serving.scheduler import Request

CFG = ModelConfig(name="tiny-serve", family="transformer", n_layers=2,
                  d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                  d_ff=64, vocab=128, dtype="float32")


@pytest.fixture(scope="module")
def qparams():
    fparams = init_params(build_schema(CFG), jax.random.PRNGKey(0))
    return quantize_model_params(
        fparams, w_bits=4, k_percent=50.0, clip_l=-8.0, clip_h=23.0,
        mode="sparqle", enable_clipping=True, tile_k=16)


class FakeClock:
    """Deterministic monotonic clock: every read advances by ``dt``."""

    def __init__(self, dt=0.001):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_and_gauge_basics():
    r = MetricsRegistry()
    c = r.counter("reqs_total", "requests", unit="requests")
    c.inc()
    c.inc(2.5)
    assert r.value("reqs_total") == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("queue_depth", "waiting", unit="requests")
    g.set(4)
    g.inc(2)
    assert r.value("queue_depth") == 6.0


def test_labels_declared_and_enforced():
    r = MetricsRegistry()
    c = r.counter("tokens_total", "t", unit="tokens",
                  labelnames=("phase",))
    c.inc(3, phase="prefill")
    c.inc(1, phase="decode")
    assert c.value(phase="prefill") == 3.0
    with pytest.raises(ValueError):
        c.inc(1)                          # missing label
    with pytest.raises(ValueError):
        c.inc(1, phase="x", shard="0")    # undeclared label


def test_metric_name_and_unit_validation():
    r = MetricsRegistry()
    for bad in ("Bad", "0start", "has-dash", "has space", ""):
        with pytest.raises(ValueError):
            r.counter(bad, unit="1")
    with pytest.raises(ValueError):
        r.counter("no_unit", unit="")
    with pytest.raises(ValueError):
        r.counter("bad_label", unit="1", labelnames=("Nope",))


def test_reregister_create_or_get():
    r = MetricsRegistry()
    a = r.counter("dup_total", "x", unit="tokens")
    assert r.counter("dup_total", "x", unit="tokens") is a
    with pytest.raises(ValueError):
        r.gauge("dup_total", unit="tokens")           # kind mismatch
    with pytest.raises(ValueError):
        r.counter("dup_total", unit="bytes")          # unit mismatch
    with pytest.raises(ValueError):
        r.counter("dup_total", unit="tokens", labelnames=("a",))


# ---------------------------------------------------------------------------
# histogram math
# ---------------------------------------------------------------------------

def test_histogram_observe_and_moments():
    r = MetricsRegistry()
    h = r.histogram("lat_seconds", "latency", unit="seconds",
                    buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.count() == 5
    assert h.sum() == pytest.approx(106.5)
    assert h.mean() == pytest.approx(106.5 / 5)
    with pytest.raises(ValueError):
        h.observe(float("nan"))


def test_histogram_percentile_interpolation():
    r = MetricsRegistry()
    h = r.histogram("lat_seconds", unit="seconds", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(v)
    # rank 2 of 4 -> second observation, inside (1, 2]: interpolated
    p50 = h.percentile(50)
    assert 1.0 <= p50 <= 2.0
    # overflow observations clamp to the last finite bound
    h.observe(999.0)
    assert h.percentile(100) == 4.0
    # empty series -> nan, bad q -> raises
    assert np.isnan(r.histogram("empty_seconds", unit="seconds")
                    .percentile(50))
    with pytest.raises(ValueError):
        h.percentile(101)


def test_histogram_time_uses_injected_clock():
    clk = FakeClock(dt=0.5)
    r = MetricsRegistry(clock=clk)
    h = r.histogram("step_seconds", unit="seconds", labelnames=("phase",))
    with h.time(phase="decode"):
        pass
    # one clock tick inside the block -> exactly dt observed
    assert h.sum(phase="decode") == pytest.approx(0.5)
    assert h.count(phase="decode") == 1


def test_histogram_rejects_bad_buckets():
    r = MetricsRegistry()
    bads = ((), (2.0, 1.0), (1.0, 1.0), (1.0, float("inf")))
    for i, bad in enumerate(bads):
        with pytest.raises(ValueError):
            r.histogram(f"h{i}_seconds", unit="seconds", buckets=bad)


# ---------------------------------------------------------------------------
# snapshot / exposition / validators
# ---------------------------------------------------------------------------

def test_snapshot_schema_and_validator():
    r = MetricsRegistry()
    r.counter("a_total", "help a", unit="tokens").inc(3)
    r.gauge("b_ratio", unit="ratio", labelnames=("shard",)).set(0.5,
                                                                shard="0")
    h = r.histogram("c_seconds", unit="seconds", buckets=(1.0, 2.0))
    h.observe(1.5)
    snap = r.snapshot()
    assert validate_snapshot(snap) == []
    assert set(snap) == {"a_total", "b_ratio", "c_seconds"}
    entry = snap["c_seconds"]
    assert entry["type"] == "histogram" and entry["unit"] == "seconds"
    s = entry["series"][0]
    assert set(s) == {"labels", "count", "sum", "bucket_counts",
                      "p50", "p90", "p99"}
    assert len(s["bucket_counts"]) == len(entry["buckets"]) + 1
    # corrupt it -> validator flags
    s["bucket_counts"].append(7)
    assert validate_snapshot(snap)


def test_render_text_exposition():
    r = MetricsRegistry()
    r.counter("reqs_total", "requests seen", unit="requests").inc(2)
    h = r.histogram("lat_seconds", unit="seconds", buckets=(1.0, 2.0),
                    labelnames=("phase",))
    h.observe(0.5, phase="p")
    h.observe(1.5, phase="p")
    text = r.render_text()
    assert "# TYPE reqs_total counter" in text
    assert "# UNIT reqs_total requests" in text
    assert "reqs_total 2" in text
    # cumulative le buckets + +Inf + _sum/_count
    assert 'lat_seconds_bucket{phase="p",le="1"} 1' in text
    assert 'lat_seconds_bucket{phase="p",le="2"} 2' in text
    assert 'lat_seconds_bucket{phase="p",le="+Inf"} 2' in text
    assert 'lat_seconds_count{phase="p"} 2' in text


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_spans_instants_and_export():
    clk = FakeClock(dt=1.0)
    tr = Tracer(clock=clk)
    tr.set_track_name(0, "engine")
    with tr.span("engine_step", step=0):
        tr.instant("finished", rid=3)
    trace = tr.export()
    assert validate_chrome_trace(trace) == []
    evs = trace["traceEvents"]
    names = [e["name"] for e in evs]
    assert "process_name" in names and "thread_name" in names
    x = next(e for e in evs if e["ph"] == "X")
    assert x["name"] == "engine_step" and x["dur"] > 0
    assert x["args"] == {"step": 0}
    i = next(e for e in evs if e["ph"] == "i")
    assert i["args"] == {"rid": 3}


def test_tracer_open_spans_flushed_and_ring_bound():
    tr = Tracer(clock=FakeClock(), capacity=4)
    h = tr.begin("lifecycle", track=5, phase="waiting")
    trace = tr.export()          # still open -> flushed read-only
    assert any(e["name"] == "lifecycle" and e["tid"] == 5
               for e in trace["traceEvents"])
    tr.end(h)
    tr.end(h)                    # double-end is a no-op
    for i in range(10):
        tr.instant("tick")
    assert len(tr) == 4 and tr.dropped > 0
    assert validate_chrome_trace(tr.export()) == []


def test_tracer_disabled_is_noop():
    tr = Tracer(enabled=False)
    assert tr.begin("x") is None
    with tr.span("y") as s:
        tr.instant("z")
        s.set(n=1)
    assert len(tr) == 0


def test_tracer_span_passes_scalar_args_to_the_profiler(monkeypatch):
    """With ``xla_annotations`` a span enters a profiler annotation that
    carries its scalar args, and args set inside the block follow."""
    made = []

    class Annotation:
        def __init__(self, name, **kwargs):
            self.name, self.kwargs, self.entered = name, dict(kwargs), 0
            made.append(self)

        def __enter__(self):
            self.entered += 1

        def __exit__(self, *exc):
            self.entered -= 1

        def set_metadata(self, **kwargs):
            self.kwargs.update(kwargs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tr = Tracer(clock=FakeClock(), xla_annotations=True)
    with tr.span("decode_batch", slots=16, tag="x", ok=True, frac=0.5,
                 shape=(2, 3)) as s:
        assert made[0].entered == 1
        s.set(bytes=4096, rows=[1])
    (ann,) = made
    assert ann.name == "decode_batch" and ann.entered == 0
    assert ann.kwargs == {"slots": 16, "tag": "x", "ok": True, "frac": 0.5,
                          "bytes": 4096}
    (ev,) = [e for e in tr.export()["traceEvents"] if e["ph"] == "X"]
    assert ev["args"] == {"slots": 16, "tag": "x", "ok": True, "frac": 0.5,
                          "shape": (2, 3), "bytes": 4096, "rows": [1]}
    tr.xla_annotations = False
    with tr.span("schedule"):
        pass
    assert len(made) == 1


# ---------------------------------------------------------------------------
# Request.stats NaN guards (no engine needed)
# ---------------------------------------------------------------------------

def test_request_stats_nan_before_any_token():
    req = Request(rid=0, prompt=[1, 2, 3], sampling=SamplingParams(),
                  arrival=0.0)
    s = req.stats()
    for key in ("ttft_s", "tpot_s", "act_sparsity",
                "act_wire_bytes_per_token", "act_wire_compression_pct",
                "spec_acceptance_rate", "spec_tokens_per_step"):
        assert np.isnan(s[key]), key
    assert s["n_generated"] == 0
    assert s["wire_tokens"] == 0 and s["draft_tokens"] == 0


def test_preempted_before_first_token_observes_no_nan():
    """A request preempted (then never resumed) before emitting must not
    feed NaN into the latency histograms — _emit guards on t_first."""
    obs = Observability(clock=FakeClock())
    req = Request(rid=1, prompt=[1], sampling=SamplingParams(),
                  arrival=0.0, preemptions=1)
    s = req.stats()
    assert np.isnan(s["ttft_s"]) and s["preemptions"] == 1
    # registry histograms stay empty (observe(nan) would have raised)
    assert obs.registry.histogram(
        "serving_ttft_seconds", unit="seconds").count() == 0
    assert validate_snapshot(obs.registry.snapshot()) == []


# ---------------------------------------------------------------------------
# fault-loop registry wiring
# ---------------------------------------------------------------------------

def test_restartable_loop_registry_counters(tmp_path):
    from repro.distributed.fault import FaultInjector, RestartableLoop

    def step_fn(state, batch):
        return {"x": state["x"] + batch}, {"loss": state["x"]}

    reg = MetricsRegistry()
    inj = FaultInjector(plan={7: "fail"})
    loop = RestartableLoop(step_fn, lambda s: jnp.asarray(s),
                           str(tmp_path), ckpt_every=5, injector=inj,
                           registry=reg)
    state, _ = loop.run({"x": jnp.asarray(0)}, 0, 10)
    assert int(state["x"]) == sum(range(10))
    # registry mirrors the LoopReport exactly
    assert reg.value("fault_steps_run_total") == loop.report.steps_run
    assert reg.value("fault_faults_total") == loop.report.faults_seen == 1
    assert reg.value("fault_restarts_total") == loop.report.restarts == 1
    assert reg.value("fault_restores_total") == loop.report.restores == 1
    # initial + step-5 + step-10(final) checkpoints at minimum
    assert reg.value("fault_checkpoints_total") >= 3
    assert reg.value("fault_time_lost_seconds") >= 0.0
    assert validate_snapshot(reg.snapshot()) == []


def test_restartable_loop_without_registry_unchanged(tmp_path):
    from repro.distributed.fault import RestartableLoop

    def step_fn(state, batch):
        return {"x": state["x"] + batch}, {}

    loop = RestartableLoop(step_fn, lambda s: jnp.asarray(s),
                           str(tmp_path), ckpt_every=5)
    state, _ = loop.run({"x": jnp.asarray(0)}, 0, 6)
    assert int(state["x"]) == sum(range(6))


# ---------------------------------------------------------------------------
# engine integration (slow: real jitted steps)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_metrics_and_trace_end_to_end(qparams):
    clk = FakeClock(dt=0.001)
    eng = Engine(CFG, qparams, clock=clk,
                 obs=Observability(clock=clk))
    rng = np.random.default_rng(0)
    handles = [eng.submit(list(rng.integers(1, 127, size=12)),
                          SamplingParams(max_new_tokens=6,
                                         temperature=0.0))
               for _ in range(3)]
    eng.run()

    # -- aggregate_stats key set pinned (downstream consumers) --
    agg = eng.aggregate_stats()
    assert set(agg) == {"steps", "pool_pages_free", "pool_utilization",
                        "pool_evictions", "wire_bytes_total",
                        "wire_compression_pct",
                        "layer_wire_bytes_per_token",
                        "layer_dense_bytes_per_token"}
    assert agg["steps"] == eng.steps
    assert len(agg["layer_wire_bytes_per_token"]) == CFG.n_layers

    # -- Request.stats key set pinned --
    s = handles[0].stats()
    assert set(s) == {"ttft_s", "tpot_s", "n_generated", "act_sparsity",
                      "act_wire_bytes_per_token", "wire_tokens",
                      "draft_tokens", "act_wire_compression_pct",
                      "preemptions", "spec_acceptance_rate",
                      "spec_tokens_per_step", "kv_demotions",
                      "kv_promotions"}
    assert s["kv_demotions"] == 0 and s["kv_promotions"] == 0  # disarmed

    # -- registry totals consistent with per-request truths --
    r = eng.obs.registry
    n_tok = sum(h.stats()["n_generated"] for h in handles)
    assert r.value("serving_tokens_emitted_total") == n_tok
    assert r.value("serving_requests_finished_total") == len(handles)
    wire_sum = sum(h.stats()["act_wire_bytes_per_token"]
                   * h.stats()["wire_tokens"] for h in handles)
    assert r.value("serving_wire_bytes_total") == pytest.approx(wire_sum)

    # -- latency histograms vs exact per-request stats --
    ttfts = sorted(h.stats()["ttft_s"] for h in handles)
    hist = r.get("serving_ttft_seconds")
    assert hist.count() == len(handles)
    assert hist.sum() == pytest.approx(sum(ttfts))   # sums are exact
    # bucket-interpolated p50 must land in the bucket holding the true
    # median (histogram resolution is the bucket width, nothing finer)
    median = ttfts[len(ttfts) // 2]
    bounds = [0.0] + list(DEFAULT_LATENCY_BUCKETS)
    idx = next(i for i in range(len(bounds) - 1)
               if bounds[i] < median <= bounds[i + 1])
    p50 = hist.percentile(50)
    assert bounds[idx] <= p50 <= bounds[idx + 1]
    tpot_hist = r.get("serving_tpot_seconds")
    assert tpot_hist.count() == n_tok - len(handles)  # gaps, not tokens

    # -- snapshot + trace artifacts validate --
    snap = eng.metrics_snapshot()
    assert validate_snapshot(snap) == []
    # per-layer gauges populated for every layer
    layers = {s_["labels"]["layer"]
              for s_ in snap["serving_layer_wire_bytes_per_token"]["series"]}
    assert layers == {str(i) for i in range(CFG.n_layers)}
    trace = eng.obs.tracer.export()
    assert validate_chrome_trace(trace) == []
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"engine_step", "prefill_chunk", "decode_batch",
            "waiting", "prefill", "decode"} <= names
    # per-request lifecycle tracks are named
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               and "request" in e["args"]["name"]
               for e in trace["traceEvents"])


DECODE_CHILDREN = ["decode_inputs", "decode_launch", "decode_wait",
                   "decode_readback", "decode_sample", "decode_emit"]
PHASE_SPANS = {"schedule": "schedule", "prefill": "prefill_chunk",
               "decode": "decode_batch"}


def _run_tiny_engine(qparams, obs):
    clk = FakeClock(dt=0.001)
    obs = obs(clk)
    eng = Engine(CFG, qparams, clock=clk, obs=obs)
    rng = np.random.default_rng(1)
    for n in (12, 20):
        eng.submit(list(rng.integers(1, 127, size=n)),
                   SamplingParams(max_new_tokens=5, temperature=0.0))
    eng.run()
    return eng


def _inside(child, parent):
    return (child["ts"] >= parent["ts"] and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"])


@pytest.mark.slow
def test_engine_decode_and_schedule_spans_nest_in_order(qparams):
    """Each ``decode_batch`` holds the six decode child spans, in order and
    nowhere else; each ``engine_step`` holds one ``schedule``; and every
    timed phase has exactly one span per histogram observation."""
    eng = _run_tiny_engine(qparams, lambda clk: Observability(clock=clk))
    assert eng.obs.tracer.dropped == 0
    evs = [e for e in eng.obs.tracer.export()["traceEvents"]
           if e["ph"] == "X" and e["tid"] == 0]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    batches = by_name["decode_batch"]
    assert batches and all(e["args"]["slots"] >= 1 for e in batches)
    for batch in batches:
        kids = sorted((e for e in evs
                       if e is not batch and _inside(e, batch)),
                      key=lambda e: e["ts"])
        assert [e["name"] for e in kids] == DECODE_CHILDREN
        assert all(a["ts"] + a["dur"] <= b["ts"]
                   for a, b in zip(kids, kids[1:]))
        assert kids[2]["args"]["bytes"] == eng._n_slots * CFG.vocab * 4
        assert kids[3]["args"]["bytes"] > 0
    for name in DECODE_CHILDREN:
        assert len(by_name[name]) == len(batches)
    steps = by_name["engine_step"]
    for step in steps:
        sched = [e for e in by_name["schedule"] if _inside(e, step)]
        assert len(sched) == 1
        assert set(sched[0]["args"]) == {"waiting", "decode"}
    assert sum(e["args"]["decode"] for e in by_name["schedule"]) == sum(
        e["args"]["slots"] for e in batches)
    hist = eng.obs.registry.get("serving_step_seconds")
    for phase, span in PHASE_SPANS.items():
        assert hist.count(phase=phase) == len(by_name[span]) > 0, phase
    assert hist.count(phase="schedule") == len(steps) == eng.steps


@pytest.mark.slow
def test_decode_batch_counts_the_attention_pages_it_reads(qparams):
    """Each ``decode_batch`` span carries the paged kernel's page counts
    for the step's positions (every decode slot, an idle one at pos 0),
    and ``serving_paged_attn_pages_total{kind}`` adds up to slots x table
    width x decode steps, its ``read`` part to the spans' pages."""
    from repro.kernels.kv_attention import context_pages
    clk = FakeClock(dt=0.001)
    eng = Engine(CFG, qparams, clock=clk, obs=Observability(clock=clk))
    rng = np.random.default_rng(2)
    handles = [eng.submit(list(rng.integers(1, 127, size=n)),
                          SamplingParams(max_new_tokens=m,
                                         temperature=0.0))
               for n, m in ((12, 30), (7, 6))]
    ps, width, slots = (eng.pool.page_size, eng._n_page_steps,
                        eng._n_slots)
    expected = []
    while eng.sched.has_work():
        pos = [len(h.context) - 1 for h in handles
               if h.out_tokens and not h.done]
        n_batches = len(_spans(eng, "decode_batch"))
        eng.step()
        if len(_spans(eng, "decode_batch")) > n_batches:
            expected.append(sum(int(context_pages(p, ps)) for p in pos)
                            + slots - len(pos))
    batches = _spans(eng, "decode_batch")
    assert len(batches) == len(expected)
    assert len(set(expected)) >= 3          # the contexts cross pages
    assert [e["args"]["attn_pages"] for e in batches] == expected
    assert {e["args"]["attn_page_steps"] for e in batches} == {
        slots * width}
    r = eng.obs.registry
    read = r.value("serving_paged_attn_pages_total", kind="read")
    skipped = r.value("serving_paged_attn_pages_total", kind="skipped")
    assert read == sum(expected)
    assert read + skipped == slots * width * len(batches)


def _spans(eng, name):
    return [e for e in eng.obs.tracer.export()["traceEvents"]
            if e["ph"] == "X" and e["name"] == name]


@pytest.mark.slow
def test_engine_with_tracing_off_records_no_span_but_times_phases(qparams):
    eng = _run_tiny_engine(
        qparams, lambda clk: Observability(clock=clk, trace=False))
    assert len(eng.obs.tracer) == 0
    assert not [e for e in eng.obs.tracer.export()["traceEvents"]
                if e["ph"] != "M"]
    hist = eng.obs.registry.get("serving_step_seconds")
    assert hist.count(phase="schedule") == eng.steps > 0
    assert hist.count(phase="prefill") >= 2
    assert hist.count(phase="decode") > 0


@pytest.mark.slow
def test_spec_engine_draft_token_accounting(qparams):
    eng = SpeculativeEngine(CFG, qparams, spec=SpecConfig(gamma=2))
    rng = np.random.default_rng(0)
    handles = [eng.submit(list(rng.integers(1, 127, size=12)),
                          SamplingParams(max_new_tokens=8,
                                         temperature=0.0))
               for _ in range(2)]
    eng.run()
    r = eng.obs.registry
    for h in handles:
        s = h.stats()
        # drafts excluded from the wire denominator: telemetered tokens
        # only (prefill chunks + γ+1 verify windows), drafts separate
        assert s["wire_tokens"] == h.sparsity_n
        assert s["draft_tokens"] == eng.spec.gamma * h.spec_steps
        assert np.isfinite(s["act_wire_bytes_per_token"])
    agg = eng.aggregate_stats()
    assert agg["spec_gamma"] == 2
    assert (r.value("serving_spec_draft_proposed_total")
            == eng.draft_proposed_total)
    assert (r.value("serving_spec_draft_accepted_total")
            == eng.draft_accepted_total)
    assert agg["spec_acceptance_rate"] == pytest.approx(
        eng.draft_accepted_total / eng.draft_proposed_total)
    # draft/verify sub-phases timed inside each decode-batch phase
    step_lat = r.get("serving_step_seconds")
    n_batches = step_lat.count(phase="decode")
    assert n_batches > 0
    assert step_lat.count(phase="draft") == n_batches
    assert step_lat.count(phase="verify") == n_batches
    names = [e["name"] for e in eng.obs.tracer.export()["traceEvents"]
             if e["ph"] == "X"]
    for phase, span in (("decode", "decode_batch"), ("draft", "spec_draft"),
                        ("verify", "spec_verify")):
        assert names.count(span) == step_lat.count(phase=phase), phase
    assert validate_snapshot(eng.metrics_snapshot()) == []
