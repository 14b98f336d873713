"""Attribution + SLO subsystem: HLO coverage, drift math, watchdog.

Covers the PR-9 acceptance points: ``launch/hlo_analysis`` strict mode
against the REAL compiled serving steps (single-device and 2x2 mesh,
CPU backend), deterministic sliding-window percentiles, drift-metric
math on a synthetic clock via the ``register_cost`` seam, and the SLO
watchdog firing (test-pinned) on an injected latency spike while
staying silent on the baseline run.
"""
import jax
import pytest

from repro.configs.base import ModelConfig
from repro.core.costmodel import HardwareConfig
from repro.core.qlinear import quantize_model_params
from repro.launch import hlo_analysis as H
from repro.models.schema import init_params
from repro.models.schema_builder import build_schema
from repro.obs import Observability
from repro.obs.attribution import StepAttribution, StepCost
from repro.obs.slo import (SLO, SLOMonitor, SlidingWindow, parse_slo,
                           parse_slo_list)
from repro.obs.validate import validate_attribution
from repro.serving import (Engine, PoolConfig, SamplingParams,
                           SchedulerConfig, SpecConfig, SpeculativeEngine)

# a CPU has no entry in costmodel.DEVICE_PEAKS: pass roofline peaks
CPU_PEAKS = HardwareConfig()

CFG = ModelConfig(name="tiny-attr", family="transformer", n_layers=2,
                  d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                  d_ff=64, vocab=128, dtype="float32")


class FakeClock:
    """Deterministic monotonic clock: every read advances by ``dt``."""

    def __init__(self, dt=0.001):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


def _qparams(cfg, seed=0):
    fp = init_params(build_schema(cfg), jax.random.PRNGKey(seed))
    return quantize_model_params(
        fp, w_bits=4, k_percent=50.0, clip_l=-8.0, clip_h=23.0,
        mode="sparqle", enable_clipping=True, tile_k=16)


def _engine(cfg=CFG, mesh=None, gamma=0, slos=None, clock=None):
    kw = dict(pool_config=PoolConfig(n_pages=32, page_size=4),
              sched_config=SchedulerConfig(max_decode_batch=4,
                                           token_budget=64,
                                           prefill_chunk=8,
                                           max_pages_per_seq=8),
              mesh=mesh, slos=slos)
    if clock is not None:
        kw["clock"] = clock
    qp = _qparams(cfg)
    if gamma:
        return SpeculativeEngine(cfg, qp, spec=SpecConfig(gamma=gamma),
                                 **kw)
    return Engine(cfg, qp, **kw)


# ---------------------------------------------------------------------------
# hlo_analysis: sub-byte dtypes + strict coverage
# ---------------------------------------------------------------------------

def test_s4_dtype_bytes_are_fractional():
    assert H.shape_bytes("s4[16]{0}") == 8.0
    assert H.shape_bytes("u4[3]") == 1.5
    assert H.shape_bytes("s2[8]") == 2.0
    assert H.shape_bytes("pred[10]") == 10.0


def test_unknown_dtype_fails_strict():
    text = """HloModule m
ENTRY %main (p: myfancytype[8]) -> myfancytype[8] {
  %p = myfancytype[8]{0} parameter(0)
  ROOT %r = myfancytype[8]{0} copy(%p)
}
"""
    with pytest.raises(H.HloCoverageError, match="unknown dtype"):
        H.analyze(text, strict=True)
    stats = H.analyze(text)                  # non-strict still records
    assert stats.unknown_dtypes
    assert not stats.complete


def test_unparsed_op_fails_strict():
    text = """HloModule m
ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %oops = utterly unparseable line
  ROOT %r = f32[4]{0} copy(%p)
}
"""
    with pytest.raises(H.HloCoverageError, match="unparsed"):
        H.analyze(text, strict=True)
    stats = H.analyze(text)
    assert any("oops" in s for s in stats.unparsed_ops)


def test_no_entry_fails_strict():
    with pytest.raises(H.HloCoverageError, match="ENTRY"):
        H.analyze("HloModule empty\n", strict=True)


# ---------------------------------------------------------------------------
# attribution against the real compiled serving steps
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_attribute_real_steps_single_device():
    eng = _engine()
    attr = eng.attribute_steps(hw=CPU_PEAKS)
    assert set(attr.phases()) == {"prefill", "decode"}
    for phase in attr.phases():
        c = attr.cost(phase)
        # strict analyze() inside attribute() already guarantees full
        # coverage; the numbers must be real work, not zeros
        assert c.flops > 0 and c.hbm_bytes > 0
        assert c.compile_seconds > 0
    # one prefill chunk moves `prefill_chunk` tokens, one decode step
    # moves one token per slot
    assert attr.cost("prefill").tokens_per_step == 8
    assert attr.cost("decode").tokens_per_step == 4
    # idempotent: re-attribution returns the cached costs
    again = eng.attribute_steps(hw=CPU_PEAKS)
    assert again is attr
    assert again.cost("decode") is attr.cost("decode")
    # gauges registered and set
    r = eng.obs.registry
    assert r.value("serving_step_attr_flops", phase="decode") > 0
    problems = validate_attribution(r.snapshot(), require=True)
    assert problems == []


@pytest.mark.slow
def test_attribute_real_steps_spec_engine():
    eng = _engine(gamma=2)
    attr = eng.attribute_steps(hw=CPU_PEAKS)
    assert set(attr.phases()) == {"prefill", "decode", "draft", "verify"}
    draft, verify = attr.cost("draft"), attr.cost("verify")
    # the timed draft phase wraps gamma jitted calls
    assert draft.calls_per_step == 2
    assert draft.tokens_per_step == 4 * 2
    assert verify.tokens_per_step == 4 * 3
    # the LSB4-only draft program does strictly less dot work per call
    # than gamma-scaled full decode would
    assert draft.flops < 2 * attr.cost("decode").flops


@pytest.mark.slow
def test_attribute_real_steps_mesh(mesh):
    m = mesh(data=2, model=2)
    eng = _engine(mesh=m)
    attr = eng.attribute_steps(hw=CPU_PEAKS)
    assert set(attr.phases()) == {"prefill", "decode"}
    for phase in attr.phases():
        c = attr.cost(phase)
        assert c.flops > 0 and c.hbm_bytes > 0
        # tensor parallelism must show up as collective payload
        assert c.coll_bytes.get("total", 0.0) > 0
    problems = validate_attribution(eng.obs.registry.snapshot(),
                                    require=True)
    assert problems == []


@pytest.mark.slow
def test_runtime_join_after_real_run():
    eng = _engine(clock=FakeClock(dt=0.001))
    eng.attribute_steps(hw=CPU_PEAKS)
    for i in range(3):
        eng.submit([1, 2, 3, 4 + i], SamplingParams(max_new_tokens=3))
    eng.run()
    snap = eng.metrics_snapshot()
    r = eng.obs.registry
    for phase in ("prefill", "decode"):
        assert r.value("serving_roofline_compute_util_ratio",
                       phase=phase) > 0
        assert r.value("serving_costmodel_latency_drift_ratio",
                       phase=phase) > 0
    wire = r.value("serving_costmodel_wire_drift_ratio")
    # Eq.1 tracks the measured codec to a couple percent (PR 3)
    assert abs(wire - 1.0) < 0.05
    assert validate_attribution(snap, require=True) == []


# ---------------------------------------------------------------------------
# drift math on a synthetic clock (register_cost seam)
# ---------------------------------------------------------------------------

def _seamed_attr():
    obs = Observability(clock=FakeClock())
    attr = StepAttribution(obs, hw=CPU_PEAKS)
    attr.register_cost(
        StepCost(phase="decode", flops=1e9, hbm_bytes=2e9,
                 coll_bytes={"total": 0.0}, tokens_per_step=8),
        predict_seconds=lambda s: 0.010)       # constant 10 ms predicted
    return obs, attr


def test_roofline_join_math():
    obs, attr = _seamed_attr()
    attr.observe_runtime("decode", 0.020)      # 20 ms measured
    r = obs.registry
    assert r.value("serving_roofline_achieved_flops_per_s",
                   phase="decode") == pytest.approx(1e9 / 0.020)
    assert r.value("serving_roofline_compute_util_ratio",
                   phase="decode") == pytest.approx(
                       1e9 / 0.020 / attr.hw.peak_flops)
    assert r.value("serving_roofline_memory_util_ratio",
                   phase="decode") == pytest.approx(
                       2e9 / 0.020 / attr.hw.hbm_bw)
    assert r.value("serving_costmodel_latency_drift_ratio",
                   phase="decode") == pytest.approx(2.0)


def test_latency_drift_is_edge_triggered_vs_reference():
    obs, attr = _seamed_attr()
    r = obs.registry
    attr.observe_runtime("decode", 0.020)      # pins reference ratio 2.0
    attr.observe_runtime("decode", 0.030)      # ratio 3.0, within 2x band
    assert r.value("serving_costmodel_drift_events_total",
                   phase="decode") == 0
    attr.observe_runtime("decode", 0.050)      # ratio 5.0 > 2*ref: fires
    assert r.value("serving_costmodel_drift_events_total",
                   phase="decode") == 1
    attr.observe_runtime("decode", 0.060)      # still out: no re-fire
    assert r.value("serving_costmodel_drift_events_total",
                   phase="decode") == 1
    attr.observe_runtime("decode", 0.020)      # recovery re-arms
    attr.observe_runtime("decode", 0.002)      # ratio 0.2 < ref/2: fires
    assert r.value("serving_costmodel_drift_events_total",
                   phase="decode") == 2
    instants = [e for e in obs.tracer._events
                if e["name"] == "costmodel_drift"]
    assert len(instants) == 2
    assert all(e["args"]["kind"] == "latency" for e in instants)


def test_wire_drift_edge_triggered():
    obs, attr = _seamed_attr()
    r = obs.registry
    attr.observe_wire(100.0, 100.5)            # ratio ~0.995: in band
    assert r.value("serving_costmodel_drift_events_total",
                   phase="wire") == 0
    attr.observe_wire(130.0, 100.0)            # ratio 1.3 > 1.15: fires
    assert r.value("serving_costmodel_drift_events_total",
                   phase="wire") == 1
    attr.observe_wire(135.0, 100.0)            # sustained: no re-fire
    assert r.value("serving_costmodel_drift_events_total",
                   phase="wire") == 1
    assert r.value("serving_costmodel_wire_drift_ratio") == \
        pytest.approx(1.35)


# ---------------------------------------------------------------------------
# sliding window percentiles: deterministic nearest-rank
# ---------------------------------------------------------------------------

def test_sliding_window_nearest_rank():
    w = SlidingWindow(maxlen=100)
    for v in [5.0, 1.0, 3.0, 2.0, 4.0]:
        w.observe(v)
    assert w.percentile(50) == 3.0             # ceil(0.5*5)=3rd of sorted
    assert w.percentile(95) == 5.0
    assert w.percentile(20) == 1.0
    assert w.percentile(100) == 5.0
    assert w.over_fraction(3.0) == pytest.approx(2 / 5)


def test_sliding_window_evicts_oldest():
    w = SlidingWindow(maxlen=3)
    for v in [10.0, 20.0, 30.0, 40.0]:
        w.observe(v)
    assert len(w) == 3 and w.total == 4
    assert w.percentile(50) == 30.0            # 10.0 evicted
    with pytest.raises(ValueError):
        w.observe(float("nan"))


def test_parse_slo_specs():
    slo = parse_slo("ttft:p95<0.25")
    assert (slo.signal, slo.percentile, slo.target) == ("ttft", 95.0, 0.25)
    assert slo.unit == "seconds"
    assert parse_slo("queue_depth:p50<4").unit == "requests"
    assert len(parse_slo_list("ttft:p95<1,tpot:p99<0.5")) == 2
    assert parse_slo_list("") == []
    with pytest.raises(ValueError):
        parse_slo("nonsense")
    with pytest.raises(ValueError):
        parse_slo("latency:p95<1")             # unknown signal
    with pytest.raises(ValueError):
        SLO(name="bad", signal="ttft", target=1.0, percentile=0.0)


# ---------------------------------------------------------------------------
# SLO watchdog: fires on an injected spike, silent on the baseline
# ---------------------------------------------------------------------------

def test_slo_violation_fires_on_spike_and_rearms():
    obs = Observability(clock=FakeClock())
    mon = SLOMonitor([SLO(name="tpot", signal="tpot", target=0.1,
                          percentile=95.0, window=8)], obs)
    r = obs.registry
    for _ in range(8):
        mon.observe("tpot", 0.01)              # healthy baseline
    assert r.value("serving_slo_compliant", slo="tpot") == 1.0
    assert r.value("serving_slo_violations_total", slo="tpot") == 0
    # injected latency spike: window p95 jumps over target
    for _ in range(8):
        mon.observe("tpot", 0.5)
    assert r.value("serving_slo_compliant", slo="tpot") == 0.0
    assert r.value("serving_slo_violations_total", slo="tpot") == 1.0
    assert r.value("serving_slo_burn_rate", slo="tpot") > 1.0
    instants = [e for e in obs.tracer._events
                if e["name"] == "slo_violation"]
    assert len(instants) == 1                  # edge-triggered, not 8
    assert instants[0]["args"]["slo"] == "tpot"
    # recovery drains the spike out of the window and re-arms the edge
    for _ in range(8):
        mon.observe("tpot", 0.01)
    assert r.value("serving_slo_compliant", slo="tpot") == 1.0
    for _ in range(8):
        mon.observe("tpot", 0.5)
    assert r.value("serving_slo_violations_total", slo="tpot") == 2.0


def test_slo_min_samples_gates_judgement():
    obs = Observability(clock=FakeClock())
    mon = SLOMonitor([SLO(name="q", signal="queue_depth", target=1.0,
                          window=16, min_samples=4)], obs)
    for _ in range(3):
        mon.observe("queue_depth", 50.0)       # over target but unjudged
    assert obs.registry.value("serving_slo_compliant", slo="q") == 1.0
    mon.observe("queue_depth", 50.0)           # 4th sample: judged
    assert obs.registry.value("serving_slo_compliant", slo="q") == 0.0
    rep = mon.report()[0]
    assert rep["violating"] and rep["violations"] == 1


@pytest.mark.slow
def test_engine_slos_silent_on_baseline_run():
    # generous targets on a fast synthetic run: the watchdog must stay
    # quiet end-to-end (the CI fast lane runs the same shape via
    # `bench_serving --slo ... --slo-fail`)
    slos = parse_slo_list("ttft:p95<60,tpot:p95<60,queue_depth:p50<64")
    eng = _engine(slos=slos, clock=FakeClock(dt=0.001))
    for i in range(3):
        eng.submit([1, 2, 3, 4 + i], SamplingParams(max_new_tokens=3))
    eng.run()
    assert eng.slo is not None
    assert all(v == 0 for v in eng.slo.violations().values())
    assert all(not rep["violating"] for rep in eng.slo.report())
    # every signal actually produced samples
    assert all(rep["samples"] > 0 for rep in eng.slo.report())


@pytest.mark.slow
def test_engine_slo_fires_on_tight_target():
    # a FakeClock tick is 1 ms, and every _emit reads the clock, so any
    # sub-millisecond TPOT target must violate deterministically
    slos = [SLO(name="tight", signal="tpot", target=1e-6, window=8)]
    eng = _engine(slos=slos, clock=FakeClock(dt=0.001))
    eng.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
    eng.run()
    assert eng.slo.violations()["tight"] >= 1
    names = [e["name"] for e in eng.obs.tracer._events]
    assert "slo_violation" in names


def test_device_peaks_are_keyed_by_device_kind():
    """Roofline peaks come from a table keyed by device_kind; a device
    without an entry raises instead of borrowing another chip's peaks."""
    from repro.core.costmodel import hardware_for
    hw = hardware_for("TPU v5 lite")
    assert (hw.peak_flops, hw.peak_int8_ops, hw.hbm_bw) == (197e12, 393e12,
                                                            819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        hardware_for(jax.devices()[0].device_kind)
