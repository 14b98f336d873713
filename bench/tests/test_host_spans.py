"""The readers of the engine's host spans: on a hand-made span log, and
through a traced run of a cell at smoke size on the CPU."""
import pytest

from bench.metrics import decode_finish_ms, decode_launch_ms, schedule_ms
from bench.tests.smoke import run_smoke

READERS = {"schedule_ms": schedule_ms, "decode_launch_ms": decode_launch_ms,
           "decode_finish_ms": decode_finish_ms}


def _step(t, schedule, children):
    """One engine step's spans from ``t`` (seconds): ``schedule``, then a
    ``decode_batch`` holding ``children`` [(name, seconds), ...]."""
    spans = [["schedule", t, t + schedule, {"waiting": 0, "decode": 16}]]
    t0 = t = t + schedule
    for name, dt in children:
        spans.append([name, t, t + dt, {}])
        t += dt
    spans.append(["decode_batch", t0, t, {"slots": 16}])
    return spans


CHILDREN = [("decode_inputs", 0.001), ("decode_launch", 0.002),
            ("decode_wait", 2.78), ("decode_readback", 0.0005),
            ("decode_sample", 0.003), ("decode_emit", 0.0015)]


def test_readers_average_over_the_steps():
    ctx = {"spans": _step(0.0, 0.0002, CHILDREN)
           + _step(3.0, 0.0004, [(n, 2 * dt) for n, dt in CHILDREN])}
    assert schedule_ms.read(ctx) == pytest.approx(0.3)
    assert decode_launch_ms.read(ctx) == pytest.approx(1.5 * 3.0)
    assert decode_finish_ms.read(ctx) == pytest.approx(1.5 * 5.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_find_nothing_without_their_spans(name):
    """A program that records only the spans of an earlier version (or
    nothing) gives no reading, and no error."""
    assert READERS[name].read({"spans": []}) is None
    older = [["engine_step", 0.0, 3.0, {"step": 0}],
             ["decode_batch", 0.1, 2.9, {"slots": 16}]]
    assert READERS[name].read({"spans": older}) is None


def test_traced_smoke_run_reports_the_host_spans():
    """A traced run reports all three, and the engine's ring buffer lost
    no event, so it still holds every prefill chunk the check reads."""
    tracers = []

    def keep_tracer(system):
        tracers.append(system.engine.obs.tracer)

    res = run_smoke("granite-8b.codegen", 4, trace=True, fault=keep_tracer)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    for name in READERS:
        assert got[name] > 0, (name, got)
    assert got["decode_occupancy"] == 100.0
    assert res["correct"], res["check"]
    assert tracers[0].dropped == 0
