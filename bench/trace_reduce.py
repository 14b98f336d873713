"""Reduction of a profiler trace to what the per-layer metrics read.

``load_xplane`` turns a ``.xplane.pb`` into a plain dict, and every other
function here reads that dict, so the same code runs on a recorded trace
in the tests:

    {"window_ns": float,
     "devices": {"<plane>": {"modules": [[name, start_ns, end_ns], ...],
                             "ops":     [[name, start_ns, end_ns], ...]}},
     "host":    [[name, start_ns, end_ns], ...]}

All times are on the profiler's clock, counted from the start of the
window: the host annotation ``bench_window`` that the harness holds open
from just after the profiler started to just before it stopped.
``modules`` are XLA program executions, ``ops`` the operations inside
them, ``host`` the host-side annotations (the engine's spans when they
are passed to the profiler).
"""
from __future__ import annotations

import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
HOST_SPANS = ("engine_step", "prefill_chunk", "decode_batch")
WINDOW_SPAN = "bench_window"
# operations that only enclose others (their time is their body's)
_ENCLOSING = re.compile(r"^%?(while|conditional|call)(\.\d+)?$")


def load_xplane(path: str) -> Dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict = {}
    host: List = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.end_ns]
                                for e in line.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append([e.name, e.start_ns, e.end_ns])
                    elif e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.end_ns)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} annotation")
    lo = window[0]

    def shift(events):
        return [[n, s - lo, e - lo] for n, s, e in events]
    return {"window_ns": float(window[1] - lo),
            "devices": {k: {kk: shift(v) for kk, v in d.items()}
                        for k, d in devices.items()},
            "host": shift(host)}


def clip(events: Iterable[Sequence], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for _, s, e in events
            if e > lo and s < hi]


def union_length(intervals: Iterable[Interval]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(trace: Dict) -> float:
    """Device busy time in the window, averaged over the devices: the
    union of the intervals in which a program executed."""
    devs = trace["devices"].values()
    if not devs:
        return 0.0
    w = trace["window_ns"]
    return sum(union_length(clip(d["modules"], 0.0, w))
               for d in devs) / len(devs)


def module_calls(trace: Dict, prefix: str) -> List[Sequence]:
    """Executions of the jitted program named ``prefix`` (e.g.
    ``jit_engine_decode``), on every device, within the window."""
    w = trace["window_ns"]
    return [m for d in trace["devices"].values() for m in d["modules"]
            if m[0].split("(")[0] == prefix and m[2] > 0 and m[1] < w]


def op_events(trace: Dict, match: Callable[[str], bool]) -> List[Sequence]:
    w = trace["window_ns"]
    return [o for d in trace["devices"].values() for o in d["ops"]
            if match(o[0]) and o[2] > 0 and o[1] < w]


def total_ns(events: Iterable[Sequence]) -> float:
    return sum(e - s for _, s, e in events)


def op_short_name(name: str) -> str:
    """``%copy.161 = s8[...] copy(...)`` -> ``copy``; a Pallas kernel is
    named by its call target."""
    head = name.split(" = ")[0].lstrip("%")
    short = re.sub(r"\.\d+$", "", head)
    if 'custom_call_target="tpu_custom_call"' in name:
        return f"{short} (tpu_custom_call)"
    return short


def top_ops(trace: Dict, n: int = 10) -> List[List]:
    """The ``n`` device operations that took most time, by short name,
    in seconds per device."""
    agg: Dict[str, float] = {}
    n_dev = max(1, len(trace["devices"]))
    for name, s, e in op_events(trace, lambda _: True):
        short = op_short_name(name)
        if _ENCLOSING.match(short):
            continue
        agg[short] = agg.get(short, 0.0) + (e - s)
    ranked = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / n_dev] for k, v in ranked]


def idle_gaps(trace: Dict, n: int = 10) -> List[List]:
    """The ``n`` longest gaps between program executions on the first
    device, each named by the host span open over most of it."""
    if not trace["devices"]:
        return []
    dev = sorted(trace["devices"])[0]
    w = trace["window_ns"]
    busy = sorted(clip(trace["devices"][dev]["modules"], 0.0, w))
    gaps, cur = [], 0.0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < w:
        gaps.append((cur, w))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        out.append([f"idle under {host_span_over(trace, s, e)}",
                    (e - s) / 1e9])
    return out


def host_span_over(trace: Dict, s: float, e: float) -> str:
    """The innermost (shortest) host span covering most of [s, e]."""
    best: Optional[Tuple[float, str]] = None
    for name, hs, he in trace["host"]:
        if min(he, e) - max(hs, s) > 0.5 * (e - s):
            if best is None or he - hs < best[0]:
                best = (he - hs, name)
    return best[1] if best else "no engine span"


def within(spans: Iterable[Sequence], name: str,
           events: Iterable[Sequence]) -> List[Tuple[float, float]]:
    """For each host span ``name``: (span length, summed length of the
    ``events`` that started inside it), in ns."""
    evs = sorted(events, key=lambda e: e[1])
    out = []
    for n, s, e in spans:
        if n != name:
            continue
        inside = sum(ee - es for _, es, ee in evs if s <= es < e)
        out.append((e - s, inside))
    return out
