#!/usr/bin/env python3
"""Run one cell of the serving benchmark once.

    python3 bench/run.py --workload granite-8b.codegen --seed 7 \
        --seconds 30 --trace 0

A cell of ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``: the model's widths, its reference and the
system that serves it) and a traffic mix (``bench/traffic/<mix>.json``).
The run makes the weights from the seed, builds the engine, warms up its
two step programs, starts open-loop traffic, opens the measured window
once every decode slot holds a request that has emitted its first token,
measures for ``--seconds``, and then checks the tokens served against the
float32 reference. ``bench/cells/<cell>.json`` holds the limit of the
cell's check. ``--control 1`` puts the control in the program's place in
that check (the reference at 4-bit activations, which has to come out not
correct); a measured run never passes it.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
the window with the profiler and prints the per-layer metrics, each read
by ``bench/metrics/<name>.py``. The last line of standard output is one
JSON object; the numbers compared and their limits are the last lines of
standard error. Without a TPU the run fails and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(bm: Dict, name: str) -> Dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                     f"(have {[w['name'] for w in bm['workloads']]})")


def config_file(bm: Dict, name: str) -> Dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(ROOT, c["file"]))
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def mix_file(name: str, bench_dir: str = BENCH) -> Dict:
    return load_json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def cell_file(workload: str) -> Dict:
    """The cell's own numbers: the limits of its check."""
    return load_json(os.path.join(BENCH, "cells", f"{workload}.json"))


def module(kind: str, name: str, bench_dir: str = BENCH):
    """``bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bm: Dict, workload: str, section: str) -> List[Dict]:
    """The metrics of ``section`` that this cell reports."""
    return [m for m in bm[section]
            if workload in m.get("workloads", [workload])]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _percentile(xs: List[float], q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(xs, np.float64), q))


class CompileCounter:
    """Counts XLA compilations (and persistent-cache loads) in a process."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_loads = 0

        def on_event(event: str, duration: float, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
            elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
                self.cache_loads += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program goes into it, so only a cell's first run compiles."""
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, require_tpu: bool) -> Optional[Dict]:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if require_tpu and info["platform"] != "tpu":
        print(f"no TPU: JAX runs on {info['platform']!r}; the benchmark "
              f"measures on a TPU only", file=sys.stderr)
        return None
    if info["count"] < chips:
        print(f"the cell needs {chips} chips, JAX sees {info['count']}",
              file=sys.stderr)
        return None
    return info


def pool_pages(mix: Dict) -> int:
    """A pool that holds every decode slot at the mix's longest context,
    and no more (one page more: the engine keeps page 0 for padding)."""
    eng = mix["engine"]
    return 1 + eng["decode_slots"] * -(-mix["max_context"]
                                       // eng["page_size"])


def pages_in_use(run: Dict, page_size: int) -> int:
    """Pages the requests that were decoding at the window's opening held
    then: each request's prompt and the tokens it had emitted."""
    return sum(-(-n // page_size) for n in run["contexts_at_open"])


def drive(system, reqs: List[Dict], mix: Dict, seconds: float,
          tracer=None) -> Dict:
    """Open-loop traffic: submit each request when due, step the engine,
    record when every token came. Returns the run's records."""
    clock = time.monotonic
    recs = [{"due": None, "submit": None, "rid": None, "times": [],
             "n_prompt": len(r["prompt"]), "max_new": r["max_new"]}
            for r in reqs]
    by_rid: Dict[int, Dict] = {}
    steps: List[Dict] = []
    t_traffic = clock()
    nxt, opened, failed = 0, None, 0
    active, contexts_at_open = 0, []
    while True:
        now = clock()
        while nxt < len(reqs) and t_traffic + reqs[nxt]["due_s"] <= now:
            rec = recs[nxt]
            rec["due"] = t_traffic + reqs[nxt]["due_s"]
            rec["submit"] = clock()
            try:
                rec["rid"] = system.submit(reqs[nxt]["prompt"],
                                           reqs[nxt]["max_new"])
                by_rid[rec["rid"]] = rec
            except ValueError:
                failed += 1
            nxt += 1
        if opened is None and active >= system.slots:
            opened = now
            contexts_at_open = [r["n_prompt"] + len(r["times"]) for r in recs
                                if 0 < len(r["times"]) < r["max_new"]]
            if tracer is not None:
                tracer.start()
        if opened is None and now - t_traffic >= mix["window"][
                "max_warmup_s"]:
            raise SystemExit(f"{active} of {system.slots} decode slots busy "
                             f"after {now - t_traffic:.1f} s of traffic: "
                             f"the window never opened")
        if opened is not None and now >= opened + seconds:
            break
        if not system.has_work():
            if nxt >= len(reqs):
                break
            time.sleep(max(0.0, min(t_traffic + reqs[nxt]["due_s"],
                                    (opened or now) + seconds) - now))
            continue
        events = system.step()
        t = clock()
        decode_ctx = []
        for rid, _tok in events:
            rec = by_rid.get(rid)
            if rec is None:
                continue
            n_before = len(rec["times"])
            if n_before:
                decode_ctx.append(rec["n_prompt"] + n_before)
            else:
                active += 1
            rec["times"].append(t)
            if len(rec["times"]) == rec["max_new"]:
                active -= 1
        if opened is not None:
            steps.append({"t_end": t, "decode": decode_ctx})
            if tracer is not None:
                tracer.after_step(steps)
    if tracer is not None:
        tracer.stop()
    if opened is None:
        raise SystemExit(f"the traffic ended with {active} of {system.slots}"
                         f" decode slots ever busy at once: the window "
                         f"never opened")
    return {"recs": recs, "steps": steps, "open": opened, "close": now,
            "t_traffic": t_traffic, "failed": failed, "submitted": nxt,
            "warmup_s": (opened or now) - t_traffic,
            "contexts_at_open": contexts_at_open}


def end_to_end(run: Dict, t_process: float) -> Dict[str, float]:
    t0, t1 = run["open"], run["close"]
    tokens, gaps = 0, []
    for rec in run["recs"]:
        times = rec["times"]
        tokens += sum(1 for t in times if t0 < t <= t1)
        gaps += [b - a for a, b in zip(times, times[1:])
                 if a > t0 and b <= t1]
    out = {"output_tokens_per_s": tokens / (t1 - t0),
           "setup_s": t0 - t_process}
    if gaps:
        out["itl_p50_ms"] = 1e3 * _percentile(gaps, 50)
        out["itl_p90_ms"] = 1e3 * _percentile(gaps, 90)
    return out


class ProfilerWindow:
    """Traces the first ``trace_steps`` steps of the window."""

    def __init__(self, system, mix: Dict, out_dir: str):
        self.system, self.mix, self.dir = system, mix, out_dir
        self.t_start = self.t_stop = None
        self.window = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.system.annotate_device_trace(True)
        jax.profiler.start_trace(self.dir)
        self.window = jax.profiler.TraceAnnotation("bench_window")
        self.window.__enter__()
        self.t_start = time.monotonic()

    def after_step(self, steps: List[Dict]):
        if len(steps) >= self.mix["window"]["trace_steps"]:
            self.stop()

    def stop(self):
        import jax
        if self.t_stop is None:
            self.t_stop = time.monotonic()
            self.window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.system.annotate_device_trace(False)


def per_layer(bm: Dict, workload: str, model: Dict, mix: Dict, system,
              prof: ProfilerWindow, run: Dict, info: Dict, n_pages: int,
              peaks: Optional[Dict] = None) -> Dict:
    """Reduce the traced window and read every per-layer metric."""
    from bench import peaks as P
    from bench import trace_reduce as T
    paths = sorted(glob.glob(os.path.join(prof.dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    trace = T.load_xplane(paths[-1])
    shutil.rmtree(prof.dir, ignore_errors=True)
    spans = [[e["name"], e["t0"], e["t1"], e["args"]]
             for e in system.spans()
             if prof.t_start <= e["t0"] and e["t1"] <= prof.t_stop]
    steps = [s for s in run["steps"] if s["t_end"] <= prof.t_stop]
    ctx = {"model": model, "mix": mix, "trace": trace, "steps": steps,
           "spans": spans, "peaks": peaks or P.peaks(info["kind"]),
           "n_pages": n_pages, "page_size": mix["engine"]["page_size"],
           "slots": mix["engine"]["decode_slots"]}
    out = {}
    for m in metrics_of(bm, workload, "per_layer"):
        value = module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
            note = ctx.get("notes", {}).get(m["name"])
            if note:
                out[m["name"]]["bound"] = note
    busy = T.busy_ns(trace) / 1e9
    breakdown = {"device_ops": T.top_ops(trace),
                 "idle_gaps": T.idle_gaps(trace)}
    return out, {"busy_s": busy, "window_s": trace["window_ns"] / 1e9}, \
        breakdown


def sample_for_check(run: Dict, system, mix: Dict, seed: int) -> List[Dict]:
    """Requests to compare with the reference: drawn from the seed among
    those that served tokens and were never preempted, the one with the
    longest context always among them."""
    from bench.traffic import seed_rng
    chunks = system.prefill_chunks()
    pool = []
    for i, rec in enumerate(run["recs"]):
        rid = rec["rid"]
        if rid is None or not rec["times"] or system.preempted(rid):
            continue
        served = system.served(rid)
        spans = sorted(chunks.get(rid, []))
        ids = []
        for k, (start, n) in enumerate(spans):
            if start != len(ids):
                break
            ids += [k] * n
        if len(ids) != rec["n_prompt"]:
            continue
        pool.append({"index": i, "served": served, "chunk": ids,
                     "n_prompt": rec["n_prompt"]})
    if not pool:
        return []
    longest = max(pool, key=lambda p: p["n_prompt"] + len(p["served"]))
    rest = [p for p in pool if p is not longest]
    rng = seed_rng(seed, 3)
    k = min(len(rest), mix["check"]["sample_requests"] - 1)
    pick = [rest[j] for j in rng.choice(len(rest), size=k, replace=False)]
    return [longest] + pick


def _gaps(logits, tokens):
    """How far below the best logit each given token's logit lies; a
    token outside the vocabulary is infinitely far."""
    import jax.numpy as jnp
    import numpy as np
    tokens = jnp.asarray(tokens)
    if not bool(((tokens >= 0) & (tokens < logits.shape[-1])).all()):
        return np.full(tokens.shape, np.inf)
    got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return np.asarray(logits.max(axis=-1) - got, np.float64)


def _gap_stats(gaps, prefix: str) -> Dict[str, float]:
    import numpy as np
    g = np.concatenate(gaps)
    return {f"{prefix}max_logit_gap": float(g.max()),
            f"{prefix}p99_logit_gap": float(np.percentile(g, 99)),
            f"{prefix}mean_logit_gap": float(g.mean()),
            f"{prefix}off_argmax_share": float((g > 0).mean())}


def check(model: Dict, ref, weights, reqs: List[Dict], sample: List[Dict],
          control: bool = False) -> Dict[str, float]:
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over every served token of the sample (and, for the
    record, other statistics of the same gaps).

    With ``control`` the control stands in the program's place: the
    tokens compared are those that the reference computed at 4-bit
    activations puts first, at each position of the same prompts and
    served tokens. The program's own gaps are then kept under
    ``program_*`` for the record."""
    seqs = [{"tokens": reqs[s["index"]]["prompt"] + s["served"][:-1],
             "n_prompt": s["n_prompt"], "chunk": s["chunk"]}
            for s in sample]
    logits = ref.forward(model, weights, seqs)
    served = [_gaps(lg, s["served"]) for s, lg in zip(sample, logits)]
    if control:
        low = ref.forward(model, weights, seqs, act_bits=4)
        out = _gap_stats([_gaps(lg, lo.argmax(axis=-1))
                          for lg, lo in zip(logits, low)], "")
        out.update(_gap_stats(served, "program_"))
    else:
        out = _gap_stats(served, "")
    out.update(served_tokens=sum(len(s["served"]) for s in sample),
               requests=len(sample))
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, smoke: bool = False,
             overrides: Optional[Dict] = None, fault=None,
             control: bool = False) -> Optional[Dict]:
    """One run of one cell; returns the result object (None without a
    chip). ``control`` puts the control in the program's place in the
    check; ``smoke``/``overrides``/``fault`` serve the CPU tests."""
    overrides = overrides or {}
    bm = overrides.get("benchmark") or benchmark()
    c = cell(bm, workload)
    config = overrides.get("config") or config_file(bm, c["config"])
    mix = overrides.get("mix") or mix_file(c["traffic"])
    cell_data = overrides.get("cell") or cell_file(workload)
    model = config["smoke_model"] if smoke else config["model"]

    import jax
    info = device_info(c["chips"], require_tpu)
    if info is None:
        return None
    if require_tpu:
        print(f"compile cache: {use_compile_cache()}", flush=True)
    counter = CompileCounter()
    ref = module("references", config["reference"])
    sysmod = module("systems", config["system"])

    weights = jax.block_until_ready(ref.make_weights(model, seed))
    weight_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(weights))
    n_pages = pool_pages(mix)
    system = sysmod.System(dict(config, model=model), mix, weights, n_pages,
                           smoke=smoke)
    sysmod.warm_up(system)
    if fault is not None:
        fault(system)
    page_bytes = sysmod.page_bytes(model, mix["engine"]["page_size"])
    print(f"weights {weight_bytes / 1e9:.3f} GB, pool {n_pages} pages "
          f"({n_pages * page_bytes / 1e9:.3f} GB); "
          f"compiles before traffic {counter.compiles}, cache loads "
          f"{counter.cache_loads}", flush=True)

    from bench import traffic
    reqs = traffic.requests(mix, seed, seconds, model["vocab"])
    prof = (ProfilerWindow(system, mix, os.path.join(ROOT, "bench_out",
                                                     "trace"))
            if trace else None)
    compiles_before = counter.compiles
    run = drive(system, reqs, mix, seconds, tracer=prof)
    in_window = counter.compiles - compiles_before
    late = [r["submit"] - r["due"] for r in run["recs"]
            if r["due"] is not None and r["due"] >= run["open"]]
    if late:
        print(f"generator lateness over {len(late)} requests due in the "
              f"window: median {1e3 * _percentile(late, 50):.3f} ms, "
              f"p99 {1e3 * _percentile(late, 99):.3f} ms", flush=True)
    used = pages_in_use(run, mix["engine"]["page_size"])
    print(f"warm-up {run['warmup_s']:.2f} s, window "
          f"{run['close'] - run['open']:.2f} s, {len(run['steps'])} steps; "
          f"compiles in traffic and window: {in_window}; KV in use at the "
          f"window's opening {used} pages ({used * page_bytes / 1e9:.3f} "
          f"GB) of {n_pages}", flush=True)

    result: Dict = {}
    if trace:
        metrics, dev_extra, breakdown = per_layer(
            bm, workload, model, mix, system, prof, run, info, n_pages,
            overrides.get("peaks"))
    else:
        e2e = end_to_end(run, T_PROCESS)
        wanted = metrics_of(bm, workload, "end_to_end")
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in wanted if m["name"] in e2e}
        dev_extra, breakdown = {}, None
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")

    sample = sample_for_check(run, system, mix, seed)
    system.close()
    del system
    gc.collect()
    got = (check(model, ref, weights, reqs, sample, control) if sample
           else {"max_logit_gap": float("inf"), "served_tokens": 0,
                 "requests": 0})
    limit = cell_data["max_logit_gap"]["limit"]
    correct = (limit is not None and got["max_logit_gap"] <= limit
               and in_window == 0)
    compared = {"max_logit_gap": {"value": got["max_logit_gap"],
                                  "limit": limit},
                "compiles_in_window": {"value": in_window, "limit": 0}}
    result.update({
        "correct": bool(correct),
        "attempted": run["submitted"],
        "failed": run["failed"],
        "metrics": metrics,
        "device": dict(info, memory_peak_bytes=peak, **dev_extra),
    })
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["readings"] = {k: v for k, v in got.items()
                          if k not in ("served_tokens", "requests")}
    result["check"] = dict(compared, served_tokens=got["served_tokens"],
                           requests_compared=got["requests"])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), control=bool(args.control))
    if result is None:
        return 2
    for name, c in result["check"].items():
        if isinstance(c, dict):
            print(f"check {name}: {c['value']} (limit {c['limit']})",
                  file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
