"""The system under test: ``repro.serving.Engine`` on one device, built as
``repro.launch.serve.make_engine`` builds it, serving the benchmark's
weights.

This is the only module of the benchmark that imports the program. It
hands the program the arrays ``references/*.make_weights`` made (wrapped
in the program's served-projection type), submits requests, steps the
engine, and reads back what the program reports of itself: emitted
tokens, preemptions and its span log.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Dict, List, Tuple

import jax.numpy as jnp

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..",
                    "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.core.qlinear import SparqleLinear  # noqa: E402
from repro.core.quantize import QuantizedTensor  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models.registry import get_config  # noqa: E402
from repro.serving import SamplingParams  # noqa: E402



def _projection(p: Dict, clip_l: float, clip_h: float) -> SparqleLinear:
    lead = p["scale"].shape[:-2]
    fill = (jnp.full(lead, clip_l, jnp.float32),
            jnp.full(lead, clip_h, jnp.float32))
    return SparqleLinear(
        w=QuantizedTensor(q=p["q"], scale=p["scale"],
                          zero=jnp.zeros_like(p["scale"]), bits=4),
        col_mask=p["mask"], l=fill[0], h=fill[1], mode="sparqle",
        packed=True, wire_format="unpacked")


def served_params(model: Dict, weights: Dict) -> Dict:
    """The program's served parameter tree over the benchmark's arrays
    (no copy of the weight bytes)."""
    q = model["quant"]
    layer: Dict = {}
    for name, leaf in weights["layers"].items():
        if isinstance(leaf, dict) and "q" in leaf:
            layer[name] = _projection(leaf, q["clip_l"], q["clip_h"])
        else:
            layer[name] = leaf
    params = {"embed": {"table": weights["embed"]},
              "stages": {"s0": {"p0": layer}},
              "final_norm": weights["final_norm"]}
    if "lm_head" in weights:
        params["lm_head"] = _projection(weights["lm_head"], q["clip_l"],
                                        q["clip_h"])
    return params


def page_bytes(model: Dict, page_size: int) -> int:
    """HBM bytes of one pool page over all layers: int4 K and V nibbles
    and a float32 scale per token and KV head for each."""
    per_token_head = model["head_dim"] // 2 * 2 + 4 * 2
    return (model["n_layers"] * page_size * model["n_kv_heads"]
            * per_token_head)


class System:
    """One engine, its requests and what it reports."""

    def __init__(self, config: Dict, mix: Dict, weights: Dict,
                 n_pages: int, smoke: bool = False):
        eng = mix["engine"]
        self.model = config["model"]
        self.cfg = get_config(config["program_arch"], smoke=smoke)
        args = argparse.Namespace(
            prompt_len=mix["max_context"], gen=0, spec_gamma=0,
            page_size=eng["page_size"], batch=eng["decode_slots"],
            decode_slots=eng["decode_slots"], n_pages=n_pages,
            token_budget=eng["prefill_chunk"] + eng["decode_slots"],
            prefill_chunk=eng["prefill_chunk"], slo=None)
        self.engine = serve.make_engine(self.cfg,
                                        served_params(self.model, weights),
                                        args)
        self.slots = eng["decode_slots"]
        self.chunk = eng["prefill_chunk"]
        self.max_pages = -(-mix["max_context"] // eng["page_size"])
        self.handles: Dict[int, object] = {}
        self.zero = self._clock_zero()

    # -- driving -----------------------------------------------------------

    def submit(self, prompt: List[int], max_new: int) -> int:
        h = self.engine.submit(prompt, SamplingParams(max_new_tokens=max_new))
        self.handles[h.rid] = h
        return h.rid

    def step(self) -> List[Tuple[int, int]]:
        return self.engine.step()

    def has_work(self) -> bool:
        return self.engine.sched.has_work()

    def served(self, rid: int) -> List[int]:
        return list(self.handles[rid].out_tokens)

    def preempted(self, rid: int) -> bool:
        return self.handles[rid].preemptions > 0

    def annotate_device_trace(self, on: bool) -> None:
        """Put the engine's spans into the profiler's trace as well."""
        self.engine.obs.tracer.xla_annotations = on

    # -- what the program reports ------------------------------------------

    def _clock_zero(self) -> float:
        """The ``time.monotonic`` reading at which the engine's span clock
        reads 0 (span ``ts`` are microseconds from there)."""
        tr = self.engine.obs.tracer
        t = time.monotonic()
        tr.instant("bench_clock_mark")
        mark = [e for e in tr.export()["traceEvents"]
                if e.get("name") == "bench_clock_mark"][-1]
        return t - mark["ts"] / 1e6

    def spans(self) -> List[Dict]:
        """The engine's span log: complete spans with ``t0``/``t1`` in
        ``time.monotonic`` seconds."""
        out = []
        for e in self.engine.obs.tracer.export()["traceEvents"]:
            if e.get("ph") == "X":
                t0 = self.zero + e["ts"] / 1e6
                out.append(dict(e, t0=t0, t1=t0 + e["dur"] / 1e6))
        return out

    def prefill_chunks(self) -> Dict[int, List[Tuple[int, int]]]:
        """(start, n) of every prefill chunk each request ran."""
        out: Dict[int, List[Tuple[int, int]]] = {}
        for e in self.spans():
            if e.get("name") == "prefill_chunk":
                a = e["args"]
                out.setdefault(a["rid"], []).append((a["start"], a["n"]))
        return out

    def close(self) -> None:
        """Free the pool and the program's references to the weights."""
        self.engine.pool.state = None
        self.engine = None
        self.handles = {}


def warm_up(system: System) -> None:
    """Compile the cell's two step shapes, and no others, through the
    public path: one request of one chunk and two tokens."""
    rid = system.submit([1] * min(system.chunk, 8), 2)
    while not system.handles[rid].done:
        system.step()
