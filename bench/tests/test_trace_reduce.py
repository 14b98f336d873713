"""The trace reduction on a recorded TPU v5e trace: the first decode step of
a traced ``granite-8b.codegen`` window (16 slots, 192 pages a slot, a pool
of 7,800 pages) and the idle gap after it, with the engine's host spans."""
import gzip
import json
import os

import pytest

from bench import trace_reduce as T
from bench.metrics import (decode_mfu, decode_occupancy, decode_step_ms,
                           device_idle_share, host_ms_per_decode_step,
                           paged_attn_roofline, paged_attn_share)

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_granite_codegen_decode.json.gz")
MODEL = {"n_layers": 36, "d_model": 4096, "n_heads": 32, "n_kv_heads": 8,
         "head_dim": 128, "d_ff": 14336, "vocab": 49152, "mlp": "swiglu"}
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def ctx():
    with gzip.open(DATA) as f:
        trace = json.load(f)
    return {"trace": trace, "model": MODEL, "peaks": PEAKS, "n_pages": 7800,
            "page_size": 16, "slots": 16,
            "steps": [{"decode": [400] * 16}],
            "spans": [["decode_batch", 0.0, 2.868, {"slots": 16}]]}


def test_modules_and_busy_union(ctx):
    tr = ctx["trace"]
    dec = T.module_calls(tr, "jit_engine_decode")
    assert len(dec) == 1 and not T.module_calls(tr, "jit_prefill_chunk")
    assert decode_step_ms.read(ctx) == pytest.approx(2860.2, rel=1e-3)
    busy = T.busy_ns(tr)
    assert T.total_ns(dec) <= busy < T.total_ns(dec) + 1e6
    idle = device_idle_share.read(ctx)
    assert idle == pytest.approx(100 * (1 - busy / tr["window_ns"]))
    assert 0.1 < idle < 1


def test_union_of_overlapping_intervals():
    assert T.union_length([(0, 10), (5, 15), (20, 30), (21, 22)]) == 25
    assert T.union_length([]) == 0


def test_paged_kernel_is_found_and_dominates_decode(ctx):
    assert len(paged_attn_roofline.kernel_events(ctx)) == 36  # per layer
    assert 85 < paged_attn_share.read(ctx) < 95
    assert 0 < paged_attn_roofline.read(ctx) < 1   # far below its roofline
    assert ctx["notes"]["paged_attn_roofline"] == "memory"
    assert 0 < decode_mfu.read(ctx) < 1


def test_host_time_occupancy_and_idle_gaps_follow_the_spans(ctx):
    assert 0 < host_ms_per_decode_step.read(ctx) < 20
    assert decode_occupancy.read(ctx) == 100.0
    gaps = T.idle_gaps(ctx["trace"])
    assert gaps and all(g[1] > 0 for g in gaps)
    assert gaps[0][0] == "idle under decode_batch"
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)


def test_top_ops_rank_the_kernel_first(ctx):
    top = T.top_ops(ctx["trace"])
    assert len(top) <= 10
    assert top[0][0].endswith("(tpu_custom_call)")
    assert all(not t[0].startswith("while") for t in top)
    assert [t[1] for t in top] == sorted((t[1] for t in top), reverse=True)
