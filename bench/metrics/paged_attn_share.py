"""Kernels: the paged decode-attention kernel's device time over the decode
step's device time."""
from bench import trace_reduce as T
from bench.metrics.paged_attn_roofline import kernel_events


def read(ctx):
    calls = T.module_calls(ctx["trace"], "jit_engine_decode")
    events = kernel_events(ctx)
    if not calls or not events:
        return None
    return 100.0 * T.total_ns(events) / T.total_ns(calls)
