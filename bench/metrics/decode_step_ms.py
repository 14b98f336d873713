"""Jitted steps: device time of one ``jit_engine_decode`` execution."""
from bench import trace_reduce as T


def read(ctx):
    calls = T.module_calls(ctx["trace"], "jit_engine_decode")
    if not calls:
        return None
    return T.total_ns(calls) / len(calls) / 1e6
