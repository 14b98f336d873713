"""Engine host loop: each ``decode_batch`` span (host) less the device time
of the ``jit_engine_decode`` execution that started inside it, on the
profiler's one clock; mean over the traced decode steps."""
from bench import trace_reduce as T


def read(ctx):
    tr = ctx["trace"]
    pairs = [(span, dev) for span, dev in T.within(
        tr["host"], "decode_batch",
        T.module_calls(tr, "jit_engine_decode")) if dev > 0]
    if not pairs:
        return None
    return sum(span - dev for span, dev in pairs) / len(pairs) / 1e6
