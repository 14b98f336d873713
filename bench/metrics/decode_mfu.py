"""Model: operations the traced decode steps needed (projections and head
for each active slot, attention over each slot's real context; see
``bench/counts.py``) over the decode device time at the int8 peak."""
from bench import counts as C
from bench import trace_reduce as T


def read(ctx):
    calls = T.module_calls(ctx["trace"], "jit_engine_decode")
    steps = [s["decode"] for s in ctx["steps"] if s["decode"]]
    if not calls or not steps:
        return None
    ops = sum(C.decode_step_ops(ctx["model"], s) for s in steps)
    secs = T.total_ns(calls) / 1e9
    return 100.0 * ops / (secs * ctx["peaks"]["int8_ops"])
