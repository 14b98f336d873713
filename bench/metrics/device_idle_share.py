"""Device: share of the traced window in which no program ran on the chip
(1 - union of program executions / window)."""
from bench import trace_reduce as T


def read(ctx):
    tr = ctx["trace"]
    if not tr["devices"] or tr["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - T.busy_ns(tr) / tr["window_ns"])
