"""Scheduler: mean share of decode slots filled per decode step, from the
engine's ``decode_batch`` spans (``slots`` = requests in the batch)."""


def read(ctx):
    slots = [s[3]["slots"] for s in ctx["spans"] if s[0] == "decode_batch"]
    if not slots:
        return None
    return 100.0 * sum(slots) / len(slots) / ctx["slots"]
