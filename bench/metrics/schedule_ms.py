"""Scheduler: host time of the engine's ``schedule`` span (the
scheduler's admission and batch planning), mean per engine step."""


def read(ctx):
    ms = [1e3 * (s[2] - s[1]) for s in ctx["spans"] if s[0] == "schedule"]
    if not ms:
        return None
    return sum(ms) / len(ms)
