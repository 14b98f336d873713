"""Engine host loop: host time from the start of a decode step to the
return of its jitted call (``decode_inputs`` + ``decode_launch`` spans),
mean per ``decode_batch`` span."""

SPANS = ("decode_inputs", "decode_launch")


def per_decode_batch_ms(spans, names):
    """Summed length of the spans ``names`` over the number of
    ``decode_batch`` spans, in ms; None where either is missing."""
    batches = sum(1 for s in spans if s[0] == "decode_batch")
    inside = [s[2] - s[1] for s in spans if s[0] in names]
    if not batches or not inside:
        return None
    return 1e3 * sum(inside) / batches


def read(ctx):
    return per_decode_batch_ms(ctx["spans"], SPANS)
