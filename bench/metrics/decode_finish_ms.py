"""Engine host loop: host time after a decode step's logits reached the
host: telemetry read back, sampling, and wire accounting with emission
(``decode_readback`` + ``decode_sample`` + ``decode_emit`` spans), mean
per ``decode_batch`` span."""
from bench.metrics.decode_launch_ms import per_decode_batch_ms

SPANS = ("decode_readback", "decode_sample", "decode_emit")


def read(ctx):
    return per_decode_batch_ms(ctx["spans"], SPANS)
