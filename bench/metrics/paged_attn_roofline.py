"""Kernels: least time the paged decode-attention calls need (the larger of
their operations at the bf16 peak and their context pages' bytes at HBM
bandwidth, per call; ``bench/counts.py``) over the kernel's device time.
The kernel is the Pallas call (``tpu_custom_call``) that reads the pool."""
from bench import counts as C
from bench import trace_reduce as T


def kernel_events(ctx):
    m = ctx["model"]
    pool = (f"s8[{ctx['n_pages']},{ctx['page_size']},{m['n_kv_heads']},"
            f"{m['head_dim'] // 2}]")
    return T.op_events(ctx["trace"], lambda name: (
        'custom_call_target="tpu_custom_call"' in name and pool in name))


def read(ctx):
    events = kernel_events(ctx)
    steps = [s["decode"] for s in ctx["steps"] if s["decode"]]
    if not events or not steps:
        return None
    p = ctx["peaks"]
    least, bounds = 0.0, set()
    for contexts in steps:
        ops, n_bytes = C.paged_attention_call(ctx["model"], contexts,
                                              ctx["page_size"])
        t, bound = C.least_seconds(ops, n_bytes, p["bf16_flops"],
                                   p["hbm_bytes_per_s"])
        least += t * ctx["model"]["n_layers"]
        bounds.add(bound)
    ctx.setdefault("notes", {})["paged_attn_roofline"] = "+".join(
        sorted(bounds))
    return 100.0 * least / (T.total_ns(events) / 1e9)
