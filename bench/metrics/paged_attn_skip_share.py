"""Kernels: share of the paged decode-attention kernel's grid steps that
skip a page past the sequence's context, from the engine's
``decode_batch`` span args: 100 x (1 - sum ``attn_pages`` / sum
``attn_page_steps``). A program whose spans lack the args reads nothing."""


def read(ctx):
    args = [s[3] for s in ctx["spans"] if s[0] == "decode_batch"
            and "attn_pages" in s[3] and "attn_page_steps" in s[3]]
    steps = sum(a["attn_page_steps"] for a in args)
    if not steps:
        return None
    return 100.0 * (1.0 - sum(a["attn_pages"] for a in args) / steps)
