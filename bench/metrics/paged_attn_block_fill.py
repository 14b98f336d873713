"""Kernels: share of the page slots the paged decode-attention kernel
gathers that hold context, from the engine's ``decode_batch`` span args:
100 x sum ``attn_pages`` / sum ``attn_block_pages`` (the blocks holding
context span whole blocks of table columns; the rest of their last block
is masked work). A program whose spans lack the args reads nothing."""


def read(ctx):
    args = [s[3] for s in ctx["spans"] if s[0] == "decode_batch"
            and "attn_pages" in s[3] and "attn_block_pages" in s[3]]
    slots = sum(a["attn_block_pages"] for a in args)
    if not slots:
        return None
    return 100.0 * sum(a["attn_pages"] for a in args) / slots
