"""Operations and bytes that the served model needs, from shapes alone.

``model`` is the ``model`` section of a configuration file. Operations
count a multiply and an add as two. Nothing here depends on how the
program computes: a linear is 2*K*N operations per token however many
passes the program makes over it, and attention reads the pages that
hold a sequence's context, not the pages the program happens to touch.
"""
from __future__ import annotations

from typing import Dict, Iterable, Sequence, Tuple


def linear_ops_per_token(model: Dict) -> int:
    """Operations of one token through every layer's projections."""
    d, h, kvh, hd, f = (model["d_model"], model["n_heads"],
                        model["n_kv_heads"], model["head_dim"], model["d_ff"])
    attn = d * (h + 2 * kvh) * hd + h * hd * d
    mlp = (3 if model["mlp"] == "swiglu" else 2) * d * f
    return 2 * model["n_layers"] * (attn + mlp)


def head_ops_per_token(model: Dict) -> int:
    return 2 * model["d_model"] * model["vocab"]


def attention_ops(model: Dict, context: int) -> int:
    """One query attending ``context`` keys, in every layer (QK and PV)."""
    return 4 * model["n_layers"] * model["n_heads"] * model["head_dim"] \
        * context


def decode_step_ops(model: Dict, contexts: Sequence[int]) -> int:
    """One decode step of the active slots; ``contexts`` are the positions
    each query attends (its own included)."""
    per_token = linear_ops_per_token(model) + head_ops_per_token(model)
    return sum(per_token + attention_ops(model, n) for n in contexts)


def prefill_chunk_ops(model: Dict, start: int, n: int) -> int:
    """One prefill chunk of ``n`` valid tokens at ``start``: the chunk's
    projections, causal attention over the past and the chunk, and the
    head of its last position."""
    attn = sum(attention_ops(model, start + i + 1) for i in range(n))
    return n * linear_ops_per_token(model) + attn \
        + head_ops_per_token(model)


def kv_page_bytes_per_layer(model: Dict, page_size: int) -> int:
    """One page of one layer: int4 K and V, a float32 scale per token and
    KV head for each."""
    return page_size * model["n_kv_heads"] * (model["head_dim"] + 8)


def paged_attention_call(model: Dict, contexts: Iterable[int],
                         page_size: int) -> Tuple[int, int]:
    """(operations, bytes) one decode-attention call over one layer needs:
    each active query's context pages, its query and its output (bf16)."""
    ops = 0
    n_bytes = 0
    q_bytes = 2 * 2 * model["n_heads"] * model["head_dim"]
    for n in contexts:
        ops += 4 * model["n_heads"] * model["head_dim"] * n
        n_bytes += (-(-n // page_size)
                    * kv_page_bytes_per_layer(model, page_size) + q_bytes)
    return ops, n_bytes


def least_seconds(ops: float, n_bytes: float, peak_ops: float,
                  bytes_per_s: float) -> Tuple[float, str]:
    """Roofline: the larger of the compute and the memory time, and which
    one it is."""
    t_ops, t_bytes = ops / peak_ops, n_bytes / bytes_per_s
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
