"""Shared neural layers: norms, RoPE, blockwise attention, MLPs.

All attention is *blockwise* (FlashAttention-style tiling with running
max/denominator, pure ``lax.scan``): the 32k-prefill and 500k-decode shape
cells make materializing (S x S) score tensors impossible even at compile
time. Computation runs in f32 accumulators over bf16 operands.

Conventions: activations (B, S, D); attention internals (B, S, KVH, G, hd)
with G = n_heads // n_kv_heads (GQA groups); masks built from absolute
positions so the same code path serves causal, sliding-window, prefix-LM
and bidirectional (encoder) attention — and gemma3's scanned per-layer
local/global flag just widens the window dynamically.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + gamma.astype(jnp.float32))
            ).astype(dt)


def layer_norm(x, gamma, beta, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma + beta).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd) [or (..., H, hd) with scalar positions]; rotates
    pairs (even, odd) across the last dim."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                        # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# blockwise (flash) attention
# ---------------------------------------------------------------------------

class AttnSpec(NamedTuple):
    causal: bool = True
    window: int = 0          # 0 = unlimited; sliding window otherwise
    prefix_len: int = 0      # positions < prefix_len attend bidirectionally


def _mask(qi: jax.Array, kj: jax.Array, spec: AttnSpec,
          is_global: Optional[jax.Array]) -> jax.Array:
    """(bq, bkv) boolean allow-mask from absolute positions."""
    qi = qi[:, None]
    kj = kj[None, :]
    allow = jnp.ones(jnp.broadcast_shapes(qi.shape, kj.shape), bool)
    if spec.causal:
        causal_ok = kj <= qi
        if spec.prefix_len:
            causal_ok = causal_ok | (kj < spec.prefix_len)
        allow = allow & causal_ok
    if spec.window:
        in_window = (qi - kj) < spec.window
        if is_global is not None:
            in_window = in_window | is_global  # scanned per-layer flag
        allow = allow & in_window
    return allow


def flash_attention(
    q: jax.Array,          # (B, Sq, H, hd)
    k: jax.Array,          # (B, Skv, KVH, hd)
    v: jax.Array,          # (B, Skv, KVH, hd)
    spec: AttnSpec,
    *,
    q_offset: int | jax.Array = 0,
    is_global: Optional[jax.Array] = None,
    bq: int = 512,
    bkv: int = 1024,
) -> jax.Array:
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    hdv = v.shape[-1]                # may differ from hd (MLA)
    g = h // kvh
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (sq, bq, skv, bkv)
    scale = hd ** -0.5

    qg = q.reshape(b, sq, kvh, g, hd)
    n_q, n_kv = sq // bq, skv // bkv

    # (n_q, B, bq, KVH, G, hd) / (n_kv, B, bkv, KVH, hd)
    q_blocks = qg.reshape(b, n_q, bq, kvh, g, hd).transpose(1, 0, 2, 3, 4, 5)
    k_blocks = k.reshape(b, n_kv, bkv, kvh, hd).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(b, n_kv, bkv, kvh, hdv).transpose(1, 0, 2, 3, 4)

    def q_step(_, qb_i):
        qb, iq = qb_i
        qpos = q_offset + iq * bq + jnp.arange(bq)

        def kv_step(carry, kb_vb_j):
            m, l, acc = carry
            kb, vb, jk = kb_vb_j
            kpos = jk * bkv + jnp.arange(bkv)
            s = jnp.einsum("bihgd,bjhd->bhgij", qb.astype(jnp.float32),
                           kb.astype(jnp.float32)) * scale
            allow = _mask(qpos, kpos, spec, is_global)
            s = jnp.where(allow[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgij,bjhd->bhgid", p, vb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, bq), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, bq, hdv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (k_blocks, v_blocks, jnp.arange(n_kv)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]   # (B,KVH,G,bq,hdv)
        out = out.transpose(0, 3, 1, 2, 4)             # (B,bq,KVH,G,hdv)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (q_blocks, jnp.arange(n_q)))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h, hdv)
    return out


def decode_attention(
    q: jax.Array,          # (B, H, hd) — one new token per sequence
    k_cache: jax.Array,    # (B, Smax, KVH, hd)
    v_cache: jax.Array,
    pos: jax.Array,        # (B,) current position (0-based index of new token)
    spec: AttnSpec,
    is_global: Optional[jax.Array] = None,
) -> jax.Array:
    b, h, hd = q.shape
    _, smax, kvh, _ = k_cache.shape
    hdv = v_cache.shape[-1]
    g = h // kvh
    scale = hd ** -0.5
    qg = q.reshape(b, kvh, g, hd)
    s = jnp.einsum("bhgd,bjhd->bhgj", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    j = jnp.arange(smax)[None, :]                       # (1, Smax)
    allow = j <= pos[:, None]
    if spec.window:
        in_w = (pos[:, None] - j) < spec.window
        if is_global is not None:
            in_w = in_w | is_global
        allow = allow & in_w
    s = jnp.where(allow[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgj,bjhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, hdv).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    h = constrain(h, ("batch", "seq", "mlp"))
    return h @ w_down


def gelu_mlp(x, w_fc, b_fc, w_proj, b_proj):
    h = jax.nn.gelu(x @ w_fc + b_fc)
    h = constrain(h, ("batch", "seq", "mlp"))
    return h @ w_proj + b_proj


# ---------------------------------------------------------------------------
# activation wire telemetry (SPARQLe serving path)
# ---------------------------------------------------------------------------

def act_wire_telemetry(x: jax.Array) -> dict:
    """Per-token wire accounting of a hidden-activation tensor (..., D).

    Int8-quantizes ``x`` per token and reports, per row:

      * ``sparsity``    — MSB4 sub-precision sparsity,
      * ``wire_bytes``  — MEASURED bytes in the packed wire format
        (``core/packing.py``: LSB4 pairs + PBM words + compacted MSB
        stream, including the padding/word-rounding slack),
      * ``dense_bytes`` — the dense int8 baseline (D bytes).

    The paged serving steps call this on the INTER-LAYER hidden (residual)
    stream — the tensor the paper's drain path writes back to SRAM in
    SPARQLe format between layers. It is a stream-level measurement, not
    the per-projection operand accounting: each projection additionally
    norms (and, with clipping enabled, §3.2-clips) its input before
    encoding, which shifts per-projection sparsity relative to the
    numbers reported here (bench_compression.py measures those per-site).
    """
    from repro.core.packing import (dense_bytes_rows,
                                    measured_wire_bytes_rows)
    from repro.core.quantize import quantize_activations
    from repro.core.sparqle import subprecision_sparsity

    with jax.named_scope("telemetry"):
        q = quantize_activations(x, bits=8, per_token=True).q
        return {
            "sparsity": subprecision_sparsity(q, axis=-1),
            "wire_bytes": measured_wire_bytes_rows(q).astype(jnp.float32),
            "dense_bytes": jnp.full(q.shape[:-1], dense_bytes_rows(q),
                                    jnp.float32),
        }


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def unembed(x: jax.Array, table_or_head: jax.Array, transpose: bool) -> jax.Array:
    w = table_or_head.T if transpose else table_or_head
    return x @ w.astype(x.dtype)
