"""Unified functional model: one forward/prefill/decode for all families.

Architecture = embedding + a list of scanned stages (``stages.py``) + final
norm + head. Each stage scans a *period* of heterogeneous sub-layers with
stacked params, so the HLO is O(period) in depth. Mixers: GQA attention
(full / sliding-window / global, RoPE, optional qk-norm), MLA (deepseek —
*absorbed* compressed-KV attention, see note below), and Mamba-2 SSD.
FFNs: dense (SwiGLU / GeGLU / GELU), MoE (shared + routed), or none.

Every projection goes through :func:`repro.core.qlinear.linear`, so the same
code serves float params (training) and SPARQLe-quantized params (the
paper's sub-precision serving path) — the technique is a first-class,
zero-code-change feature of the framework.

MLA note (DESIGN.md §2): we use the weight-absorbed form everywhere —
attention scores are computed directly against the compressed KV cache
(c_kv, k_rope), never materializing per-head K/V. This is mandatory at
decode (naive expansion of a 32k-token cache for 128 heads is ~100s of GB)
and memory-safe at prefill at the cost of extra score/context FLOPs
(contraction over kv_lora_rank instead of head_dim); the blockwise
re-materialized prefill variant is tracked as a §Perf iteration.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.qlinear import linear, msb_skip_scope
from repro.core.quantize import quantize_weights
from repro.distributed.sharding import constrain
from repro.distributed.tp import tp_ctx
from repro.models import moe as moe_lib
from repro.models import ssd as ssd_lib
from repro.models.layers import (AttnSpec, NEG_INF, act_wire_telemetry,
                                 decode_attention, embed, flash_attention,
                                 layer_norm, rms_norm, rope)
from repro.models.stages import LayerDef, Stage, build_stages

Params = Dict[str, Any]
Cache = Dict[str, Any]


# ---------------------------------------------------------------------------
# small helpers
# ---------------------------------------------------------------------------

def _norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.norm_type == "layer":
        return layer_norm(x, p["gamma"], p["beta"], cfg.rms_eps)
    return rms_norm(x, p["gamma"], cfg.rms_eps)


def _kv_quant(cfg: ModelConfig, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Quantize one KV tensor (..., hd) -> (container, f32 scale).

    For kv_bits == 4 the two's-complement nibbles are PACKED two-per-byte
    (..., hd/2), halving KV-cache HBM/footprint for real — the sub-byte
    packing the paper's wire format implies, applied to the cache
    (§Perf iteration: decode cells are cache-bandwidth-bound).
    """
    qt = quantize_weights(x, bits=cfg.kv_bits, axis=-1)
    q = qt.q
    if cfg.kv_bits == 4 and q.shape[-1] % 2 == 0:
        lo = jnp.bitwise_and(q[..., 0::2], 0xF)
        hi = jnp.left_shift(jnp.bitwise_and(q[..., 1::2], 0xF), 4)
        q = jnp.bitwise_or(lo, hi).astype(jnp.int8)
    return q, qt.scale[..., 0]


def _kv_dequant(cfg: ModelConfig, q: jax.Array, s: jax.Array,
                dtype) -> jax.Array:
    if cfg.kv_bits == 4:
        # unpack two's-complement nibbles: (x << 4) >> 4 sign-extends
        lo = jnp.right_shift(jnp.left_shift(q, 4), 4)
        hi = jnp.right_shift(q, 4)
        q = jnp.stack([lo, hi], axis=-1).reshape(*q.shape[:-1],
                                                 q.shape[-1] * 2)
    return (q.astype(jnp.float32) * s[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# GQA attention mixer
# ---------------------------------------------------------------------------

def _attn_qkv(cfg: ModelConfig, p: Params, h: jax.Array, positions,
              theta: float):
    """h (..., D) -> q (..., H, hd), k/v (..., KVH, hd), roped."""
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = linear(h, p["wq"], p.get("bq"))
    k = linear(h, p["wk"], p.get("bk"))
    v = linear(h, p["wv"], p.get("bv"))
    q = q.reshape(*q.shape[:-1], H, hd)
    k = k.reshape(*k.shape[:-1], KVH, hd)
    v = v.reshape(*v.shape[:-1], KVH, hd)
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    q = rope(q, positions, theta)
    k = rope(k, positions, theta)
    return q, k, v


def attn_full(cfg: ModelConfig, ld: LayerDef, p: Params, x: jax.Array,
              positions: jax.Array, prefix_len: int,
              make_cache: Optional[int]) -> Tuple[jax.Array, Optional[Cache]]:
    """Training / prefill attention over the whole sequence."""
    b, s, d = x.shape
    theta = ld.rope_theta or cfg.rope_theta
    h = _norm(cfg, p["ln"], x)
    q, k, v = _attn_qkv(cfg, p, h, positions, theta)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    v = constrain(v, ("batch", "seq", "kv_heads", None))
    spec = AttnSpec(causal=cfg.causal, window=ld.window,
                    prefix_len=prefix_len)
    o = flash_attention(q, k, v, spec)
    o = o.reshape(b, s, cfg.n_heads * cfg.hd)
    out = linear(o, p["wo"], p.get("bo"), tp="row")

    cache = None
    if make_cache is not None:
        smax = make_cache
        kq, ks = _kv_quant(cfg, k)
        vq, vs = _kv_quant(cfg, v)
        pad = [(0, 0), (0, smax - s), (0, 0), (0, 0)]
        pad3 = [(0, 0), (0, smax - s), (0, 0)]
        cache = {
            "k_q": jnp.pad(kq, pad), "k_s": jnp.pad(ks, pad3),
            "v_q": jnp.pad(vq, pad), "v_s": jnp.pad(vs, pad3),
        }
    return out, cache


def attn_decode(cfg: ModelConfig, ld: LayerDef, p: Params, x: jax.Array,
                cache: Cache, pos: jax.Array) -> Tuple[jax.Array, Cache]:
    """One-token attention against the quantized KV cache. x: (B, D)."""
    b, d = x.shape
    theta = ld.rope_theta or cfg.rope_theta
    h = _norm(cfg, p["ln"], x)
    q, k_new, v_new = _attn_qkv(cfg, p, h, pos, theta)
    # insert the new token's quantized K/V at its position
    bidx = jnp.arange(b)
    kq, ks = _kv_quant(cfg, k_new)
    vq, vs = _kv_quant(cfg, v_new)
    cache = {
        "k_q": cache["k_q"].at[bidx, pos].set(kq),
        "k_s": cache["k_s"].at[bidx, pos].set(ks),
        "v_q": cache["v_q"].at[bidx, pos].set(vq),
        "v_s": cache["v_s"].at[bidx, pos].set(vs),
    }
    k = _kv_dequant(cfg, cache["k_q"], cache["k_s"], x.dtype)
    v = _kv_dequant(cfg, cache["v_q"], cache["v_s"], x.dtype)
    k = constrain(k, ("batch", "kv_seq", "kv_heads", None))
    v = constrain(v, ("batch", "kv_seq", "kv_heads", None))
    spec = AttnSpec(causal=cfg.causal, window=ld.window)
    o = decode_attention(q, k, v, pos, spec)
    o = o.reshape(b, cfg.n_heads * cfg.hd)
    return linear(o, p["wo"], p.get("bo"), tp="row"), cache


# ---------------------------------------------------------------------------
# MLA mixer (deepseek) — absorbed compressed-KV attention
# ---------------------------------------------------------------------------

def _mla_q(cfg: ModelConfig, p: Params, h: jax.Array, positions):
    """h (..., D) -> q_nope (..., H, dn), q_rope (..., H, dr)."""
    H, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    cq = linear(h, p["wq_a"])
    cq = rms_norm(cq, p["q_norm"], cfg.rms_eps)
    q = linear(cq, p["wq_b"]).reshape(*h.shape[:-1], H, dn + dr)
    qn, qr = q[..., :dn], q[..., dn:]
    qr = rope(qr, positions, cfg.rope_theta)
    return qn, qr


def _mla_ckv(cfg: ModelConfig, p: Params, h: jax.Array, positions):
    """h (..., D) -> compressed c_kv (..., rkv), roped shared k_rope (..., dr)."""
    rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    ckv_full = linear(h, p["wkv_a"])
    ckv, kr = ckv_full[..., :rkv], ckv_full[..., rkv:]
    ckv = rms_norm(ckv, p["kv_norm"], cfg.rms_eps)
    kr = rope(kr[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return ckv, kr


def _mla_absorbed_weights(cfg: ModelConfig, p: Params):
    """Split wkv_b into W_uk (rkv, H, dn) and W_uv (rkv, H, dv).

    SPARQLe-quantized wkv_b is applied through its dequantized form here —
    absorption is a float-domain rewrite (noted in DESIGN.md: the absorbed
    matmuls contract activations x activations, the paper's out-of-scope
    case, so they stay unquantized).
    """
    w = p["wkv_b"]
    if not isinstance(w, jax.Array):          # SparqleLinear (maybe packed)
        w = w.dequantize()
    H, dn, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim
    w = w.reshape(cfg.kv_lora_rank, H, dn + dv)
    return w[..., :dn], w[..., dn:]


def mla_full(cfg: ModelConfig, ld: LayerDef, p: Params, x: jax.Array,
             positions: jax.Array, prefix_len: int,
             make_cache: Optional[int]) -> Tuple[jax.Array, Optional[Cache]]:
    b, s, d = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    h = _norm(cfg, p["ln"], x)
    qn, qr = _mla_q(cfg, p, h, positions)          # (B,S,H,dn/dr)
    ckv, kr = _mla_ckv(cfg, p, h, positions)       # (B,S,rkv) / (B,S,dr)
    w_uk, w_uv = _mla_absorbed_weights(cfg, p)

    o = _mla_flash(qn, qr, ckv, kr, w_uk, w_uv, causal=cfg.causal)
    out = linear(o.reshape(b, s, H * dv), p["wo"])

    cache = None
    if make_cache is not None:
        smax = make_cache
        cq, cs = _kv_quant(cfg, ckv)
        cache = {
            "ckv_q": jnp.pad(cq, [(0, 0), (0, smax - s), (0, 0)]),
            "ckv_s": jnp.pad(cs, [(0, 0), (0, smax - s)]),
            "kr": jnp.pad(kr, [(0, 0), (0, smax - s), (0, 0)]),
        }
    return out, cache


def _mla_flash(qn, qr, ckv, kr, w_uk, w_uv, *, causal: bool,
               bq: int = 512, bkv: int = 1024) -> jax.Array:
    """Blockwise absorbed MLA attention. Returns (B, S, H, dv)."""
    b, s_orig, H, dn = qn.shape
    rkv = ckv.shape[-1]
    dr = qr.shape[-1]
    dv = w_uv.shape[-1]
    scale = (dn + dr) ** -0.5
    bq = min(bq, s_orig)
    bkv = min(bkv, s_orig)
    pad = max((-s_orig) % bq, (-s_orig) % bkv)
    if pad:  # tail-pad; causal masking hides padded KV from valid queries
        assert causal, "non-causal MLA would attend padded positions"
        padfn = lambda t: jnp.pad(  # noqa: E731
            t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        qn, qr, ckv, kr = map(padfn, (qn, qr, ckv, kr))
    s = s_orig + pad
    n_q, n_kv = s // bq, s // bkv

    qn_b = qn.reshape(b, n_q, bq, H, dn).transpose(1, 0, 2, 3, 4)
    qr_b = qr.reshape(b, n_q, bq, H, dr).transpose(1, 0, 2, 3, 4)
    ckv_b = ckv.reshape(b, n_kv, bkv, rkv).transpose(1, 0, 2, 3)
    kr_b = kr.reshape(b, n_kv, bkv, dr).transpose(1, 0, 2, 3)

    def q_step(_, qs):
        qnb, qrb, iq = qs
        # absorb: q_eff (B, bq, H, rkv) — computed per q block only
        q_eff = jnp.einsum("bihd,rhd->bihr", qnb.astype(jnp.float32),
                           w_uk.astype(jnp.float32))
        qpos = iq * bq + jnp.arange(bq)

        def kv_step(carry, kvs):
            m, l, acc = carry
            cb, krb, jk = kvs
            kpos = jk * bkv + jnp.arange(bkv)
            sc = jnp.einsum("bihr,bjr->bhij", q_eff, cb.astype(jnp.float32))
            sc += jnp.einsum("bihd,bjd->bhij", qrb.astype(jnp.float32),
                             krb.astype(jnp.float32))
            sc *= scale
            if causal:
                allow = kpos[None, :] <= qpos[:, None]
                sc = jnp.where(allow[None, None], sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(axis=-1))
            pr = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + pr.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhij,bjr->bhir", pr, cb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, H, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, H, bq), jnp.float32)
        a0 = jnp.zeros((b, H, bq, rkv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (ckv_b, kr_b, jnp.arange(n_kv)))
        ctx = acc / jnp.maximum(l, 1e-30)[..., None]        # (B,H,bq,rkv)
        o = jnp.einsum("bhir,rhd->bihd", ctx, w_uv.astype(jnp.float32))
        return None, o.astype(qn.dtype)                     # (B,bq,H,dv)

    _, outs = jax.lax.scan(q_step, None, (qn_b, qr_b, jnp.arange(n_q)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, s, H, dv)
    return out[:, :s_orig]


def mla_decode(cfg: ModelConfig, ld: LayerDef, p: Params, x: jax.Array,
               cache: Cache, pos: jax.Array) -> Tuple[jax.Array, Cache]:
    b, d = x.shape
    H, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    h = _norm(cfg, p["ln"], x)
    qn, qr = _mla_q(cfg, p, h, pos)                # (B,H,dn/dr)
    ckv_new, kr_new = _mla_ckv(cfg, p, h, pos)     # (B,rkv) / (B,dr)
    bidx = jnp.arange(b)
    cq, cs = _kv_quant(cfg, ckv_new)
    cache = {
        "ckv_q": cache["ckv_q"].at[bidx, pos].set(cq),
        "ckv_s": cache["ckv_s"].at[bidx, pos].set(cs),
        "kr": cache["kr"].at[bidx, pos].set(kr_new),
    }
    ckv = _kv_dequant(cfg, cache["ckv_q"], cache["ckv_s"], x.dtype)
    ckv = constrain(ckv, ("batch", "kv_seq", None))
    kr = cache["kr"]
    w_uk, w_uv = _mla_absorbed_weights(cfg, p)

    q_eff = jnp.einsum("bhd,rhd->bhr", qn.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    sc = jnp.einsum("bhr,bjr->bhj", q_eff, ckv.astype(jnp.float32))
    sc += jnp.einsum("bhd,bjd->bhj", qr.astype(jnp.float32),
                     kr.astype(jnp.float32))
    sc *= (dn + dr) ** -0.5
    smax = ckv.shape[1]
    allow = jnp.arange(smax)[None, :] <= pos[:, None]
    sc = jnp.where(allow[:, None, :], sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    ctx = jnp.einsum("bhj,bjr->bhr", pr, ckv.astype(jnp.float32))
    o = jnp.einsum("bhr,rhd->bhd", ctx, w_uv.astype(jnp.float32))
    return linear(o.reshape(b, H * dv).astype(x.dtype), p["wo"]), cache


# ---------------------------------------------------------------------------
# SSD mixer (mamba2 / jamba)
# ---------------------------------------------------------------------------

def _ssd_dims(cfg: ModelConfig):
    din = cfg.d_inner
    g, n, p_ = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_head_dim
    nh = din // p_
    return din, g, n, p_, nh


def _ssd_in_split(cfg: ModelConfig, zxbcdt: jax.Array):
    din, g, n, p_, nh = _ssd_dims(cfg)
    z = zxbcdt[..., :din]
    xbc = zxbcdt[..., din:din + din + 2 * g * n]
    dt = zxbcdt[..., din + din + 2 * g * n:]
    return z, xbc, dt


def ssd_full(cfg: ModelConfig, ld: LayerDef, p: Params, x: jax.Array,
             positions, prefix_len, make_cache) -> Tuple[jax.Array, Optional[Cache]]:
    b, s, d = x.shape
    din, g, n, p_, nh = _ssd_dims(cfg)
    h = _norm(cfg, p["ln"], x)
    zxbcdt = linear(h, p["w_in"])
    z, xbc, dt = _ssd_in_split(cfg, zxbcdt)
    conv_out = jax.nn.silu(
        ssd_lib.causal_conv1d(xbc, p["conv_w"], p["conv_b"]))
    xs = conv_out[..., :din].reshape(b, s, g, nh // g, p_)
    b_in = conv_out[..., din:din + g * n].reshape(b, s, g, n)
    c_in = conv_out[..., din + g * n:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"]).reshape(b, s, g, nh // g)
    xs = constrain(xs, ("batch", "seq", None, "heads", None))
    y, h_fin = ssd_lib.ssd_chunked(xs, dt, p["a_log"], b_in, c_in,
                                   p["d_skip"], cfg.ssm_chunk)
    y = y.reshape(b, s, din)
    y = ssd_lib.gated_rms_norm(y, z, p["gn"], cfg.rms_eps)
    out = linear(y, p["w_out"])

    cache = None
    if make_cache is not None:
        w = cfg.conv_width
        cache = {"h": h_fin, "conv": xbc[:, s - (w - 1):s, :]}
    return out, cache


def ssd_decode(cfg: ModelConfig, ld: LayerDef, p: Params, x: jax.Array,
               cache: Cache, pos: jax.Array) -> Tuple[jax.Array, Cache]:
    b, d = x.shape
    din, g, n, p_, nh = _ssd_dims(cfg)
    h = _norm(cfg, p["ln"], x)
    zxbcdt = linear(h, p["w_in"])
    z, xbc, dt = _ssd_in_split(cfg, zxbcdt)
    conv_new, conv_out = ssd_lib.conv1d_step(
        cache["conv"], xbc, p["conv_w"], p["conv_b"])
    conv_out = jax.nn.silu(conv_out)
    xs = conv_out[..., :din].reshape(b, g, nh // g, p_)
    b_in = conv_out[..., din:din + g * n].reshape(b, g, n)
    c_in = conv_out[..., din + g * n:].reshape(b, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"]).reshape(b, g, nh // g)
    y, h_new = ssd_lib.ssd_decode_step(cache["h"], xs, dt, p["a_log"],
                                       b_in, c_in, p["d_skip"])
    y = y.reshape(b, din)
    y = ssd_lib.gated_rms_norm(y, z, p["gn"], cfg.rms_eps)
    return linear(y, p["w_out"]), {"h": h_new, "conv": conv_new}


# ---------------------------------------------------------------------------
# FFNs
# ---------------------------------------------------------------------------

def dense_ffn(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    h = _norm(cfg, p["ln2"], x)
    if cfg.mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else functools.partial(
            jax.nn.gelu, approximate=True)
        g = act(linear(h, p["w_gate"]))
        u = linear(h, p["w_up"])
        hh = constrain(g * u, ("batch", "seq", "mlp"))
        return linear(hh, p["w_down"], tp="row")
    hh = jax.nn.gelu(linear(h, p["w_fc"], p.get("b_fc")), approximate=True)
    hh = constrain(hh, ("batch", "seq", "mlp"))
    return linear(hh, p["w_proj"], p.get("b_proj"), tp="row")


def moe_ffn(cfg: ModelConfig, p: Params,
            x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, load-balance aux loss).

    Under a tensor-parallel trace whose BATCH is sharded over a data axis
    (the decode/verify serving steps), the flat token batch is
    all-gathered before routing and the local rows sliced back out after
    the combine: expert capacity and within-expert ranking are functions
    of the whole batch, so routing on local shards alone would keep/drop
    different assignments than the single-device step. The gathered rows
    arrive in global slot order (shards own contiguous slot ranges), so
    dispatch, capacity and combine are bit-identical to the unsharded
    batch; the expert FFNs themselves are sharded on their hidden dim
    (one int32 psum per down-projection — see ``distributed/tp.py``).
    """
    h = _norm(cfg, p["ln2"], x)
    shp = h.shape
    flat = h.reshape(-1, shp[-1])
    ctx = tp_ctx()
    gathered = ctx is not None and ctx.batch_axis is not None
    if gathered:
        t_local = flat.shape[0]
        flat = jax.lax.all_gather(flat, ctx.batch_axis, axis=0, tiled=True)
    mp = p["moe"]
    y = moe_lib.moe_ffn_dist(
        flat, mp["w_router"], mp["w_gate"], mp["w_up"], mp["w_down"],
        top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
        router_type=cfg.router_type)
    if cfg.n_shared_experts:
        y = y + moe_lib.shared_expert_ffn(
            flat, mp["w_shared_gate"], mp["w_shared_up"],
            mp["w_shared_down"])
    aux = moe_lib.load_balance_loss(flat, mp["w_router"], cfg.top_k)
    if gathered:
        start = jax.lax.axis_index(ctx.batch_axis) * t_local
        y = jax.lax.dynamic_slice_in_dim(y, start, t_local, axis=0)
    return y.reshape(shp), aux


# ---------------------------------------------------------------------------
# layer / stage application
# ---------------------------------------------------------------------------

_MIXER_FULL = {"attn": attn_full, "mla": mla_full, "ssd": ssd_full}
_MIXER_DEC = {"attn": attn_decode, "mla": mla_decode, "ssd": ssd_decode}


def _apply_layer_full(cfg, ld: LayerDef, p: Params, x, positions,
                      prefix_len, make_cache):
    y, cache = _MIXER_FULL[ld.mixer](cfg, ld, p, x, positions, prefix_len,
                                     make_cache)
    x = x + y
    aux = jnp.zeros((), jnp.float32)
    if ld.ffn == "dense":
        x = x + dense_ffn(cfg, p, x)
    elif ld.ffn == "moe":
        y, aux = moe_ffn(cfg, p, x)
        x = x + y
    x = constrain(x, ("batch", "seq", "embed"))
    return x, cache, aux


def _apply_ffn_decode(cfg, ld: LayerDef, p: Params, x):
    """Single-token FFN residual, shared by every decode cache layout."""
    if ld.ffn == "dense":
        return x + dense_ffn(cfg, p, x[:, None, :])[:, 0]
    if ld.ffn == "moe":
        return x + moe_ffn(cfg, p, x[:, None, :])[0][:, 0]
    return x


def _apply_layer_decode(cfg, ld: LayerDef, p: Params, x, cache, pos):
    y, cache = _MIXER_DEC[ld.mixer](cfg, ld, p, x, cache, pos)
    return _apply_ffn_decode(cfg, ld, p, x + y), cache


def _stage_scan_full(cfg, stage: Stage, sparams, x, positions, prefix_len,
                     make_cache, remat: bool):
    """Returns (x, caches-or-None, total aux loss)."""

    def body(carry, pslice):
        h, aux = carry
        caches = {}
        for pi, ld in enumerate(stage.period):
            h, c, a = _apply_layer_full(cfg, ld, pslice[f"p{pi}"], h,
                                        positions, prefix_len, make_cache)
            aux = aux + a
            if make_cache is not None:
                caches[f"p{pi}"] = c
        return (h, aux), (caches if make_cache is not None else None)

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                    sparams)
    return x, caches, aux


def _scan_layers_cached(cfg, params: Params, caches: Cache, x: jax.Array,
                        layer, telemetry: bool = False):
    """Every stage's layer scan over its params and cache (or pool) slices.

    The decode-like forwards (legacy and paged decode, verify, chunked
    prefill) differ only in ``layer(ld, p, h, c) -> (h, c)``: one
    sub-layer over its cache slice. With ``telemetry`` the hidden stream
    entering each sub-layer is measured (``act_wire_telemetry``).
    Returns (x, new caches, per-layer telemetry (L, ...) or {}).
    """
    new_caches: Cache = {"stages": {}}
    layer_tels = []
    for si, stage in enumerate(build_stages(cfg)):
        def body(h, inp, stage=stage):
            pslice, cslice = inp
            tels = []
            new_c = {}
            for pi, ld in enumerate(stage.period):
                if telemetry:
                    tels.append(act_wire_telemetry(h))  # one per SUB-layer
                h, c = layer(ld, pslice[f"p{pi}"], h, cslice[f"p{pi}"])
                new_c[f"p{pi}"] = c
            tel = ({k: jnp.stack([t[k] for t in tels], 0) for k in tels[0]}
                   if telemetry else {})
            return h, (new_c, tel)

        x, (nc, tel) = jax.lax.scan(body, x, (params["stages"][f"s{si}"],
                                              caches["stages"][f"s{si}"]))
        new_caches["stages"][f"s{si}"] = nc
        # scan stacks to (repeat, period, ...): flatten to (L_s, ...)
        layer_tels.append({k: v.reshape(-1, *v.shape[2:])
                           for k, v in tel.items()})
    tel = {k: jnp.concatenate([t[k] for t in layer_tels], axis=0)
           for k in layer_tels[0]}
    return x, new_caches, tel


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def _embed(cfg: ModelConfig, params: Params, tokens: Optional[jax.Array],
           prefix: Optional[jax.Array] = None) -> jax.Array:
    """Input embeddings in the compute dtype.

    The precomputed ``prefix`` embeddings (if any) followed along axis 1 by
    the table rows of ``tokens`` (if any), times sqrt(d_model) where the
    config sets ``scale_embed`` (gemma).
    """
    dt = cfg.cdtype
    x = None
    if tokens is not None:
        x = embed(tokens, params["embed"]["table"]).astype(dt)
    if prefix is not None:
        prefix = prefix.astype(dt)
        x = prefix if x is None else jnp.concatenate([prefix, x], axis=1)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    return x


def embed_inputs(cfg: ModelConfig, params: Params,
                 batch: Dict[str, jax.Array]):
    """Returns (x (B,S,D), positions (S,), prefix_len)."""
    prefix_len = 0
    if cfg.family == "encoder":               # stub frontend: precomputed
        x = _embed(cfg, params, None, batch["frames"])
    elif cfg.family == "vlm":                 # stub SigLIP: precomputed
        x = _embed(cfg, params, batch["tokens"], batch["patches"])
        prefix_len = batch["patches"].shape[1]
    else:
        x = _embed(cfg, params, batch["tokens"])
    positions = jnp.arange(x.shape[1])
    return x, positions, prefix_len


def head_logits(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    with jax.named_scope("lm_head"):
        x = _norm(cfg, params["final_norm"], x)
        if cfg.tie_embeddings:
            # tied head: the embedding table stays replicated under TP
            # (token lookup needs the full vocab), so logits are already
            # complete
            return linear(x, params["embed"]["table"].T)
        logits = linear(x, params["lm_head"])
        ctx = tp_ctx()
        if ctx is not None and logits.shape[-1] != cfg.vocab:
            # column-parallel head: gather the vocab shards back into the
            # full distribution (exact concatenation, shard order = axis
            # order) — sampling policy lives host-side in the engine
            logits = jax.lax.all_gather(logits, ctx.axis,
                                        axis=logits.ndim - 1, tiled=True)
        return logits


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            *, remat: bool = False, with_aux: bool = False):
    """Full-sequence forward -> logits (B, S, V) [, aux loss]."""
    x, aux = forward_hidden(cfg, params, batch, remat=remat, with_aux=True)
    logits = head_logits(cfg, params, x)
    return (logits, aux) if with_aux else logits


def forward_hidden(cfg: ModelConfig, params: Params, batch, *,
                   remat: bool = False, with_aux: bool = False):
    """Forward without the head (final pre-norm hidden states)."""
    x, positions, prefix_len = embed_inputs(cfg, params, batch)
    x = constrain(x, ("batch", "seq", "embed"))
    total_aux = jnp.zeros((), jnp.float32)
    for si, stage in enumerate(build_stages(cfg)):
        x, _, aux = _stage_scan_full(cfg, stage, params["stages"][f"s{si}"],
                                     x, positions, prefix_len, None, remat)
        total_aux = total_aux + aux
    return (x, total_aux) if with_aux else x


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            *, max_len: int) -> Tuple[jax.Array, Cache]:
    """Prefill: logits of the LAST position + initialized caches."""
    x, positions, prefix_len = embed_inputs(cfg, params, batch)
    x = constrain(x, ("batch", "seq", "embed"))
    cache: Cache = {"stages": {}}
    for si, stage in enumerate(build_stages(cfg)):
        x, c, _ = _stage_scan_full(cfg, stage, params["stages"][f"s{si}"], x,
                                   positions, prefix_len, max_len, False)
        cache["stages"][f"s{si}"] = c
    logits = head_logits(cfg, params, x[:, -1:, :])[:, 0]
    return logits, cache


def decode_step(cfg: ModelConfig, params: Params, cache: Cache,
                token: jax.Array, pos: jax.Array,
                embeds: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Cache]:
    """One decode step. token (B,) int32, pos (B,) int32 -> logits (B, V)."""
    if cfg.family == "encoder":
        raise ValueError("encoder-only model has no decode step")
    x = _embed(cfg, params, token if embeds is None else None, embeds)
    x = constrain(x, ("batch", "embed"))
    x, new_cache, _ = _scan_layers_cached(
        cfg, params, cache, x,
        lambda ld, p, h, c: _apply_layer_decode(cfg, ld, p, h, c, pos))
    logits = head_logits(cfg, params, x[:, None, :])[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------------
# paged-cache serving entry points (serving/engine.py)
#
# The contiguous decode path above owns a (B, Smax, ...) cache per layer;
# the serving engine instead owns a shared page pool per layer —
# (n_pages, page_size, KVH, ...) in the same packed-int4 wire format —
# and per-sequence block tables mapping sequence-order page steps to
# physical pages. KV is quantized on write and never dequantized in HBM
# on the decode hot path (kernels/kv_attention.py walks the table).
# ---------------------------------------------------------------------------


def check_paged_support(cfg: ModelConfig) -> None:
    """Raise unless every layer fits the paged attention serving path."""
    if cfg.family in ("encoder", "vlm"):
        raise NotImplementedError(
            f"paged serving needs a token-only decoder, got {cfg.family}")
    if cfg.kv_bits != 4 or cfg.hd % 2:
        raise NotImplementedError(
            f"paged pool stores packed int4 KV: kv_bits=4, even head_dim "
            f"required (got kv_bits={cfg.kv_bits}, hd={cfg.hd})")
    for stage in build_stages(cfg):
        for ld in stage.period:
            if ld.mixer != "attn" or ld.window:
                raise NotImplementedError(
                    f"paged serving supports full-attention GQA layers only "
                    f"(got mixer={ld.mixer!r}, window={ld.window})")


def _act_subprecision_sparsity(x: jax.Array) -> jax.Array:
    """Per-row MSB4 sparsity of the int8-quantized activations (B,)."""
    from repro.core.quantize import quantize_activations
    from repro.core.sparqle import subprecision_sparsity
    q = quantize_activations(x, bits=8, per_token=True).q
    return subprecision_sparsity(q, axis=-1)


def _write_kv_pages(pool: Cache, block_tables: jax.Array,
                    positions: jax.Array, kq: jax.Array, ks: jax.Array,
                    vq: jax.Array, vs: jax.Array,
                    keep: Optional[jax.Array] = None) -> Cache:
    """Scatter quantized K/V into the pool pages of their positions.

    ``positions`` (B,) or (B, T) are absolute token positions, row b's
    read through ``block_tables[b]``; K/V and their scales carry the same
    leading dims. A token whose ``keep`` is False writes into the null
    page 0 instead: a prefill chunk's padded tail, or a decode token whose
    page sits in the KV2 slab. A KV2 slab in the pool passes through.
    """
    ps = pool["k_q"].shape[1]
    n_steps = block_tables.shape[1]
    if positions.ndim == 1:
        rows = jnp.arange(block_tables.shape[0])
        page = block_tables[rows, jnp.clip(positions // ps, 0, n_steps - 1)]
    else:
        page = jnp.take_along_axis(
            block_tables, jnp.clip(positions // ps, 0, n_steps - 1), axis=1)
    if keep is not None:
        page = jnp.where(keep, page, 0)
    off = positions % ps
    with jax.named_scope("kv_write"):
        return {
            **pool,
            "k_q": pool["k_q"].at[page, off].set(kq),
            "k_s": pool["k_s"].at[page, off].set(ks),
            "v_q": pool["v_q"].at[page, off].set(vq),
            "v_s": pool["v_s"].at[page, off].set(vs),
        }


def attn_decode_paged(cfg: ModelConfig, ld: LayerDef, p: Params,
                      x: jax.Array, pool: Cache, block_tables: jax.Array,
                      pos: jax.Array,
                      tier_tables: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, Cache]:
    """One-token attention against the paged pool. x: (B, D).

    Writes the new token's quantized K/V into its page slot, then attends
    through the block table with the paged Pallas kernel (the pool stays
    in packed-int4 wire format end to end). With ``tier_tables`` (B, Pmax)
    the mixed-tier kernel reads each page from the slab its tier id names
    (the KV2 precision ladder — serving/kv_pool.py); the write still lands
    in the KV4 slab, since the engine promotes any page before it is
    written (the frontier page is always tier 0).
    """
    from repro.kernels.kv_attention import (kv4_paged_decode_attention,
                                            kv_tiered_paged_decode_attention)
    b, d = x.shape
    kvh, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    theta = ld.rope_theta or cfg.rope_theta
    h = _norm(cfg, p["ln"], x)
    q, k_new, v_new = _attn_qkv(cfg, p, h, pos, theta)
    kq, ks = _kv_quant(cfg, k_new)
    vq, vs = _kv_quant(cfg, v_new)
    keep = None
    if tier_tables is not None:
        # a demoted page id indexes the KV2 slab — never scatter there
        ps, n_steps = pool["k_q"].shape[1], tier_tables.shape[1]
        keep = tier_tables[jnp.arange(b),
                           jnp.clip(pos // ps, 0, n_steps - 1)] == 0
    pool = _write_kv_pages(pool, block_tables, pos, kq, ks, vq, vs, keep)
    with jax.named_scope("paged_attention"):
        if tier_tables is None:
            o = kv4_paged_decode_attention(
                q.reshape(b, kvh, g, cfg.hd), pool["k_q"], pool["k_s"],
                pool["v_q"], pool["v_s"], block_tables, pos)
        else:
            o = kv_tiered_paged_decode_attention(
                q.reshape(b, kvh, g, cfg.hd), pool["k_q"], pool["k_s"],
                pool["v_q"], pool["v_s"], pool["k2_q"], pool["k2_s"],
                pool["v2_q"], pool["v2_s"], block_tables, tier_tables, pos)
    o = o.reshape(b, cfg.n_heads * cfg.hd)
    return linear(o, p["wo"], p.get("bo"), tp="row"), pool


def decode_step_paged(cfg: ModelConfig, params: Params, pool: Cache,
                      token: jax.Array, pos: jax.Array,
                      block_tables: jax.Array, *,
                      tier_tables: Optional[jax.Array] = None,
                      msb_skip: bool = False,
                      with_telemetry: bool = True
                      ) -> Tuple[jax.Array, Cache, Dict[str, jax.Array]]:
    """One continuous-batching decode step over the paged pool.

    token/pos (B,) int32, block_tables (B, Pmax) int32. Inactive slots
    should carry an all-zero block-table row: their KV writes land in the
    reserved null page 0 and their outputs are discarded by the engine.
    Returns (logits (B, V), new pool, telemetry dict):

      * ``sparsity``          (B,)   — final-hidden MSB4 sparsity,
      * ``layer_sparsity``    (L, B) — MSB4 sparsity of the hidden
        (residual) stream entering each layer,
      * ``layer_wire_bytes``  (L, B) — MEASURED packed-wire bytes of that
        inter-layer stream (``core/packing.py`` layout; see
        ``layers.act_wire_telemetry`` for what this does and does not
        include),
      * ``layer_dense_bytes`` (L, B) — dense int8 baseline bytes.

    ``msb_skip`` traces every sparqle projection in LSB4-only draft mode
    (the 1-compute-round proposer of self-speculative decoding; see
    ``serving/spec_decode.py``) — the K/V written to the pool are then
    the draft's approximations, which the verification step overwrites.
    ``with_telemetry=False`` drops the wire accounting from the traced
    program (the draft hot path) and returns an empty telemetry dict.
    ``tier_tables`` (B, Pmax) arms the KV2 precision-ladder read path
    (see :func:`attn_decode_paged`); None keeps the KV4-only program.
    """
    def layer(ld, p, h, c):
        y, c = attn_decode_paged(cfg, ld, p, h, c, block_tables, pos,
                                 tier_tables)
        return _apply_ffn_decode(cfg, ld, p, h + y), c

    with msb_skip_scope(msb_skip):
        x = constrain(_embed(cfg, params, token), ("batch", "embed"))
        x, new_pool, tel = _scan_layers_cached(cfg, params, pool, x, layer,
                                               with_telemetry)
        telemetry: Dict[str, jax.Array] = {}
        if with_telemetry:
            telemetry["sparsity"] = _act_subprecision_sparsity(x)
            telemetry.update({f"layer_{k}": v for k, v in tel.items()})
        logits = head_logits(cfg, params, x[:, None, :])[:, 0]
    return logits, new_pool, telemetry


def attn_verify_paged(cfg: ModelConfig, ld: LayerDef, p: Params,
                      x: jax.Array, pool: Cache, block_tables: jax.Array,
                      pos: jax.Array) -> Tuple[jax.Array, Cache]:
    """Draft-window attention for speculative verification. x: (B, T, D).

    Window token ``t`` of sequence ``b`` sits at absolute position
    ``pos[b] + t``. All T tokens' K/V are quantized and scattered into
    their page slots FIRST (overwriting whatever the LSB-only draft pass
    left there), then the whole window attends through the block table in
    one multi-token paged kernel call — each token causally masked to its
    own position, so it sees the window's just-written full-precision K/V
    but never its own future.
    """
    from repro.kernels.kv_attention import kv4_paged_verify_attention
    b, t, d = x.shape
    kvh, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    theta = ld.rope_theta or cfg.rope_theta
    h = _norm(cfg, p["ln"], x)
    positions = pos[:, None] + jnp.arange(t)[None, :]       # (B, T)
    q, k_new, v_new = _attn_qkv(cfg, p, h, positions, theta)
    kq, ks = _kv_quant(cfg, k_new)
    vq, vs = _kv_quant(cfg, v_new)
    pool = _write_kv_pages(pool, block_tables, positions, kq, ks, vq, vs)
    o = kv4_paged_verify_attention(
        q.reshape(b, t, kvh, g, cfg.hd), pool["k_q"], pool["k_s"],
        pool["v_q"], pool["v_s"], block_tables, pos)
    o = o.reshape(b, t, cfg.n_heads * cfg.hd)
    return linear(o, p["wo"], p.get("bo"), tp="row"), pool


def verify_window_paged(cfg: ModelConfig, params: Params, pool: Cache,
                        tokens: jax.Array, pos: jax.Array,
                        block_tables: jax.Array
                        ) -> Tuple[jax.Array, Cache, Dict[str, jax.Array]]:
    """Score a whole draft window in ONE full-precision batched step.

    tokens (B, T) int32 — window token 0 is the last accepted token,
    tokens 1..T-1 the draft proposals; pos (B,) int32 — absolute position
    of tokens[:, 0]; block_tables (B, Pmax) int32. Returns
    (logits (B, T, V), new pool, telemetry):

      * ``logits[:, t]`` is the full-precision next-token distribution
        after window token t — exactly what a sequential decode at
        ``pos + t`` would produce (the attention kernel is bit-exact
        against that loop; see ``kernels/kv_attention.py``);
      * the pool comes back with full-precision K/V written at every
        window position, which is what makes greedy speculative decoding
        byte-identical to the non-speculative engine: rejected tail
        positions hold stale K/V but sit beyond the accepted position,
        masked until overwritten;
      * telemetry: ``sparsity`` (B,) mean final-hidden MSB4 sparsity over
        the window; ``layer_sparsity`` (L, B) mean over window tokens;
        ``layer_wire_bytes`` / ``layer_dense_bytes`` (L, B) measured
        packed-wire vs dense int8 bytes summed over the window's
        inter-layer hidden stream.
    """
    def layer(ld, p, h, c):
        y, c = attn_verify_paged(cfg, ld, p, h, c, block_tables, pos)
        h = h + y
        if ld.ffn == "dense":
            h = h + dense_ffn(cfg, p, h)
        elif ld.ffn == "moe":
            # one routed-MoE call PER WINDOW POSITION: expert capacity is
            # a function of the flat token count (t * top_k * cf // E), so
            # batching all B*T window tokens into one dispatch would drop
            # different assignments than the B-token sequential decode
            # steps this function must be bit-exact against
            h = h + jnp.concatenate(
                [moe_ffn(cfg, p, h[:, t:t + 1])[0]
                 for t in range(h.shape[1])], axis=1)
        return h, c

    x = constrain(_embed(cfg, params, tokens), ("batch", "seq", "embed"))
    x, new_pool, tel = _scan_layers_cached(cfg, params, pool, x, layer, True)
    telemetry = {                      # per-layer (L, B, T) over the window
        "sparsity": _act_subprecision_sparsity(x).mean(axis=-1),
        "layer_sparsity": tel["sparsity"].mean(axis=-1),
        "layer_wire_bytes": tel["wire_bytes"].sum(axis=-1),
        "layer_dense_bytes": tel["dense_bytes"].sum(axis=-1),
    }
    logits = head_logits(cfg, params, x)                     # (B, T, V)
    return logits, new_pool, telemetry


def _attn_prefill_chunk_paged(cfg: ModelConfig, ld: LayerDef, p: Params,
                              x: jax.Array, pool: Cache,
                              block_table: jax.Array, start: jax.Array,
                              valid: jax.Array) -> Tuple[jax.Array, Cache]:
    """Chunked-prefill attention for ONE sequence. x: (1, C, D).

    The chunk's K/V are quantized and scattered into the sequence's pages;
    queries attend to the dequantized pool for positions < start (the wire
    format is the source of truth for past context) and to the float
    chunk K/V for the chunk itself — so a single-chunk prefill is exactly
    the legacy float prefill attention.
    """
    _, c, _ = x.shape
    kvh, g, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    theta = ld.rope_theta or cfg.rope_theta
    h = _norm(cfg, p["ln"], x)
    positions = start + jnp.arange(c)
    q, k, v = _attn_qkv(cfg, p, h, positions, theta)

    ps = pool["k_q"].shape[1]
    n_steps = block_table.shape[1]
    kq, ks = _kv_quant(cfg, k)
    vq, vs = _kv_quant(cfg, v)
    pool = _write_kv_pages(pool, block_table, positions[None], kq, ks, vq,
                           vs, keep=(jnp.arange(c) < valid)[None])

    # context = dequantized pool pages [0, start) ++ float chunk K/V
    kp = pool["k_q"][block_table[0]].reshape(n_steps * ps, kvh, hd // 2)
    ksp = pool["k_s"][block_table[0]].reshape(n_steps * ps, kvh)
    vp = pool["v_q"][block_table[0]].reshape(n_steps * ps, kvh, hd // 2)
    vsp = pool["v_s"][block_table[0]].reshape(n_steps * ps, kvh)
    k_past = _kv_dequant(cfg, kp, ksp, jnp.float32)[None]
    v_past = _kv_dequant(cfg, vp, vsp, jnp.float32)[None]
    k_cat = jnp.concatenate([k_past, k.astype(jnp.float32)], 1)
    v_cat = jnp.concatenate([v_past, v.astype(jnp.float32)], 1)

    lmax = n_steps * ps
    i = jnp.arange(c)[:, None]
    j = jnp.arange(lmax + c)[None, :]
    allow = jnp.where(j < lmax, j < start, (j - lmax) <= i)
    qg = q.reshape(1, c, kvh, g, hd).astype(jnp.float32)
    s = jnp.einsum("bikgd,bjkd->bkgij", qg, k_cat) * hd ** -0.5
    s = jnp.where(allow[None, None, None], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgij,bjkd->bikgd", pr, v_cat)
    o = o.reshape(1, c, cfg.n_heads * hd).astype(x.dtype)
    return linear(o, p["wo"], p.get("bo"), tp="row"), pool


def prefill_chunk_paged(cfg: ModelConfig, params: Params, pool: Cache,
                        tokens: jax.Array, start: jax.Array,
                        valid: jax.Array, block_table: jax.Array
                        ) -> Tuple[jax.Array, Cache, Dict[str, jax.Array]]:
    """Prefill one chunk of ONE sequence into the paged pool.

    tokens (1, C) int32 (tail-padded; ``valid`` counts real tokens),
    start — absolute position of tokens[0, 0], block_table (1, Pmax).
    Returns (logits (1, V) of the last valid position, new pool, telemetry
    dict): ``sparsity`` — mean MSB4 sparsity of the chunk's final hidden
    activations over valid tokens; ``layer_sparsity`` (L,) mean over the
    hidden stream entering each layer; ``layer_wire_bytes`` /
    ``layer_dense_bytes`` (L,) — measured packed-wire vs dense int8 bytes
    of the chunk's valid tokens on that inter-layer stream
    (``layers.act_wire_telemetry``).
    """
    def layer(ld, p, h, c):
        y, c = _attn_prefill_chunk_paged(cfg, ld, p, h, c, block_table,
                                         start, valid)
        h = h + y
        if ld.ffn == "dense":
            h = h + dense_ffn(cfg, p, h)
        elif ld.ffn == "moe":
            h = h + moe_ffn(cfg, p, h)[0]
        return h, c

    x = constrain(_embed(cfg, params, tokens), ("batch", "seq", "embed"))
    x, new_pool, tel = _scan_layers_cached(cfg, params, pool, x, layer, True)
    last = jnp.maximum(valid - 1, 0)
    valid_tok = (jnp.arange(tokens.shape[1]) < valid).astype(jnp.float32)
    n_valid = jnp.maximum(jnp.sum(valid_tok), 1.0)
    sp_tok = _act_subprecision_sparsity(x[0])
    # per-layer (L, 1, C) stats over the chunk's VALID tokens only
    lay = {k: v[:, 0, :] for k, v in tel.items()}
    telemetry = {
        "sparsity": jnp.sum(sp_tok * valid_tok) / n_valid,
        "layer_sparsity": jnp.sum(lay["sparsity"] * valid_tok, -1) / n_valid,
        "layer_wire_bytes": jnp.sum(lay["wire_bytes"] * valid_tok, -1),
        "layer_dense_bytes": jnp.sum(lay["dense_bytes"] * valid_tok, -1),
    }
    x_last = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
    logits = head_logits(cfg, params, x_last)[:, 0]
    return logits, new_pool, telemetry


# ---------------------------------------------------------------------------
# MTP head (deepseek-v3 auxiliary multi-token prediction)
# ---------------------------------------------------------------------------

def mtp_logits(cfg: ModelConfig, params: Params, hidden: jax.Array,
               batch: Dict[str, jax.Array]) -> jax.Array:
    """Predict token t+2 from trunk hidden t and embedding of token t+1.

    ``hidden`` is the trunk's final (pre-norm) hidden states (B, S, D).
    Returns logits (B, S-1, V) aligned so position i predicts tokens[i+2].
    """
    mp = params["mtp"]
    tok = batch["tokens"]
    h = _norm(cfg, mp["norm_h"], hidden[:, :-1, :])
    e = embed(tok[:, 1:], params["embed"]["table"]).astype(h.dtype)
    e = _norm(cfg, mp["norm_e"], e)
    x = linear(jnp.concatenate([h, e], axis=-1), mp["proj"])
    positions = jnp.arange(x.shape[1])
    ld = LayerDef("mla" if cfg.use_mla else "attn", "dense")
    stage = Stage([ld], cfg.mtp_depth)
    x, _, _ = _stage_scan_full(cfg, stage, {"p0": mp["block"]}, x, positions,
                               0, None, False)
    return head_logits(cfg, params, x)
