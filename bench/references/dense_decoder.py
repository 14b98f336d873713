"""Plain float32 reference of a dense decoder served W4A8 with a KV4 cache.

Nothing here imports the program under test. The weights are made here,
from the seed, in the served format (int4 nibbles packed two to a byte
along K, a float32 scale per output channel, a clipping mask over the
input columns); the system under test is handed the same arrays.

What a served model computes, written out plainly:

* every projection: per-token int8 quantization of its input
  (``round(x / s)``, ``s = max|x| / 127``), SPARQLe clipping of the masked
  columns (values in ``[clip_l, 0)`` go to 0, values in ``(15, clip_h]``
  to 15), an exact integer product with the int4 weights, and the two
  scales;
* keys and values quantized to int4 per token and head before attention
  reads them, except between two positions of one prefill chunk, which
  attend to each other's unquantized keys and values;
* embedding, norms, RoPE (rotating the two halves of a head), softmax,
  the MLP and the head in float32.

``forward`` runs whole sequences, layer by layer, at ``Precision.HIGHEST``.
With ``act_bits=4`` every projection input is first rounded to 4-bit
per-token levels: the control, one precision step below the int8
activations the configuration states.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _proj_shapes(m: Dict) -> Dict[str, tuple]:
    d, h, kvh, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    shapes = {"wq": (d, h * hd), "wk": (d, kvh * hd), "wv": (d, kvh * hd),
              "wo": (h * hd, d)}
    if m["mlp"] == "swiglu":
        shapes.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    else:
        shapes.update(w_fc=(d, f), w_proj=(f, d))
    return shapes


def _bias_shapes(m: Dict) -> Dict[str, int]:
    if not m["bias"]:
        return {}
    d, h, kvh, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    out = {"bq": h * hd, "bk": kvh * hd, "bv": kvh * hd, "bo": d}
    if m["mlp"] != "swiglu":
        out.update(b_fc=f, b_proj=d)
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (two 32-bit words of it)."""
    s = int(seed) % 2 ** 64
    key = jax.random.fold_in(jax.random.PRNGKey(0), s & 0xFFFFFFFF)
    return jax.random.fold_in(key, s >> 32)


def _pack_int4(q: jax.Array) -> jax.Array:
    """(K, N) int values in [-8, 7] -> (K/2, N) bytes: row 2i in the low
    nibble, row 2i+1 in the high nibble."""
    lo = q[0::2].astype(jnp.int32) & 0xF
    hi = q[1::2].astype(jnp.int32) & 0xF
    byte = lo | (hi << 4)
    return jnp.where(byte >= 128, byte - 256, byte).astype(jnp.int8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of the packing above, as float32 (..., K, N)."""
    u = packed.astype(jnp.int32) & 0xFF
    lo, hi = u & 0xF, u >> 4
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    w = jnp.stack([lo, hi], axis=-2)                   # (..., K/2, 2, N)
    return w.reshape(*packed.shape[:-2], packed.shape[-2] * 2,
                     packed.shape[-1]).astype(jnp.float32)


def _quantized_projection(key, k: int, n: int, q: Dict) -> Dict:
    """One served projection from a N(0, 1/k) draw: int4 per output
    channel, and the mask of the least important input-column tiles
    (L1 norm of the weight rows, summed per tile)."""
    w = jax.random.normal(key, (k, n), jnp.float32) * (k ** -0.5)
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.maximum(amax / 7.0, 1e-8)
    qi = jnp.clip(jnp.round(w / scale), -8, 7)
    tile = q["tile_k"]
    blocks = jnp.sum(jnp.abs(w), axis=1).reshape(-1, tile).sum(axis=1)
    kk = int(blocks.shape[0] * q["k_percent"] / 100.0 + 0.5)
    thresh = jnp.sort(blocks)[kk - 1]
    mask = jnp.repeat(blocks <= thresh, tile)
    return {"q": _pack_int4(qi), "scale": scale, "mask": mask}


def _make(m: Dict, key: jax.Array) -> Dict:
    q = m["quant"]
    L, d, v = m["n_layers"], m["d_model"], m["vocab"]
    keys = iter(jax.random.split(key, 64))
    out: Dict = {"embed": jax.random.normal(next(keys), (v, d)) * 0.02}
    layers: Dict = {}
    for name, (k, n) in _proj_shapes(m).items():
        layers[name] = jax.lax.map(
            functools.partial(_quantized_projection, k=k, n=n, q=q),
            jax.random.split(next(keys), L))
    for name, n in _bias_shapes(m).items():
        layers[name] = jax.random.normal(next(keys), (L, n)) * 0.02

    def norm(shape):
        if m["norm"] == "rms":        # served as x * (1 + gamma)
            return {"gamma": jax.random.normal(next(keys), shape) * 0.05}
        return {"gamma": 1.0 + jax.random.normal(next(keys), shape) * 0.05,
                "beta": jax.random.normal(next(keys), shape) * 0.05}

    layers["ln"] = norm((L, d))
    layers["ln2"] = norm((L, d))
    out["layers"] = layers
    out["final_norm"] = norm((d,))
    if not m["tied"]:
        out["lm_head"] = _quantized_projection(next(keys), d, v, q)
    return out


def make_weights(model: Dict, seed: int) -> Dict:
    """The served weights of ``model`` from ``seed``: one jitted call, made
    on the device, in the types they are served in."""
    return jax.jit(functools.partial(_make, model))(seed_key(seed))


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------

def _norm(m: Dict, p: Dict, x: jax.Array) -> jax.Array:
    eps = m["norm_eps"]
    if m["norm"] == "rms":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * (1.0 + p["gamma"])
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["gamma"] + p["beta"]


def _round_to_bits(x: jax.Array, bits: int) -> jax.Array:
    hi = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / hi, 1e-8)
    return jnp.clip(jnp.round(x / s), -hi - 1, hi), s


def _linear(m: Dict, p: Dict, x: jax.Array, act_bits: int) -> jax.Array:
    """x (..., K) f32 through one served projection ``p``."""
    q = m["quant"]
    if act_bits != 8:
        xi, s = _round_to_bits(x, act_bits)
        x = xi * s
    xi, s = _round_to_bits(x, 8)
    clip_lo = p["mask"] & (xi >= q["clip_l"]) & (xi < 0)
    clip_hi = p["mask"] & (xi > 15) & (xi <= q["clip_h"])
    xi = jnp.where(clip_lo, 0.0, jnp.where(clip_hi, 15.0, xi))
    acc = jnp.matmul(xi, unpack_int4(p["q"]), precision=HIGHEST)
    return acc * s * p["scale"][0]


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x (T, H, hd) at positions 0..T-1."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _kv4(x: jax.Array) -> jax.Array:
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 7.0, 1e-8)
    return jnp.clip(jnp.round(x / s), -8, 7) * s


def _attend(m: Dict, q, k, v, chunk, q_block: int):
    """One sequence. q (T, H, hd), k/v (T, KVH, hd), chunk (T,) prefill
    chunk id of each position (-1: decoded). Causal."""
    t, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    kq, vq = _kv4(k), _kv4(v)
    qg = q.reshape(t, kvh, g, hd) * hd ** -0.5
    j = jnp.arange(t)

    def block(i0):
        qb = jax.lax.dynamic_slice_in_dim(qg, i0, q_block, 0)
        cb = jax.lax.dynamic_slice_in_dim(chunk, i0, q_block, 0)
        i = i0 + jnp.arange(q_block)
        exact = (cb[:, None] >= 0) & (cb[:, None] == chunk[None, :])
        sf = jnp.einsum("ikgd,jkd->kgij", qb, k, precision=HIGHEST)
        sq = jnp.einsum("ikgd,jkd->kgij", qb, kq, precision=HIGHEST)
        s = jnp.where(exact, sf, sq)
        s = jnp.where(j[None, :] <= i[:, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        pe = jnp.where(exact, pr, 0.0)
        o = (jnp.einsum("kgij,jkd->ikgd", pe, v, precision=HIGHEST)
             + jnp.einsum("kgij,jkd->ikgd", pr - pe, vq, precision=HIGHEST))
        return o.reshape(q_block, h * hd)

    starts = jnp.arange(0, t, q_block)
    return jax.lax.map(block, starts).reshape(t, h * hd)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _layer(m, p, x, chunk, act_bits, q_block):
    """One decoder layer over a batch of padded sequences x (S, T, D)."""
    sq, t, d = x.shape
    h, kvh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    mf = m

    def lin(name, y):
        out = _linear(mf, p[name], y, act_bits)
        bias = {"wq": "bq", "wk": "bk", "wv": "bv", "wo": "bo",
                "w_fc": "b_fc", "w_proj": "b_proj"}.get(name)
        if bias in p:
            out = out + p[bias]
        return out

    y = _norm(mf, p["ln"], x)
    qh = lin("wq", y).reshape(sq, t, h, hd)
    kh = lin("wk", y).reshape(sq, t, kvh, hd)
    vh = lin("wv", y).reshape(sq, t, kvh, hd)
    theta = m["rope_theta"]
    qh = jax.vmap(lambda a: _rope(a, theta))(qh)
    kh = jax.vmap(lambda a: _rope(a, theta))(kh)
    o = jax.lax.map(lambda a: _attend(mf, *a, q_block=q_block),
                    (qh, kh, vh, chunk))
    x = x + lin("wo", o)
    y = _norm(mf, p["ln2"], x)
    if m["mlp"] == "swiglu":
        g = lin("w_gate", y)
        z = (g * jax.nn.sigmoid(g)) * lin("w_up", y)
        return x + lin("w_down", z)
    u = lin("w_fc", y)
    z = 0.5 * u * (1.0 + jnp.tanh(0.7978845608028654
                                  * (u + 0.044715 * u ** 3)))
    return x + lin("w_proj", z)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _rows_head(m, w, x, first, act_bits, n_rows):
    """Head logits of ``n_rows`` rows of one sequence's x (T, D) from
    ``first`` on (rows past the end of x read clamped, and are dropped)."""
    rows = jax.lax.dynamic_slice_in_dim(
        jnp.pad(x, ((0, n_rows), (0, 0))), first, n_rows)
    y = _norm(m, w["final_norm"], rows)
    if m["tied"]:
        return jnp.matmul(y, w["embed"].T, precision=HIGHEST)
    return _linear(m, w["lm_head"], y, act_bits)


class _Frozen(dict):
    """A hashable dict, so that the model description can be a static
    argument of the jitted functions."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def _freeze(m: Dict) -> _Frozen:
    return _Frozen({k: (_Frozen(v) if isinstance(v, dict) else v)
                    for k, v in m.items()})


def forward(model: Dict, weights: Dict, seqs: Sequence[Dict],
            act_bits: int = 8, q_block: int = 256) -> List[jax.Array]:
    """Logits that produced each sequence's served tokens.

    Each entry of ``seqs`` has ``tokens`` (the prompt and every served
    token but the last), ``n_prompt`` and ``chunk`` (prefill chunk id of
    each prompt position). Returns, per sequence, the (n_served, V)
    float32 logits at positions ``n_prompt - 1`` onward.
    """
    m = _freeze(model)
    t_max = max(len(s["tokens"]) for s in seqs)
    t_pad = -(-t_max // q_block) * q_block
    tok = np.zeros((len(seqs), t_pad), np.int32)
    chunk = np.full((len(seqs), t_pad), -1, np.int32)
    for i, s in enumerate(seqs):
        tok[i, :len(s["tokens"])] = s["tokens"]
        chunk[i, :s["n_prompt"]] = s["chunk"]
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tok)]
        chunk = jnp.asarray(chunk)
        layers = weights["layers"]
        for li in range(model["n_layers"]):
            p = jax.tree_util.tree_map(lambda a, li=li: a[li], layers)
            x = _layer(m, p, x, chunk, act_bits, q_block)
        out = []
        for i, s in enumerate(seqs):
            n = len(s["tokens"]) - s["n_prompt"] + 1
            n_rows = -(-n // q_block) * q_block
            out.append(_rows_head(m, weights, x[i], s["n_prompt"] - 1,
                                  act_bits, n_rows)[:n])
    return out
