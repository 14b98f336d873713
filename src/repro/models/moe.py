"""Mixture-of-Experts layer: sort-based token dispatch, expert parallelism.

Dispatch strategy (scales to 256 experts x 1M tokens, unlike GShard's
(T, E, C) one-hot einsum): flatten the (token, expert-choice) assignments,
``argsort`` them by expert id, compute each assignment's rank within its
expert via a vectorized ``searchsorted``, drop ranks beyond capacity, and
scatter tokens into a contiguous (E, C, D) buffer. Experts are sharded over
the "model" mesh axis, capacity slots over "data" — XLA inserts the
all-to-alls at the dispatch/combine boundaries.

Supports softmax routing (jamba/deepseek-moe) and sigmoid routing with
normalized top-k (deepseek-v3), plus always-on shared experts.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.core.qlinear import expert_linear, linear
from repro.distributed.sharding import active_mesh, constrain


def router(x: jax.Array, w_router: jax.Array, router_type: str,
           top_k: int) -> Tuple[jax.Array, jax.Array]:
    """x: (T, D) -> (weights (T, k), expert_ids (T, k))."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    if router_type == "sigmoid":          # deepseek-v3
        scores = jax.nn.sigmoid(logits)
        topv, topi = jax.lax.top_k(scores, top_k)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, top_k)
    return topv, topi


def load_balance_loss(x: jax.Array, w_router: jax.Array, top_k: int) -> jax.Array:
    """Switch-style auxiliary load-balancing loss (training substrate)."""
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    e = probs.shape[-1]
    _, topi = jax.lax.top_k(probs, top_k)
    counts = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(counts.sum(), 1.0)
    frac_probs = probs.mean(axis=0)
    return e * jnp.sum(frac_tokens * frac_probs)


def moe_ffn(
    x: jax.Array,             # (T, D) flattened tokens
    w_router: jax.Array,      # (D, E)
    w_gate: jax.Array,        # (E, D, F)
    w_up: jax.Array,          # (E, D, F)
    w_down: jax.Array,        # (E, F, D)
    *,
    top_k: int,
    capacity_factor: float = 1.0,
    router_type: str = "softmax",
) -> jax.Array:
    t, d = x.shape
    e = w_router.shape[-1]
    capacity = max(1, int(t * top_k * capacity_factor) // e)

    topv, topi = router(x, w_router, router_type, top_k)

    flat_e = topi.reshape(-1)                       # (T*k,) expert per assignment
    flat_w = topv.reshape(-1).astype(jnp.float32)
    flat_t = jnp.arange(t * top_k, dtype=jnp.int32) // top_k

    order = jnp.argsort(flat_e)                     # stable
    se = flat_e[order]
    st = flat_t[order]
    sw = flat_w[order]
    # rank within expert = index - first index of this expert id
    first = jnp.searchsorted(se, se, side="left")
    rank = jnp.arange(t * top_k, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = rank < capacity
    slot = jnp.where(keep, se.astype(jnp.int32) * capacity + rank,
                     e * capacity)                  # dropped -> overflow row

    xs = jnp.take(x, st, axis=0)                    # (T*k, D) tokens, sorted
    buf = jnp.zeros((e * capacity + 1, d), x.dtype).at[slot].set(xs)
    expert_in = buf[:-1].reshape(e, capacity, d)
    expert_in = constrain(expert_in, ("experts", "capacity", None))

    # expert FFN (SwiGLU), batched over the expert dim; expert_linear
    # dispatches between float weights and SPARQLe-quantized experts
    h = jax.nn.silu(expert_linear(expert_in, w_gate))
    h = h * expert_linear(expert_in, w_up)
    h = constrain(h, ("experts", "capacity", "mlp"))
    # under serving TP the experts are sharded on their hidden dim (NOT
    # the expert axis): dispatch/routing replicate, and the down-proj's
    # single int32 psum keeps the combine bit-exact vs a single device
    expert_out = expert_linear(h, w_down, tp="row")
    expert_out = constrain(expert_out, ("experts", "capacity", None))

    # combine via the INVERSE permutation (pure gathers): a scatter-add here
    # lowers to an SPMD scatter whose (f32 + u32) all-reduce pair over the
    # expert axis doubles combine traffic (§Perf iteration). inv_order[a]
    # is the sorted position of assignment a = t*top_k + kk.
    inv_order = jnp.argsort(order)
    gathered = expert_out.reshape(e * capacity, d)[
        jnp.minimum(slot, e * capacity - 1)]
    gathered = gathered * (sw * keep)[:, None]
    per_assignment = gathered[inv_order].reshape(t, top_k, d)
    return per_assignment.sum(axis=1).astype(x.dtype)


def moe_ffn_local_ep(
    x_l: jax.Array,            # (T_local, D) this data-shard's tokens
    w_router: jax.Array,       # (D, E_total) replicated
    w_gate, w_up, w_down,      # (E_local, ...) — THIS shard's experts
    *,
    top_k: int,
    e_total: int,
    model_axis: str,
    capacity_factor: float = 1.0,
    router_type: str = "softmax",
) -> jax.Array:
    """Expert-parallel MoE body (runs inside a fully-manual shard_map).

    Each model shard owns ``E_local = E_total / model_ways`` experts and
    holds the data shard's tokens replicated. It routes against the FULL
    router, dispatches only assignments that hit its own experts into a
    local (E_local*C, D) buffer (all local memory traffic), runs its
    expert FFNs, combines its partial outputs per token, and a single
    ``psum`` over the model axis produces the final combine — the one
    irreducible MoE reduction (T_local x D), instead of GSPMD's
    replicated (T*k x D) scatter/gather all-reduce pairs (§Perf log).
    """
    t, d = x_l.shape
    e_local = w_gate.shape[0] if not hasattr(w_gate, "w") else \
        w_gate.w.q.shape[0]
    m_idx = jax.lax.axis_index(model_axis)
    off = m_idx * e_local
    capacity = max(1, int(t * top_k * capacity_factor) // e_total)

    topv, topi = router(x_l, w_router, router_type, top_k)

    flat_g = topi.reshape(-1)                        # global expert ids
    mine = (flat_g >= off) & (flat_g < off + e_local)
    flat_e = jnp.where(mine, flat_g - off, e_local)  # foreign -> overflow
    flat_w = (topv.reshape(-1) * mine).astype(jnp.float32)
    flat_t = jnp.arange(t * top_k, dtype=jnp.int32) // top_k

    order = jnp.argsort(flat_e)
    se = flat_e[order]
    st = flat_t[order]
    sw = flat_w[order]
    first = jnp.searchsorted(se, se, side="left")
    rank = jnp.arange(t * top_k, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = (rank < capacity) & (se < e_local)
    slot = jnp.where(keep, se.astype(jnp.int32) * capacity + rank,
                     e_local * capacity - 1)

    xs = jnp.take(x_l, st, axis=0)
    xs = jnp.where(keep[:, None], xs, 0)
    buf = jnp.zeros((e_local * capacity, d), x_l.dtype).at[slot].add(xs)
    expert_in = buf.reshape(e_local, capacity, d)

    h = jax.nn.silu(expert_linear(expert_in, w_gate))
    h = h * expert_linear(expert_in, w_up)
    expert_out = expert_linear(h, w_down)

    gathered = expert_out.reshape(e_local * capacity, d)[slot]
    gathered = gathered * (sw * keep)[:, None]
    inv_order = jnp.argsort(order)
    per_assign = gathered[inv_order].reshape(t, top_k, d)
    y_partial = per_assign.sum(axis=1)
    return jax.lax.psum(y_partial.astype(jnp.float32),
                        model_axis).astype(x_l.dtype)


def moe_ffn_dist(
    x: jax.Array,
    w_router, w_gate, w_up, w_down,
    *,
    top_k: int,
    capacity_factor: float = 1.0,
    router_type: str = "softmax",
) -> jax.Array:
    """Distribution-aware MoE: manual expert parallelism via shard_map.

    The sort/scatter dispatch is data-dependent, so GSPMD cannot partition
    it — left alone it replicates (T*k, D) buffers on every device (the
    "involuntary full rematerialization" failure mode). The scalable
    formulation — what Tutel/DeepSpeed-MoE/MaxText do — is hierarchical:
    tokens are manual over the batch axes ("pod", "data"); experts are
    manual over "model" (E_total/model_ways per shard, weights never
    gathered over model); each shard dispatches locally and one psum over
    "model" performs the combine (see :func:`moe_ffn_local_ep`).
    """
    mesh = active_mesh()
    t = x.shape[0]
    e_total = w_router.shape[-1]
    data_axes = tuple(a for a in ("pod", "data")
                      if mesh is not None and mesh.shape.get(a, 1) > 1)
    nshards = 1
    for a in data_axes:
        nshards *= mesh.shape[a]
    model_ways = mesh.shape.get("model", 1) if mesh is not None else 1
    if (not data_axes or t % nshards != 0 or model_ways <= 1
            or e_total % model_ways != 0):
        return moe_ffn(x, w_router, w_gate, w_up, w_down, top_k=top_k,
                       capacity_factor=capacity_factor,
                       router_type=router_type)

    chunk = 16384  # bounds local dispatch buffers to ~chunk*k*D bytes

    def local(x_l, wr, wg, wu, wd):
        def one(xi):
            return moe_ffn_local_ep(
                xi, wr, wg, wu, wd, top_k=top_k, e_total=e_total,
                model_axis="model", capacity_factor=capacity_factor,
                router_type=router_type)

        t_l = x_l.shape[0]
        if t_l <= chunk or t_l % chunk != 0:
            return one(x_l)
        xc = x_l.reshape(t_l // chunk, chunk, x_l.shape[-1])
        return jax.lax.map(one, xc).reshape(t_l, x_l.shape[-1])

    def wspec(w):
        return jax.tree_util.tree_map(
            lambda leaf: P(*(("model",) + (None,) * (leaf.ndim - 1)))
            if leaf.ndim > 0 else P(), w)

    spec_x = P(data_axes if len(data_axes) > 1 else data_axes[0])
    manual = frozenset(data_axes) | {"model"}
    in_specs = (spec_x, P(), wspec(w_gate), wspec(w_up), wspec(w_down))
    fn = jax.shard_map(
        local, mesh=mesh, axis_names=manual,
        in_specs=in_specs, out_specs=spec_x, check_vma=False)
    return fn(x, w_router, w_gate, w_up, w_down)


def shared_expert_ffn(x, w_gate, w_up, w_down):
    """Always-on shared expert(s) — a plain SwiGLU over (possibly) stacked
    shared-expert weights folded into one wide FFN."""
    h = jax.nn.silu(linear(x, w_gate)) * linear(x, w_up)
    h = constrain(h, ("batch", "seq", "mlp"))
    return linear(h, w_down, tp="row")
