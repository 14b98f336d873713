"""Jittable step functions: train_step / serve_prefill / serve_decode.

These are the units the launchers run and the dry-run lowers. Everything
scale-critical lives here:

  * microbatched gradient accumulation (``lax.scan`` over microbatches) —
    bounds activation memory and MoE dispatch buffers;
  * chunked cross-entropy — the (tokens, vocab) logits tensor is never
    materialized for the whole batch (deepseek's 129k / gemma3's 262k
    vocab would be 100s of GB at train_4k); the head+CE run per sequence
    chunk inside a scan, recomputed in backward via remat;
  * remat (nothing saveable) over the layer scan;
  * optional int8 error-feedback compression of the cross-pod gradient
    all-reduce (``TrainKnobs.compress_pod_grads``);
  * SPARQLe-quantized serving steps (the paper path) with KV4 caches.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import spec_for
from repro.models import model as M
from repro.models.qschema import (build_quantized_schema, tree_abstract,
                                  tree_shardings)
from repro.models.registry import cache_schema
from repro.models.schema import ParamSpec, Schema
from repro.models.schema_builder import build_schema
from repro.optim.adamw import OptConfig, OptState, adamw_update


@dataclasses.dataclass(frozen=True)
class TrainKnobs:
    microbatch: int = 0          # 0 = no accumulation (whole batch at once)
    remat: bool = True
    ce_chunk: int = 512          # sequence chunk for the chunked CE
    mtp_weight: float = 0.3
    aux_weight: float = 0.01
    compress_pod_grads: bool = False


class TrainState(NamedTuple):
    params: Any
    opt: OptState


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def _xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean token cross-entropy, f32-stable. logits (..., V), targets (...)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def chunked_ce(cfg: ModelConfig, params, hidden: jax.Array,
               targets: jax.Array, chunk: int) -> jax.Array:
    """CE over the vocab head without materializing (B, S, V).

    Scans over sequence chunks; the head matmul + softmax of each chunk is
    recomputed in the backward pass (jax.checkpoint), so peak logits
    memory is (B, chunk, V).
    """
    b, s, d = hidden.shape
    assert targets.shape == (b, s), (hidden.shape, targets.shape)
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        hidden = jnp.pad(hidden, [(0, 0), (0, pad), (0, 0)])
        targets = jnp.pad(targets, [(0, 0), (0, pad)], constant_values=-1)
    sp = s + pad
    nc = sp // chunk
    hc = hidden.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, nc, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def one(h, t):
        logits = M.head_logits(cfg, params, h).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(t, 0)[..., None], axis=-1)[..., 0]
        valid = (t >= 0).astype(jnp.float32)
        return jnp.sum((lse - gold) * valid), jnp.sum(valid)

    def body(carry, xs):
        tot, cnt = carry
        h, t = xs
        l, n = one(h, t)
        return (tot + l, cnt + n), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                 (hc, tc))
    return tot / jnp.maximum(cnt, 1.0)


def cast_params_for_compute(cfg: ModelConfig, params):
    """Cast float params to the compute dtype ONCE, before any use.

    Critical under FSDP: the per-layer all-gather then moves bf16 instead
    of the f32 master copy (half the gather bytes and half the gathered
    temp footprint). jax.grad transposes the cast back to f32 grads.

    MoE expert subtrees are excluded: a convert feeding the shard_map
    dispatch trips an XLA CPU-backend CHECK failure ("Invalid binary
    instruction opcode copy") in the transpose; expert weights therefore
    gather in f32 on this backend (2x expert-gather bytes — noted in
    EXPERIMENTS.md §Perf as recoverable on the TPU backend).
    """
    dt = cfg.cdtype

    def walk(tree, in_moe=False):
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = walk(v, in_moe or k == "moe")
            elif (not in_moe and hasattr(v, "dtype")
                  and v.dtype == jnp.float32):
                out[k] = v.astype(dt)
            else:
                out[k] = v
        return out

    return walk(params)


def loss_fn(cfg: ModelConfig, knobs: TrainKnobs, params,
            batch: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict]:
    params = cast_params_for_compute(cfg, params)
    hidden, aux = M.forward_hidden(cfg, params, batch, remat=knobs.remat,
                                   with_aux=True)
    targets = batch["targets"]
    if cfg.family == "vlm":      # targets cover only the text positions
        hidden_t = hidden[:, cfg.n_prefix:cfg.n_prefix + targets.shape[1]]
    else:
        hidden_t = hidden
    ce = chunked_ce(cfg, params, hidden_t, targets, knobs.ce_chunk)
    loss = ce + knobs.aux_weight * aux
    metrics = {"ce": ce, "aux": aux}
    if cfg.mtp_depth:
        mtp_lg = M.mtp_logits(cfg, params, hidden, batch)
        # MTP position i predicts tokens[i+2] == targets[i+1]
        mtp_ce = _xent(mtp_lg, targets[:, 1:])
        loss = loss + knobs.mtp_weight * mtp_ce
        metrics["mtp_ce"] = mtp_ce
    return loss, metrics


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, ocfg: OptConfig,
                    knobs: TrainKnobs = TrainKnobs()):
    """Returns train_step(state, batch) -> (state, metrics)."""

    grad_fn = jax.value_and_grad(
        lambda p, b: loss_fn(cfg, knobs, p, b), has_aux=True)

    def accum_grads(params, batch):
        mb = knobs.microbatch
        b = batch["targets"].shape[0]
        if not mb or mb >= b:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads
        assert b % mb == 0, (b, mb)
        n = b // mb
        split = jax.tree_util.tree_map(
            lambda x: x.reshape(n, mb, *x.shape[1:]), batch)

        def body(carry, ubatch):
            gsum, lsum = carry
            (loss, _), grads = grad_fn(params, ubatch)
            gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
            return (gsum, lsum + loss), None

        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum), _ = jax.lax.scan(body, (g0, jnp.zeros(())), split)
        grads = jax.tree_util.tree_map(lambda g: g / n, gsum)
        return lsum / n, {"ce": lsum / n}, grads

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        loss, metrics, grads = accum_grads(state.params, batch)
        if knobs.compress_pod_grads:
            # int8 EF compression of the cross-pod gradient reduction.
            # Inside pjit the pod all-reduce is implicit; quantize-
            # dequantize here shrinks the tensors XLA moves across the
            # DCN-mapped axis (error feedback folded into this step).
            from repro.optim.adamw import compress_grads, decompress_grads
            q, _err = compress_grads(grads)
            grads = decompress_grads(q)
        new_params, opt, om = adamw_update(state.params, grads, state.opt,
                                           ocfg)
        metrics = dict(metrics, loss=loss, **om)
        return TrainState(new_params, opt), metrics

    return train_step


# ---------------------------------------------------------------------------
# serving steps (SPARQLe path)
# ---------------------------------------------------------------------------

def make_serve_prefill(cfg: ModelConfig, max_len: int):
    def serve_prefill(params, batch):
        logits, cache = M.prefill(cfg, params, batch, max_len=max_len)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    return serve_prefill


def make_serve_decode(cfg: ModelConfig):
    def serve_decode(params, cache, token, pos):
        logits, cache = M.decode_step(cfg, params, cache, token, pos)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache

    return serve_decode


# ---------------------------------------------------------------------------
# continuous-batching engine steps (serving/engine.py) — paged KV pool
# ---------------------------------------------------------------------------

def _data_axis(mesh: Optional[Mesh]) -> Optional[str]:
    """The mesh axis decode slots shard over: "data", or None without a
    mesh or with a single data shard."""
    from repro.distributed.tp import mesh_axis_size
    if mesh is None or mesh_axis_size(mesh, "data") == 1:
        return None
    return "data"


def _engine_step(step, cfg: ModelConfig, mesh: Optional[Mesh], param_specs,
                 pool_specs, arg_specs, out_specs, batch_axis):
    """``step(params, pool, *args, cfg=cfg)`` as an engine step.

    ``step`` defaults its keyword ``cfg`` to the whole model's config.
    Without a mesh it is returned as it is, so its name names the jitted
    program (``jit_engine_decode``, which the benchmark's readers look
    for). With a mesh it runs inside ``shard_map`` on the per-shard config
    (weights model-partitioned, pool pages data-sharded; see
    docs/sharding.md), with no ambient mesh and under a TP scope over
    "model" whose batch axis is ``batch_axis``.
    ``arg_specs`` are the partition specs of the arguments after params
    and pool, ``out_specs`` those of the (logits, telemetry) results; the
    pool keeps ``pool_specs``.
    """
    if mesh is None:
        return step
    from repro.distributed.sharding import no_mesh
    from repro.distributed.tp import (mesh_axis_size, shard_model_config,
                                      tp_scope)
    mways = mesh_axis_size(mesh, "model")
    lcfg = shard_model_config(cfg, mways)

    def body(params, pool, *args):
        with no_mesh(), tp_scope("model", mways, batch_axis=batch_axis):
            return step(params, pool, *args, cfg=lcfg)

    logits_spec, tel_specs = out_specs
    return jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(param_specs, pool_specs, *arg_specs),
        out_specs=(logits_spec, pool_specs, tel_specs))


def _tel_specs(sparsity: P, per_layer: P) -> Dict[str, P]:
    """A step's telemetry specs: its ``sparsity`` and its per-layer rows."""
    return {"sparsity": sparsity, "layer_sparsity": per_layer,
            "layer_wire_bytes": per_layer, "layer_dense_bytes": per_layer}


def abstract_like(tree: Any) -> Any:
    """Map a tree of live arrays to ``ShapeDtypeStruct`` avals.

    The attribution layer (``obs/attribution.py``) lowers each serving
    step a second time to inspect its optimized HLO; doing that against
    abstract avals — rather than the live arguments — means buffers
    marked for donation in the real jitted step are never at risk, and
    no device transfer happens. Shardings are preserved when the leaf
    carries one (sharded engines lower to the same SPMD program the
    runtime executes).
    """
    def _leaf(x: Any) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=getattr(x, "sharding", None))
    return jax.tree_util.tree_map(_leaf, tree)


def make_engine_prefill_chunk(cfg: ModelConfig, *,
                              mesh: Optional[Mesh] = None,
                              param_specs=None, pool_specs=None):
    """Chunked prefill of ONE sequence into the paged pool.

    (params, pool, tokens (1, C), start, valid, block_table (1, Pmax))
    -> (logits (1, V) at the last valid position, new pool, telemetry) —
    telemetry carries the chunk's mean MSB4 sparsity plus per-layer
    measured packed-wire vs dense activation bytes (see
    ``models.model.prefill_chunk_paged``). Shape-static in C and Pmax,
    so the engine compiles this once.

    With a ``mesh``, the same body runs inside shard_map on a per-shard
    config (weights model-partitioned, pool pages data-sharded; see
    docs/sharding.md) and ``block_table`` widens to (D, Pmax) — one row
    per data shard, the owning shard's row holding the sequence's
    shard-local pages, every other row all-null. Non-owning shards
    compute into their null page; the owner's logits/telemetry are
    selected with an exact where-masked psum over the data axis.
    """
    daxis = _data_axis(mesh)

    def prefill_chunk(params, pool, tokens, start, valid, block_table, *,
                      cfg=cfg):
        logits, pool, tel = M.prefill_chunk_paged(
            cfg, params, pool, tokens, start, valid, block_table)
        if daxis is not None:
            # exactly one data shard holds the sequence's pages (nonzero
            # block-table row); a where-masked psum selects its values
            # bit-exactly (a sum with a single nonzero term)
            mine = jnp.any(block_table != 0)
            sel = lambda t: jax.lax.psum(  # noqa: E731
                jnp.where(mine, t, jnp.zeros_like(t)), daxis)
            logits = sel(logits)
            tel = {k: sel(v) for k, v in tel.items()}
        return logits, pool, tel

    # prefill is replicated over data (the chunk's batch dim is 1): no
    # batch_axis in the TP context, so MoE routes the local chunk
    return _engine_step(
        prefill_chunk, cfg, mesh, param_specs, pool_specs,
        arg_specs=(P(), P(), P(), P(daxis, None)),
        out_specs=(P(), _tel_specs(P(), P(None))),
        batch_axis=None)


def make_engine_decode(cfg: ModelConfig, *, msb_skip: bool = False,
                       with_telemetry: bool = True, kv2: bool = False,
                       mesh: Optional[Mesh] = None,
                       param_specs=None, pool_specs=None):
    """One continuous-batching decode step over every decode slot.

    (params, pool, token (B,), pos (B,), block_tables (B, Pmax))
    -> (logits (B, V), new pool, telemetry) — telemetry carries per-slot
    hidden MSB4 sparsity (B,) plus per-layer (L, B) measured packed-wire
    vs dense activation bytes (see ``models.model.decode_step_paged``).
    Raw logits come back (not argmax'd): sampling policy is per-request
    and lives host-side in the engine.

    ``msb_skip=True`` builds the LSB4-only *draft* step of the
    self-speculative engine: every sparqle projection is traced with the
    sparse MSB pass statically elided (1 compute round instead of
    1 + (1 - s); paper §3.3). ``with_telemetry=False`` additionally drops
    the wire accounting from the traced program (telemetry comes back
    empty) — the draft runs γ times per emitted batch, so it stays lean.

    The returned function takes an optional ``tier_tables`` (B, Pmax)
    argument after ``block_tables``; given, the step reads each page from
    the slab its tier id names (the precision ladder;
    ``models.model.decode_step_paged`` with ``tier_tables``; the pool
    state must carry the KV2 slab, i.e. ``PoolConfig.kv2_pages > 0``).
    ``kv2=True`` declares that the engine passes it: unsharded engines
    only — the ladder's host bookkeeping is single-pool.

    With a ``mesh``, the step runs inside shard_map: decode slots shard
    over the "data" axis (block tables carry the slot's data shard's
    local page ids), KV heads and weights over "model". Logits come back
    with the vocab shards gathered, so the host-side sampling loop is
    unchanged.
    """
    if kv2 and mesh is not None:
        raise NotImplementedError(
            "the KV2 precision ladder is unsharded-only (kv2=True with a "
            "mesh is not wired up; see docs/serving.md)")
    daxis = _data_axis(mesh)

    def engine_decode(params, pool, token, pos, block_tables,
                      tier_tables=None, *, cfg=cfg):
        return M.decode_step_paged(cfg, params, pool, token, pos,
                                   block_tables, tier_tables=tier_tables,
                                   msb_skip=msb_skip,
                                   with_telemetry=with_telemetry)

    return _engine_step(
        engine_decode, cfg, mesh, param_specs, pool_specs,
        arg_specs=(P(daxis), P(daxis), P(daxis, None)),
        out_specs=(P(daxis, None),
                   _tel_specs(P(daxis), P(None, daxis))
                   if with_telemetry else {}),
        batch_axis=daxis)


def make_engine_verify_window(cfg: ModelConfig, *,
                              mesh: Optional[Mesh] = None,
                              param_specs=None, pool_specs=None):
    """Full-precision batched verification of a γ-token draft window.

    (params, pool, tokens (B, T), pos (B,), block_tables (B, Pmax))
    -> (logits (B, T, V), new pool, telemetry) — one step scores every
    window position of every decode slot at once and overwrites the
    draft's approximate K/V with full-precision values (see
    ``models.model.verify_window_paged``). Shape-static in T = γ + 1, so
    the speculative engine compiles exactly one extra XLA program per γ.

    With a ``mesh``, sharded exactly like :func:`make_engine_decode`
    (the window axis T stays per-shard-complete).
    """
    daxis = _data_axis(mesh)

    def engine_verify(params, pool, tokens, pos, block_tables, *, cfg=cfg):
        return M.verify_window_paged(cfg, params, pool, tokens, pos,
                                     block_tables)

    return _engine_step(
        engine_verify, cfg, mesh, param_specs, pool_specs,
        arg_specs=(P(daxis, None), P(daxis), P(daxis, None)),
        out_specs=(P(daxis, None, None),
                   _tel_specs(P(daxis), P(None, daxis))),
        batch_axis=daxis)


def pool_abstract_and_shardings(cfg: ModelConfig, n_pages: int,
                                page_size: int, mesh: Mesh):
    """Dry-run plumbing for the serving pool (mirrors the cache helper)."""
    from repro.serving.kv_pool import PoolConfig, pool_schema
    ps = pool_schema(cfg, PoolConfig(n_pages=n_pages, page_size=page_size))
    return tree_abstract(ps), tree_shardings(ps, mesh)


# ---------------------------------------------------------------------------
# abstract state + shardings (dry-run / launcher plumbing)
# ---------------------------------------------------------------------------

def _spec_tree_opt(schema: Schema) -> Schema:
    """ParamSpec tree for AdamW moments mirroring the param schema."""
    def conv(s: ParamSpec) -> ParamSpec:
        return ParamSpec(s.shape, s.axes, jnp.bfloat16, init="zeros")
    return jax.tree_util.tree_map(
        conv, schema, is_leaf=lambda x: isinstance(x, ParamSpec))


def train_state_schema(cfg: ModelConfig) -> Any:
    """ParamSpec pytree of the full TrainState (params f32 + moments)."""
    pschema = build_schema(cfg)
    step = ParamSpec((), (), jnp.int32, init="zeros")
    return TrainState(
        params=pschema,
        opt=OptState(step=step, mu=_spec_tree_opt(pschema),
                     nu=_spec_tree_opt(pschema)))


def serve_param_schema(cfg: ModelConfig, mode: str = "sparqle") -> Any:
    """SPARQLe-quantized param schema (the served form)."""
    return build_quantized_schema(build_schema(cfg), w_bits=cfg.w_bits,
                                  mode=mode)


def batch_shardings(batch_abstract: Dict[str, jax.ShapeDtypeStruct],
                    mesh: Mesh) -> Dict[str, NamedSharding]:
    out = {}
    for k, v in batch_abstract.items():
        axes = ("batch",) + (None,) * (len(v.shape) - 1)
        out[k] = NamedSharding(mesh, spec_for(axes, v.shape, mesh))
    return out


def abstract_and_shardings(schema_tree: Any, mesh: Mesh):
    return tree_abstract(schema_tree), tree_shardings(schema_tree, mesh)


def cache_abstract_and_shardings(cfg: ModelConfig, batch: int, max_len: int,
                                 mesh: Mesh):
    cs = cache_schema(cfg, batch, max_len)
    return tree_abstract(cs), tree_shardings(cs, mesh)
