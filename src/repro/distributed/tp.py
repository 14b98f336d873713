"""Tensor-parallel serving support: shard the SPARQLe stack over a mesh.

The serving engine's jitted steps become *mesh-native* by wrapping the
exact single-device step bodies in ``shard_map`` with

  * weights partitioned on the ``"model"`` axis following the Megatron
    column/row pattern (the physical realization of the logical-axis rule
    table in ``distributed/sharding.py``: ``heads``/``kv_heads``/``mlp``/
    ``vocab`` -> ``"model"``), and
  * the paged KV pool sharded on ``kv_heads`` over ``"model"`` and on the
    new ``pages`` logical axis over ``"data"`` (request-level parallelism
    — each data shard owns a slab of pages and a slice of decode slots).

Bit-exactness contract (what makes sharded greedy streams byte-identical
to the single-device engine): SPARQLe projections accumulate in *int32*.
A row-parallel (K-sharded) linear therefore

  1. computes its per-token activation scale from the GLOBAL row via an
     exact ``pmax`` over the model axis (max is order-independent),
  2. quantizes/clips/decomposes locally — the local int8/nibble planes are
     exact slices of the single-device planes, and
  3. reduces the merged dual-pass accumulator with ONE int32 ``psum``
     (LSB and shifted MSB partials summed together, not per-pass) —
     integer addition is associative, so the reduced accumulator equals
     the single-device accumulator bit for bit; the f32 rescale then
     multiplies identical operands.

Column-parallel linears are exact by construction (each shard computes an
untouched slice of the output channels). The trace-time :func:`tp_scope`
context tells ``core/qlinear.py`` which mesh axis to reduce over; model
code only marks *which* call sites are row-parallel (``tp="row"``) — the
markers are inert outside a TP trace, so the same model code serves the
single-device path unchanged.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Dict, List, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


# ---------------------------------------------------------------------------
# trace-time TP context
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TPContext:
    axis: str = "model"              # mesh axis of the weight partition
    ways: int = 1                    # its size (1 = no model parallelism)
    batch_axis: Optional[str] = None  # mesh axis the decode batch is
    #                                   sharded over (None in prefill: the
    #                                   chunk is replicated across data)


class _TPState(threading.local):
    def __init__(self):
        self.ctx: Optional[TPContext] = None


_TP = _TPState()


@contextlib.contextmanager
def tp_scope(axis: str, ways: int, batch_axis: Optional[str] = None):
    """Install the TP context for one trace (wrap the shard_map body)."""
    prev = _TP.ctx
    if ways > 1 or batch_axis is not None:
        _TP.ctx = TPContext(axis=axis, ways=ways, batch_axis=batch_axis)
    else:
        _TP.ctx = None
    try:
        yield
    finally:
        _TP.ctx = prev


def tp_ctx() -> Optional[TPContext]:
    return _TP.ctx


def mesh_axis_size(mesh: Optional[Mesh], axis: str) -> int:
    if mesh is None:
        return 1
    return mesh.shape.get(axis, 1)


# ---------------------------------------------------------------------------
# per-shard model config
# ---------------------------------------------------------------------------

def validate_tp_config(cfg: ModelConfig, ways: int) -> None:
    """Raise listing every dimension the model axis cannot divide.

    The serving TP path is strict on purpose: degrading a single
    projection to replication would break the psum placement the
    row-parallel call sites assume (see module docstring).
    """
    if ways <= 1:
        return
    problems: List[str] = []
    if cfg.n_heads % ways:
        problems.append(f"n_heads={cfg.n_heads} % model={ways}")
    if cfg.n_kv_heads % ways:
        problems.append(f"n_kv_heads={cfg.n_kv_heads} % model={ways}")
    if cfg.d_ff and cfg.d_ff % (2 * ways):
        # row-parallel w_down is nibble-PACKED along K: each shard's K
        # slice must cover whole bytes, hence the extra factor of 2
        problems.append(f"d_ff={cfg.d_ff} % 2*model={2 * ways}")
    if cfg.moe_d_ff and cfg.moe_d_ff % (2 * ways):
        problems.append(f"moe_d_ff={cfg.moe_d_ff} % 2*model={2 * ways}")
    if not cfg.tie_embeddings and cfg.vocab % ways:
        problems.append(f"vocab={cfg.vocab} % model={ways}")
    if problems:
        raise ValueError(
            f"config {cfg.name!r} cannot shard {ways}-way on the model "
            f"axis: " + ", ".join(problems))


def shard_model_config(cfg: ModelConfig, ways: int) -> ModelConfig:
    """The per-shard config the shard_map body runs: head counts divided
    by the model ways, head_dim pinned so ``cfg.hd`` stays the global
    value. Everything else (d_model, vocab, capacity factors, ...) is
    untouched — runtime shapes flow from the (sharded) params."""
    if ways <= 1:
        return cfg
    validate_tp_config(cfg, ways)
    return cfg.replace(n_heads=cfg.n_heads // ways,
                       n_kv_heads=cfg.n_kv_heads // ways,
                       head_dim=cfg.hd)


# ---------------------------------------------------------------------------
# partition-spec trees
# ---------------------------------------------------------------------------

# projection leaves by Megatron role (keys of the param tree; the same
# name set core/qlinear.quantize_model_params rewrites)
_COL_KEYS = frozenset({"wq", "wk", "wv", "w_gate", "w_up", "w_fc",
                       "lm_head", "w_shared_gate", "w_shared_up"})
_ROW_KEYS = frozenset({"wo", "w_down", "w_proj", "w_shared_down"})
_COL_BIAS_KEYS = frozenset({"bq", "bk", "bv", "b_fc"})


def _last_dim(ndim: int, axis: str) -> P:
    return P(*([None] * (ndim - 1) + [axis]))


def _dim(ndim: int, which: int, axis: str) -> P:
    spec: List[Optional[str]] = [None] * ndim
    spec[which] = axis
    return P(*spec)


def _sl_pspecs(sl, kind: str, axis: str):
    """Partition-spec 'SparqleLinear' mirroring one quantized leaf.

    col: weight sharded on output channels (q/scale/zero last dim).
    row: weight sharded on the (packed) K dim; scales replicated (they
    are per-output-channel); the column-importance mask follows K.
    """
    from repro.core.qlinear import SparqleLinear
    from repro.core.quantize import QuantizedTensor
    q, scale = sl.w.q, sl.w.scale
    if kind == "col":
        qs = _last_dim(q.ndim, axis)
        ss = _last_dim(scale.ndim, axis)
        ms = None if sl.col_mask is None else P()
    else:
        qs = _dim(q.ndim, q.ndim - 2, axis)
        ss = P()
        ms = None if sl.col_mask is None else _last_dim(sl.col_mask.ndim,
                                                        axis)
    lh = None if sl.l is None else P()
    return SparqleLinear(
        w=QuantizedTensor(q=qs, scale=ss, zero=ss, bits=sl.w.bits),
        col_mask=ms, l=lh, h=None if sl.h is None else P(),
        mode=sl.mode, packed=sl.packed, wire_format=sl.wire_format)


def param_pspecs(params: Dict[str, Any], axis: str = "model") -> Any:
    """PartitionSpec tree for a (quantized) serving param tree.

    Projections are partitioned on ``axis`` per the column/row table
    above; float leaves (norms, embedding table, router, row-parallel
    biases) replicate. Works for float param trees too (the same names
    shard their float leaves), though only int-accumulating quantized
    modes carry the bit-exactness guarantee.
    """
    from repro.core.qlinear import SparqleLinear

    def leaf_spec(key: str, v):
        if isinstance(v, SparqleLinear):
            if key in _COL_KEYS:
                return _sl_pspecs(v, "col", axis)
            if key in _ROW_KEYS:
                return _sl_pspecs(v, "row", axis)
            return jax.tree_util.tree_map(lambda x: P(), v)
        if v is None:
            return None
        if key in _COL_KEYS:
            return _last_dim(v.ndim, axis)
        if key in _ROW_KEYS:
            return _dim(v.ndim, v.ndim - 2, axis)
        if key in _COL_BIAS_KEYS:
            return _last_dim(v.ndim, axis)
        return P()

    def walk(tree):
        out = {}
        for k, v in tree.items():
            out[k] = walk(v) if isinstance(v, dict) else leaf_spec(k, v)
        return out

    return walk(params)


def pool_pspecs(cfg: ModelConfig, pool_cfg, mesh: Mesh) -> Any:
    """PartitionSpec tree for the paged pool state, straight from the
    logical-axis rule table: ``pages`` -> "data", ``kv_heads`` -> "model"
    (see ``serving/kv_pool.pool_schema``)."""
    from repro.distributed.sharding import spec_for
    from repro.models.schema import ParamSpec
    from repro.serving.kv_pool import pool_schema
    return jax.tree_util.tree_map(
        lambda s: spec_for(s.axes, s.shape, mesh),
        pool_schema(cfg, pool_cfg),
        is_leaf=lambda x: isinstance(x, ParamSpec))


def device_put_tree(tree: Any, pspecs: Any, mesh: Mesh) -> Any:
    """Place every leaf per its PartitionSpec tree (same structure;
    ``None`` leaves pair with ``None`` specs and are skipped)."""
    def put(leaf, spec):
        return jax.device_put(leaf, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(put, tree, pspecs)
