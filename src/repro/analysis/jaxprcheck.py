"""Jaxpr-level contract checker (layer 2 of the analyzer).

Traces the *real* engine step functions from ``launch/steps.py`` —
prefill / decode / draft (msb_skip) / verify, single-device and
2x2-mesh, transformer and MoE — on tiny configs via ``jax.make_jaxpr``
(nothing executes), then walks the ClosedJaxpr (descending into every
sub-jaxpr carried in eqn params: scan, pjit, cond, shard_map,
pallas_call) and asserts the representation contracts:

* **JXP001** — every collective primitive instance must match the
  committed allowlist (key ``<kind>:<prim>:<axes>:<dtype>``).
* **JXP002** — row-parallel psum discipline: psums over the model axis
  are int32 only (the merged LSB+MSB accumulator — never a float
  partial), paired 1:1 with the f32 pmax that computes the global
  per-token scale, and the transformer step body contains exactly one
  per row-parallel linear (wo + w_down = 2; see docs/sharding.md).
* **JXP003** — int32 accumulator dtype discipline: from each int8-plane
  ``dot_general`` the dataflow stays integer-typed until the single
  ``convert_element_type`` rescale; no float op touches the accumulator.
* **JXP004** — msb_skip elision: the draft jaxpr holds exactly half the
  int32 matmuls of the full step, and none of its matmul activation
  operands are produced by the MSB-plane extraction (arithmetic right
  shift). The weight operand is not looked at: packed int4 weights
  reach the dots as nibble planes sign-extended by the same shift.
* **JXP005** — no ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` (jax.debug.print) inside any serving step.

Empirical anchors (tiny 2-layer configs): the full decode
carries 32 int8-plane dots (16 of them shift-fed MSB dots; each pass of
a packed linear is two dots, one per weight nibble plane), the draft 16
(0 shift-fed); a 2x2 mesh decode carries exactly 2 int32 ``psum`` and
2 f32 ``pmax`` eqns over the model axis.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr, JaxprEqn, Literal

from .findings import Finding

COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
    "pbroadcast", "reduce_scatter", "axis_index",
}
CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback"}

# row-parallel linears per scanned stage body, by family: the attention
# output projection plus the FFN down projection (transformer); MoE adds
# the routed-expert and shared-expert down projections, but its eqn
# count varies per step kind (the verify window unrolls the ffn), so the
# exact-count check is asserted on the transformer decode only.
TRANSFORMER_ROW_SITES = 2

# layout/dtype-preserving ops: following *through* these keeps the
# "produced by a right shift" property of an MSB-plane operand
_LAYOUT_PRIMS = {"convert_element_type", "reshape", "broadcast_in_dim",
                 "squeeze", "transpose"}

# integer-preserving consumers of the int32 accumulator (JXP003)
_INT_OK_PRIMS = {
    "add", "sub", "mul", "neg", "max", "min", "rem", "and", "or", "xor",
    "shift_left", "shift_right_arithmetic", "shift_right_logical",
    "psum", "select_n", "broadcast_in_dim", "reshape", "transpose",
    "squeeze", "slice", "dynamic_slice", "dynamic_update_slice",
    "concatenate", "pad", "gather", "reduce_sum", "reduce_max",
    "expand_dims", "rev", "stop_gradient", "clamp",
}


def iter_eqns(jaxpr: Jaxpr) -> Iterator[Tuple[Jaxpr, int, JaxprEqn]]:
    """Yield (enclosing jaxpr, eqn index, eqn) over every nesting level,
    descending into sub-jaxprs carried in eqn params (scan/pjit/cond/
    while/shard_map/pallas_call kernels)."""
    for i, eqn in enumerate(jaxpr.eqns):
        yield jaxpr, i, eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


def _subjaxprs(v) -> Iterator[Jaxpr]:
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _subjaxprs(x)


def _axes_str(eqn: JaxprEqn) -> str:
    ax = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(ax, str):
        ax = (ax,)
    return "+".join(str(a) for a in ax) or "-"


def _in_dtype(eqn: JaxprEqn) -> str:
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            return str(aval.dtype)
    return "-"


def _is_int_plane_dot(eqn: JaxprEqn) -> bool:
    return (eqn.primitive.name == "dot_general"
            and str(eqn.outvars[0].aval.dtype) == "int32"
            and all(jnp.issubdtype(v.aval.dtype, jnp.integer)
                    for v in eqn.invars if hasattr(v.aval, "dtype")))


@dataclass
class TracedStep:
    name: str          # e.g. "decode/transformer/mesh"
    kind: str          # prefill | decode | draft | verify
    family: str        # transformer | moe
    mesh: bool
    jaxpr: ClosedJaxpr


# ------------------------------------------------------------- tracing

def tiny_configs() -> Dict[str, object]:
    from repro.configs.base import ModelConfig
    return {
        "transformer": ModelConfig(
            name="lint-tiny", family="transformer", n_layers=2,
            d_model=32, n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64,
            vocab=128, dtype="float32"),
        "moe": ModelConfig(
            name="lint-tiny-moe", family="moe", n_layers=2, d_model=32,
            n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64, moe_d_ff=32,
            n_experts=4, top_k=2, n_shared_experts=1, vocab=128,
            dtype="float32"),
    }


def trace_steps(with_mesh: Optional[bool] = None) -> List[TracedStep]:
    """Trace every serving step shape on tiny configs. ``with_mesh``
    None = auto (mesh variants when >= 4 devices are available)."""
    from repro.core.qlinear import quantize_model_params
    from repro.launch import steps as S
    from repro.models.schema import init_params
    from repro.models.schema_builder import build_schema
    from repro.serving.kv_pool import PoolConfig, init_pool_state

    if with_mesh is None:
        with_mesh = len(jax.devices()) >= 4

    B, P, C, T = 2, 4, 8, 3
    pc = PoolConfig(n_pages=8, page_size=4)
    out: List[TracedStep] = []
    for family, cfg in tiny_configs().items():
        fparams = init_params(build_schema(cfg), jax.random.PRNGKey(0))
        qparams = quantize_model_params(fparams, w_bits=4, tile_k=16)
        pool = init_pool_state(cfg, pc)
        meshes: List[Optional[object]] = [None]
        if with_mesh:
            from repro.launch.mesh import make_smoke_mesh
            meshes.append(make_smoke_mesh(data=2, model=2))
        for mesh in meshes:
            tag = "mesh" if mesh is not None else "single"
            kw: Dict[str, object] = {}
            if mesh is not None:
                from repro.distributed import tp
                kw = dict(mesh=mesh,
                          param_specs=tp.param_pspecs(qparams),
                          pool_specs=tp.pool_pspecs(cfg, pc, mesh))

            pre = S.make_engine_prefill_chunk(cfg, **kw)
            out.append(TracedStep(
                f"prefill/{family}/{tag}", "prefill", family,
                mesh is not None,
                jax.make_jaxpr(pre)(
                    qparams, pool, jnp.zeros((1, C), jnp.int32),
                    jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                    jnp.zeros((2, P), jnp.int32))))

            for kind, skip in (("decode", False), ("draft", True)):
                dec = S.make_engine_decode(
                    cfg, msb_skip=skip, with_telemetry=not skip, **kw)
                out.append(TracedStep(
                    f"{kind}/{family}/{tag}", kind, family,
                    mesh is not None,
                    jax.make_jaxpr(dec)(
                        qparams, pool, jnp.zeros((B,), jnp.int32),
                        jnp.zeros((B,), jnp.int32),
                        jnp.zeros((B, P), jnp.int32))))

            ver = S.make_engine_verify_window(cfg, **kw)
            out.append(TracedStep(
                f"verify/{family}/{tag}", "verify", family,
                mesh is not None,
                jax.make_jaxpr(ver)(
                    qparams, pool, jnp.zeros((B, T), jnp.int32),
                    jnp.zeros((B,), jnp.int32),
                    jnp.zeros((B, P), jnp.int32))))
    return out


# --------------------------------------------------------------- rules

def check_collectives(step: TracedStep, out: List[Finding]) -> None:
    """JXP001: every collective must be explicitly allowlisted."""
    for _, i, eqn in iter_eqns(step.jaxpr.jaxpr):
        p = eqn.primitive.name
        if p not in COLLECTIVE_PRIMS:
            continue
        key = f"{step.kind}:{p}:{_axes_str(eqn)}:{_in_dtype(eqn)}"
        out.append(Finding(
            "JXP001", key,
            f"step={step.name} eqn#{i} {p}",
            f"collective `{p}` over axes ({_axes_str(eqn)}) on "
            f"{_in_dtype(eqn)} operands"))


def check_row_psum(step: TracedStep, out: List[Finding]) -> None:
    """JXP002: one int32 psum per row-parallel linear, paired with the
    f32 pmax global-scale reduce."""
    n_psum_model = n_pmax_model = 0
    for _, i, eqn in iter_eqns(step.jaxpr.jaxpr):
        p = eqn.primitive.name
        if p not in ("psum", "pmax"):
            continue
        axes = _axes_str(eqn)
        if "model" not in axes.split("+"):
            continue
        dt = _in_dtype(eqn)
        if p == "psum":
            n_psum_model += 1
            if dt != "int32":
                out.append(Finding(
                    "JXP002", f"{step.kind}:psum:{axes}:{dt}",
                    f"step={step.name} eqn#{i} psum",
                    f"psum over the model axis on {dt} operands — the "
                    "row-parallel reduce must run on the merged int32 "
                    "accumulator, not a float partial"))
        else:
            n_pmax_model += 1
            if dt != "float32":
                out.append(Finding(
                    "JXP002", f"{step.kind}:pmax:{axes}:{dt}",
                    f"step={step.name} eqn#{i} pmax",
                    f"pmax over the model axis on {dt} operands — the "
                    "global per-token scale reduce must be f32"))
    if n_psum_model != n_pmax_model:
        out.append(Finding(
            "JXP002", f"{step.kind}:psum-pmax-pairing",
            f"step={step.name}",
            f"{n_psum_model} int32 psum(s) vs {n_pmax_model} f32 "
            "pmax(es) over the model axis — each row-parallel linear "
            "contributes exactly one of each"))
    if step.mesh and step.family == "transformer" and \
            step.kind == "decode" and \
            n_psum_model != TRANSFORMER_ROW_SITES:
        out.append(Finding(
            "JXP002", f"{step.kind}:row-site-count",
            f"step={step.name}",
            f"expected exactly {TRANSFORMER_ROW_SITES} model-axis psums "
            f"(one per row-parallel linear: wo, w_down), found "
            f"{n_psum_model}"))


def check_acc_dtype(step: TracedStep, out: List[Finding]) -> None:
    """JXP003: int8 planes accumulate in int32, and the accumulator
    stays integer until the rescale."""
    # (a) accumulation width/kind: every dot over int8 operands must
    # produce int32+ — a float output means the planes were accumulated
    # in floating point (rounding breaks bit-exactness), a narrow int
    # output means preferred_element_type was dropped (overflow).
    for _, i, eqn in iter_eqns(step.jaxpr.jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        if not all(str(getattr(v.aval, "dtype", "")) == "int8"
                   for v in eqn.invars):
            continue
        odt = eqn.outvars[0].aval.dtype
        if not jnp.issubdtype(odt, jnp.integer):
            out.append(Finding(
                "JXP003", f"{step.kind}:float-accum",
                f"step={step.name} eqn#{i} dot_general",
                f"int8-plane matmul accumulates in {odt} — the dual-pass "
                "accumulator must be int32 (bit-exactness)"))
        elif jnp.iinfo(odt).bits < 32:
            out.append(Finding(
                "JXP003", f"{step.kind}:narrow-accum",
                f"step={step.name} eqn#{i} dot_general",
                f"int8-plane matmul accumulates in {odt} — narrower than "
                "int32, the accumulator can overflow"))
    # (b) dataflow discipline: from each int32 accumulator, only
    # integer ops until the convert_element_type rescale.
    for jaxpr, _, _ in _unique_jaxprs(step.jaxpr.jaxpr):
        consumers: Dict[object, List[Tuple[int, JaxprEqn]]] = {}
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    consumers.setdefault(v, []).append((i, eqn))
        frontier = [ov for eqn in jaxpr.eqns if _is_int_plane_dot(eqn)
                    for ov in eqn.outvars]
        seen = set()
        while frontier:
            var = frontier.pop()
            if var in seen:
                continue
            seen.add(var)
            for i, eqn in consumers.get(var, ()):
                p = eqn.primitive.name
                if p == "convert_element_type":
                    # the rescale boundary (int32 -> f32) or an integer
                    # widening — only the former ends tracking
                    if jnp.issubdtype(eqn.outvars[0].aval.dtype,
                                      jnp.integer):
                        frontier.extend(eqn.outvars)
                    continue
                out_float = any(
                    jnp.issubdtype(ov.aval.dtype, jnp.floating)
                    for ov in eqn.outvars if hasattr(ov.aval, "dtype"))
                if p in _INT_OK_PRIMS and not out_float:
                    frontier.extend(eqn.outvars)
                elif out_float:
                    out.append(Finding(
                        "JXP003", f"{step.kind}:{p}",
                        f"step={step.name} eqn#{i} {p}",
                        f"float op `{p}` consumes the int32 accumulator "
                        "before the rescale convert_element_type"))
                # higher-order eqns (scan/pjit/...) end tracking here:
                # their inner jaxprs are checked independently by the
                # outer _unique_jaxprs loop


def _unique_jaxprs(jaxpr: Jaxpr):
    yield jaxpr, 0, None
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _unique_jaxprs(sub)


def count_int_plane_dots(jaxpr: Jaxpr) -> Tuple[int, int]:
    """(total int8-plane dots, dots whose activation operand (the lhs)
    is fed by an MSB-plane right shift, through layout ops and the
    strided column slices of a packed weight's parity split)."""
    total = shift_fed = 0
    for sub, _, _ in _unique_jaxprs(jaxpr):
        producer = {}
        for eqn in sub.eqns:
            for ov in eqn.outvars:
                producer[ov] = eqn

        def from_shift(var, depth: int = 0) -> bool:
            if isinstance(var, Literal) or var not in producer or \
                    depth > 8:
                return False
            e = producer[var]
            if e.primitive.name == "shift_right_arithmetic":
                return True
            if e.primitive.name in _LAYOUT_PRIMS | {"slice"}:
                return any(from_shift(iv, depth + 1) for iv in e.invars
                           if not isinstance(iv, Literal))
            return False

        for eqn in sub.eqns:
            if _is_int_plane_dot(eqn):
                total += 1
                if from_shift(eqn.invars[0]):
                    shift_fed += 1
    return total, shift_fed


def check_msb_skip(full: TracedStep, draft: TracedStep,
                   out: List[Finding]) -> None:
    """JXP004: the draft holds exactly half the int8-plane matmuls and
    none of them consume the MSB plane (shift-fed operands)."""
    f_total, f_shift = count_int_plane_dots(full.jaxpr.jaxpr)
    d_total, d_shift = count_int_plane_dots(draft.jaxpr.jaxpr)
    if f_shift == 0:
        out.append(Finding(
            "JXP004", f"{full.kind}:msb-detector",
            f"step={full.name}",
            "detector self-check failed: the full step shows no "
            "shift-fed MSB-plane matmuls — the MSB extraction signature "
            "changed and the elision check is blind"))
    if d_total * 2 != f_total:
        out.append(Finding(
            "JXP004", f"{draft.kind}:dot-halving",
            f"step={draft.name}",
            f"msb_skip draft has {d_total} int8-plane matmuls vs "
            f"{f_total} in the full step — expected exactly half (the "
            "MSB pass statically elided)"))
    if d_shift != 0:
        out.append(Finding(
            "JXP004", f"{draft.kind}:msb-dot",
            f"step={draft.name}",
            f"{d_shift} matmul(s) in the msb_skip draft consume an "
            "MSB-plane operand (produced by the >>4 extraction) — the "
            "sparse plane leaked into the draft datapath"))


def check_callbacks(step: TracedStep, out: List[Finding]) -> None:
    """JXP005: no host callbacks inside serving steps."""
    for _, i, eqn in iter_eqns(step.jaxpr.jaxpr):
        p = eqn.primitive.name
        if p in CALLBACK_PRIMS or "callback" in p or p == "debug_print":
            out.append(Finding(
                "JXP005", f"{step.kind}:{p}",
                f"step={step.name} eqn#{i} {p}",
                f"host callback `{p}` inside a serving step jaxpr"))


def run(with_mesh: Optional[bool] = None) -> List[Finding]:
    steps = trace_steps(with_mesh=with_mesh)
    out: List[Finding] = []
    for st in steps:
        check_collectives(st, out)
        check_row_psum(st, out)
        check_acc_dtype(st, out)
        check_callbacks(st, out)
    by_name = {st.name: st for st in steps}
    for st in steps:
        if st.kind == "draft":
            full = by_name[st.name.replace("draft/", "decode/")]
            check_msb_skip(full, st, out)
    return out
