"""Tests for the static-analysis subsystem (src/repro/analysis/).

Per-rule unit tests run the AST rules on synthetic source trees and the
jaxpr rules on toy traced functions — each rule has a deliberately
broken fixture proven to fail and a clean fixture proven to pass. The
self-check tests then assert the real repo is green under the committed
allowlist (the same gate CI runs via ``python -m repro.analysis
--check``).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import RULES, VERSION, ruleset_hash
from repro.analysis import astlint, jaxprcheck
from repro.analysis.findings import (ALLOWLIST_PATH, Allowlist, Finding,
                                     apply_allowlist)
from repro.analysis.jaxprcheck import TracedStep

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _lint_tree(tmp_path, files, docs=""):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    docs_path = ""
    if docs:
        docs_path = str(tmp_path / "observability.md")
        (tmp_path / "observability.md").write_text(docs)
    return astlint.run(str(tmp_path), docs_path=docs_path)


def _rules(findings):
    return sorted({f.rule_id for f in findings})


# ------------------------------------------------------------- SPL001

def test_spl001_print_in_jitted_fn(tmp_path):
    fs = _lint_tree(tmp_path, {"mod.py": """
        import jax

        @jax.jit
        def step(x):
            print("debug", x)
            return x + 1
    """})
    assert any(f.rule_id == "SPL001" and "print" in f.message
               for f in fs)


def test_spl001_time_via_reachability(tmp_path):
    # the side effect sits in a helper reached through a call chain and
    # a higher-order reference (lax.scan body), not in the root itself
    fs = _lint_tree(tmp_path, {"mod.py": """
        import time
        import jax

        def helper(x):
            t0 = time.perf_counter()
            return x * t0

        def body(c, x):
            return helper(c), x

        @jax.jit
        def step(x):
            return jax.lax.scan(body, x, None, length=3)
    """})
    assert any(f.rule_id == "SPL001" and "perf_counter" in f.message
               for f in fs)


def test_spl001_obs_calls_flagged(tmp_path):
    fs = _lint_tree(tmp_path, {"mod.py": """
        import jax

        @jax.jit
        def step(x, tracer, m):
            tracer.span("oops")
            m.inc(1)
            return x
    """})
    msgs = [f.message for f in fs if f.rule_id == "SPL001"]
    assert any("tracer.span" in m for m in msgs)
    assert any(".inc()" in m for m in msgs)


def test_spl001_clean_and_host_side_untouched(tmp_path):
    # a host-side (non-root, unreachable) function may print/time freely
    fs = _lint_tree(tmp_path, {"mod.py": """
        import time
        import jax

        @jax.jit
        def step(x):
            return x + 1

        def host_loop(x):
            t0 = time.perf_counter()
            print("host", t0)
            return step(x)
    """})
    assert not [f for f in fs if f.rule_id == "SPL001"]


# ------------------------------------------------------------- SPL002

def test_spl002_device_op_in_host_module(tmp_path):
    fs = _lint_tree(tmp_path, {"serving/scheduler.py": """
        import jax.numpy as jnp

        def admit(n):
            return jnp.zeros((n,))
    """})
    assert any(f.rule_id == "SPL002" and "jnp.zeros" in f.message
               for f in fs)


def test_spl002_dtype_attrs_and_other_modules_ok(tmp_path):
    fs = _lint_tree(tmp_path, {
        # dtype attribute access is not a device op
        "serving/scheduler.py": """
            import jax.numpy as jnp
            DTYPE = jnp.int8
        """,
        # device ops outside host-only modules are fine
        "models/net.py": """
            import jax.numpy as jnp

            def f(x):
                return jnp.tanh(x)
        """})
    assert not [f for f in fs if f.rule_id == "SPL002"]


# ------------------------------------------------------------- SPL003

def test_spl003_tracer_leaks(tmp_path):
    fs = _lint_tree(tmp_path, {"mod.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            if jnp.any(x > 0):
                y = float(jnp.max(x))
            else:
                y = x.sum().item()
            return y
    """})
    msgs = [f.message for f in fs if f.rule_id == "SPL003"]
    assert any(".item()" in m for m in msgs)
    assert any("float()" in m for m in msgs)
    assert any("control flow" in m for m in msgs)


def test_spl003_static_python_ok(tmp_path):
    fs = _lint_tree(tmp_path, {"mod.py": """
        import jax

        @jax.jit
        def step(x, n: int = 4):
            if n > 2:                 # static config, not traced
                x = x * float(n)      # float() of a python int
            return x
    """})
    assert not [f for f in fs if f.rule_id == "SPL003"]


# ------------------------------------------------------------- SPL004

_DOCS = "catalog: `good_total` and `depth_now` are documented.\n"


def test_spl004_naming_and_catalog(tmp_path):
    fs = _lint_tree(tmp_path, {"eng.py": """
        def setup(r):
            a = r.counter("Bad-Name", "x", unit="1")
            b = r.counter("missing_suffix", "x", unit="1")
            c = r.gauge("undocumented_depth", "x", unit="1")
            return a, b, c
    """}, docs=_DOCS)
    msgs = [f.message for f in fs if f.rule_id == "SPL004"]
    assert any("violates" in m for m in msgs)
    assert any("_total" in m for m in msgs)
    assert any("not cataloged" in m for m in msgs)


def test_spl004_documented_metrics_pass(tmp_path):
    fs = _lint_tree(tmp_path, {"eng.py": """
        def setup(r):
            return (r.counter("good_total", "x", unit="1"),
                    r.gauge("depth_now", "x", unit="1"))
    """}, docs=_DOCS)
    assert not [f for f in fs if f.rule_id == "SPL004"]


# ----------------------------------------------------- jaxpr toy rules

def _toy_step(fn, *args, kind="decode", mesh=False, name=None):
    return TracedStep(name or f"{kind}/toy/{'mesh' if mesh else 'single'}",
                      kind, "transformer", mesh, jax.make_jaxpr(fn)(*args))


def _shmap(fn, n_out=1):
    """Wrap fn in a 1x1 shard_map so collectives trace as primitives."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_smoke_mesh
    outs = P() if n_out == 1 else tuple(P() for _ in range(n_out))
    return jax.shard_map(fn, mesh=make_smoke_mesh(), in_specs=P(),
                         out_specs=outs, check_vma=False)


def test_jxp002_row_psum_discipline():
    x = jnp.ones((4, 8), jnp.float32)

    def good(x):
        acc = (x.astype(jnp.int8) @ jnp.ones((8, 8), jnp.int8)
               ).astype(jnp.int32)
        scale = jax.lax.pmax(jnp.max(jnp.abs(x)), "model")
        acc = jax.lax.psum(acc, "model")
        return acc.astype(jnp.float32) * scale

    out = []
    jaxprcheck.check_row_psum(_toy_step(_shmap(good), x, mesh=True), out)
    # 1 psum + 1 pmax pair up, but a transformer decode expects 2 sites
    assert [f.key for f in out] == ["decode:row-site-count"]

    def float_psum(x):
        acc = x @ jnp.ones((8, 8), jnp.float32)
        return jax.lax.psum(acc, "model")

    out = []
    jaxprcheck.check_row_psum(
        _toy_step(_shmap(float_psum), x, mesh=True), out)
    keys = [f.key for f in out]
    assert "decode:psum:model:float32" in keys       # float accumulator
    assert "decode:psum-pmax-pairing" in keys        # psum without pmax


def test_jxp001_collectives_vs_real_allowlist():
    x = jnp.ones((4, 8), jnp.float32)

    def stray(x):
        acc = (x.astype(jnp.int8) @ jnp.ones((8, 8), jnp.int8)
               ).astype(jnp.int32)
        acc = jax.lax.psum(acc, "model")             # allowlisted shape
        return jax.lax.ppermute(acc.astype(jnp.float32), "data",
                                [(0, 0)])            # stray collective

    out = []
    jaxprcheck.check_collectives(
        _toy_step(_shmap(stray), x, mesh=True), out)
    active, allowed = apply_allowlist(out, Allowlist.load())
    assert [f.key for f in allowed] == ["decode:psum:model:int32"]
    assert [f.key for f in active] == ["decode:ppermute:data:float32"]


def test_jxp003_accumulator_discipline():
    q = jnp.ones((4, 8), jnp.int8)
    w = jnp.ones((8, 8), jnp.int8)

    def good(q, w):
        acc = jax.lax.dot_general(q, w, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * 0.5

    out = []
    jaxprcheck.check_acc_dtype(_toy_step(good, q, w), out)
    assert out == []

    def float_accum(q, w):
        return jax.lax.dot_general(q, w, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    out = []
    jaxprcheck.check_acc_dtype(_toy_step(float_accum, q, w), out)
    assert [f.key for f in out] == ["decode:float-accum"]

    def narrow_accum(q, w):
        return jax.lax.dot_general(q, w, (((1,), (0,)), ((), ())))

    out = []
    jaxprcheck.check_acc_dtype(_toy_step(narrow_accum, q, w), out)
    assert [f.key for f in out] == ["decode:narrow-accum"]

    def bitcast_touch(q, w):
        acc = jax.lax.dot_general(q, w, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return jax.lax.bitcast_convert_type(acc, jnp.float32)

    out = []
    jaxprcheck.check_acc_dtype(_toy_step(bitcast_touch, q, w), out)
    assert [f.key for f in out] == ["decode:bitcast_convert_type"]


def _toy_dual_pass(q, w):
    lsb = jnp.bitwise_and(q, jnp.int8(15))
    msb = jax.lax.shift_right_arithmetic(q, jnp.int8(4))
    dims = (((1,), (0,)), ((), ()))
    dense = jax.lax.dot_general(lsb, w, dims,
                                preferred_element_type=jnp.int32)
    sparse = jax.lax.dot_general(msb, w, dims,
                                 preferred_element_type=jnp.int32)
    return dense + sparse * 16


def _toy_lsb_only(q, w):
    lsb = jnp.bitwise_and(q, jnp.int8(15))
    return jax.lax.dot_general(lsb, w, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def test_jxp004_msb_skip_elision():
    q = jnp.ones((4, 8), jnp.int8)
    w = jnp.ones((8, 8), jnp.int8)
    full = _toy_step(_toy_dual_pass, q, w, kind="decode")
    draft = _toy_step(_toy_lsb_only, q, w, kind="draft")

    out = []
    jaxprcheck.check_msb_skip(full, draft, out)
    assert out == []

    # a draft that silently kept the MSB pass must fail both ways
    broken = _toy_step(_toy_dual_pass, q, w, kind="draft")
    out = []
    jaxprcheck.check_msb_skip(full, broken, out)
    keys = [f.key for f in out]
    assert "draft:dot-halving" in keys
    assert "draft:msb-dot" in keys


def test_jxp004_detector_self_check():
    # if the full step stops showing shift-fed dots, the rule must
    # report its own blindness instead of passing vacuously
    q = jnp.ones((4, 8), jnp.int8)
    w = jnp.ones((8, 8), jnp.int8)
    not_dual = _toy_step(_toy_lsb_only, q, w, kind="decode")
    draft = _toy_step(_toy_lsb_only, q, w, kind="draft")
    out = []
    jaxprcheck.check_msb_skip(not_dual, draft, out)
    assert any(f.key == "decode:msb-detector" for f in out)


def test_jxp005_callback_ban():
    def leaky(x):
        jax.debug.print("x = {}", x)
        return x + 1

    out = []
    jaxprcheck.check_callbacks(
        _toy_step(leaky, jnp.ones((2,)), kind="decode"), out)
    assert [f.rule_id for f in out] == ["JXP005"]

    def clean(x):
        return x + 1

    out = []
    jaxprcheck.check_callbacks(
        _toy_step(clean, jnp.ones((2,)), kind="decode"), out)
    assert out == []


# -------------------------------------------------- allowlist plumbing

def test_allowlist_match_and_stale(tmp_path):
    p = tmp_path / "allow.txt"
    p.write_text("# comment\n"
                 "JXP001  *:psum:model:int32  the one reduce\n"
                 "SPL002  never/matches.py::*  stale entry\n")
    al = Allowlist.load(str(p))
    f1 = Finding("JXP001", "decode:psum:model:int32", "x", "m")
    f2 = Finding("JXP001", "decode:psum:data:float32", "x", "m")
    active, allowed = apply_allowlist([f1, f2], al)
    assert allowed == [f1] and active == [f2]
    assert f1.allowlisted and f1.allow_reason == "the one reduce"
    assert [e.pattern for e in al.stale_entries()] == \
        ["never/matches.py::*"]


def test_allowlist_requires_reason(tmp_path):
    p = tmp_path / "allow.txt"
    p.write_text("JXP001  some:key\n")
    with pytest.raises(ValueError, match="reason"):
        Allowlist.load(str(p))


def test_ruleset_hash_tracks_rules():
    h = ruleset_hash()
    assert len(h) == 16 and h == ruleset_hash()
    assert set(RULES) == {"SPL001", "SPL002", "SPL003", "SPL004",
                          "JXP001", "JXP002", "JXP003", "JXP004",
                          "JXP005"}


def test_provenance_meta_stamps_analyzer():
    sys.path.insert(0, REPO)
    try:
        from benchmarks.common import provenance_meta
        meta = provenance_meta()
    finally:
        sys.path.pop(0)
    assert meta["analyzer_version"] == VERSION
    assert meta["analyzer_ruleset"] == ruleset_hash()


# ------------------------------------------------------- repo self-check

def test_repo_ast_layer_green():
    fs = astlint.run(os.path.join(REPO, "src"),
                     docs_path=os.path.join(REPO, "docs",
                                            "observability.md"))
    active, _ = apply_allowlist(fs, Allowlist.load())
    assert active == [], "\n".join(f.render() for f in active)


def test_repo_msb_skip_contract_fast():
    # the acceptance-critical contract on the REAL traced decode step,
    # transformer single-device only so it stays in the fast lane
    from repro.core.qlinear import quantize_model_params
    from repro.launch import steps as S
    from repro.models.schema import init_params
    from repro.models.schema_builder import build_schema
    from repro.serving.kv_pool import PoolConfig, init_pool_state

    cfg = jaxprcheck.tiny_configs()["transformer"]
    fparams = init_params(build_schema(cfg), jax.random.PRNGKey(0))
    qparams = quantize_model_params(fparams, w_bits=4, tile_k=16)
    pool = init_pool_state(cfg, PoolConfig(n_pages=8, page_size=4))
    args = (qparams, pool, jnp.zeros((2,), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2, 4), jnp.int32))
    full = TracedStep(
        "decode/transformer/single", "decode", "transformer", False,
        jax.make_jaxpr(S.make_engine_decode(cfg))(*args))
    draft = TracedStep(
        "draft/transformer/single", "draft", "transformer", False,
        jax.make_jaxpr(S.make_engine_decode(
            cfg, msb_skip=True, with_telemetry=False))(*args))
    out = []
    jaxprcheck.check_msb_skip(full, draft, out)
    jaxprcheck.check_acc_dtype(full, out)
    jaxprcheck.check_acc_dtype(draft, out)
    jaxprcheck.check_callbacks(full, out)
    jaxprcheck.check_callbacks(draft, out)
    assert out == [], "\n".join(f.render() for f in out)
    # and the empirical anchor: the dual-pass full step really carries
    # shift-fed MSB dots for the detector to see
    total, shift_fed = jaxprcheck.count_int_plane_dots(full.jaxpr.jaxpr)
    assert total == 2 * shift_fed > 0


@pytest.mark.slow
def test_repo_jaxpr_layer_green_single_device():
    fs = jaxprcheck.run(with_mesh=False)
    active, _ = apply_allowlist(fs, Allowlist.load())
    assert active == [], "\n".join(f.render() for f in active)


@pytest.mark.slow
def test_cli_check_green_with_mesh():
    # the exact CI gate: both layers, mesh traces on 4 forced host
    # devices, committed allowlist, exit 0
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--check"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s)" in r.stdout
    assert "stale allowlist entry" not in r.stdout


def test_allowlist_file_exists_with_reasons():
    al = Allowlist.load(ALLOWLIST_PATH)
    assert al.entries, "committed allowlist must not be empty"
    for e in al.entries:
        assert len(e.reason) > 10, f"entry {e.pattern} needs a reason"
