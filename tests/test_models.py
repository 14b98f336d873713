"""Per-architecture smoke tests (assignment deliverable f) + model-level
SPARQLe integration: every arch instantiates a reduced config, runs one
forward and one train step on CPU, asserts shapes and no NaNs; decode
matches full forward; quantized serving agrees with float."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.qlinear import quantize_model_params
from repro.launch import steps as S
from repro.models import model as M
from repro.models.registry import ARCHS, SMOKES, cell_plan
from repro.models.schema import init_params, param_count
from repro.models.schema_builder import build_schema
from repro.optim.adamw import OptConfig, init_opt_state

ALL = sorted(SMOKES)

# heavy smoke archs (deep scans / MoE dispatch / SSD hybrids): several
# compile-minutes each -> excluded from the CI fast job via @slow
SLOW_ARCHS = {"jamba-v0.1-52b", "deepseek-v3-671b", "deepseek-moe-16b",
              "gemma3-27b"}


def _mark_slow(names):
    return [pytest.param(n, marks=pytest.mark.slow) if n in SLOW_ARCHS
            else n for n in names]



def _batch(cfg: ModelConfig, b=2, s=24, key=0, train=True):
    k = jax.random.PRNGKey(key)
    out = {}
    if cfg.family == "encoder":
        out["frames"] = jax.random.normal(
            k, (b, s, cfg.d_model)).astype(cfg.cdtype)
        tgt = s
    elif cfg.family == "vlm":
        out["patches"] = jax.random.normal(
            k, (b, cfg.n_prefix, cfg.d_model)).astype(cfg.cdtype)
        out["tokens"] = jax.random.randint(k, (b, s - cfg.n_prefix), 0,
                                           cfg.vocab)
        tgt = s - cfg.n_prefix
    else:
        out["tokens"] = jax.random.randint(k, (b, s), 0, cfg.vocab)
        tgt = s
    if train:
        out["targets"] = jax.random.randint(k, (b, tgt), 0, cfg.vocab)
    return out


@pytest.mark.parametrize("name", _mark_slow(ALL))
def test_smoke_forward(name):
    cfg = SMOKES[name]
    params = init_params(build_schema(cfg), jax.random.PRNGKey(0))
    batch = _batch(cfg, train=False)
    logits = M.forward(cfg, params, batch)
    b = 2
    s = 24 if cfg.family != "vlm" else 24
    assert logits.shape == (b, s, cfg.vocab)
    assert not np.isnan(np.asarray(logits, dtype=np.float32)).any()


@pytest.mark.parametrize("name", _mark_slow(ALL))
def test_smoke_train_step(name):
    cfg = SMOKES[name]
    params = init_params(build_schema(cfg), jax.random.PRNGKey(0))
    ocfg = OptConfig(warmup_steps=1, total_steps=4)
    step = jax.jit(S.make_train_step(cfg, ocfg,
                                     S.TrainKnobs(microbatch=0, ce_chunk=8)))
    state = S.TrainState(params, init_opt_state(params, ocfg))
    batch = _batch(cfg)
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))
    state, m2 = step(state, batch)
    assert float(m2["loss"]) < float(m["loss"]) + 1.0  # no blow-up


@pytest.mark.parametrize("name", _mark_slow(
    [n for n in ALL if SMOKES[n].family not in ("encoder",)]))
def test_smoke_decode_consistency(name):
    """prefill + decode == forward on the extended sequence (tight KV)."""
    cfg = SMOKES[name].replace(kv_bits=8)
    params = init_params(build_schema(cfg), jax.random.PRNGKey(0))
    B, Ss, NEW = 2, 16, 3
    batch = _batch(cfg, b=B, s=Ss, train=False)
    toks = batch.get("tokens")
    lg_pre, cache = M.prefill(cfg, params, batch, max_len=Ss + NEW)
    new = jax.random.randint(jax.random.PRNGKey(5), (B, NEW), 0, cfg.vocab)
    ext = dict(batch)
    ext["tokens"] = jnp.concatenate([toks, new], 1)
    ref = M.forward(cfg, params, ext)
    outs = [lg_pre]
    for t in range(NEW):
        pos = jnp.full((B,), Ss + t, jnp.int32)
        lg, cache = M.decode_step(cfg, params, cache, new[:, t], pos)
        outs.append(lg)
    dec = jnp.stack(outs, 1).astype(jnp.float32)
    refl = ref[:, Ss - 1:Ss + NEW].astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(dec - refl)) /
                (jnp.max(jnp.abs(refl)) + 1e-9))
    # loose: bf16 step-vs-batch accumulation differences compound through
    # MoE top-k routing and SSD state updates (jamba sits near the line,
    # and the exact value shifts with the XLA version)
    assert rel < 0.12, rel


def test_scale_embed_set_exactly_for_gemma_configs():
    """The sqrt(d_model) embedding scale is a config field, not a test of
    the model's name: the registry sets it for gemma3-27b and paligemma-3b
    only, and a smoke decode with it set differs from one with it
    cleared."""
    assert sorted(n for n, c in ARCHS.items() if c.scale_embed) == [
        "gemma3-27b", "paligemma-3b"]
    assert all(SMOKES[n].scale_embed == ARCHS[n].scale_embed for n in ARCHS)
    cfg = SMOKES["paligemma-3b"].replace(kv_bits=8)
    params = init_params(build_schema(cfg), jax.random.PRNGKey(0))
    B, S = 2, 12
    batch = _batch(cfg, b=B, s=S, train=False)
    token = jnp.full((B,), 7, jnp.int32)

    def smoke_decode(c):
        _, cache = M.prefill(c, params, batch, max_len=S + 1)
        lg, _ = M.decode_step(c, params, cache, token,
                              jnp.full((B,), S, jnp.int32))
        return np.asarray(lg, np.float32)

    scaled = smoke_decode(cfg)
    plain = smoke_decode(cfg.replace(scale_embed=False))
    assert np.isfinite(scaled).all() and np.isfinite(plain).all()
    assert np.abs(scaled - plain).max() > 1e-2


@pytest.mark.parametrize("name", _mark_slow(
    ["granite-8b", "deepseek-moe-16b", "jamba-v0.1-52b", "mamba2-2.7b"]))
def test_smoke_sparqle_serving(name):
    """SPARQLe-served forward: close to float where the architecture
    permits, and ALWAYS exactly equal to the dense-quantized mode (the
    decomposition identity at model level)."""
    cfg = SMOKES[name]
    params = init_params(build_schema(cfg), jax.random.PRNGKey(0))
    qp = quantize_model_params(params, w_bits=cfg.w_bits, tile_k=16)
    batch = _batch(cfg, train=False)
    lf = M.forward(cfg, params, batch).astype(jnp.float32)
    lq = M.forward(cfg, qp, batch).astype(jnp.float32)
    assert not np.isnan(np.asarray(lq)).any()
    cos = float((lf * lq).sum() /
                (jnp.linalg.norm(lf) * jnp.linalg.norm(lq) + 1e-9))
    if cfg.family == "hybrid":
        # random-init SSD recurrence + router flips amplify W4A8 error
        # (the paper's §3.2 error-propagation caveat); trained-model
        # accuracy is covered by benchmarks/bench_accuracy.py
        assert cos > 0.5, cos
    else:
        assert cos > 0.9, cos
    # decomposition identity: sparqle mode == dense quantized mode
    qp_dense = quantize_model_params(params, w_bits=cfg.w_bits,
                                     tile_k=16, mode="dense")
    ld = M.forward(cfg, qp_dense, batch).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                               rtol=2e-2, atol=2e-2)


def test_full_config_param_counts():
    """The FULL configs hit their nominal parameter counts (structure is
    faithful to the assignment table) — via schema, no allocation."""
    expected = {
        "starcoder2-3b": (2.8, 3.6), "granite-8b": (7.0, 9.0),
        "gemma3-27b": (24, 30), "yi-6b": (5.5, 6.6),
        "hubert-xlarge": (0.8, 1.1), "jamba-v0.1-52b": (47, 56),
        "deepseek-v3-671b": (640, 700), "deepseek-moe-16b": (15, 18),
        "paligemma-3b": (2.2, 3.2), "mamba2-2.7b": (2.4, 3.0),
    }
    for name, (lo, hi) in expected.items():
        n = param_count(build_schema(ARCHS[name])) / 1e9
        assert lo <= n <= hi, (name, n)


def test_cell_plan_covers_40():
    total = runs = 0
    for name in ARCHS:
        for _, ok, why in cell_plan(name):
            total += 1
            runs += ok
            if not ok:
                assert why
    assert total == 40 and runs == 32


@pytest.mark.parametrize("name", ALL)
def test_stage_plans_cover_layers(name):
    from repro.models.stages import build_stages, total_layers
    cfg = ARCHS[name]
    assert total_layers(build_stages(cfg)) == cfg.n_layers
