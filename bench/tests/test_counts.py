"""The operation and byte counts of ``bench/counts.py`` against what the
program compiles and allocates, at smoke size on the CPU."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import counts as C
from bench import run

sys.path.insert(0, os.path.join(run.ROOT, "src"))


# a non-gated GELU MLP with LayerNorm and biases, as starcoder2-3b has it:
# the counts' and the reference's other MLP path, which no configuration
# file of the benchmark holds yet
GELU_SMOKE = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab": 512, "mlp": "gelu",
              "norm": "layer", "bias": True}


def _smoke(name):
    from repro.models.registry import get_config
    if name == "starcoder2-3b":
        model = GELU_SMOKE
    else:
        model = run.load_json(os.path.join(run.BENCH, "configs",
                                           f"{name}.json"))["smoke_model"]
    return get_config(name, smoke=True), model


@pytest.mark.parametrize("name", ["granite-8b", "starcoder2-3b"])
def test_prefill_chunk_ops_match_the_compiled_step(name):
    """A float-weight prefill chunk of one layer wide enough that the
    elementwise work is small, compiled (XLA counts a scanned layer's body
    once): its operations are the chunk's projections, the head of one
    position, and attention of every query over the whole block table
    (the step masks, it does not skip); the rest, under a seventh, is
    elementwise work such as casting the float weights to bf16."""
    from repro.launch import steps as S
    from repro.models.schema import init_params
    from repro.models.schema_builder import build_schema
    from repro.serving.kv_pool import PoolConfig, init_pool_state
    cfg, model = _smoke(name)
    wide = dict(n_layers=1, d_model=512, n_heads=8, n_kv_heads=2,
                head_dim=64, d_ff=2048)
    cfg, model = cfg.replace(**wide), dict(model, **wide)
    c, pmax, ps = 16, 4, 8
    params = init_params(build_schema(cfg), jax.random.PRNGKey(0))
    pool = init_pool_state(cfg, PoolConfig(n_pages=pmax + 1, page_size=ps))
    step = jax.jit(S.make_engine_prefill_chunk(cfg))
    compiled = step.lower(params, pool, jnp.zeros((1, c), jnp.int32),
                          jnp.int32(0), jnp.int32(c),
                          jnp.zeros((1, pmax), jnp.int32)).compile()
    cost = compiled.cost_analysis()
    flops = (cost[0] if isinstance(cost, list) else cost)["flops"]
    layers = model["n_layers"]
    attn_all = (4 * layers * model["n_heads"] * model["head_dim"] * c
                * (pmax * ps + c))
    dots = (c * C.linear_ops_per_token(model) + C.head_ops_per_token(model)
            + attn_all)
    assert dots <= flops < 1.15 * dots
    # what the model needs is the causal part only
    need = C.prefill_chunk_ops(model, 0, c)
    assert need < dots


@pytest.mark.parametrize("name", ["granite-8b", "starcoder2-3b"])
def test_page_bytes_match_the_program_pool(name):
    from repro.serving.kv_pool import PoolConfig, init_pool_state
    cfg, model = _smoke(name)
    n_pages, ps = 7, 8
    pool = init_pool_state(cfg, PoolConfig(n_pages=n_pages, page_size=ps))
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(pool))
    per_page = C.kv_page_bytes_per_layer(model, ps) * model["n_layers"]
    assert nbytes == n_pages * per_page
    sysmod = run.module("systems", "paged_engine")
    assert sysmod.page_bytes(model, ps) == per_page


def test_paged_attention_counts_real_context_pages():
    model = {"n_heads": 4, "n_kv_heads": 2, "head_dim": 16}
    ops, n_bytes = C.paged_attention_call(model, [1, 8, 9], page_size=8)
    assert ops == 4 * 4 * 16 * (1 + 8 + 9)
    page = C.kv_page_bytes_per_layer(model, 8)
    assert n_bytes == (1 + 1 + 2) * page + 3 * 2 * 2 * 4 * 16
    t, bound = C.least_seconds(ops, n_bytes, 1e12, 1e9)
    assert bound == "memory" and t == pytest.approx(n_bytes / 1e9)
