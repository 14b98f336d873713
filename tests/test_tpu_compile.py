"""Compile rehearsal for TPU v5e, without a chip.

The TPU compiler is installed on the CPU hosts that run these tests; it
compiles for a described v5e and refuses what the chip would refuse
(block shapes off the (8, 128) tiling, scalars outside SMEM, programs too
large for HBM) — none of which interpret-mode tests can see. Each test
compiles one main-path program at granite-8b widths (B=8, KVH=8, G=4,
hd=128, 16-token pages) and checks that the Pallas kernel is really
there (``tpu_custom_call``), i.e. that the platform-dependent launch in
``repro.kernels.pallas_call`` picked the compiled kernel for TPU, and
that the kernel's instruction carries the kernel's own name, which is
how a device trace names it.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under several test workers only the one given this file loads it.
Keep these tests in this one file for the same reason.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, KVH, G, HD, PS = 8, 8, 4, 128, 16
N_PAGES, N_STEPS = 65, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool_args(dev, width=HD // 2, n_pages=N_PAGES):
    vals = _sds(dev, (n_pages, PS, KVH, width), jnp.int8)
    scales = _sds(dev, (n_pages, PS, KVH), jnp.float32)
    return vals, scales, vals, scales


def _assert_kernel(compiled, name):
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"%{name}" in text, name


def test_paged_decode_attention_compiles(one_chip):
    from repro.kernels.kv_attention import kv4_paged_decode_attention
    _assert_kernel(kv4_paged_decode_attention.lower(
        _sds(one_chip, (B, KVH, G, HD), jnp.bfloat16), *_pool_args(one_chip),
        _sds(one_chip, (B, N_STEPS), jnp.int32),
        _sds(one_chip, (B,), jnp.int32)).compile(),
        "kv4_paged_decode_attention")


def test_paged_verify_attention_compiles(one_chip):
    from repro.kernels.kv_attention import kv4_paged_verify_attention
    _assert_kernel(kv4_paged_verify_attention.lower(
        _sds(one_chip, (B, 3, KVH, G, HD), jnp.bfloat16),
        *_pool_args(one_chip), _sds(one_chip, (B, N_STEPS), jnp.int32),
        _sds(one_chip, (B,), jnp.int32)).compile(),
        "kv4_paged_verify_attention")


def test_tiered_paged_decode_attention_compiles(one_chip):
    from repro.kernels.kv_attention import kv_tiered_paged_decode_attention
    tables = _sds(one_chip, (B, N_STEPS), jnp.int32)
    _assert_kernel(kv_tiered_paged_decode_attention.lower(
        _sds(one_chip, (B, KVH, G, HD), jnp.bfloat16), *_pool_args(one_chip),
        *_pool_args(one_chip, width=HD // 4, n_pages=9), tables, tables,
        _sds(one_chip, (B,), jnp.int32)).compile(),
        "kv_tiered_paged_decode_attention")


def test_contiguous_decode_attention_compiles(one_chip):
    from repro.kernels.kv_attention import kv4_decode_attention
    s = N_STEPS * PS
    vals = _sds(one_chip, (B, s, KVH, HD // 2), jnp.int8)
    scales = _sds(one_chip, (B, s, KVH), jnp.float32)
    _assert_kernel(kv4_decode_attention.lower(
        _sds(one_chip, (B, KVH, G, HD), jnp.bfloat16), vals, scales, vals,
        scales, _sds(one_chip, (B,), jnp.int32), bs=PS).compile(),
        "kv4_decode_attention")


def test_engine_decode_step_compiles_at_granite_width(one_chip):
    """The whole paged decode step, granite-8b widths cut to 2 layers,
    served W4 weights as abstract shapes: it compiles, carries the
    kernel, and fits the chip's 16 GB."""
    from repro.core.qlinear import quantize_model_params
    from repro.launch import steps as S
    from repro.models.qschema import tree_abstract
    from repro.models.registry import get_config
    from repro.models.schema import init_params
    from repro.models.schema_builder import build_schema
    from repro.serving.kv_pool import PoolConfig, pool_schema

    cfg = get_config("granite-8b").replace(n_layers=2)
    params = jax.eval_shape(
        lambda k: quantize_model_params(init_params(build_schema(cfg), k),
                                        w_bits=4, tile_k=128),
        jax.random.PRNGKey(0))
    pool = tree_abstract(pool_schema(
        cfg, PoolConfig(n_pages=N_PAGES, page_size=PS)))
    place = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: _sds(one_chip, a.shape, a.dtype), t)
    step = jax.jit(S.make_engine_decode(cfg), donate_argnums=(1,))
    compiled = step.lower(
        place(params), place(pool), _sds(one_chip, (B,), jnp.int32),
        _sds(one_chip, (B,), jnp.int32),
        _sds(one_chip, (B, N_STEPS), jnp.int32)).compile()
    _assert_kernel(compiled, "kv4_paged_decode_attention")
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    # packed W4 weights reach the dots as their two nibble planes: no
    # int8 weight of an unpacked (K, N) shape, nor the (K/2, 2, N) stack
    # that interleaves the planes back into one, is built
    text = compiled.as_text()
    d, kv, ff = cfg.d_model, cfg.n_kv_heads * cfg.hd, cfg.d_ff
    for k, n in ((d, ff), (ff, d), (d, d), (d, kv)):
        for dims in (f"{k},{n}", f"{k // 2},2,{n}"):
            assert not re.search(rf"s8\[(\d+,)*{dims}\]", text), dims
