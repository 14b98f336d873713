"""Quantization substrate: W4/W2/A8/KV4 + the qlinear dispatch layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # degrade to seeded fixed examples
    from _hypothesis_fallback import given, settings, st

from repro.core.qlinear import (SparqleLinear, expert_linear, linear,
                                msb_skip_scope, quantize_leaf,
                                quantize_model_params)
from repro.core.quantize import (fake_quantize, quantize_activations,
                                 quantize_kv, quantize_weights)
from repro.core.sparqle import subprecision_sparsity


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_weight_quant_range_and_error(bits):
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.3
    qt = quantize_weights(w, bits=bits, axis=0)
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    q = np.asarray(qt.q)
    assert q.min() >= lo and q.max() <= hi
    err = np.abs(np.asarray(qt.dequantize()) - np.asarray(w))
    # error bounded by half a quantization step per channel
    step = np.asarray(qt.scale)
    assert (err <= 0.5 * step + 1e-6).all()


def test_activation_quant_per_token_scales():
    x = jnp.stack([jnp.ones(16) * 0.1, jnp.ones(16) * 100.0])
    qt = quantize_activations(x, bits=8, per_token=True)
    assert qt.scale.shape == (2, 1)
    np.testing.assert_allclose(np.asarray(qt.dequantize()), np.asarray(x),
                               rtol=0.02)


def test_zero_point_adjustment_boosts_sparsity():
    """Paper §3.1: zero-point shift moves non-centered (SiLU-like)
    activations into the MSB4==0 range."""
    x = jax.nn.silu(jax.random.normal(jax.random.PRNGKey(1), (128, 128)) * 2)
    q_sym = quantize_activations(x, zero_point=False).q
    q_zp = quantize_activations(x, zero_point=True).q
    assert float(subprecision_sparsity(q_zp)) > \
        float(subprecision_sparsity(q_sym))


def test_kv4_roundtrip_error():
    kv = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 8, 32))
    qt = quantize_kv(kv, bits=4)
    err = np.abs(np.asarray(qt.dequantize()) - np.asarray(kv))
    rel = err.max() / np.abs(np.asarray(kv)).max()
    assert rel < 0.2


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([2, 4, 8]))
def test_property_quant_monotone(seed, bits):
    """Quantization preserves per-channel ordering up to one step."""
    w = jax.random.normal(jax.random.PRNGKey(seed), (32,)) * 2
    qt = quantize_weights(w.reshape(-1, 1), bits=bits, axis=0)
    deq = np.asarray(qt.dequantize()).ravel()
    worig = np.asarray(w)
    order = np.argsort(worig)
    assert (np.diff(deq[order]) >= -float(qt.scale.max()) - 1e-6).all()


def test_fake_quantize_shape_dtype():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 8, 16))
    y = fake_quantize(x, bits=8)
    assert y.shape == x.shape


# ---------------------------------------------------------------------------
# qlinear dispatch
# ---------------------------------------------------------------------------

def test_linear_float_and_quantized_agree():
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(5), (64, 32)) * 0.2
    sl = quantize_leaf(w, w_bits=4, enable_clipping=False)
    yf = linear(x, w)
    yq = linear(x, sl)
    cos = float((yf * yq).sum() /
                (jnp.linalg.norm(yf) * jnp.linalg.norm(yq)))
    assert cos > 0.98


def test_sparqle_mode_equals_dense_mode():
    """Decomposition is exact: sparqle and dense served modes agree."""
    x = jax.random.normal(jax.random.PRNGKey(6), (8, 64))
    w = jax.random.normal(jax.random.PRNGKey(7), (64, 32)) * 0.2
    sls = quantize_leaf(w, mode="sparqle", enable_clipping=False)
    sld = quantize_leaf(w, mode="dense", enable_clipping=False)
    np.testing.assert_allclose(np.asarray(linear(x, sls)),
                               np.asarray(linear(x, sld)), rtol=1e-5)


def test_expert_linear_quantized():
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 6, 32))   # (E, C, K)
    w = jax.random.normal(jax.random.PRNGKey(9), (4, 32, 16)) * 0.2
    sl = quantize_leaf(w, w_bits=4, enable_clipping=False)
    yf = expert_linear(x, w)
    yq = expert_linear(x, sl)
    cos = float((yf * yq).sum() /
                (jnp.linalg.norm(yf) * jnp.linalg.norm(yq)))
    assert cos > 0.98


@pytest.mark.parametrize(
    "mode,wire_format,msb_skip,batched,k", [
        ("sparqle", "unpacked", False, False, 256),
        ("sparqle", "unpacked", False, False, 200),
        ("sparqle", "packed", False, False, 200),
        ("sparqle", "unpacked", True, False, 200),
        ("sparqle", "packed", True, False, 96),
        ("dense", "unpacked", False, False, 200),
        ("sparqle", "unpacked", False, True, 200),
        ("sparqle", "packed", True, True, 96),
        ("dense", "unpacked", False, True, 96),
    ])
def test_packed_weight_dots_equal_unpacked_weight_bit_for_bit(
        mode, wire_format, msb_skip, batched, k):
    """A packed leaf's dots over the two nibble planes give exactly the
    output of the same leaf served with its unpacked int8 weight (K not a
    multiple of 128 included)."""
    rng = np.random.RandomState(k + 7 * batched)
    lead = (3, 5) if batched else (5,)
    x = rng.randn(*lead, k).astype(np.float32)
    x[..., ::9] *= 6.0                  # outliers: a populated MSB4 plane
    w = rng.randn(*lead[:-1], k, 24).astype(np.float32)
    sl = quantize_leaf(jnp.asarray(w), mode=mode, wire_format=wire_format,
                       tile_k=8)
    assert sl.packed
    ref_sl = dataclasses.replace(
        sl, w=dataclasses.replace(sl.w, q=sl.unpacked_q()), packed=False)
    apply = expert_linear if batched else linear
    with msb_skip_scope(msb_skip):
        got = apply(jnp.asarray(x), sl)
        ref = apply(jnp.asarray(x), ref_sl)
    assert got.shape == ref.shape == (*lead, 24)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("w_bits,path", [(4, "nibble_planes"),
                                         (8, "unpacked")])
def test_packed_projection_counts_one_per_layer_slice(w_bits, path):
    from repro.core.qlinear import packed_projection_counts
    params = {"stages": {"s0": {"p0": {
        "wq": jnp.ones((3, 16, 32)),                 # stacked (L,K,N)
        "moe": {"w_gate": jnp.ones((4, 16, 8))},     # experts (E,K,N)
        "ln": {"gamma": jnp.zeros((3, 16))},
    }}}, "lm_head": jnp.ones((16, 64))}
    counts = packed_projection_counts(
        quantize_model_params(params, w_bits=w_bits, tile_k=8))
    assert counts == {"nibble_planes": 0, "unpacked": 0, path: 5}


def test_quantize_model_params_structure():
    params = {
        "stages": {"s0": {"p0": {
            "wq": jnp.ones((2, 16, 32)),            # stacked (L,K,N)
            "ln": {"gamma": jnp.zeros((2, 16))},
            "moe": {"w_gate": jnp.ones((4, 16, 8)),  # experts (E,K,N)
                    "w_router": jnp.ones((16, 4))},
        }}},
        "lm_head": jnp.ones((16, 64)),
    }
    q = quantize_model_params(params, tile_k=8)
    assert isinstance(q["stages"]["s0"]["p0"]["wq"], SparqleLinear)
    # int4 payload nibble-packed two-per-byte along K
    assert q["stages"]["s0"]["p0"]["wq"].w.q.shape == (2, 8, 32)
    assert q["stages"]["s0"]["p0"]["wq"].shape == (2, 16, 32)
    assert q["stages"]["s0"]["p0"]["wq"].w.scale.shape == (2, 1, 32)
    assert isinstance(q["stages"]["s0"]["p0"]["moe"]["w_gate"],
                      SparqleLinear)
    # router and norms untouched
    assert isinstance(q["stages"]["s0"]["p0"]["moe"]["w_router"], jax.Array)
    assert isinstance(q["stages"]["s0"]["p0"]["ln"]["gamma"], jax.Array)
    assert isinstance(q["lm_head"], SparqleLinear)
