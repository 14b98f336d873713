"""The leaf-by-leaf served-tree builder reproduces init-then-quantize.

``qschema.init_served_params`` builds a served W4 tree without the float
tree ever existing (one jitted init -> quantize program per leaf, one
layer slice per call for stacked leaves). It must give exactly what
``quantize_model_params(init_params(...))`` gives, so that a full-width
model served on a chip is the same model the small-size tests check.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.qlinear import quantize_model_params
from repro.models.qschema import init_served_params
from repro.models.registry import get_config
from repro.models.schema import (ParamSpec, init_layer, init_leaf,
                                 init_params)
from repro.models.schema_builder import build_schema


@pytest.mark.parametrize("arch", ["granite-8b", "deepseek-moe-16b"])
def test_served_builder_equals_init_then_quantize(arch):
    """Bit-equal trees at smoke size: a plain GQA stack, and a stack with
    routed (layer x expert)-batched and shared-expert projections."""
    cfg = get_config(arch, smoke=True)
    schema = build_schema(cfg)
    key = jax.random.PRNGKey(3)
    kw = dict(w_bits=cfg.w_bits, k_percent=50.0, tile_k=16)
    ref = quantize_model_params(init_params(schema, key), **kw)
    got = init_served_params(schema, key, **kw)
    ref_leaves, ref_def = jax.tree_util.tree_flatten(ref)
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    assert got_def == ref_def
    for r, g in zip(ref_leaves, got_leaves):
        assert r.shape == g.shape and r.dtype == g.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


@pytest.mark.parametrize("shape", [(3, 64, 48), (2, 4, 5, 7), (5, 33, 17)])
def test_init_layer_is_a_slice_of_init_leaf(shape):
    spec = ParamSpec(shape, ("layers",) + (None,) * (len(shape) - 1))
    key = jax.random.PRNGKey(7)
    full = np.asarray(init_leaf(key, spec))
    one = jax.jit(lambda k, i: init_layer(k, spec, i))
    for i in range(shape[0]):
        np.testing.assert_array_equal(np.asarray(one(key, jnp.int32(i))),
                                      full[i])



def test_init_layer_rejects_draws_past_32_bit_counters():
    spec = ParamSpec((2, 2 ** 16, 2 ** 16), ("layers", None, None))
    with pytest.raises(ValueError, match="2\\*\\*32"):
        init_layer(jax.random.PRNGKey(0), spec, 0)
