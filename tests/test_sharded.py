"""Sharded-vs-single-device bit-exactness at the kernel/op level.

The tensor-parallel serving path (distributed/tp.py, docs/sharding.md)
claims BIT-exact equality with the single-device kernels, not closeness:
column partitions compute untouched output slices, and row partitions
reduce the merged int32 dual-pass accumulator with one psum (integer
addition is associative) after an exact global pmax for the per-token
scale. Every test here asserts array_equal, never allclose.

All tests take the `mesh` fixture and skip when the host exposes too few
devices; the CI `test-multidevice` lane runs them on 8 forced CPU
devices.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.quantize import quantize_weights
from repro.kernels.ops import sparqle_linear, sparqle_linear_sharded
from repro.kernels.sparqle_encode import sparqle_encode
from repro.kernels.sparqle_matmul import sparqle_matmul


def _operands(m=8, k=64, n=32, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(m, k).astype(np.float32))
    w = quantize_weights(jnp.asarray(rng.randn(k, n).astype(np.float32)),
                         bits=4, axis=0)
    mask = jnp.asarray(rng.rand(k) < 0.5)
    return x, w, mask


@pytest.mark.parametrize("ways", [2, 4])
@pytest.mark.parametrize("wire_format", ["unpacked", "packed"])
@pytest.mark.parametrize("msb_skip", [False, True])
@pytest.mark.parametrize("partition", ["col", "row"])
def test_sparqle_linear_sharded_bit_exact(mesh, ways, wire_format,
                                          msb_skip, partition):
    """Both wire formats and the msb_skip draft dispatch, col and row
    partitioned 2- and 4-way, against the unsharded Pallas kernel."""
    m = mesh(model=ways)
    x, w, col_mask = _operands()
    kw = dict(col_mask=col_mask, clip_l=jnp.float32(-8.0),
              clip_h=jnp.float32(23.0), wire_format=wire_format,
              msb_skip=msb_skip, bm=8, bn=8, bk=16)
    ref = sparqle_linear(x, w, **kw)
    got = sparqle_linear_sharded(x, w, mesh=m, axis="model",
                                 partition=partition, **kw)
    assert got.shape == ref.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("ways", [2, 4])
@pytest.mark.parametrize("mode,wire_format", [
    ("sparqle", "unpacked"), ("sparqle", "packed"), ("dense", "unpacked")])
def test_packed_row_parallel_linear_bit_exact(mesh, ways, mode,
                                              wire_format):
    """``linear(..., tp="row")`` on a packed leaf, K sharded in packed
    rows: each shard's nibble-plane dots pair its own even/odd activation
    columns, and the psum'd output equals the single-device output of
    the same leaf with its unpacked int8 weight."""
    from repro.core.qlinear import linear, quantize_leaf
    from repro.distributed import tp
    m = mesh(model=ways)
    rng = np.random.RandomState(5)
    x = rng.randn(6, 128).astype(np.float32)
    x[:, ::9] *= 6.0
    sl = quantize_leaf(jnp.asarray(rng.randn(128, 24).astype(np.float32)),
                       mode=mode, wire_format=wire_format, tile_k=16)
    assert sl.packed
    unpacked = dataclasses.replace(
        sl, w=dataclasses.replace(sl.w, q=sl.unpacked_q()), packed=False)
    ref = linear(jnp.asarray(x), unpacked)

    def body(xs, w):
        with tp.tp_scope("model", ways):
            return linear(xs, w, tp="row")

    fn = jax.shard_map(
        body, mesh=m, check_vma=False,
        in_specs=(P(None, "model"), tp.param_pspecs({"wo": sl})["wo"]),
        out_specs=P(None, None))
    got = fn(jnp.asarray(x), sl)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_sparqle_linear_sharded_no_clipping(mesh):
    m = mesh(model=2)
    x, w, _ = _operands(seed=3)
    ref = sparqle_linear(x, w, bm=8, bn=8, bk=16)
    got = sparqle_linear_sharded(x, w, mesh=m, partition="row",
                                 bm=8, bn=8, bk=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_row_sharded_is_single_psum_of_merged_acc(mesh):
    """The row partition reduces ONE merged int32 accumulator: kernel
    acc_out (LSB + shifted MSB already summed) psum'd across shards must
    reproduce the full-K accumulator bit for bit."""
    from repro.core.sparqle import encode, tile_population
    m = mesh(model=2)
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randint(-128, 128, (8, 32)), jnp.int8)
    wq = jnp.asarray(rng.randint(-8, 8, (32, 16)), jnp.int8)
    ones_a = jnp.ones((8, 1), jnp.float32)
    ones_w = jnp.ones((1, 16), jnp.float32)

    def full_acc(qv, wv):
        act = encode(qv, 1.0)
        pop = tile_population(act.pbm, 8, 16)
        return sparqle_matmul(act.lsb4, act.msb4, pop, wv, ones_a, ones_w,
                              bm=8, bn=16, bk=16, acc_out=True)

    ref = full_acc(q, wq)
    assert ref.dtype == jnp.int32

    def body(qv, wv):
        return jax.lax.psum(full_acc(qv, wv), "model")

    fn = jax.shard_map(
        body, mesh=m, check_vma=False, in_specs=(P(None, "model"),
                                             P("model", None)),
                          out_specs=P(None, None))
    np.testing.assert_array_equal(np.asarray(fn(q, wq)), np.asarray(ref))


def test_acc_out_matches_rescaled_output():
    """acc_out * scales == the kernel's own drain-path rescale (runs on
    any device count — no mesh needed)."""
    from repro.core.sparqle import encode, tile_population
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randint(-128, 128, (8, 32)), jnp.int8)
    wq = jnp.asarray(rng.randint(-8, 8, (32, 16)), jnp.int8)
    asc = jnp.asarray(np.abs(rng.randn(8, 1)) + 0.1, jnp.float32)
    wsc = jnp.asarray(np.abs(rng.randn(1, 16)) + 0.1, jnp.float32)
    act = encode(q, 1.0)
    pop = tile_population(act.pbm, 8, 16)
    out = sparqle_matmul(act.lsb4, act.msb4, pop, wq, asc, wsc,
                         bm=8, bn=16, bk=16)
    acc = sparqle_matmul(act.lsb4, act.msb4, pop, wq, asc, wsc,
                         bm=8, bn=16, bk=16, acc_out=True)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(acc.astype(jnp.float32) * asc * wsc))


@pytest.mark.parametrize("ways", [2, 4])
def test_sparqle_encode_sharded_rows_bit_exact(mesh, ways):
    """The drain-path encoder is per-row: sharding M over the mesh must
    reproduce every plane and tile population exactly."""
    m = mesh(model=ways)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 32).astype(np.float32))
    scale = jnp.asarray(np.abs(rng.randn(16, 1)) + 0.05, jnp.float32)
    ref = sparqle_encode(x, scale, bm=4, bk=32)

    def body(xv, sv):
        return sparqle_encode(xv, sv, bm=4, bk=32)

    fn = jax.shard_map(
        body, mesh=m, check_vma=False,
        in_specs=(P("model", None), P("model", None)),
        out_specs=(P("model", None), P("model", None), P("model", None),
                   P("model", None)))
    got = fn(x, scale)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


@pytest.mark.parametrize("ways", [2, 4])
def test_paged_decode_attention_kv_head_sharded(mesh, ways):
    """kv4_paged_decode_attention with KV heads sharded over the model
    axis: every shard runs the identical flash-decoding body on its head
    slice, so the assembled output is bit-exact."""
    from repro.kernels.kv_attention import kv4_paged_decode_attention
    m = mesh(model=ways)
    b, kvh, g, hd, npages, ps, nsteps = 2, 4, 2, 8, 6, 4, 3
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, kvh, g, hd), jnp.float32)
    kq = jnp.asarray(rng.randint(-128, 128, (npages, ps, kvh, hd // 2)),
                     jnp.int8)
    ks = jnp.asarray(np.abs(rng.randn(npages, ps, kvh)) + 0.1, jnp.float32)
    vq = jnp.asarray(rng.randint(-128, 128, (npages, ps, kvh, hd // 2)),
                     jnp.int8)
    vs = jnp.asarray(np.abs(rng.randn(npages, ps, kvh)) + 0.1, jnp.float32)
    bt = jnp.asarray(rng.randint(0, npages, (b, nsteps)), jnp.int32)
    pos = jnp.asarray([5, 9], jnp.int32)

    ref = kv4_paged_decode_attention(q, kq, ks, vq, vs, bt, pos)

    fn = jax.shard_map(
        kv4_paged_decode_attention, mesh=m, check_vma=False,
        in_specs=(P(None, "model"), P(None, None, "model"),
                  P(None, None, "model"), P(None, None, "model"),
                  P(None, None, "model"), P(None, None), P(None)),
        out_specs=P(None, "model"))
    np.testing.assert_array_equal(np.asarray(fn(q, kq, ks, vq, vs, bt,
                                                pos)),
                                  np.asarray(ref))


def test_paged_verify_attention_kv_head_sharded(mesh):
    """Multi-token verify attention shards the same way (window axis
    complete on every shard)."""
    from repro.kernels.kv_attention import kv4_paged_verify_attention
    m = mesh(model=2)
    b, t, kvh, g, hd, npages, ps, nsteps = 2, 3, 2, 2, 8, 6, 4, 3
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(b, t, kvh, g, hd), jnp.float32)
    kq = jnp.asarray(rng.randint(-128, 128, (npages, ps, kvh, hd // 2)),
                     jnp.int8)
    ks = jnp.asarray(np.abs(rng.randn(npages, ps, kvh)) + 0.1, jnp.float32)
    vq = jnp.asarray(rng.randint(-128, 128, (npages, ps, kvh, hd // 2)),
                     jnp.int8)
    vs = jnp.asarray(np.abs(rng.randn(npages, ps, kvh)) + 0.1, jnp.float32)
    bt = jnp.asarray(rng.randint(0, npages, (b, nsteps)), jnp.int32)
    pos = jnp.asarray([4, 7], jnp.int32)

    ref = kv4_paged_verify_attention(q, kq, ks, vq, vs, bt, pos)

    fn = jax.shard_map(
        kv4_paged_verify_attention, mesh=m, check_vma=False,
        in_specs=(P(None, None, "model"), P(None, None, "model"),
                  P(None, None, "model"), P(None, None, "model"),
                  P(None, None, "model"), P(None, None), P(None)),
        out_specs=P(None, None, "model"))
    np.testing.assert_array_equal(np.asarray(fn(q, kq, ks, vq, vs, bt,
                                                pos)),
                                  np.asarray(ref))


def test_smoke_mesh_error_names_xla_flags():
    """make_smoke_mesh must fail with an actionable message (naming the
    XLA_FLAGS workaround), never a bare jax shape error."""
    import jax as _jax
    from repro.launch.mesh import make_smoke_mesh
    too_many = len(_jax.devices()) + 1
    with pytest.raises(RuntimeError,
                       match="xla_force_host_platform_device_count"):
        make_smoke_mesh(data=too_many, model=1)
