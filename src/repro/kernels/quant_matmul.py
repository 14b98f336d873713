"""Pallas TPU kernel: dense int8 x int4 matmul — the paper's baseline.

This is the iso-MAC *dense accelerator baseline* of paper §4 (Table 1): a
standard W4A8 matmul with no sub-precision decomposition. It exists so the
benchmark harness can compare SPARQLe vs baseline at the kernel level with
identical tiling, and so the serving path has a non-SPARQLe quantized mode.

Same tiling/accumulation structure as ``sparqle_matmul`` (one int8 pass
instead of two int4 passes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call
from repro.kernels.sparqle_matmul import DEFAULT_BK, DEFAULT_BM, DEFAULT_BN


def _kernel(a_ref, w_ref, ascale_ref, wscale_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...].astype(jnp.int8), w_ref[...].astype(jnp.int8),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _drain():
        out_ref[...] = (
            acc_ref[...].astype(jnp.float32)
            * ascale_ref[...].astype(jnp.float32)
            * wscale_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def quant_matmul(
    a: jax.Array,          # (M, K) int8 activations
    w: jax.Array,          # (K, N) int8 (int4 payload)
    act_scale: jax.Array,  # (M, 1) f32
    w_scale: jax.Array,    # (1, N) f32
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
) -> jax.Array:
    m, k = a.shape
    k2, n = w.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0

    n_k = k // bk
    return pallas_call(
        functools.partial(_kernel, n_k=n_k),
        name="quant_matmul",
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, w, act_scale, w_scale)
