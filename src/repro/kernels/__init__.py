"""Pallas TPU kernels for the SPARQLe compute hot-spots.

  * sparqle_matmul   — dual-pass (LSB4 dense + PBM-gated MSB4) W4A8 matmul
  * quant_matmul     — dense int8 x int4 baseline (the paper's baseline
                       accelerator, iso-tiling)
  * sparqle_encode   — fused drain-path output quantize + decompose
  * kv_attention     — decode attention with in-VMEM unpack/dequant of the
                       packed-int4 KV cache (flash-decoding structure);
                       contiguous and paged (block-table) variants share
                       one kernel body

Each kernel ships with a pure-jnp oracle in ref.py and interpret-mode
allclose sweeps in tests/test_kernels.py; ops.py holds the jit'd public
wrappers (padding). Every kernel is launched through :func:`pallas_call`,
which picks compiled or interpreted execution from the platform the
program is lowered for — callers never choose.
"""
import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, *, name: str, **kwargs):
    """``pl.pallas_call`` whose execution mode follows the lowering platform.

    On TPU the kernel is compiled by Mosaic; on CPU (the test platform) it
    runs in the Pallas interpreter. Lowering for any other platform raises
    — there is no silent interpreter fallback on an accelerator. The choice
    is made per lowering (``jax.lax.platform_dependent``), so a program
    compiled for a described TPU from a CPU host gets the real kernel.

    ``name`` names the kernel's custom call in the compiled program, and so
    its events in a device trace; without it every kernel would take the
    name of its ``platform_dependent`` branch.
    """
    compiled = pl.pallas_call(kernel, name=name, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, name=name, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          tpu=compiled)

    return call
