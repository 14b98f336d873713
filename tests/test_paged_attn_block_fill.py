"""The reader of ``paged_attn_block_fill``: on hand-made ``decode_batch``
spans, and the engine's ``attn_block_pages`` count it reads, at page and
block boundaries."""
import jax
import numpy as np
import pytest

from bench.metrics import paged_attn_block_fill
from repro.configs.base import ModelConfig
from repro.core.qlinear import quantize_model_params
from repro.models.schema import init_params
from repro.models.schema_builder import build_schema
from repro.serving import Engine, PoolConfig, SchedulerConfig

CFG = ModelConfig(name="tiny-serve", family="transformer", n_layers=2,
                  d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                  d_ff=64, vocab=128, dtype="float32")


def _batch(pages, block_pages):
    return ["decode_batch", 0.0, 1.0,
            {"slots": 16, "attn_pages": pages, "attn_page_steps": 3072,
             "attn_block_pages": block_pages}]


def test_block_fill_reads_the_decode_batch_page_counts():
    """Context pages over gathered page slots, summed over the steps
    first; a ``decode_batch`` without the block count (a program that
    records none) is left out, and one that gathers nothing reads
    nothing."""
    spans = [_batch(313, 384), _batch(329, 392),
             ["decode_batch", 1.0, 2.0,
              {"slots": 16, "attn_pages": 300, "attn_page_steps": 3072}]]
    assert paged_attn_block_fill.read({"spans": spans}) == pytest.approx(
        100.0 * 642 / 776)
    assert paged_attn_block_fill.read({"spans": [_batch(0, 0)]}) is None


@pytest.mark.parametrize("spans", [
    [],
    [["engine_step", 0.0, 3.0, {"step": 0}],
     ["decode_batch", 0.1, 2.9, {"slots": 16}]],
], ids=["no_spans", "spans_without_page_counts"])
def test_block_fill_finds_nothing_without_the_block_count(spans):
    """A program whose ``decode_batch`` carries no block count (or that
    records no span) gives no reading, and no error."""
    assert paged_attn_block_fill.read({"spans": spans}) is None


@pytest.fixture(scope="module")
def qparams():
    fparams = init_params(build_schema(CFG), jax.random.PRNGKey(0))
    return quantize_model_params(
        fparams, w_bits=4, k_percent=50.0, clip_l=-8.0, clip_h=23.0,
        mode="sparqle", enable_clipping=True, tile_k=16)


# (page size, table width) -> positions and the page slots of the blocks
# holding their context: 16-token pages in blocks of 8 columns (20
# columns: blocks of 8, 8 and a short 4), 8-token pages in blocks of 16
BOUNDARY_CASES = {
    (16, 20): {0: 8, 15: 8, 16: 8, 127: 8, 128: 16, 255: 16, 256: 24,
               319: 24},
    (8, 12): {0: 12, 7: 12, 95: 12},
}


@pytest.mark.parametrize("ps,width", sorted(BOUNDARY_CASES))
def test_engine_counts_block_pages_at_page_and_block_boundaries(
        qparams, ps, width):
    """``attn_block_pages`` is the page slots of the blocks holding each
    slot's context, whole blocks of ``block_pages`` columns (capped at
    the table width): it grows by a block at a block's first context
    page, not at each page, and ``serving_paged_attn_block_pages_total``
    adds the same counts."""
    from repro.kernels.kv_attention import block_pages, context_pages
    eng = Engine(CFG, qparams,
                 pool_config=PoolConfig(n_pages=64, page_size=ps),
                 sched_config=SchedulerConfig(max_pages_per_seq=width))
    n = block_pages(ps, width)
    cases = BOUNDARY_CASES[(ps, width)]
    total = 0
    for p, slots in cases.items():
        got = eng._count_attn_pages(np.array([p, 0], np.int32))
        assert got["attn_block_pages"] == slots + n, p
        assert got["attn_pages"] == context_pages(p, ps) + 1
        assert got["attn_pages"] <= got["attn_block_pages"]
        total += got["attn_block_pages"]
    assert eng.obs.registry.value(
        "serving_paged_attn_block_pages_total") == total
