"""The reader of ``paged_attn_skip_share``: on hand-made ``decode_batch``
spans, and through a traced run of a cell at smoke size on the CPU."""
import pytest

from bench.metrics import paged_attn_skip_share
from bench.tests.smoke import run_smoke


def _batch(pages, steps=3072):
    return ["decode_batch", 0.0, 1.0,
            {"slots": 16, "attn_pages": pages, "attn_page_steps": steps}]


def test_skip_share_reads_the_decode_batch_page_counts():
    """Pages read over grid steps, summed over the steps first; a
    ``decode_batch`` without the counts (a program that records none)
    is left out, and one with no grid step reads nothing."""
    spans = [_batch(313), _batch(329),
             ["decode_batch", 1.0, 2.0, {"slots": 16}]]
    assert paged_attn_skip_share.read({"spans": spans}) == pytest.approx(
        100.0 * (1.0 - 642 / 6144))
    assert paged_attn_skip_share.read({"spans": [_batch(16, 0)]}) is None


@pytest.mark.parametrize("spans", [
    [],
    [["engine_step", 0.0, 3.0, {"step": 0}],
     ["decode_batch", 0.1, 2.9, {"slots": 16}]],
], ids=["no_spans", "spans_without_page_counts"])
def test_skip_share_finds_nothing_without_the_page_counts(spans):
    """A program whose ``decode_batch`` carries no page counts (or that
    records no span) gives no reading, and no error."""
    assert paged_attn_skip_share.read({"spans": spans}) is None


def test_traced_smoke_run_reports_the_skip_share():
    """A traced run at smoke size reports the share: the smoke contexts
    fill a few of the table's columns, so some but not all are skipped."""
    res = run_smoke("granite-8b.codegen", 5, trace=True)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert 0.0 < got["paged_attn_skip_share"] < 100.0, got
    assert res["correct"], res["check"]
