"""The check that decides ``correct``, driven through the whole run at
smoke size on the CPU (the look for a chip skipped): a sound run passes,
the control fails, and so does the timed path broken underneath.

Readings at this size, 12 seeds (seeds 1-12, 2 s windows): widest logit
gap of the program's served tokens 0.023-0.113; of the control's
0.213-0.454. The limit in ``smoke.py`` lies between them."""
import jax
import numpy as np
import pytest

from bench.tests.smoke import SMOKE_LIMIT, run_smoke

CELLS = ["granite-8b.codegen"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_reads_above_the_limit(workload):
    """The control in the program's place comes out not correct through
    the same decision, on every seed."""
    limit = SMOKE_LIMIT[workload.split(".")[0]]
    for seed in (2, 5, 9):
        res = run_smoke(workload, seed, 2.0, control=True)
        assert not res["correct"], res["check"]
        gap = res["check"]["max_logit_gap"]
        assert gap["limit"] == limit and gap["value"] > limit
        assert gap["value"] > res["readings"]["program_max_logit_gap"]


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(workload):
    res = run_smoke(workload, 3)
    assert res["check"]["served_tokens"] > 0
    assert res["correct"], res["check"]


def _alter_tokens(system):
    """Every token after a request's first is the least likely one."""
    eng = system.engine
    sample = eng._sample

    def altered(req, logits):
        if req.n_generated >= 1:
            return int(np.argmin(logits))
        return sample(req, logits)
    eng._sample = altered


def _state_unchanged(system):
    """The decode step computes on a copy of the pool and returns the
    pool it was given: nothing it decodes is written."""
    eng = system.engine
    step = eng._decode_fn

    def unchanged(params, pool, *args):
        logits, _, tel = step(params, jax.tree_util.tree_map(
            lambda a: a.copy(), pool), *args)
        return logits, pool, tel
    eng._decode_fn = unchanged


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged])
@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_timed_path_is_not_correct(workload, fault):
    res = run_smoke(workload, 3, fault=fault)
    assert res["check"]["served_tokens"] > 0
    assert not res["correct"], res["check"]
