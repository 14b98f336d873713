"""A cell shrunk to a size the CPU runs in seconds, for the tests."""
import copy

from bench import run

# program gap and control gap read at this size, 12 seeds each: see
# test_check.py; the limit sits between them
SMOKE_LIMIT = {"granite-8b": 0.16}
PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11}


def smoke_mix(name: str) -> dict:
    mix = copy.deepcopy(run.mix_file(name))
    mix["prompt_tokens"].update(median=24, min=8, max=60)
    mix["output_tokens"].update(median=24, min=16, max=32)
    mix["max_context"] = 92
    mix["arrivals"].update(burst=4)
    mix["engine"].update(decode_slots=4, page_size=8, prefill_chunk=16)
    mix["check"].update(sample_requests=4)
    mix["window"].update(max_warmup_s=30)
    return mix


def run_smoke(workload: str, seed: int, seconds: float = 3.0,
              trace: bool = False, fault=None, control: bool = False):
    config = workload.split(".")[0]
    return run.run_cell(
        workload, seed, seconds, trace, require_tpu=False, smoke=True,
        overrides={"mix": smoke_mix(workload.split(".")[1]),
                   "cell": {"max_logit_gap": {"limit": SMOKE_LIMIT[config]}},
                   "peaks": PEAKS},
        fault=fault, control=control)
