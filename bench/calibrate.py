#!/usr/bin/env python3
"""Readings that a cell's check limit is set from, on the chip.

    python3 bench/calibrate.py --workload granite-8b.codegen \\
        --seeds 11,12,13 --seconds 30

Runs the cell once per seed in one process, each time as
``run.py --control 1`` runs it: the window drives the program, and the
check then reads both the program's widest logit gap (the lower reading)
and the control's, the reference at 4-bit activations in the program's
place (the upper reading), which decides ``correct``. Prints one JSON line
per seed. The limit in ``cells/<cell>.json`` goes between the largest
lower and the smallest upper reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           control=True)
        if res is None:
            return 2
        r = res["readings"]
        line = {"workload": args.workload, "seed": seed,
                "program_max_logit_gap": r["program_max_logit_gap"],
                "control_max_logit_gap": r["max_logit_gap"],
                "control_correct": res["correct"],
                "served_tokens": res["check"]["served_tokens"],
                "compiles_in_window":
                    res["check"]["compiles_in_window"]["value"],
                **{k: v for k, v in r.items() if k != "max_logit_gap"},
                **{k: v["value"] for k, v in res["metrics"].items()},
                "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
