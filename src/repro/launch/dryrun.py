import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run (deliverable e): lower + compile every cell.

For every (architecture x assigned input shape) cell and both production
meshes (single-pod 16x16, multi-pod 2x16x16), this driver builds abstract
inputs (ShapeDtypeStructs — zero allocation), jits the right step function
with explicit in/out shardings, lowers, compiles, and records:

  * ``compiled.memory_analysis()``  — per-device bytes (does it fit?),
  * ``compiled.cost_analysis()``    — HLO FLOPs / bytes for the roofline,
  * collective bytes parsed from the optimized HLO (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute),

into ``runs/dryrun/<mesh>/<arch>__<shape>.json``, which
``benchmarks/roofline.py`` consumes.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multipod --list
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
from jax.sharding import NamedSharding

from repro.configs.base import SHAPES
from repro.distributed.sharding import mesh_context, spec_for
from repro.launch import steps as S
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_production_mesh
from repro.models.registry import (cell_plan, get_config, input_specs,
                                   runnable_cells)
from repro.optim.adamw import OptConfig

RUNS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                        "runs", "dryrun")


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, mesh, *,
               microbatch: Optional[int] = None,
               profile: str = "baseline",
               knob_overrides: Optional[Dict[str, Any]] = None):
    """Build + lower + compile one cell. Returns (record, compiled)."""
    from repro.distributed.sharding import profile_rules

    cfg = get_config(arch)
    shp = SHAPES[shape_name]
    kn: Dict[str, Any] = dict(knob_overrides or {})
    rules = profile_rules(profile, cfg, shp.kind, mesh,
                          global_batch=shp.global_batch)
    rules.update(kn.pop("rules", {}))
    t0 = time.time()

    with mesh_context(mesh, rules=rules):
        if shp.kind == "train":
            # tuned: MoE archs take larger microbatches (fewer accumulation
            # steps -> fewer per-ubatch expert-weight gathers + grad psums)
            default_mb = 64 if (profile == "tuned" and cfg.n_experts) else 32
            mb = microbatch if microbatch is not None else kn.pop(
                "microbatch", default_mb)
            knobs = S.TrainKnobs(microbatch=mb, **kn)
            ocfg = OptConfig()
            step = S.make_train_step(cfg, ocfg, knobs)
            st_schema = S.train_state_schema(cfg)
            st_abs, st_shard = S.abstract_and_shardings(st_schema, mesh)
            batch_abs = input_specs(cfg, shp, "train")
            b_shard = S.batch_shardings(batch_abs, mesh)
            jitted = jax.jit(step, in_shardings=(st_shard, b_shard),
                             donate_argnums=0)
            lowered = jitted.lower(st_abs, batch_abs)

        elif shp.kind == "prefill":
            pschema = S.serve_param_schema(cfg)
            p_abs, p_shard = S.abstract_and_shardings(pschema, mesh)
            batch_abs = input_specs(cfg, shp, "prefill")
            b_shard = S.batch_shardings(batch_abs, mesh)
            step = S.make_serve_prefill(cfg, max_len=shp.seq_len)
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(p_abs, batch_abs)

        else:  # decode
            pschema = S.serve_param_schema(cfg)
            p_abs, p_shard = S.abstract_and_shardings(pschema, mesh)
            c_abs, c_shard = S.cache_abstract_and_shardings(
                cfg, shp.global_batch, shp.seq_len, mesh)
            tok_abs = input_specs(cfg, shp, "decode")
            tp_shard = {
                k: NamedSharding(mesh, spec_for(("batch",), v.shape, mesh))
                for k, v in tok_abs.items()}
            step = S.make_serve_decode(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, c_shard, tp_shard["token"],
                              tp_shard["pos"]),
                donate_argnums=1)
            lowered = jitted.lower(p_abs, c_abs, tok_abs["token"],
                                   tok_abs["pos"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    t1 = time.time()
    st = analyze(compiled.as_text())
    t_analyze = time.time() - t1
    n_dev = mesh.devices.size

    # All numbers below are PER DEVICE: the partitioned HLO carries shard
    # shapes, and memory_analysis reports the per-device program.
    record = {
        "arch": arch,
        "shape": shape_name,
        "kind": shp.kind,
        "profile": profile,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_devices": int(n_dev),
        "flops_hlo": st.flops,                      # all dots, scan-aware
        "dot_flops_by_dtype": st.dot_flops_by_dtype,
        "hbm_bytes_hlo": st.hbm_bytes,
        "collective_bytes": st.coll_bytes,
        "collective_count": st.coll_count,
        "top_dots": [[v, k] for v, k in st.top_dots],
        "top_colls": [[v, k] for v, k in st.top_colls],
        "xla_flops": float(cost.get("flops", -1)),  # f32 ops only (CPU BE)
        "xla_bytes_accessed": float(cost.get("bytes accessed", -1)),
        "memory": {
            "argument_size_b": int(mem.argument_size_in_bytes),
            "output_size_b": int(mem.output_size_in_bytes),
            "temp_size_b": int(mem.temp_size_in_bytes),
            "generated_code_size_b": int(mem.generated_code_size_in_bytes),
        },
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "analyze_s": round(t_analyze, 1),
    }
    return record, compiled


def run_cells(cells, mesh_kind: str, out_dir: str,
              knob_overrides=None, profile: str = "baseline"
              ) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    os.makedirs(out_dir, exist_ok=True)
    results = {}
    for arch, shape_name in cells:
        key = f"{arch}__{shape_name}"
        path = os.path.join(out_dir, key + ".json")
        try:
            rec, compiled = lower_cell(arch, shape_name, mesh,
                                       profile=profile,
                                       knob_overrides=knob_overrides)
            del compiled
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            per_dev = (rec["memory"]["argument_size_b"]
                       + rec["memory"]["temp_size_b"])
            print(f"OK   {mesh_kind:9s} {key:42s} "
                  f"flops/dev={rec['flops_hlo']:.3e} "
                  f"coll/dev={rec['collective_bytes'].get('total', 0):.3e}B "
                  f"mem/dev={per_dev/2**30:.2f}GiB "
                  f"compile={rec['compile_s']}s", flush=True)
            results[key] = rec
        except Exception as e:  # noqa: BLE001 — report, continue, fail at end
            print(f"FAIL {mesh_kind:9s} {key}: {e}", flush=True)
            traceback.print_exc()
            results[key] = {"error": str(e)}
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["singlepod", "multipod", "both"])
    ap.add_argument("--profile", default="baseline",
                    choices=["baseline", "tuned"])
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default=RUNS_DIR)
    args = ap.parse_args()

    cells = runnable_cells()
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]
    if args.list:
        for arch, s in cells:
            print(arch, s)
        for arch in sorted({a for a, _ in runnable_cells()}):
            for sname, runs, why in cell_plan(arch):
                if not runs:
                    print(f"SKIP {arch} {sname}: {why}")
        return

    meshes = (["singlepod", "multipod"] if args.mesh == "both"
              else [args.mesh])
    n_fail = 0
    for mk in meshes:
        sub = mk if args.profile == "baseline" else f"{mk}-{args.profile}"
        res = run_cells(cells, mk, os.path.join(args.out, sub),
                        profile=args.profile)
        n_fail += sum(1 for r in res.values() if "error" in r)
    print(f"\ndry-run complete; {n_fail} failures")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
