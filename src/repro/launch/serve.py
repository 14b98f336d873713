"""Serving driver on the SPARQLe quantized path.

Default: the continuous-batching engine (`repro.serving`) — requests are
admitted FCFS under a token budget into a paged packed-KV4 cache pool,
prefill is chunked, decode slots are backfilled every step, and decode
attention streams the pool in wire format through the paged Pallas
kernel. Reports per-request TTFT/TPOT, generation throughput, achieved
MSB4 sub-precision sparsity, and the cost model's prediction at that
sparsity (paper §5.1).

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
        --prompt-len 64 --gen 16 --batch 4

``--legacy`` runs the original fixed-batch path (one monolithic cache,
single prefill + lockstep Python decode loop) for comparison; paged-vs-
legacy token equivalence is covered by tests/test_serving.py.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.core.costmodel import (HardwareConfig, LMShape, evaluate_model)
from repro.core.qlinear import quantize_model_params
from repro.core.quantize import quantize_activations
from repro.core.sparqle import subprecision_sparsity
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed.sharding import mesh_context
from repro.launch import steps as S
from repro.launch.mesh import make_smoke_mesh
from repro.launch.runtime import device_label, use_compile_cache
from repro.models import model as M
from repro.models.qschema import init_served_params
from repro.models.registry import get_config
from repro.models.schema import init_params
from repro.models.schema_builder import build_schema


def _legacy_serve(cfg, qparams, batch, plen, args) -> None:
    max_len = plen + args.gen
    prefill = jax.jit(S.make_serve_prefill(cfg, max_len))
    decode = jax.jit(S.make_serve_decode(cfg))

    t0 = time.time()
    tok, cache = prefill(qparams, batch)
    tok.block_until_ready()
    t_prefill = time.time() - t0

    out = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.full((args.batch,), plen + i, jnp.int32)
        tok, cache = decode(qparams, cache, tok, pos)
        out.append(tok)
    jax.block_until_ready(out[-1])
    t_decode = (time.time() - t0) / max(1, args.gen - 1)

    gen = jnp.stack(out, 1)
    print(f"generated {gen.shape} tokens; "
          f"prefill {t_prefill*1e3:.0f} ms, "
          f"{t_decode*1e3:.1f} ms/token (timings on {device_label()})")


def make_engine(cfg, qparams, args, serve_mesh=None):
    """The paged engine ``args`` describe: a pool that holds the whole
    batch (unless ``--n-pages``), scheduler limits from the engine knobs,
    and the speculative engine when ``--spec-gamma`` > 0."""
    from repro.serving import (Engine, PoolConfig, SchedulerConfig,
                               SpecConfig, SpeculativeEngine)
    gamma = getattr(args, "spec_gamma", 0)
    data_ways = 1
    if serve_mesh is not None:
        data_ways = serve_mesh.shape.get("data", 1)
    pages_per_seq = -(-(args.prompt_len + args.gen + gamma)
                      // args.page_size)
    n_slots = min(args.batch, args.decode_slots)
    n_slots += (-n_slots) % data_ways            # slots split over data
    # a request's pages live in ONE data shard, so the default pool must
    # give every shard room for its share of the batch (ceil), not an
    # even split of the global worst case
    batch_per_shard = -(-args.batch // data_ways)
    n_pages = args.n_pages or (
        data_ways * (1 + pages_per_seq * batch_per_shard))
    n_pages += (-n_pages) % data_ways            # pages split over data
    from repro.obs.slo import parse_slo_list
    slos = [slo for s in getattr(args, "slo", None) or []
            for slo in parse_slo_list(s)]
    kw = dict(
        pool_config=PoolConfig(n_pages=n_pages, page_size=args.page_size),
        sched_config=SchedulerConfig(
            max_decode_batch=n_slots,
            token_budget=args.token_budget,
            prefill_chunk=args.prefill_chunk,
            max_pages_per_seq=pages_per_seq),
        mesh=serve_mesh, slos=slos)
    if gamma > 0:
        return SpeculativeEngine(cfg, qparams, spec=SpecConfig(gamma=gamma),
                                 **kw)
    return Engine(cfg, qparams, **kw)


def _engine_serve(cfg, qparams, prompts, args, serve_mesh=None):
    from repro.serving import SamplingParams
    eng = make_engine(cfg, qparams, args, serve_mesh)
    if getattr(args, "attribute", False):
        attr = eng.attribute_steps()
        for phase, c in sorted(attr.summary().items()):
            print(f"attributed {phase}: {c['flops']/1e6:.1f} MFLOP/step, "
                  f"{c['hbm_bytes']/1e6:.1f} MB HBM/step, "
                  f"{c['coll_bytes_total']/1e3:.1f} kB collectives "
                  f"(compiled in {c['compile_seconds']:.2f} s)")
    if serve_mesh is not None:
        print(f"serving on mesh {dict(serve_mesh.shape)} "
              f"({serve_mesh.size} devices): decode slots/pages sharded "
              f"over 'data', weights+KV heads over 'model'")
    t0 = time.time()
    handles = [eng.submit(np.asarray(p).tolist(),
                          SamplingParams(max_new_tokens=args.gen))
               for p in prompts]
    eng.run()
    wall = time.time() - t0

    stats = [h.stats() for h in handles]
    n_tok = sum(s["n_generated"] for s in stats)
    ttft = [s["ttft_s"] for s in stats]
    tpot = [s["tpot_s"] for s in stats if np.isfinite(s["tpot_s"])]
    spars = [s["act_sparsity"] for s in stats]
    print(f"engine: {len(handles)} requests, {n_tok} tokens in "
          f"{wall:.2f} s ({n_tok / wall:.1f} tok/s, "
          f"{eng.steps} steps; timings on {device_label()})")
    print(f"  TTFT  mean {np.mean(ttft)*1e3:.0f} ms  "
          f"p95 {np.percentile(ttft, 95)*1e3:.0f} ms")
    if tpot:
        print(f"  TPOT  mean {np.mean(tpot)*1e3:.1f} ms/token")
    print(f"  decode-time MSB4 sparsity mean {np.mean(spars)*100:.1f}%")
    agg = eng.aggregate_stats()
    if "wire_compression_pct" in agg:
        print(f"  measured wire format: {agg['wire_compression_pct']:.1f}% "
              f"activation bytes saved vs dense int8 "
              f"({agg['wire_bytes_total']/1e3:.1f} kB on the wire)")
    if "spec_acceptance_rate" in agg:
        print(f"  speculative: gamma={agg['spec_gamma']}, "
              f"{agg['spec_acceptance_rate']*100:.1f}% drafts accepted, "
              f"{agg['spec_tokens_per_step']:.2f} tokens/cycle")
    print(f"  pool: {agg['pool_utilization']*100:.0f}% pages in use at "
          f"drain, {agg['pool_evictions']} evictions")
    if eng.slo is not None:
        for rep in eng.slo.report():
            state = "VIOLATING" if rep["violating"] else "ok"
            print(f"  SLO {rep['slo']}: p{rep['percentile']:g} = "
                  f"{rep['value']:.4g} {rep['unit']} (target "
                  f"{rep['target']:g}) [{state}], "
                  f"{rep['violations']} violation(s), burn rate "
                  f"{rep['burn_rate']:.2f}")
    return eng


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--k-percent", type=float, default=50.0)
    ap.add_argument("--clip-l", type=float, default=-8.0)
    ap.add_argument("--clip-h", type=float, default=23.0)
    ap.add_argument("--mode", default="sparqle", choices=["sparqle", "dense"])
    ap.add_argument("--no-clip", action="store_true")
    ap.add_argument("--ckpt", default=None,
                    help="restore float params from this checkpoint dir")
    ap.add_argument("--seed", type=int, default=0)
    # engine knobs
    ap.add_argument("--legacy", action="store_true",
                    help="fixed-batch serving path (no engine)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--n-pages", type=int, default=0,
                    help="0 = size the pool to fit the whole batch")
    ap.add_argument("--token-budget", type=int, default=128)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--decode-slots", type=int, default=8)
    ap.add_argument("--spec-gamma", type=int, default=0,
                    help="self-speculative decoding: LSB4-only draft "
                         "window per verify cycle (0 = off)")
    ap.add_argument("--slo", action="append", default=[],
                    help="declarative SLO spec, repeatable and/or "
                         "comma-separated (e.g. --slo ttft:p95<0.25 "
                         "--slo queue_depth:p50<4): "
                         "the engine watches the signal's sliding-window "
                         "percentile and reports violations + burn rate "
                         "(docs/observability.md)")
    ap.add_argument("--attribute", action="store_true",
                    help="attribute the compiled serving steps at warm-up "
                         "(per-step FLOPs/HBM/collective bytes + live "
                         "roofline and cost-model drift gauges)")
    ap.add_argument("--metrics-out", default="",
                    help="write the engine's metrics-registry snapshot "
                         "(JSON) here after the run (engine path only)")
    ap.add_argument("--trace-out", default="",
                    help="write the engine's Chrome trace-event JSON "
                         "here after the run — load in Perfetto / "
                         "chrome://tracing (engine path only)")
    ap.add_argument("--mesh", default="",
                    help="DATA,MODEL device mesh for the engine (e.g. "
                         "'2,4'): decode slots + pool pages shard over "
                         "the data axis, weights/KV heads tensor-"
                         "parallel over the model axis. Needs "
                         "data*model jax devices (on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N). "
                         "Token streams are bit-exact vs the default "
                         "single-device engine (docs/sharding.md).")
    return ap


def served_params(cfg, args):
    """The served (quantized) parameter tree ``args`` describe: seeded
    random weights built leaf by leaf, so the float tree never exists
    (``qschema.init_served_params``), or a float checkpoint (``--ckpt``)
    restored whole and then quantized."""
    quant = dict(w_bits=cfg.w_bits, k_percent=args.k_percent,
                 clip_l=args.clip_l, clip_h=args.clip_h, mode=args.mode,
                 enable_clipping=not args.no_clip,
                 tile_k=16 if args.smoke else 128)
    schema = build_schema(cfg)
    key = jax.random.PRNGKey(args.seed)
    if not args.ckpt:
        return init_served_params(schema, key, **quant)
    params = init_params(schema, key)
    latest = store.latest_step(args.ckpt)
    params = store.restore(args.ckpt, latest, {"params": params})["params"]
    return quantize_model_params(params, **quant)


def prompt_batch(cfg, args):
    """(prompt tokens (batch, prompt_len), model batch dict): seeded
    synthetic prompts, with stub patch embeddings for a VLM."""
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.prompt_len,
                                  global_batch=args.batch, seed=args.seed))
    prompts = jnp.asarray(data.batch_at(0)["tokens"])
    if cfg.family == "vlm":
        return prompts, {
            "patches": jax.random.normal(
                jax.random.PRNGKey(1),
                (args.batch, cfg.n_prefix, cfg.d_model)).astype(cfg.cdtype),
            "tokens": prompts[:, :args.prompt_len - cfg.n_prefix]}
    return prompts, {"tokens": prompts}


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    use_compile_cache()
    print(f"devices: {device_label()}")

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.family == "encoder":
        raise SystemExit("encoder-only arch has no decode; see examples/")
    serve_mesh = None
    if args.mesh:
        try:
            d, m = (int(v) for v in args.mesh.split(","))
        except ValueError:
            raise SystemExit(f"--mesh expects 'DATA,MODEL', got "
                             f"{args.mesh!r}")
        if d * m > 1:
            serve_mesh = make_smoke_mesh(data=d, model=m)
        if args.legacy:
            raise SystemExit("--mesh drives the paged engine; it has no "
                             "effect on --legacy (drop one of the two)")
    if args.legacy and (args.metrics_out or args.trace_out):
        raise SystemExit("--metrics-out/--trace-out read the paged "
                         "engine's observability bundle; the --legacy "
                         "path has none (drop one of the two)")
    if args.legacy and (args.slo or args.attribute):
        raise SystemExit("--slo/--attribute drive the paged engine's "
                         "observability; the --legacy path has none "
                         "(drop one of the two)")
    # ambient 1x1 mesh for the GSPMD tail paths (sparsity/cost-model
    # report); the engine gets the serving mesh explicitly
    mesh = make_smoke_mesh()

    with mesh_context(mesh):
        qparams = served_params(cfg, args)
        prompts, batch = prompt_batch(cfg, args)
        plen = args.prompt_len

        if args.legacy:
            _legacy_serve(cfg, qparams, batch, plen, args)
        else:
            try:
                M.check_paged_support(cfg)
            except NotImplementedError as e:
                raise SystemExit(
                    f"{e}\n(this arch serves via --legacy only)")
            eng = _engine_serve(cfg, qparams, list(np.asarray(prompts)),
                                args, serve_mesh=serve_mesh)
            if args.metrics_out:
                import json
                with open(args.metrics_out, "w") as f:
                    json.dump(eng.metrics_snapshot(), f, indent=1)
                print(f"  metrics snapshot -> {args.metrics_out}")
            if args.trace_out:
                eng.obs.tracer.export_chrome(args.trace_out)
                print(f"  chrome trace     -> {args.trace_out}")

        # achieved sub-precision sparsity of the hidden stream
        hidden = jax.jit(lambda p, b: M.forward_hidden(cfg, p, b))(
            qparams, batch)
        q = quantize_activations(hidden.reshape(-1, hidden.shape[-1]),
                                 bits=8, per_token=True).q
        s = float(subprecision_sparsity(q))
        print(f"MSB4 sub-precision sparsity of hidden activations: "
              f"{s*100:.1f}%")

        # analytical accelerator prediction at this sparsity (paper §5.1)
        lm = LMShape(cfg.name, cfg.n_layers, cfg.d_model,
                     max(1, cfg.n_heads), max(1, cfg.n_kv_heads),
                     max(1, cfg.d_ff or cfg.moe_d_ff), cfg.vocab,
                     w_bits=cfg.w_bits)
        rep = evaluate_model(lm, s, HardwareConfig(),
                             prefill_tokens=plen * args.batch,
                             decode_batch=args.batch)
        imp = rep.improvements()
        print("cost-model prediction at this sparsity: "
              f"TTFT -{imp['ttft_latency_pct']:.1f}%, "
              f"TPOT -{imp['tpot_latency_pct']:.1f}%, "
              f"prefill E -{imp['prefill_energy_pct']:.1f}%, "
              f"decode E -{imp['decode_energy_pct']:.1f}%")


if __name__ == "__main__":
    main()
