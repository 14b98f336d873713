#!/usr/bin/env python3
"""Serve granite-8b at full published width on a TPU, and check the result.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # tensor-parallel engine, 4 chips

One chip: builds the served W4A8 tree of seeded random weights leaf by leaf
(36 layers, d_model 4096, d_ff 14336, vocab 49152), then drives the paged
continuous-batching engine through the functions ``repro.launch.serve``
uses: 8 requests of 512 prompt tokens, 32 generated tokens each. Checks:

  * every request emitted 32 in-vocab tokens and every sampled logits row
    was finite;
  * the compiled paged decode kernel, run over the run's own pool pages,
    matches ``kernels.ref.kv4_decode_attention_ref`` (KERNEL_TOL);
  * each request's first-token logits, from a single 512-token prefill
    chunk, match the contiguous legacy prefill (PREFILL_TOL).

``--chips 4`` runs only the tensor-parallel engine on a (data=1, model=4)
mesh and, as its reference, the single-device engine in the same process
on the same requests; greedy streams must be equal or first-token logits
within PREFILL_TOL. It prints the weight bytes each device holds.

Numbers printed before the last line are smoke numbers (one cold run, not
a benchmark). The last line is the JSON verdict, printed only when every
phase passed on a TPU; anything else exits nonzero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "granite-8b"
N_REQUESTS, PROMPT_LEN, GEN = 8, 512, 32
# serve.py flags: one 512-token prefill chunk per prompt, all 8 prompts
# admitted in the first step (budget 8 x 512), pool sized for the batch
SERVE_ARGS = ["--arch", ARCH, "--batch", str(N_REQUESTS),
              "--prompt-len", str(PROMPT_LEN), "--gen", str(GEN),
              "--prefill-chunk", str(PROMPT_LEN),
              "--token-budget", str(N_REQUESTS * PROMPT_LEN),
              "--decode-slots", str(N_REQUESTS), "--seed", "0"]
# kernel vs f32 reference (HIGHEST precision): max |diff| <= atol + rtol|ref|
KERNEL_TOL = dict(rtol=2e-2, atol=2e-2)
# engine vs reference logits: max |diff| over the row <= this share of
# the row's max |logit| (bf16 activations, different attention blocking)
PREFILL_TOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def _sampling_probe(eng):
    """Wrap the engine's sampler to observe, per request, every logits
    row it samples from, its first row, and the pages and context length
    it holds at its last sample (the pages are released right after)."""
    import numpy as np
    seen = {"finite": True, "first": {}, "pages": {}}
    sample = eng._sample

    def probe(req, logits):
        row = np.asarray(logits, np.float32)
        seen["finite"] &= bool(np.isfinite(row).all())
        seen["first"].setdefault(req.rid, row)
        seen["pages"][req.rid] = (list(eng.pool.pages_of(req.rid)),
                                  len(req.context))
        return sample(req, logits)

    eng._sample = probe
    return seen


def _compile_seconds(eng):
    """Lower and compile the engine's two step programs from abstract
    arguments (the persistent compile cache then serves the run)."""
    import jax
    import jax.numpy as jnp
    sds = jax.ShapeDtypeStruct
    params_a, pool_a = eng._attr_abstract_args()
    n_steps = eng._n_page_steps
    out = {}
    t0 = time.time()
    eng._prefill_fn.lower(
        params_a, pool_a, sds((1, eng._chunk), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32),
        sds((eng._data_ways, n_steps), jnp.int32)).compile()
    out["prefill"] = time.time() - t0
    t0 = time.time()
    eng._decode_fn.lower(
        params_a, pool_a, sds((eng._n_slots,), jnp.int32),
        sds((eng._n_slots,), jnp.int32),
        sds((eng._n_slots, n_steps), jnp.int32)).compile()
    out["decode"] = time.time() - t0
    return out


def serve_requests(cfg, qparams, args, prompts, mesh=None, tag="engine"):
    """Run the batch through a fresh engine; returns (engine, handles,
    probe) after checking token counts, vocab range and finiteness."""
    import numpy as np
    from repro.launch import serve
    from repro.serving import SamplingParams
    eng = serve.make_engine(cfg, qparams, args, serve_mesh=mesh)
    for step, secs in _compile_seconds(eng).items():
        log(f"[{tag}] compile {step} step: {secs:.1f} s")
    seen = _sampling_probe(eng)
    t0 = time.time()
    handles = [eng.submit(np.asarray(p).tolist(),
                          SamplingParams(max_new_tokens=args.gen))
               for p in prompts]
    eng.run()
    wall = time.time() - t0
    n_tok = sum(h.n_generated for h in handles)
    log(f"[{tag}] smoke numbers (one cold run, not a benchmark): "
        f"{len(handles)} requests, {n_tok} tokens in {wall:.2f} s wall, "
        f"{eng.steps} engine steps")
    done = sum(h.n_generated == args.gen and
               all(0 <= t < cfg.vocab for t in h.out_tokens)
               for h in handles)
    log(f"[{tag}] requests complete with {args.gen} in-vocab tokens: "
        f"{done}/{len(handles)}; sampled logits finite: {seen['finite']}")
    if done != len(handles) or not seen["finite"]:
        raise SystemExit(f"[{tag}] FAIL: incomplete or non-finite output")
    return eng, handles, seen


def check_kernel(eng, seen, cfg) -> None:
    """The compiled paged decode kernel over the run's own pool pages
    (first and last layer) vs the dense f32 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.kv_attention import kv4_paged_decode_attention
    from repro.kernels.ref import kv4_decode_attention_ref
    rids = sorted(seen["pages"])
    n_steps = eng._n_page_steps
    bt = np.zeros((len(rids), n_steps), np.int32)
    pos = np.zeros((len(rids),), np.int32)
    for i, rid in enumerate(rids):
        pages, ctx_len = seen["pages"][rid]
        bt[i, :len(pages)] = pages
        pos[i] = ctx_len - 1        # last position written to the pool
    bt, pos = jnp.asarray(bt), jnp.asarray(pos)
    kvh, g, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    q = jax.random.normal(jax.random.PRNGKey(1), (len(rids), kvh, g, hd))
    layers = eng.pool.state["stages"]["s0"]["p0"]
    worst = 0.0
    for layer in (0, cfg.n_layers - 1):
        kq, ks, vq, vs = (layers[k][layer] for k in ("k_q", "k_s", "v_q",
                                                      "v_s"))
        out = kv4_paged_decode_attention(q, kq, ks, vq, vs, bt, pos)

        def contiguous(x):
            g_ = x[bt]
            return g_.reshape(g_.shape[0], -1, *g_.shape[3:])

        with jax.default_matmul_precision("highest"):
            ref = kv4_decode_attention_ref(q, contiguous(kq), contiguous(ks),
                                           contiguous(vq), contiguous(vs),
                                           pos)
        out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
        excess = np.abs(out - ref) - (KERNEL_TOL["atol"]
                                      + KERNEL_TOL["rtol"] * np.abs(ref))
        err = float(np.abs(out - ref).max())
        worst = max(worst, err)
        log(f"paged decode kernel vs reference, layer {layer}: max |diff| "
            f"{err:.3e} (tolerance atol {KERNEL_TOL['atol']} + rtol "
            f"{KERNEL_TOL['rtol']}·|ref|): "
            f"{'pass' if excess.max() <= 0 else 'FAIL'}")
        if excess.max() > 0:
            raise SystemExit("FAIL: paged decode kernel disagrees with the "
                             "reference")


def rel_logit_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def check_prefill(cfg, qparams, args, batch, seen, handles) -> None:
    """Engine first-token logits (single 512-token chunk) vs the
    contiguous legacy prefill of the same prompts."""
    import jax
    import numpy as np
    from repro.launch import steps as S
    from repro.models import model as M
    max_len = args.prompt_len + args.gen
    ref = np.asarray(jax.jit(
        lambda p, b: M.prefill(cfg, p, b, max_len=max_len)[0])(
            qparams, batch), np.float32)
    ref_tok = np.asarray(jax.jit(S.make_serve_prefill(cfg, max_len))(
        qparams, batch)[0])
    errs = [rel_logit_err(seen["first"][h.rid], ref[i])
            for i, h in enumerate(handles)]
    same_tok = sum(int(h.out_tokens[0] == ref_tok[i])
                   for i, h in enumerate(handles))
    ok = max(errs) <= PREFILL_TOL
    log(f"engine prefill vs legacy prefill first-token logits: max "
        f"relative error {max(errs):.3e} (tolerance {PREFILL_TOL}), first "
        f"tokens equal {same_tok}/{len(handles)}: "
        f"{'pass' if ok else 'FAIL'}")
    if not ok:
        raise SystemExit("FAIL: engine prefill disagrees with the legacy "
                         "prefill")


def build(args):
    import jax
    from repro.launch import serve
    from repro.models.registry import get_config
    cfg = get_config(args.arch, smoke=args.smoke)
    log(f"arch {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV heads of {cfg.hd}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, W{cfg.w_bits}A8 KV4")
    t0 = time.time()
    qparams = jax.block_until_ready(serve.served_params(cfg, args))
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(qparams))
    log(f"served weights built leaf by leaf in {time.time() - t0:.1f} s: "
        f"{n_bytes / 1e9:.3f} GB")
    prompts, batch = serve.prompt_batch(cfg, args)
    return cfg, qparams, prompts, batch


def peak_hbm(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


def run_one_chip(serve_argv) -> None:
    import jax
    from repro.launch import serve
    args = serve.build_parser().parse_args(serve_argv)
    cfg, qparams, prompts, batch = build(args)
    eng, handles, seen = serve_requests(cfg, qparams, args, prompts)
    log(f"peak HBM in use after serving: {peak_hbm(jax.devices()[0])}")
    check_kernel(eng, seen, cfg)
    check_prefill(cfg, qparams, args, batch, seen, handles)


def run_four_chips(serve_argv) -> None:
    import jax
    import numpy as np
    from repro.launch import serve
    from repro.launch.mesh import make_smoke_mesh
    args = serve.build_parser().parse_args(serve_argv)
    cfg, qparams, prompts, batch = build(args)
    _, ref_handles, ref_seen = serve_requests(cfg, qparams, args, prompts,
                                              tag="single device")
    mesh = make_smoke_mesh(data=1, model=4)
    eng, handles, seen = serve_requests(cfg, qparams, args, prompts,
                                        mesh=mesh, tag="tp (1, 4)")
    held = {d.id: 0 for d in mesh.devices.flat}
    for leaf in jax.tree_util.tree_leaves(eng.params):
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    log("weight bytes held per device (tp engine): " + ", ".join(
        f"device {d}: {b / 1e9:.3f} GB" for d, b in sorted(held.items())))
    for d in mesh.devices.flat:
        log(f"peak HBM in use, device {d.id}: {peak_hbm(d)}")
    same = sum(h.out_tokens == r.out_tokens
               for h, r in zip(handles, ref_handles))
    errs = [rel_logit_err(seen["first"][h.rid], ref_seen["first"][r.rid])
            for h, r in zip(handles, ref_handles)]
    ok = same == len(handles) or max(errs) <= PREFILL_TOL
    log(f"tp vs single-device: greedy streams equal {same}/{len(handles)}, "
        f"first-token logits max relative error {max(errs):.3e} "
        f"(tolerance {PREFILL_TOL}): {'pass' if ok else 'FAIL'}")
    if not ok:
        raise SystemExit("FAIL: tensor-parallel engine disagrees with the "
                         "single-device engine")
    if max(held.values()) > 0.5 * sum(held.values()):
        raise SystemExit("FAIL: weights are not spread over the devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip serving path and its checks; "
                         "4: the tensor-parallel engine vs single device")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.runtime import device_info, use_compile_cache
    info = device_info()
    log(f"devices: {info}")
    if info["platform"] != "tpu":
        log(f"no TPU found (JAX platform {info['platform']!r}); the chip "
            f"smoke test runs on a TPU only")
        return 1
    if info["count"] < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
            f"{info['count']}")
        return 1
    log(f"compile cache: {use_compile_cache()}")
    log(f"jax {jax.__version__}")
    if args.chips == 4:
        run_four_chips(SERVE_ARGS)
    else:
        run_one_chip(SERVE_ARGS)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
