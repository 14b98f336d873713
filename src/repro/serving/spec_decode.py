"""Self-speculative decoding: LSB4-only drafting, batched full verification.

SPARQLe's hybrid format contains a free draft model (paper §3.3): the
dense LSB4 pass costs 1 compute round while the full LSB+MSB path costs
1 + (1 - s) rounds, so a forward with the sparse MSB pass *statically
elided* (``qlinear.msb_skip_scope``) is a cheap, always-resident
approximation of the full model — same weights, same KV cache, no second
network. This module turns that into self-speculative decoding:

  1. **draft** — γ decode steps through the LSB4-only jitted step
     (``steps.make_engine_decode(msb_skip=True, with_telemetry=False)``).
     Each step writes the draft's *approximate* K/V into the request's
     pages and proposes the next token (greedy at temperature 0, sampled
     from the draft distribution otherwise).
  2. **verify** — ONE full-precision batched step
     (``steps.make_engine_verify_window``) scores the whole (γ+1)-token
     window for every decode slot at once, overwriting the draft K/V
     with full-precision values. The multi-token paged attention kernel
     is bit-exact against a loop of single-token decodes, so at
     temperature 0 the verified stream is byte-identical to the
     non-speculative engine's greedy tokens.
  3. **accept** — greedy exact-match acceptance at temperature 0
     (emit full-precision argmax tokens while they match the draft, then
     the correction/bonus token); standard rejection sampling otherwise
     (accept draft d with prob min(1, p_full(d)/p_draft(d)); on reject,
     sample the residual max(0, p_full - p_draft)). Every cycle emits
     between 1 and γ+1 tokens.
  4. **rollback** — ``PagedKVPool.truncate`` releases tail pages past
     the accepted context; rejected K/V left mid-page sits beyond the
     causal mask until overwritten.

Budget/memory accounting: a speculative decode slot burns 2γ+1 compute
tokens per scheduler step and writes K/V up to γ positions ahead, which
``SchedulerConfig.decode_tokens_per_slot`` / ``decode_lookahead`` feed
into the scheduler's token budget, page growth and admission checks.

Telemetry semantics: the γ draft steps run the LSB4-only jitted step
compiled ``with_telemetry=False`` — they carry NO wire-byte or sparsity
telemetry, by design (telemetry reductions would erase most of the
draft's latency win). Only the verify window's γ+1 tokens enter the
wire-byte accounting, so ``Request.wire_tokens`` counts telemetered
tokens and ``Request.draft_tokens`` counts the untelemetered draft
compute tokens separately. Folding drafts into the wire denominator
would understate bytes/token by up to (2γ+1)/(γ+1)× — keep them apart.

    eng = SpeculativeEngine(cfg, qparams, spec=SpecConfig(gamma=3))
    h = eng.submit(prompt, SamplingParams(max_new_tokens=32))
    eng.run()
    h.stats()["spec_acceptance_rate"], h.stats()["spec_tokens_per_step"]
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.serving.engine import Engine
from repro.serving.kv_pool import PoolConfig
from repro.serving.scheduler import Request, SchedulerConfig


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    gamma: int = 2                   # draft tokens per verify cycle

    def __post_init__(self):
        if self.gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {self.gamma}")


def _softmax(logits: np.ndarray, temperature: float) -> np.ndarray:
    z = (logits.astype(np.float64) - logits.max()) / temperature
    p = np.exp(z)
    return p / p.sum()


class SpeculativeEngine(Engine):
    """Continuous-batching engine with self-speculative decode steps.

    Drop-in for :class:`Engine`: same submit/stream/run API, same paged
    pool, same chunked prefill. Only the decode path changes — γ LSB-only
    draft steps followed by one batched full-precision verify instead of
    one full decode per token. With ``mode='sparqle'`` params the draft
    is genuinely sub-precision (acceptance < 1); with ``mode='dense'``
    params the draft equals the target and speculation degenerates to
    always-accept.
    """

    def __init__(self, cfg: ModelConfig, params,
                 pool_config: Optional[PoolConfig] = None,
                 sched_config: Optional[SchedulerConfig] = None,
                 spec: SpecConfig = SpecConfig(),
                 clock=time.monotonic, mesh=None, obs=None, slos=None):
        from repro.launch import steps as S
        self.spec = spec
        g = spec.gamma
        if pool_config is not None and pool_config.kv2_pages:
            # the draft and verify steps read the pool through
            # tier-unaware gathers, so a demoted page would be read as
            # garbage mid-window; the ladder is base-engine-only for now
            raise NotImplementedError(
                "the KV2 precision ladder (kv2_pages > 0) is not "
                "supported by the speculative engine")
        sched_config = dataclasses.replace(
            sched_config or SchedulerConfig(),
            decode_tokens_per_slot=2 * g + 1,   # γ draft + (γ+1) verify
            decode_lookahead=g)
        super().__init__(cfg, params, pool_config=pool_config,
                         sched_config=sched_config, clock=clock, mesh=mesh,
                         obs=obs, slos=slos)
        # draft/verify share the engine's mesh layout (self.mesh is None
        # when no multi-device mesh was given): the LSB4-only draft and
        # the batched verify run inside the same shard_map partitioning
        # as the base decode step, so a sharded speculative stream is
        # bit-exact vs the sharded (and single-device) base engine
        self._draft_fn = jax.jit(
            S.make_engine_decode(cfg, msb_skip=True, with_telemetry=False,
                                 mesh=self.mesh,
                                 param_specs=self._param_specs,
                                 pool_specs=self._pool_specs),
            donate_argnums=(1,))
        self._verify_fn = jax.jit(
            S.make_engine_verify_window(cfg, mesh=self.mesh,
                                        param_specs=self._param_specs,
                                        pool_specs=self._pool_specs),
            donate_argnums=(1,))
        # engine-level speculative counters (per-request ones live on
        # Request; these survive request handles going out of scope)
        self.draft_proposed_total = 0
        self.draft_accepted_total = 0
        self.spec_steps_total = 0
        self.spec_emitted_total = 0
        r = self.obs.registry
        self._m_spec_proposed = r.counter(
            "serving_spec_draft_proposed_total", "draft tokens the "
            "verifier examined", unit="tokens")
        self._m_spec_accepted = r.counter(
            "serving_spec_draft_accepted_total", "examined draft tokens "
            "the full-precision model accepted", unit="tokens")
        self._m_spec_cycles = r.counter(
            "serving_spec_cycles_total", "draft+verify cycles run (one "
            "per decode slot per engine step)", unit="steps")
        self._m_spec_emitted = r.counter(
            "serving_spec_tokens_emitted_total", "tokens emitted by "
            "accept/correct/bonus across all cycles", unit="tokens")

    # -- performance attribution ------------------------------------------

    def attribute_steps(self, hw=None):
        """Extend base attribution with the speculative steps.

        The ``draft`` phase wall-time (``serving_step_seconds{phase=
        draft}``) wraps the whole γ-step host loop, so the draft cost is
        attributed with ``calls_per_step=γ`` — one timed phase executes
        the LSB4-only decode program γ times — keeping the runtime
        roofline join apples-to-apples. ``verify`` is one (γ+1)-token
        window step per phase.
        """
        attr = super().attribute_steps(hw=hw)
        g = self.spec.gamma
        sds = jax.ShapeDtypeStruct
        params_a, pool_a = self._attr_abstract_args()
        if "draft" not in attr.phases():
            attr.attribute(
                "draft", self._draft_fn,
                (params_a, pool_a, sds((self._n_slots,), jnp.int32),
                 sds((self._n_slots,), jnp.int32),
                 sds((self._n_slots, self._n_page_steps), jnp.int32)),
                tokens_per_step=self._n_slots * g, calls_per_step=g,
                predict_seconds=self._spec_predictor("draft"))
        if "verify" not in attr.phases():
            attr.attribute(
                "verify", self._verify_fn,
                (params_a, pool_a, sds((self._n_slots, g + 1), jnp.int32),
                 sds((self._n_slots,), jnp.int32),
                 sds((self._n_slots, self._n_page_steps), jnp.int32)),
                tokens_per_step=self._n_slots * (g + 1),
                predict_seconds=self._spec_predictor("verify"))
        return attr

    def _spec_predictor(self, phase: str):
        """sparsity -> predicted seconds per TIMED phase: γ LSB4-only
        decode rounds for draft, one (γ+1)-token window for verify."""
        from repro.core import costmodel as CM
        shape = self._costmodel_shape()
        hw = self._attr.hw
        g = self.spec.gamma
        seq_for_attn = self._n_page_steps * self.pool.page_size
        lsb_only = phase == "draft"
        m_tokens = self._n_slots if lsb_only else self._n_slots * (g + 1)
        calls = g if lsb_only else 1

        def predict(sparsity: float) -> float:
            layers = CM.lm_linear_layers(
                shape, m_tokens, sparsity, seq_for_attn=seq_for_attn,
                decode=True)
            cost = CM.phase_cost(layers, hw, sparqle=True,
                                 lsb_only=lsb_only)
            return calls * cost.cycles / (hw.freq_ghz * 1e9)
        return predict

    # -- decode path -------------------------------------------------------

    def _run_decode(self, decode: List[Request],
                    span) -> List[Tuple[int, int]]:
        # the cycle's kernel calls are spanned by spec_draft/spec_verify;
        # ``decode_batch`` gets no attention page counts here
        del span
        B, g = self._n_slots, self.spec.gamma
        token = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        tables = np.zeros((B, self._n_page_steps), np.int32)
        for req in decode:
            token[req.slot] = req.context[-1]
            pos[req.slot] = len(req.context) - 1
            tables[req.slot] = self._block_table_row(req)

        # ---- draft: γ LSB4-only steps, token fed forward host-side ----
        window = np.zeros((B, g + 1), np.int32)
        window[:, 0] = token
        jpos = jnp.asarray(pos)
        jtables = jnp.asarray(tables)
        cur = jnp.asarray(token)
        dlogs = []
        with self.obs.phase("draft", "spec_draft", slots=len(decode),
                            gamma=g):
            for i in range(g):
                dlg, self.pool.state, _ = self._draft_fn(
                    self.params, self.pool.state, cur,
                    jpos + jnp.int32(i), jtables)
                dlg = np.asarray(dlg)
                dlogs.append(dlg)
                nxt = np.zeros((B,), np.int32)
                for req in decode:
                    nxt[req.slot] = self._sample(req, dlg[req.slot])
                window[:, i + 1] = nxt
                cur = jnp.asarray(nxt)
        draft_logits = np.stack(dlogs, axis=1)          # (B, γ, V)
        self._m_tokens.inc(len(decode) * g, phase="draft")

        # ---- verify: one full-precision batched window step ----
        with self.obs.phase("verify", "spec_verify", slots=len(decode),
                            window=g + 1):
            vlg, self.pool.state, tel = self._verify_fn(
                self.params, self.pool.state, jnp.asarray(window),
                jpos, jtables)
            vlg = np.asarray(vlg)                       # (B, γ+1, V)
        self._m_tokens.inc(len(decode) * (g + 1), phase="verify")
        sparsity = np.asarray(tel["sparsity"])
        layer_wire = np.asarray(tel["layer_wire_bytes"], np.float64)
        layer_dense = np.asarray(tel["layer_dense_bytes"], np.float64)
        layer_spars = np.asarray(tel["layer_sparsity"], np.float64)

        events: List[Tuple[int, int]] = []
        for req in decode:
            s = req.slot
            req.sparsity_sum += float(sparsity[s]) * (g + 1)
            req.sparsity_n += g + 1
            # γ draft compute tokens ran telemetry-free (module
            # docstring) — tracked apart from the wire denominator
            req.draft_tokens += g
            self._account_wire(
                req, float(layer_wire[:, s].sum()),
                float(layer_dense[:, s].sum()),
                layer_wire[:, s], layer_dense[:, s],
                layer_spars[:, s] * (g + 1), g + 1)
            events.extend(
                self._accept_and_emit(req, window[s], vlg[s],
                                      draft_logits[s]))
            if not req.done:
                # KV rollback: free tail pages past the accepted context
                # (context[-1]'s own slot is kept — the next cycle writes
                # there first); stale rejected K/V left mid-page sits
                # beyond the causal mask until overwritten
                self.pool.truncate(req.rid, len(req.context))
        return events

    # -- acceptance --------------------------------------------------------

    def _accept_and_emit(self, req: Request, window: np.ndarray,
                         vlogits: np.ndarray, dlogits: np.ndarray
                         ) -> List[Tuple[int, int]]:
        """Walk one request's verified window, emitting accepted tokens.

        ``window`` (γ+1,) — window[0] is the request's last accepted
        token, window[1:] the draft proposals. ``vlogits`` (γ+1, V) —
        full-precision logits after each window token. ``dlogits``
        (γ, V) — the draft logits each proposal was sampled from.
        """
        g = self.spec.gamma
        t = req.sampling.temperature
        events: List[Tuple[int, int]] = []
        emitted = accepted = examined = 0

        if t <= 0.0:
            # greedy exact-match: emit full-precision argmaxes while the
            # draft guessed them; the first mismatch emits the correction
            # (and a fully-accepted window emits the free bonus token)
            for i in range(g + 1):
                if req.done:
                    break
                y = int(np.argmax(vlogits[i]))
                ev = self._emit(req, y)
                if ev:
                    events.append(ev)
                emitted += 1
                if i == g:
                    break
                examined += 1
                if int(window[i + 1]) != y:
                    break
                accepted += 1
        else:
            # rejection sampling: emitted tokens are distributed per the
            # full-precision model regardless of draft quality
            rng = self._rngs.setdefault(
                req.rid,
                np.random.default_rng(req.sampling.seed + req.rid))
            rejected = False
            for i in range(g):
                if req.done:
                    break
                d = int(window[i + 1])
                p_full = _softmax(vlogits[i], t)
                p_draft = _softmax(dlogits[i], t)
                examined += 1
                if rng.random() < min(1.0, p_full[d] /
                                      max(p_draft[d], 1e-300)):
                    ev = self._emit(req, d)
                    if ev:
                        events.append(ev)
                    emitted += 1
                    accepted += 1
                    continue
                res = np.maximum(p_full - p_draft, 0.0)
                tot = res.sum()
                p = res / tot if tot > 0.0 else p_full
                ev = self._emit(req, int(rng.choice(len(p), p=p)))
                if ev:
                    events.append(ev)
                emitted += 1
                rejected = True
                break
            if not rejected and not req.done:
                p_full = _softmax(vlogits[g], t)
                ev = self._emit(req, int(rng.choice(len(p_full),
                                                    p=p_full)))
                if ev:
                    events.append(ev)
                emitted += 1

        # proposed counts only drafts the verifier actually EXAMINED: a
        # request finishing mid-window leaves its tail drafts unjudged,
        # and counting those would deflate the acceptance rate that
        # costmodel.evaluate_speculative takes as alpha
        req.draft_proposed += examined
        req.draft_accepted += accepted
        req.spec_steps += 1
        req.spec_emitted += emitted
        self.draft_proposed_total += examined
        self.draft_accepted_total += accepted
        self.spec_steps_total += 1
        self.spec_emitted_total += emitted
        self._m_spec_proposed.inc(examined)
        self._m_spec_accepted.inc(accepted)
        self._m_spec_cycles.inc()
        self._m_spec_emitted.inc(emitted)
        return events

    # -- telemetry ---------------------------------------------------------

    def aggregate_stats(self) -> dict:
        out = super().aggregate_stats()
        r = self.obs.registry
        proposed = int(r.value("serving_spec_draft_proposed_total"))
        accepted = int(r.value("serving_spec_draft_accepted_total"))
        cycles = int(r.value("serving_spec_cycles_total"))
        emitted = int(r.value("serving_spec_tokens_emitted_total"))
        out["spec_gamma"] = self.spec.gamma
        if proposed:
            out["spec_acceptance_rate"] = accepted / proposed
        if cycles:
            out["spec_tokens_per_step"] = emitted / cycles
        return out
