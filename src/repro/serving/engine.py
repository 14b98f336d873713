"""Continuous-batching serving engine over the paged packed-KV4 pool.

Ties together the scheduler (admission / chunked prefill / decode batch
formation), the page pool (wire-format KV storage), and two jitted step
functions (launch/steps.py):

  * ``prefill_chunk`` — one (1, prefill_chunk) slice of one prompt;
  * ``decode``        — one token for every decode slot at once, through
    the paged decode-attention Pallas kernel.

Both are shape-static (chunk width, decode batch width, block-table
width), so the whole serving loop compiles exactly twice. Inactive
decode slots ride along pointing at the pool's null page.

    eng = Engine(cfg, qparams)
    h = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=8))
    for tok in eng.stream(h):
        ...
    print(h.stats())
"""
from __future__ import annotations

import time
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.kv_attention import context_pages
from repro.models.model import check_paged_support
from repro.obs import Observability
from repro.serving.kv_pool import PagedKVPool, PoolConfig
from repro.serving.scheduler import (Request, SamplingParams, Scheduler,
                                     SchedulerConfig)


class Engine:
    def __init__(self, cfg: ModelConfig, params,
                 pool_config: Optional[PoolConfig] = None,
                 sched_config: Optional[SchedulerConfig] = None,
                 clock=time.monotonic, mesh=None,
                 obs: Optional[Observability] = None,
                 slos=None):
        """``mesh`` (a ("data", "model") Mesh, e.g. ``make_smoke_mesh``)
        makes the engine mesh-native: the jitted steps run inside
        shard_map with weights tensor-parallel on "model", the paged pool
        sharded on kv_heads over "model" and pages over "data", and
        decode slots partitioned over "data". The public API and the
        greedy token streams are unchanged — sharded steps are bit-exact
        vs the single-device ones (docs/sharding.md). A 1-device mesh
        (or None) keeps the original single-device path.

        ``obs`` (``repro.obs.Observability``) is the engine's metrics
        registry + span tracer; by default the engine creates its own
        around ``clock``. Every layer of the stack reports into it
        (docs/observability.md) and it backs ``aggregate_stats()``,
        ``metrics_snapshot()`` and the ``--metrics-out``/``--trace-out``
        artifacts. Instrumentation is host-side only — the traced/jitted
        step programs are unchanged.

        ``slos`` (iterable of ``repro.obs.slo.SLO``) arms the SLO
        watchdog: the engine feeds ``ttft``/``tpot`` at emit time and
        ``queue_depth`` once per scheduler iteration, and violations
        show up as counters + trace instants (docs/observability.md
        §SLOs).
        """
        from repro.launch import steps as S
        from repro.obs.slo import attach_engine_slos
        check_paged_support(cfg)
        self.cfg = cfg
        self._clock = clock
        self.obs = obs if obs is not None else Observability(clock=clock)
        self._init_metrics()
        self.slo = attach_engine_slos(self, slos)
        self._attr = None  # StepAttribution, built by attribute_steps()
        self.mesh = mesh if (mesh is not None and mesh.size > 1) else None
        pool_config = pool_config or PoolConfig()
        sched_config = sched_config or SchedulerConfig()
        if self.mesh is not None:
            from repro.distributed import tp
            mways = tp.mesh_axis_size(self.mesh, "model")
            dways = tp.mesh_axis_size(self.mesh, "data")
            tp.validate_tp_config(cfg, mways)
            if sched_config.max_decode_batch % dways:
                raise ValueError(
                    f"max_decode_batch={sched_config.max_decode_batch} "
                    f"must divide over the data axis ({dways}): each data "
                    f"shard owns a contiguous slice of decode slots")
            self._data_ways = dways
            self._param_specs = tp.param_pspecs(params, axis="model")
            self._pool_specs = tp.pool_pspecs(cfg, pool_config, self.mesh)
            params = tp.device_put_tree(params, self._param_specs,
                                        self.mesh)
        else:
            self._data_ways = 1
            self._param_specs = self._pool_specs = None
        self.params = params
        self.pool = PagedKVPool(cfg, pool_config,
                                n_shards=self._data_ways, obs=self.obs)
        if self.mesh is not None:
            from repro.distributed import tp
            self.pool.state = tp.device_put_tree(
                self.pool.state, self._pool_specs, self.mesh)
        # KV2 precision ladder: armed by PoolConfig.kv2_pages > 0. The
        # decode step gains a tier-table argument and routes each page
        # through the slab its tier id names; demotion/promotion policy
        # runs host-side around the step (docs/serving.md §ladder).
        self._kv2 = self.pool.kv2_armed
        if self._kv2 and self.mesh is not None:
            raise NotImplementedError(
                "the KV2 precision ladder is unsharded-only "
                "(kv2_pages > 0 with a mesh is not wired up)")
        self.sched = Scheduler(self.pool, sched_config, obs=self.obs)
        scfg = self.sched.cfg
        self._chunk = scfg.prefill_chunk
        self._n_slots = scfg.max_decode_batch
        self._n_page_steps = scfg.max_pages_per_seq
        # donate the pool state: the old pages buffer is dead the moment a
        # step returns, and without aliasing every token would copy the
        # whole pool (exactly the HBM traffic the paged design removes)
        self._prefill_fn = jax.jit(
            S.make_engine_prefill_chunk(cfg, mesh=self.mesh,
                                        param_specs=self._param_specs,
                                        pool_specs=self._pool_specs),
            donate_argnums=(1,))
        self._decode_fn = jax.jit(
            S.make_engine_decode(cfg, kv2=self._kv2, mesh=self.mesh,
                                 param_specs=self._param_specs,
                                 pool_specs=self._pool_specs),
            donate_argnums=(1,))
        self._rngs: Dict[int, np.random.Generator] = {}
        self.steps = 0
        # per-layer measured wire-format telemetry (lazily sized (L,) on
        # the first step's telemetry): MEASURED packed activation bytes vs
        # the dense int8 baseline, plus token-weighted MSB4 sparsity,
        # summed over every telemetered token
        self.layer_wire_bytes: Optional[np.ndarray] = None
        self.layer_dense_bytes: Optional[np.ndarray] = None
        self.layer_sparsity_sum: Optional[np.ndarray] = None
        self.wire_tokens = 0

    def _init_metrics(self) -> None:
        """Register the engine's metrics (idempotent via the registry's
        create-or-get). Scheduler/pool metrics register in their own
        constructors against the same registry."""
        r = self.obs.registry
        self._m_steps = r.counter(
            "serving_engine_steps_total", "scheduler iterations run",
            unit="steps")
        self._m_tokens = r.counter(
            "serving_tokens_processed_total", "compute tokens through the "
            "jitted steps, by phase", unit="tokens", labelnames=("phase",))
        self._m_emitted = r.counter(
            "serving_tokens_emitted_total", "sampled tokens handed to "
            "requests", unit="tokens")
        self._m_ttft = r.histogram(
            "serving_ttft_seconds", "request arrival to first emitted "
            "token", unit="seconds")
        self._m_tpot = r.histogram(
            "serving_tpot_seconds", "gap between consecutive emitted "
            "tokens of one request", unit="seconds")
        self._m_attn_pages = r.counter(
            "serving_paged_attn_pages_total", "grid steps of one paged "
            "decode-attention call (decode slots x block-table columns) "
            "that read a context page or skip past the context",
            unit="pages", labelnames=("kind",))
        self._m_wire = r.counter(
            "serving_wire_bytes_total", "measured packed-wire activation "
            "bytes (inter-layer hidden stream)", unit="bytes")
        self._m_dense = r.counter(
            "serving_dense_bytes_total", "dense int8 baseline bytes for "
            "the same activations", unit="bytes")
        self._g_pool_free = r.gauge(
            "serving_pool_pages_free", "free pages across all shards",
            unit="pages")
        self._g_pool_util = r.gauge(
            "serving_pool_utilization_ratio", "fraction of usable pages "
            "allocated", unit="ratio")
        self._g_layer_wire = r.gauge(
            "serving_layer_wire_bytes_per_token", "measured wire bytes "
            "per telemetered token entering each layer", unit="bytes",
            labelnames=("layer",))
        self._g_layer_sparsity = r.gauge(
            "serving_layer_msb_sparsity_ratio", "token-weighted MSB4 "
            "sub-precision sparsity of the hidden stream entering each "
            "layer", unit="ratio", labelnames=("layer",))
        self._g_kv2_used = r.gauge(
            "serving_pool_kv2_pages_used", "pages currently held at the "
            "KV2 tier (0 when the ladder is disarmed)", unit="pages")
        self._g_kv_saved = r.gauge(
            "serving_pool_kv_bytes_saved", "KV HBM bytes currently freed "
            "by demoted pages (KV4 cost minus KV2 cost of held KV2 "
            "pages)", unit="bytes")

    # -- public API --------------------------------------------------------

    def submit(self, prompt: List[int],
               sampling: SamplingParams = SamplingParams()) -> Request:
        """Enqueue a request; returns its handle (tokens land on
        ``handle.out_tokens`` as the engine steps)."""
        return self.sched.submit([int(t) for t in prompt], sampling,
                                 self._clock())

    def stream(self, req: Request) -> Iterator[int]:
        """Drive the engine until ``req`` finishes, yielding its tokens
        as they are produced (other in-flight requests progress too)."""
        seen = 0
        while True:
            while seen < len(req.out_tokens):
                yield req.out_tokens[seen]
                seen += 1
            if req.done:
                return
            self.step()

    def run(self, max_steps: int = 100_000) -> None:
        """Step until every submitted request has finished."""
        for _ in range(max_steps):
            if not self.sched.has_work():
                return
            self.step()
        raise RuntimeError(f"engine did not drain in {max_steps} steps")

    def step(self) -> List[Tuple[int, int]]:
        """One scheduler iteration. Returns [(rid, token), ...] emitted.

        Each phase (schedule / per-chunk prefill / decode batch / demote)
        is spanned on the tracer's engine track and timed into
        ``serving_step_seconds{phase=}`` by one ``Observability.phase``
        — all host-side, around (never inside) the jitted calls.
        """
        obs = self.obs
        events: List[Tuple[int, int]] = []
        with obs.tracer.span("engine_step", step=self.steps):
            if self._kv2:
                self.pool.tick()
            with obs.phase("schedule",
                           waiting=len(self.sched.waiting)) as span:
                plan = self.sched.schedule()
                span.set(decode=len(plan.decode))
            if self.slo is not None:
                self.slo.observe("queue_depth", float(len(self.sched.waiting)))
            for req, start, n in plan.prefill:
                with obs.phase("prefill", "prefill_chunk", rid=req.rid,
                               start=start, n=n):
                    events.extend(self._run_prefill_chunk(req, start, n))
                self._m_tokens.inc(n, phase="prefill")
            if plan.decode:
                with obs.phase("decode", "decode_batch",
                               slots=len(plan.decode)) as span:
                    events.extend(self._run_decode(plan.decode, span))
            if self._kv2:
                # background cold sweep AFTER the decode writes landed:
                # a page demoted here is first read (tier-routed) next
                # step, so the step that demotes never races its reader
                with obs.phase("demote"):
                    self.pool.demote_cold()
        self._m_steps.inc()
        self.steps += 1
        return events

    # -- performance attribution ------------------------------------------

    def attribute_steps(self, hw=None):
        """Attribute the engine's jitted steps against their compiled HLO.

        Lowers + compiles each serving step (prefill_chunk / decode; the
        speculative engine extends this with draft / verify) against
        abstract avals of its real runtime arguments — same shapes,
        dtypes and shardings, so the analyzed program is the SPMD
        program the engine executes — and registers per-step FLOPs, HBM
        bytes and collective bytes (``serving_step_attr_*``). Explicit
        and idempotent: call once after construction (the bench and
        ``serve.py --attribute`` do); re-attribution is a no-op.

        ``hw`` (``costmodel.HardwareConfig``) sets the roofline peaks
        and the cost-model latency predictor's substrate; defaults to
        the published peaks of the device the engine runs on
        (``costmodel.hardware_for``), which raises for a device without
        a table entry. Returns the ``StepAttribution``.
        """
        from repro.core.costmodel import hardware_for
        from repro.obs.attribution import StepAttribution
        if self._attr is None:
            if hw is None:
                hw = hardware_for(jax.devices()[0].device_kind)
            self._attr = StepAttribution(self.obs, hw=hw)
        sds = jax.ShapeDtypeStruct
        params_a, pool_a = self._attr_abstract_args()
        if "prefill" not in self._attr.phases():
            self._attr.attribute(
                "prefill", self._prefill_fn,
                (params_a, pool_a, sds((1, self._chunk), jnp.int32),
                 sds((), jnp.int32), sds((), jnp.int32),
                 sds((self._data_ways, self._n_page_steps), jnp.int32)),
                tokens_per_step=self._chunk,
                predict_seconds=self._phase_predictor("prefill"))
        if "decode" not in self._attr.phases():
            decode_avals = (params_a, pool_a,
                            sds((self._n_slots,), jnp.int32),
                            sds((self._n_slots,), jnp.int32),
                            sds((self._n_slots, self._n_page_steps),
                                jnp.int32))
            if self._kv2:  # tier tables ride after the block tables
                decode_avals += (
                    sds((self._n_slots, self._n_page_steps), jnp.int32),)
            self._attr.attribute(
                "decode", self._decode_fn, decode_avals,
                tokens_per_step=self._n_slots,
                predict_seconds=self._phase_predictor("decode"))
        return self._attr

    def _attr_abstract_args(self):
        from repro.launch import steps as S
        return S.abstract_like(self.params), S.abstract_like(self.pool.state)

    def _costmodel_shape(self):
        """The engine config as a ``costmodel.LMShape`` (same mapping
        ``launch/serve.py`` uses for its analytic report)."""
        from repro.core import costmodel as CM
        cfg = self.cfg
        return CM.LMShape(cfg.name, cfg.n_layers, cfg.d_model,
                          max(1, cfg.n_heads), max(1, cfg.n_kv_heads),
                          max(1, cfg.d_ff or cfg.moe_d_ff), cfg.vocab,
                          w_bits=cfg.w_bits)

    def _phase_predictor(self, phase: str):
        """sparsity -> predicted seconds/step closure over
        ``costmodel.phase_cost`` (paper §4, Table 1 substrate)."""
        from repro.core import costmodel as CM
        shape = self._costmodel_shape()
        hw = self._attr.hw
        decode = phase != "prefill"
        m_tokens = self._chunk if phase == "prefill" else self._n_slots
        seq_for_attn = self._n_page_steps * self.pool.page_size

        def predict(sparsity: float) -> float:
            layers = CM.lm_linear_layers(
                shape, m_tokens, sparsity, seq_for_attn=seq_for_attn,
                decode=decode)
            cost = CM.phase_cost(layers, hw, sparqle=True)
            return cost.cycles / (hw.freq_ghz * 1e9)
        return predict

    def aggregate_stats(self) -> Dict[str, float]:
        """Pool-level counters to pair with per-request ``req.stats()``.

        ``wire_*`` keys report the MEASURED packed-wire-format accounting
        of the inter-layer hidden activation stream (core/packing.py
        layout; ``models.layers.act_wire_telemetry``), per layer and in
        aggregate — the engine's view of what Eq. 1 predicts
        analytically. Stream-level, not per-projection: norm/clipping
        inside each layer shifts per-projection operand sparsity
        (bench_compression.py measures those sites).

        Integer counters read back from the metrics registry (they are
        incremented at the same sites that used to maintain ad-hoc
        attributes, so the values are identical); the ``wire_*`` floats
        stay sourced from the engine's float64 accumulation arrays so
        summation order — and therefore every historical digit — is
        unchanged.
        """
        self._refresh_gauges()
        r = self.obs.registry
        out = {
            "steps": int(r.value("serving_engine_steps_total")),
            "pool_pages_free": int(r.value("serving_pool_pages_free")),
            "pool_utilization": float(
                r.value("serving_pool_utilization_ratio")),
            "pool_evictions": int(r.value("serving_pool_evictions_total")),
        }
        if self._kv2:
            out["pool_demotions"] = int(
                r.value("serving_pool_demotions_total"))
            out["pool_promotions"] = int(
                r.value("serving_pool_promotions_total"))
            out["kv_bytes_reclaimed"] = int(
                r.value("serving_pool_kv_bytes_reclaimed_total"))
            out["kv2_pages_used"] = int(self.pool.kv2_used)
            out["kv_bytes_saved"] = int(self.pool.kv_bytes_saved())
        if self.layer_wire_bytes is not None and self.wire_tokens:
            wire = float(self.layer_wire_bytes.sum())
            dense = float(self.layer_dense_bytes.sum())
            out["wire_bytes_total"] = wire
            out["wire_compression_pct"] = (1.0 - wire / dense) * 100.0
            out["layer_wire_bytes_per_token"] = (
                self.layer_wire_bytes / self.wire_tokens).tolist()
            out["layer_dense_bytes_per_token"] = (
                self.layer_dense_bytes / self.wire_tokens).tolist()
        return out

    def _refresh_gauges(self) -> None:
        """Push point-in-time state into the registry gauges. Called on
        read (``aggregate_stats``/``metrics_snapshot``), not per step, so
        the hot path never pays for them."""
        self._g_pool_free.set(self.pool.num_free)
        self._g_pool_util.set(self.pool.utilization())
        self._g_kv2_used.set(self.pool.kv2_used)
        self._g_kv_saved.set(self.pool.kv_bytes_saved())
        if self.layer_wire_bytes is not None and self.wire_tokens:
            per_tok = self.layer_wire_bytes / self.wire_tokens
            spars = self.layer_sparsity_sum / self.wire_tokens
            for i in range(per_tok.shape[0]):
                self._g_layer_wire.set(float(per_tok[i]), layer=str(i))
                self._g_layer_sparsity.set(float(spars[i]), layer=str(i))
        self._join_attribution()

    def _join_attribution(self) -> None:
        """Join attributed step costs with measured step wall-times into
        the roofline/drift gauges (read-time, like the other gauges)."""
        if self._attr is None:
            return
        mean_sparsity = 0.0
        if self.layer_sparsity_sum is not None and self.wire_tokens:
            mean_sparsity = float(
                self.layer_sparsity_sum.mean() / self.wire_tokens)
        step_seconds = self.obs.step_seconds
        for phase in self._attr.phases():
            n = step_seconds.count(phase=phase)
            if n:
                self._attr.observe_runtime(
                    phase, step_seconds.mean(phase=phase),
                    sparsity=mean_sparsity)
        if self.layer_wire_bytes is not None and self.wire_tokens:
            from repro.core.packing import PBM_WORD_BITS, pad_k
            kp = pad_k(self.cfg.d_model)
            fixed = kp / 2.0 + (kp // PBM_WORD_BITS) * 4.0  # LSB4 + PBM
            spars = self.layer_sparsity_sum / self.wire_tokens
            predicted = float(sum(fixed + (1.0 - s) * kp / 2.0
                                  for s in spars))  # Eq. 1 per layer
            measured = float(self.layer_wire_bytes.sum() / self.wire_tokens)
            self._attr.observe_wire(measured, predicted)

    def metrics_snapshot(self) -> Dict[str, object]:
        """Refresh gauges and return the full registry snapshot
        (``repro.obs.MetricsRegistry.snapshot`` schema)."""
        self._refresh_gauges()
        return self.obs.registry.snapshot()

    def _account_wire(self, req: Request, wire: float, dense: float,
                      layer_wire: np.ndarray, layer_dense: np.ndarray,
                      layer_spars_weighted: np.ndarray,
                      n_tokens: int) -> None:
        """Fold one telemetered slab of tokens into the byte accounting.

        ``layer_spars_weighted`` is per-layer MSB sparsity already scaled
        by ``n_tokens`` so the engine-level accumulator stays a plain
        token-weighted sum. Draft (LSB4-only) tokens never reach here —
        they carry no telemetry — so ``wire_tokens`` is exactly the
        denominator the byte totals were measured over.
        """
        req.wire_bytes_sum += wire
        req.dense_bytes_sum += dense
        req.wire_tokens += n_tokens
        if self.layer_wire_bytes is None:
            self.layer_wire_bytes = np.zeros(layer_wire.shape[0], np.float64)
            self.layer_dense_bytes = np.zeros(layer_wire.shape[0], np.float64)
            self.layer_sparsity_sum = np.zeros(
                layer_wire.shape[0], np.float64)
        self.layer_wire_bytes += layer_wire
        self.layer_dense_bytes += layer_dense
        self.layer_sparsity_sum += layer_spars_weighted
        self.wire_tokens += n_tokens
        self._m_wire.inc(wire)
        self._m_dense.inc(dense)

    # -- internals ---------------------------------------------------------

    def _block_table_row(self, req: Request) -> np.ndarray:
        row = np.zeros((self._n_page_steps,), np.int32)
        pages = self.pool.pages_of(req.rid)
        row[:len(pages)] = pages
        return row

    def _tier_table_row(self, req: Request) -> np.ndarray:
        """Per-page tier ids parallel to :meth:`_block_table_row` (the
        padded tail is tier 0, matching the KV4 null page it points at)."""
        row = np.zeros((self._n_page_steps,), np.int32)
        tiers = self.pool.tiers_of(req.rid)
        row[:len(tiers)] = tiers
        return row

    def _prefill_tables(self, req: Request) -> np.ndarray:
        """(D, Pmax) block table for the prefill step: one row per data
        shard, the owning shard's row holding the request's (shard-local)
        pages, every other row all-null (D = 1 without a mesh)."""
        tables = np.zeros((self._data_ways, self._n_page_steps), np.int32)
        tables[self.pool.shard_of(req.rid)] = self._block_table_row(req)
        return tables

    def _sample(self, req: Request, logits: np.ndarray) -> int:
        t = req.sampling.temperature
        if t <= 0.0:
            return int(np.argmax(logits))
        rng = self._rngs.setdefault(
            req.rid, np.random.default_rng(req.sampling.seed + req.rid))
        z = (logits.astype(np.float64) - logits.max()) / t
        p = np.exp(z)
        p /= p.sum()
        return int(rng.choice(len(p), p=p))

    def _emit(self, req: Request, token: int) -> Optional[Tuple[int, int]]:
        now = self._clock()
        if req.t_first is None:
            req.t_first = now
            ttft = now - req.arrival
            self._m_ttft.observe(ttft)
            if self.slo is not None:
                self.slo.observe("ttft", ttft)
        elif req.t_last is not None:
            tpot = now - req.t_last
            self._m_tpot.observe(tpot)
            if self.slo is not None:
                self.slo.observe("tpot", tpot)
        req.t_last = now
        self._m_emitted.inc()
        req.context.append(token)
        req.out_tokens.append(token)
        s = req.sampling
        if (req.n_generated >= s.max_new_tokens or
                (s.stop_token is not None and token == s.stop_token)):
            self.sched.finish(req)
            self._rngs.pop(req.rid, None)
        return (req.rid, token)

    def _run_prefill_chunk(self, req: Request, start: int,
                           n: int) -> List[Tuple[int, int]]:
        toks = np.zeros((1, self._chunk), np.int32)
        toks[0, :n] = req.context[start:start + n]
        logits, self.pool.state, tel = self._prefill_fn(
            self.params, self.pool.state, jnp.asarray(toks),
            jnp.asarray(start, jnp.int32), jnp.asarray(n, jnp.int32),
            jnp.asarray(self._prefill_tables(req)))
        req.sparsity_sum += float(tel["sparsity"]) * n
        req.sparsity_n += n
        layer_wire = np.asarray(tel["layer_wire_bytes"], np.float64)
        layer_dense = np.asarray(tel["layer_dense_bytes"], np.float64)
        layer_spars = np.asarray(tel["layer_sparsity"], np.float64)
        self._account_wire(req, float(layer_wire.sum()),
                           float(layer_dense.sum()), layer_wire,
                           layer_dense, layer_spars * n, n)
        if not self.sched.prefill_advanced(req, n):
            return []
        self.sched.to_running(req)
        ev = self._emit(req, self._sample(req, np.asarray(logits[0])))
        return [ev] if ev else []

    def _decode_inputs(self, decode: List[Request]) -> Tuple:
        """The decode step's arguments after the pool state, on the host:
        token, position and block-table arrays (and the tier table when
        the KV2 ladder is armed), one row per decode slot."""
        B = self._n_slots
        token = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        tables = np.zeros((B, self._n_page_steps), np.int32)
        if self._kv2:
            # touch BEFORE snapshotting tables: this step writes K/V at
            # pos, so the page covering it must be KV4 (promote-on-touch)
            # and its coldness stamp refreshed. Touching may swap page
            # ids, hence the ordering.
            ps = self.pool.page_size
            for req in decode:
                fp = (len(req.context) - 1) // ps
                self.pool.touch(req.rid, fp, fp)
            tiers = np.zeros((B, self._n_page_steps), np.int32)
            for req in decode:
                tiers[req.slot] = self._tier_table_row(req)
        for req in decode:
            token[req.slot] = req.context[-1]
            pos[req.slot] = len(req.context) - 1
            tables[req.slot] = self._block_table_row(req)
        return (token, pos, tables) + ((tiers,) if self._kv2 else ())

    def _count_attn_pages(self, pos: np.ndarray) -> Dict[str, int]:
        """Grid steps of one paged decode-attention call at positions
        ``pos`` (every decode slot, active or not) that read a context
        page, and all of its grid steps; the kernel skips the rest.
        Counted into ``serving_paged_attn_pages_total{kind}``."""
        read = int(context_pages(pos, self.pool.page_size).sum())
        steps = pos.shape[0] * self._n_page_steps
        self._m_attn_pages.inc(read, kind="read")
        self._m_attn_pages.inc(steps - read, kind="skipped")
        return {"attn_pages": read, "attn_page_steps": steps}

    def _run_decode(self, decode: List[Request],
                    span) -> List[Tuple[int, int]]:
        """One decode step, in spans nested under ``decode_batch``
        (``span``, which gains the step's attention page counts):
        inputs, launch, wait (logits to the host), readback (telemetry to
        the host), sample, emit. Sampling reads nothing that accounting
        and emission write, so sampling every slot first emits the same
        tokens in the same order."""
        tr = self.obs.tracer
        with tr.span("decode_inputs"):
            host = self._decode_inputs(decode)
            args = tuple(jnp.asarray(a) for a in host)
        span.set(**self._count_attn_pages(host[1]))
        with tr.span("decode_launch"):
            logits, self.pool.state, tel = self._decode_fn(
                self.params, self.pool.state, *args)
        with tr.span("decode_wait", bytes=logits.nbytes):
            logits = np.asarray(logits)
        tel_keys = ("sparsity", "layer_wire_bytes", "layer_dense_bytes",
                    "layer_sparsity")
        with tr.span("decode_readback",
                     bytes=sum(tel[k].nbytes for k in tel_keys)):
            sparsity, layer_wire, layer_dense, layer_spars = (
                np.asarray(tel[k], np.float64) for k in tel_keys)
        with tr.span("decode_sample"):
            tokens = [self._sample(req, logits[req.slot]) for req in decode]
        events = []
        with tr.span("decode_emit"):
            for req, token in zip(decode, tokens):
                req.sparsity_sum += float(sparsity[req.slot])
                req.sparsity_n += 1
                self._account_wire(
                    req, float(layer_wire[:, req.slot].sum()),
                    float(layer_dense[:, req.slot].sum()),
                    layer_wire[:, req.slot], layer_dense[:, req.slot],
                    layer_spars[:, req.slot], 1)
                ev = self._emit(req, token)
                if ev:
                    events.append(ev)
        self._m_tokens.inc(len(decode), phase="decode")
        return events
