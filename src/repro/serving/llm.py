"""Per-request generation front end: ``LLM`` / ``EngineConfig`` /
``RequestOutput``.

This is the serving surface callers program against — the engine
(:class:`repro.serving.engine.OfflineEngine`) stays the scheduling core,
but nobody should have to scrape ``SequenceState`` internals or hand-wire
pools/offloaders/backends:

    llm = LLM("yi-9b", config=EngineConfig(mb_size=2, num_microbatches=2))
    outs = llm.generate(prompts, SamplingParams(temperature=0.8, top_p=0.95))
    for o in outs:
        print(o.request_id, o.finish_reason, o.token_ids)

Sampling params are **per request**: ``generate`` accepts one
``SamplingParams`` for all prompts or one per prompt, and a single engine
run serves greedy and sampled requests side by side in the same
continuously-batched pipe.  Outputs are reproducible functions of
``(config.seed, request_id)`` across backends, microbatch layout, and
admission order.

``EngineConfig`` consolidates the engine's construction knobs into one
validated object; ``EngineConfig.plan(...)`` carries the §4.3 planner
arguments (measured stage time + link latency → N_B / batch / pools) and
subsumes ``OfflineEngine.from_plan``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence, Union

from repro.config import ModelConfig, get_arch, reduced_config
from repro.serving.engine import OfflineEngine
from repro.serving.kv_cache import PoolConfig
from repro.serving.request import (FinishReason, Request, SamplingParams,
                                   SequenceState, Status)

_BACKENDS = ("local", "pipelined")


@dataclass
class EngineConfig:
    """Everything needed to build an :class:`OfflineEngine`, validated.

    Either set the knobs directly, or build one via :meth:`plan` to derive
    (``num_microbatches``, ``mb_size``, pool split) from a measured stage
    time + link latency through the paper's §4.3 planner.
    """
    mb_size: int = 4                  # sequences per microbatch
    num_microbatches: int = 1         # N_B
    pool: Optional[PoolConfig] = None
    offload: bool = True              # double-buffer the global pools
                                      # (no-op when the pool has none)
    backend: str = "local"            # "local" | "pipelined"
    n_stages: int = 2                 # N_S (pipelined backend)
    seed: int = 0
    mesh: Optional[object] = None
    # chunked prefill: prompts are admitted in budgeted chunks interleaved
    # with decode ticks (fully-paged archs; recurrent/sliding-window archs
    # fall back to exact-length prefill).  0 = derive a default — 32
    # tokens, or ~the planned per-microbatch batch under .plan() so one
    # chunk costs <= one decode tick of model FLOPs.
    prefill_chunk: int = 0            # tokens per chunk (0 = auto)
    max_prefill_tokens_per_tick: int = 0   # per-tick budget (0 = one chunk)
    prefill_mode: str = "auto"        # "auto" | "chunked" | "exact"
    # deterministic fault injection (tests / drills): a
    # repro.distributed.elastic.FaultPlan consumed by the pipelined
    # backend — dropped ticks are re-injected by the engine, outputs stay
    # bit-identical to an undisturbed run
    fault_plan: Optional[object] = None
    # inter-stage link seam (pipelined backend): a
    # repro.distributed.transport.Transport instance, a DeploymentPlan,
    # or a float (uniform simulated one-way latency in seconds).  None =
    # InProcessTransport, today's zero-cost shard_map links.  Simulated
    # links never touch the computation — outputs stay bit-identical —
    # they account per-link latency/bandwidth on a virtual clock.
    transport: Optional[object] = None
    # "circular" is DeServe §4.3 (the default); "round_flush" reproduces
    # the vLLM-PP baseline (pipe drained every token round) for the
    # latency-curve comparison
    schedule: str = "circular"
    # wire codec for the inter-stage activation payload (pipelined
    # backend): "fp32" ships raw compute-dtype activations (bit-identical
    # outputs); "int8" quantizes per row INSIDE the tick jits — one f32
    # scale per row travels with the payload — ~4x fewer bytes on every
    # ring link at a bounded logit perturbation.  The backend wraps a
    # bookkeeping transport in CompressedTransport so the accounted wire
    # bytes equal the packed payload.  (top-k has no in-jit path: it
    # remains accounting-only via CompressedTransport(method="topk").)
    wire_dtype: str = "fp32"
    # runtime invariant auditor (repro.analysis.invariants): audits page
    # accounting, the Status FSM, transport books, and jit cache sizes
    # after every submit/step/reshard, raising InvariantViolation at the
    # tick that corrupted state.  None = follow the REPRO_STRICT
    # environment variable (the test suite defaults it on); True/False
    # force it either way.  Host-side bookkeeping only — no device syncs.
    strict: Optional[bool] = None
    # paged-decode attention: KV pages per DMA batch and compute step
    # (0 = autotuned from the bytes of one whole page; see
    # repro.kernels.paged_attention.tuned_pages_per_block)
    attn_pages_per_block: int = 0
    # decode-tick sampling epilogue: replace the full-vocab sort with one
    # lax.top_k partition when every sampled row's top_k fits the cap —
    # bit-identical outputs either way (ineligible ticks fall back in-jit)
    sample_fast_path: bool = True
    # §4.2 offload swaps: keep the departing microbatch's host copy as a
    # lazy device future (D2H overlaps the next tick) instead of a
    # blocking numpy materialisation at the tick boundary
    offload_async: bool = True
    # online serving: share fully-prefilled prompt blocks across requests
    # with a common prefix (refcounted paged-KV sharing — a prefix hit
    # adopts the cached pages and starts prefill past them).  Requires
    # chunked prefill; local pages only (global pools parity-swap).
    prefix_cache: bool = False
    # latency-SLO admission shaping (repro.serving.engine.SLOConfig):
    # sheds the per-tick prefill token budget while smoothed tick time
    # exceeds the inter-token target, restores it when the oldest queued
    # request nears the TTFT target.  None = no shaping.
    slo: Optional[object] = None
    # flight recorder (repro.obs): None/False = off (zero-cost — the hot
    # path carries no recorder), True = default-capacity TraceRecorder,
    # int = ring capacity, or an existing TraceRecorder instance.  The
    # recorder threads through backend/transport/offloader and surfaces
    # as engine.recorder (export via repro.obs.write_chrome_trace) and
    # per-request as RequestOutput.trace.
    trace: object = None
    plan_args: Optional[dict] = None  # set by .plan(); overrides mb_size /
                                      # num_microbatches / pool / offload

    def __post_init__(self) -> None:
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {_BACKENDS}, "
                             f"got {self.backend!r}")
        if self.mb_size < 1:
            raise ValueError(f"mb_size must be >= 1, got {self.mb_size}")
        if self.num_microbatches < 1:
            raise ValueError("num_microbatches must be >= 1, "
                             f"got {self.num_microbatches}")
        if self.n_stages < 1:
            raise ValueError(f"n_stages must be >= 1, got {self.n_stages}")
        if self.prefill_mode not in ("auto", "chunked", "exact"):
            raise ValueError("prefill_mode must be 'auto'|'chunked'|'exact'"
                             f", got {self.prefill_mode!r}")
        if self.prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, "
                             f"got {self.prefill_chunk}")
        if self.max_prefill_tokens_per_tick < 0:
            raise ValueError("max_prefill_tokens_per_tick must be >= 0, "
                             f"got {self.max_prefill_tokens_per_tick}")
        if self.prefill_chunk and self.max_prefill_tokens_per_tick and \
                self.max_prefill_tokens_per_tick < self.prefill_chunk:
            raise ValueError(
                f"max_prefill_tokens_per_tick="
                f"{self.max_prefill_tokens_per_tick} < prefill_chunk="
                f"{self.prefill_chunk}: the per-tick budget must fit at "
                "least one chunk")
        if self.plan_args is None and self.backend == "pipelined" \
                and self.num_microbatches < self.n_stages:
            raise ValueError(
                f"pipelined backend needs num_microbatches >= n_stages "
                f"(N_B >= N_S), got N_B={self.num_microbatches} < "
                f"N_S={self.n_stages}")
        if self.fault_plan is not None and self.backend != "pipelined":
            raise ValueError(
                "fault_plan requires backend='pipelined' — the local "
                "backend has no stages to drop")
        if self.schedule not in ("circular", "round_flush"):
            raise ValueError("schedule must be 'circular'|'round_flush', "
                             f"got {self.schedule!r}")
        if self.wire_dtype not in ("fp32", "int8"):
            raise ValueError("wire_dtype must be 'fp32'|'int8', got "
                             f"{self.wire_dtype!r} (top-k stays wire-byte "
                             "accounting only — no in-jit codec)")
        if self.backend != "pipelined" and (self.transport is not None or
                                            self.schedule != "circular" or
                                            self.wire_dtype != "fp32"):
            raise ValueError(
                "transport / schedule / wire_dtype require "
                "backend='pipelined' — the local backend has no stage "
                "boundaries for a link to cross")
        if self.attn_pages_per_block < 0:
            raise ValueError("attn_pages_per_block must be >= 0 (0 = "
                             f"autotuned), got {self.attn_pages_per_block}")
        if self.prefix_cache and self.prefill_mode == "exact":
            raise ValueError(
                "prefix_cache=True needs chunked prefill (a prefix hit "
                "resumes prefill mid-prompt via the chunk cursor) — "
                "prefill_mode='exact' cannot share prompt blocks")

    @classmethod
    def plan(cls, *, n_stages: Optional[int] = None,
             stage_time: float, latency: Optional[float] = None,
             m_kv_bytes: float, page_size: int = 16,
             max_pages_per_seq: int = 16, bandwidth: float = 0.0,
             use_offload: bool = True, max_microbatches: int = 64,
             choice=None, mb_size_cap: int = 0, backend: str = "local",
             seed: int = 0, mesh=None, prefill_chunk: int = 0,
             max_prefill_tokens_per_tick: int = 0,
             prefill_mode: str = "auto",
             fault_plan: Optional[object] = None,
             deployment: Optional[object] = None,
             transport: Optional[object] = None,
             schedule: str = "circular",
             wire_dtype: str = "fp32",
             prefix_cache: bool = False,
             slo: Optional[object] = None,
             trace: object = None,
             strict: Optional[bool] = None) -> "EngineConfig":
        """A config whose (N_B, per-microbatch batch, pool split) are
        derived by ``repro.core.scheduler.plan_schedule`` at build time —
        the planned counterpart of hand-set knobs (subsumes
        ``OfflineEngine.from_plan``).  ``prefill_chunk=0`` derives the
        chunk from the plan: ~the per-microbatch decode batch, so one
        chunk costs at most one decode tick of stage time — shrunk
        further on a bandwidth-capped deployment so one chunk's wire
        time also fits a stage tick (the thin-link rule; see
        ``serving.engine.prefill_chunk_cap``).

        ``deployment`` — a :class:`repro.distributed.transport
        .DeploymentPlan` (e.g. from ``framework.registry.match``):
        supplies ``n_stages`` (its stage count), ``latency`` (its
        **max ring-link latency** — the slowest link sets the §4.3
        bubble budget, replacing a scalar guess) plus the full per-link
        ``link_latencies`` the planner now consumes, the worst
        ``LinkSpec`` that caps the prefill chunk, and, on the pipelined
        backend, a per-link :class:`SimulatedLinkTransport` unless an
        explicit ``transport`` is given."""
        link_latencies = worst_link = None
        if deployment is not None:
            if n_stages is None:
                n_stages = deployment.n_stages
            if latency is None:
                latency = deployment.max_link_latency
            if transport is None and backend == "pipelined":
                transport = deployment.transport()
            link_latencies = list(deployment.link_latencies)
            worst_link = deployment.worst_link
        if n_stages is None or latency is None:
            raise ValueError("EngineConfig.plan needs n_stages= and "
                             "latency= (or a deployment= plan supplying "
                             "both)")
        return cls(backend=backend, n_stages=n_stages, seed=seed, mesh=mesh,
                   prefill_chunk=prefill_chunk,
                   max_prefill_tokens_per_tick=max_prefill_tokens_per_tick,
                   prefill_mode=prefill_mode, fault_plan=fault_plan,
                   transport=transport, schedule=schedule,
                   wire_dtype=wire_dtype, prefix_cache=prefix_cache,
                   slo=slo, trace=trace, strict=strict,
                   plan_args=dict(
                       n_stages=n_stages, stage_time=stage_time,
                       latency=latency, link_latencies=link_latencies,
                       worst_link=worst_link, m_kv_bytes=m_kv_bytes,
                       page_size=page_size,
                       max_pages_per_seq=max_pages_per_seq,
                       bandwidth=bandwidth, use_offload=use_offload,
                       max_microbatches=max_microbatches, choice=choice,
                       mb_size_cap=mb_size_cap))

    def build(self, cfg: ModelConfig, params, rt) -> OfflineEngine:
        """Construct the engine this config describes."""
        if self.attn_pages_per_block and \
                rt.attn_pages_per_block != self.attn_pages_per_block:
            rt = rt.replace(attn_pages_per_block=self.attn_pages_per_block)
        if self.plan_args is not None:
            return OfflineEngine.from_plan(
                cfg, params, rt, backend=self.backend, seed=self.seed,
                mesh=self.mesh, prefill_chunk=self.prefill_chunk,
                max_prefill_tokens_per_tick=self.max_prefill_tokens_per_tick,
                prefill_mode=self.prefill_mode, fault_plan=self.fault_plan,
                transport=self.transport, schedule=self.schedule,
                wire_dtype=self.wire_dtype,
                sample_fast_path=self.sample_fast_path,
                offload_async=self.offload_async,
                prefix_cache=self.prefix_cache, slo=self.slo,
                trace=self.trace, strict=self.strict,
                **self.plan_args)
        pool = self.pool or PoolConfig()
        offloader = None
        if self.offload and pool.n_global_pages:
            from repro.core.offload import DoubleBufferOffloader
            offloader = DoubleBufferOffloader(pool, self.num_microbatches,
                                              async_swap=self.offload_async)
        return OfflineEngine(
            cfg, params, rt, mb_size=self.mb_size,
            num_microbatches=self.num_microbatches, pool=pool,
            offloader=offloader, seed=self.seed, backend=self.backend,
            n_stages=self.n_stages, mesh=self.mesh,
            prefill_chunk=self.prefill_chunk,
            max_prefill_tokens_per_tick=self.max_prefill_tokens_per_tick,
            prefill_mode=self.prefill_mode, fault_plan=self.fault_plan,
            transport=self.transport, schedule=self.schedule,
            wire_dtype=self.wire_dtype,
            sample_fast_path=self.sample_fast_path,
            offload_async=self.offload_async,
            prefix_cache=self.prefix_cache, slo=self.slo,
            trace=self.trace, strict=self.strict)


@dataclass
class RequestOutput:
    """What a caller gets back for one request — no engine internals."""
    request_id: int
    prompt: List[int]
    token_ids: List[int]              # generated tokens so far
    finished: bool
    finish_reason: Optional[str]      # "eos" | "length" | "page_budget";
                                      # None while in flight / aborted
    status: str                       # Status value ("queued", ...)
    logprobs: Optional[List[float]] = None    # per token, if requested
    latency_steps: Optional[int] = None       # submit -> finish, engine steps
    latency_s: Optional[float] = None         # submit -> finish, wall clock
    ttft_s: Optional[float] = None            # submit -> first token sampled
    # per-request flight-recorder snapshot (EngineConfig(trace=...) on):
    # queue_wait_s / ttft_s / inter_token_s, chunks, pages,
    # prefix_hit_tokens — None when tracing is off
    trace: Optional[dict] = None

    @classmethod
    def from_seq(cls, seq: SequenceState,
                 trace: Optional[dict] = None) -> "RequestOutput":
        reason = seq.finish_reason()
        return cls(
            request_id=seq.request.request_id,
            prompt=list(seq.request.prompt),
            token_ids=list(seq.generated),
            finished=seq.status is Status.FINISHED,
            finish_reason=reason.value if reason is not None and
            seq.status is Status.FINISHED else None,
            status=seq.status.value,
            logprobs=list(seq.logprobs) if seq.logprobs is not None else None,
            latency_steps=seq.latency_steps,
            latency_s=seq.latency_s,
            ttft_s=seq.ttft_s,
            trace=trace)


class LLM:
    """Front door for offline generation over the DeServe engine.

    ``model`` is an arch name (``"yi-9b"``) or a :class:`ModelConfig`.
    By default the registered arch is shrunk with ``reduced_config`` (CPU
    scale) and parameters are randomly initialised from ``config.seed``;
    pass ``reduced=False`` and/or ``params=`` for real deployments.
    Without ``rt`` the dtype follows the configuration: bf16 at published
    widths, fp32 for reduced ones (``models.common.serving_runtime``).
    On the pipelined backend the random parameters are built where each
    stage keeps them, never whole on one device.
    """

    def __init__(self, model: Union[str, ModelConfig], *,
                 config: Optional[EngineConfig] = None, params=None,
                 rt=None, reduced: bool = True):
        import jax

        from repro.models import model as model_lib
        from repro.models.common import serving_runtime

        cfg = get_arch(model) if isinstance(model, str) else model
        if reduced and isinstance(model, str):
            cfg = reduced_config(cfg)
        self.config = config or EngineConfig()
        self.cfg = cfg
        self.rt = rt or serving_runtime(cfg)
        if params is None:
            params = model_lib.init_params(
                cfg, jax.random.PRNGKey(self.config.seed), self.rt,
                shardings=self._stage_placement())
        self.params = params
        self.engine = self.config.build(cfg, params, self.rt)
        self._next_id = 0

    def _stage_placement(self):
        """The pipelined backend's per-stage placement, or None for the
        local backend."""
        from repro.core import pipeline as PL
        if self.config.backend != "pipelined":
            return None
        n = self.config.n_stages
        return PL.stage_shardings(self.cfg,
                                  self.config.mesh or PL.stage_mesh(n), n)

    # ------------------------------------------------------------------

    def _make_requests(self, prompts: Sequence[Sequence[int]],
                       sampling_params) -> List[Request]:
        if sampling_params is None:
            sampling_params = self.engine.default_sampling
        if isinstance(sampling_params, SamplingParams):
            sampling_params = [sampling_params] * len(prompts)
        if len(sampling_params) != len(prompts):
            raise ValueError(
                f"got {len(prompts)} prompts but "
                f"{len(sampling_params)} sampling_params — pass one "
                "SamplingParams, or exactly one per prompt")
        reqs = []
        for p, sp in zip(prompts, sampling_params):
            reqs.append(Request(self._next_id, [int(t) for t in p],
                                dataclasses.replace(sp)))
            self._next_id += 1
        return reqs

    def generate(self, prompts: Sequence[Sequence[int]],
                 sampling_params: Union[SamplingParams,
                                        Sequence[SamplingParams],
                                        None] = None, *,
                 max_steps: int = 100_000) -> List[RequestOutput]:
        """Generate to completion for every prompt; returns one
        :class:`RequestOutput` per prompt, in prompt order.  If
        ``max_steps`` is exhausted first, in-flight outputs come back with
        ``finished=False`` (and ``engine.stats.aborted`` is set)."""
        seqs = self._submit(prompts, sampling_params)
        self.engine.run(max_steps=max_steps)
        return [RequestOutput.from_seq(
            s, trace=self.engine.request_trace(s.request.request_id))
            for s in seqs]

    def generate_iter(self, prompts: Sequence[Sequence[int]],
                      sampling_params: Union[SamplingParams,
                                             Sequence[SamplingParams],
                                             None] = None, *,
                      max_steps: int = 100_000
                      ) -> Iterator[List[RequestOutput]]:
        """Streaming form: yields the full output snapshot (finished and
        in-flight requests, prompt order) after every engine step, then a
        final snapshot.  Mirrors ``run()``'s drain surfacing: exhausting
        ``max_steps`` with work pending sets ``engine.stats.aborted``."""
        seqs = self._submit(prompts, sampling_params)
        self.engine.stats.aborted = False
        steps = 0
        while steps < max_steps and self.engine.step():
            steps += 1
            yield [RequestOutput.from_seq(s) for s in seqs]
        if steps >= max_steps and self.engine.pending():
            self.engine.stats.aborted = True
        # only the final snapshot carries per-request traces (the
        # per-step snapshots stay cheap)
        yield [RequestOutput.from_seq(
            s, trace=self.engine.request_trace(s.request.request_id))
            for s in seqs]

    def _submit(self, prompts, sampling_params) -> List[SequenceState]:
        reqs = self._make_requests(prompts, sampling_params)
        return self.engine.submit(reqs)

    # ------------------------------------------------------------------

    def stats(self) -> Dict:
        return self.engine.throughput_report()


def __getattr__(name):
    # lazy re-exports so `from repro.serving.llm import OnlineLLM` works
    # without importing threading machinery on the offline path
    if name in ("OnlineLLM", "RequestStream", "StreamEvent"):
        from repro.serving import online
        return getattr(online, name)
    if name == "SLOConfig":
        from repro.serving.engine import SLOConfig
        return SLOConfig
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = ["LLM", "EngineConfig", "RequestOutput", "SamplingParams",
           "FinishReason", "OnlineLLM", "RequestStream", "StreamEvent",
           "SLOConfig"]
