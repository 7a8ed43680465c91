"""Offline (throughput-oriented) serving engine with continuous batching.

The engine owns ``N_B`` *microbatches* of ``mb_size`` decode slots each —
the unit the DeServe pipeline keeps in flight.  Each step round-robins one
decode tick over the next microbatch; finished sequences release their pages
and the slot is immediately replenished from the queue (prefill), matching
the paper's workload ("replenishing them as the previous requests are
completed").

Compute is delegated to a pluggable :mod:`repro.serving.backend`: the
engine keeps every piece of host-side bookkeeping (queue, slots, page
allocator, page table, positions, stats) and the backend owns the device
caches and jit entry points.  ``backend="local"`` is the single-device
path; ``backend="pipelined"`` runs the same continuous-batching loop
through the ``N_S``-stage SPMD pipeline (``repro.core.pipeline``), where a
microbatch's decode tick enters the pipe at stage 0 and drains ``N_S − 1``
engine ticks later — the engine therefore applies decode results by the
microbatch id they carry, not the one it just injected.

Sampling is **per request and on device**: every slot carries its own
temperature / top-k / top-p and a PRNG key derived from
``(seed, request_id)`` (token ``t`` folds in ``t``), so one engine serves
mixed greedy+sampled workloads in one continuously-batched pipe and the
output stream of a request is reproducible across backends, microbatch
layout, and admission order.  The front door for callers is
:class:`repro.serving.llm.LLM`; this class is the scheduling core.

KV placement follows §4.2: microbatch ``m`` draws overflow pages from global
pool ``G_{m%2}``; the :class:`repro.core.offload.DoubleBufferOffloader`
swaps the non-resident pool to host between ticks (on TPU this is the
HBM↔host DMA the paper overlaps with compute; on CPU it is an explicit copy
— same bookkeeping, same schedule).

**Prefill is a first-class scheduler phase.**  For fully-paged archs
(every layer kind "attn"/"global") admission is *chunked*: each tick emits
at most one :class:`~repro.serving.backend.PrefillChunk` — up to
``prefill_rows`` queued/continuing prompts x ``prefill_chunk`` tokens,
budgeted by ``max_prefill_tokens_per_tick`` — through a single
fixed-shape chunk jit.  Sequences hold their slot across ticks with
``Status.PREFILLING`` and a ``prefill_pos`` cursor; the first token is
sampled only when the last chunk lands, under the same reproducible
``(seed, request_id, token_idx)`` key as every decode token.  The
device-wide page table keeps prefilling slots parked on the scratch page
(chunks carry their own table rows), so in-flight decode ticks can never
clobber half-written prompt KV; a slot's real table row is pushed — and
the row activated — only once its microbatch has no tick in flight.  On
the pipelined backend, chunks flow stage-to-stage through a second
persistent pipe and *overlap* in-flight decode microbatches.

Recurrent and sliding-window archs keep the exact-length fallback (state
correctness), one sequence at a time, with the pad length bucketed to the
next power of two so the per-length jit cache stays bounded (pad
positions are masked end-to-end — see ``model.prefill``).  All jit entry
points have static shapes.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import ModelConfig
from repro.models.common import Runtime
from repro.serving import kv_cache as kvc
from repro.serving.backend import (DecodeResult, ExecutionBackend,
                                   PrefillChunk, PrefillResult, make_backend)
from repro.serving.request import (EngineStats, Request, SamplingParams,
                                   SequenceState, Status)
from repro.serving.sampler import (RowSampling, fold_in_steps,
                                   sample_batched, token_logprobs)

log = logging.getLogger(__name__)


def _strict_default(strict: Optional[bool]) -> bool:
    """``strict=None`` defers to the ``REPRO_STRICT`` env var (the test
    suite sets it to 1), so the invariant auditor guards every CI run
    without every construction site opting in."""
    if strict is not None:
        return bool(strict)
    return os.environ.get("REPRO_STRICT", "") not in ("", "0")


def _resolve_trace(trace):
    """``trace=`` accepts: None/False (off — the hot path carries no
    recorder and pays nothing), True (a default-capacity
    :class:`~repro.obs.trace.TraceRecorder`), an int (ring capacity), or
    an existing recorder instance (shared across engines/front ends)."""
    if trace is None or trace is False:
        return None
    from repro.obs.trace import TraceRecorder
    if trace is True:
        return TraceRecorder()
    if isinstance(trace, TraceRecorder):
        return trace
    if isinstance(trace, int):
        return TraceRecorder(capacity=trace)
    raise ValueError(
        f"trace must be None/False/True, an int capacity, or a "
        f"TraceRecorder, got {trace!r}")


@functools.partial(jax.jit, static_argnames=("fast",))
def _sample_first(logits, keys, steps, temp, top_k, top_p, *, fast=True):
    """First-token sampling on prefill logits — jitted at module scope so
    the compile caches across engines/prompts (eager ``lax.cond`` inside
    ``sample_batched`` would retrace per call)."""
    toks = sample_batched(logits, fold_in_steps(keys, steps), temp, top_k,
                          top_p, fast_path=fast)
    return toks, token_logprobs(logits, toks)


@dataclasses.dataclass
class SLOConfig:
    """Latency-SLO targets for online serving admission.

    The controller shapes the per-tick prefill/decode token-budget ratio
    (the same lever as ``max_prefill_tokens_per_tick``): when the
    engine's smoothed tick time exceeds ``itl_target_s`` — inter-token
    latency for decoding requests is one tick per microbatch round —
    admission sheds prefill down to ``floor_frac`` of the budget;  when
    the oldest waiting request has been queued for half its
    ``ttft_target_s``, the budget is restored (TTFT risk needs prefill).
    A zero target disables that half of the policy."""
    ttft_target_s: float = 0.0
    itl_target_s: float = 0.0
    floor_frac: float = 0.25
    ewma_alpha: float = 0.2

    def validate(self) -> None:
        if self.ttft_target_s < 0 or self.itl_target_s < 0:
            raise ValueError("SLO targets must be >= 0")
        if not (0.0 < self.floor_frac <= 1.0):
            raise ValueError(
                f"floor_frac must be in (0, 1], got {self.floor_frac}")
        if not (0.0 < self.ewma_alpha <= 1.0):
            raise ValueError(
                f"ewma_alpha must be in (0, 1], got {self.ewma_alpha}")


class SLOController:
    """Deterministic budget shaper for :class:`SLOConfig` (host-side,
    no device state — unit-testable without an engine)."""

    def __init__(self, cfg: SLOConfig):
        cfg.validate()
        self.cfg = cfg
        self.itl_ewma = 0.0

    def observe_tick(self, dt: float) -> None:
        """Feed one engine-tick wall time into the ITL estimate."""
        a = self.cfg.ewma_alpha
        self.itl_ewma = dt if self.itl_ewma == 0.0 else \
            (1.0 - a) * self.itl_ewma + a * dt

    def budget_frac(self, oldest_wait_s: float) -> float:
        """Fraction of the per-tick prefill token budget to admit."""
        c = self.cfg
        if c.ttft_target_s and oldest_wait_s >= 0.5 * c.ttft_target_s:
            return 1.0          # TTFT at risk: prefill must not starve
        if c.itl_target_s and self.itl_ewma > c.itl_target_s:
            return max(c.floor_frac, c.itl_target_s / self.itl_ewma)
        return 1.0


def prefill_chunk_cap(cfg: ModelConfig, rt: Runtime, link, *,
                      stage_time: float,
                      wire_dtype: str = "fp32") -> int:
    """Bandwidth cap on the prefill chunk length, in tokens.

    A chunk of C tokens ships ``C x`` the per-token decode payload over
    every ring link; on a bandwidth-capped link its serialisation time
    is ``C * token_wire_bytes / bandwidth``.  The cap is the largest C
    whose wire time fits one stage tick, so a prefill chunk never
    stretches the cadence the §4.3 planner sized ``N_B`` for.  The
    per-token wire bytes honour the codec: ``d_model * elem_bytes`` raw,
    ``d_model + 4`` packed int8 (one f32 row scale per token).  Returns
    0 when there is nothing to cap (no link, or unlimited bandwidth).
    """
    bw = getattr(link, "bandwidth_bps", 0.0) if link is not None else 0.0
    if not bw or stage_time <= 0:
        return 0
    if wire_dtype == "int8":
        token_bytes = cfg.d_model + 4
    else:
        token_bytes = cfg.d_model * jnp.dtype(rt.compute_dtype).itemsize
    return max(1, int(stage_time * bw // token_bytes))


class OfflineEngine:
    def __init__(self, cfg: ModelConfig, params, rt: Runtime, *,
                 mb_size: int = 4, num_microbatches: int = 1,
                 pool: Optional[kvc.PoolConfig] = None,
                 sampling: Optional[SamplingParams] = None,
                 offloader=None, seed: int = 0,
                 backend="local", n_stages: int = 2, mesh=None,
                 prefill_chunk: int = 0,
                 max_prefill_tokens_per_tick: int = 0,
                 prefill_mode: str = "auto", fault_plan=None,
                 transport=None, schedule: str = "circular",
                 wire_dtype: str = "fp32",
                 sample_fast_path: bool = True, offload_async: bool = True,
                 prefix_cache: bool = False,
                 slo: Optional[SLOConfig] = None,
                 trace=None,
                 strict: Optional[bool] = None):
        self.cfg = cfg
        self.params = params
        self.rt = rt
        self.recorder = _resolve_trace(trace)
        self.mb_size = mb_size
        self.num_microbatches = num_microbatches
        self.batch = mb_size * num_microbatches
        self.pool = pool or kvc.PoolConfig()
        # default for requests submitted with sampling=None (resolved at
        # submit(); explicit per-request params always win — the engine
        # has no global sampling policy)
        self.default_sampling = sampling or SamplingParams()
        self.seed = seed
        self._seed_key = jax.random.PRNGKey(seed)

        # fault recovery re-injects a lost microbatch/chunk with the same
        # tokens at the same positions, which is only bit-transparent when
        # every cache write is position-keyed (paged KV, ring slots).
        # Recurrent state updates are cumulative — re-applying one would
        # double-step the state — so fault injection is gated to archs
        # without recurrent layers (snapshot/restore is a follow-on).
        if fault_plan is not None and cfg.recurrent_layer_count() > 0:
            raise ValueError(
                f"{cfg.name}: fault injection needs position-idempotent "
                "cache writes; recurrent layers accumulate state and "
                "cannot replay a lost tick without a state snapshot")
        self._offloader = offloader
        self._mesh = mesh
        self.n_stages = n_stages
        self.sample_fast_path = sample_fast_path
        self.offload_async = offload_async

        self.backend: ExecutionBackend = make_backend(
            backend, cfg, params, rt, mb_size=mb_size,
            num_microbatches=num_microbatches, pool=self.pool,
            offloader=offloader, n_stages=n_stages, mesh=mesh,
            fault_plan=fault_plan, transport=transport, schedule=schedule,
            wire_dtype=wire_dtype, sample_fast_path=sample_fast_path,
            offload_async=offload_async, recorder=self.recorder)

        # elastic control plane: per-stage EWMA tick times (feeds the
        # admission budget) + the planner/mesh-plan bookkeeping reshard()
        # updates.  Only staged backends report times; the straggler is
        # None on the local path.
        from repro.distributed.elastic import (ElasticPlanner, MeshPlan,
                                               StragglerMitigator)
        stages = getattr(self.backend, "n_stages", None)
        self.straggler = StragglerMitigator(stages) if stages else None
        # lifetime per-stage drain-time totals (reported alongside the
        # straggler's EWMAs — the raw observations that feed admission
        # weighting, otherwise invisible); reset on reshard with the
        # mitigator since the stage count may change
        self._stage_time_total = [0.0] * (stages or 0)
        self._stage_time_count = [0] * (stages or 0)
        self._elastic = ElasticPlanner(model_parallel=1,
                                       pod_size=1 << 30)
        self._mesh_plan = MeshPlan(shape=(stages or 1, 1),
                                   axes=("data", "model"),
                                   devices_used=stages or 1,
                                   devices_spare=0)

        self.alloc = kvc.PageAllocator(self.pool)
        self.table = np.zeros((self.batch, self.pool.max_pages_per_seq),
                              np.int32)
        self.cur_pos = np.zeros((self.batch,), np.int32)   # next position
        self.active = np.zeros((self.batch,), bool)
        self.slots: List[Optional[SequenceState]] = [None] * self.batch
        # per-slot sampling state (set at admission, benign when idle)
        self.samp_keys = np.zeros((self.batch, 2), np.uint32)
        self.samp_temp = np.zeros((self.batch,), np.float32)
        self.samp_top_k = np.zeros((self.batch,), np.int32)
        self.samp_top_p = np.ones((self.batch,), np.float32)

        # ---- chunked-prefill scheduler state -------------------------------
        # chunked prefill requires every layer's KV to live in the shared
        # page pools (writes redirect through per-chunk table rows);
        # recurrent state and sliding-window rings take the exact fallback
        supports_chunked = all(k in ("attn", "global")
                               for k in cfg.layer_kinds())
        if prefill_mode not in ("auto", "chunked", "exact"):
            raise ValueError(
                f"prefill_mode must be 'auto'|'chunked'|'exact', "
                f"got {prefill_mode!r}")
        if prefill_mode == "chunked" and not supports_chunked:
            raise ValueError(
                f"{cfg.name}: prefill_mode='chunked' needs every layer kind "
                "to be paged ('attn'/'global'); recurrent and sliding-window "
                "archs must use exact-length prefill")
        self.chunked_prefill = supports_chunked and prefill_mode != "exact"
        cap = self.pool.max_pages_per_seq * self.pool.page_size
        if not prefill_chunk:           # default chunk: 32 tokens, shrunk
            prefill_chunk = min(32,     # to an explicit per-tick budget
                                max_prefill_tokens_per_tick or 32)
        self.prefill_chunk = min(cap, prefill_chunk)
        if self.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, "
                             f"got {self.prefill_chunk}")
        budget = max_prefill_tokens_per_tick or self.prefill_chunk
        if budget < self.prefill_chunk:
            raise ValueError(
                f"max_prefill_tokens_per_tick={budget} < "
                f"prefill_chunk={self.prefill_chunk}: the per-tick budget "
                "must fit at least one chunk")
        self.max_prefill_tokens_per_tick = budget
        self.prefill_rows = max(1, budget // self.prefill_chunk)
        self.prefilling: List[SequenceState] = []   # own a slot, not done
        self._pending_activation: List[SequenceState] = []
        self._inject_snap: Dict[int, tuple] = {}    # mb -> (active, seqs)
                                                    # at decode injection

        # ---- online-serving policy knobs -----------------------------------
        # prefix caching shares fully-prefilled prompt blocks across
        # requests (refcounted in the allocator); it rides on the chunked
        # path — a prefix hit starts the chunk cursor mid-prompt, which
        # the exact-length fallback cannot do
        if prefix_cache and not self.chunked_prefill:
            raise ValueError(
                f"{cfg.name}: prefix_cache=True needs chunked prefill "
                "(fully-paged archs, prefill_mode != 'exact') — a prefix "
                "hit resumes prefill mid-prompt via the chunk cursor")
        self.prefix_cache: Optional[kvc.PrefixCache] = \
            kvc.PrefixCache(self.alloc) if prefix_cache else None
        self.slo: Optional[SLOController] = \
            SLOController(slo) if slo is not None else None

        self.queue: deque = deque()
        self.finished: List[SequenceState] = []
        self.stats = EngineStats()

        # strict mode: re-audit page accounting, Status FSM, transport
        # books, and jit cache sizes after every submit/step/reshard —
        # pure host bookkeeping, no device syncs (see
        # repro.analysis.invariants)
        self.strict = _strict_default(strict)
        if self.strict:
            from repro.analysis.invariants import EngineAuditor
            self.auditor: Optional[EngineAuditor] = EngineAuditor(self)
        else:
            self.auditor = None

    # ------------------------------------------------------------------
    # planned construction (DeServe §4.3: N_B, batch, pools from the link)
    # ------------------------------------------------------------------

    @classmethod
    def from_plan(cls, cfg: ModelConfig, params, rt: Runtime, *,
                  n_stages: int, stage_time: float, latency: float,
                  m_kv_bytes: float, page_size: int = 16,
                  max_pages_per_seq: int = 16, bandwidth: float = 0.0,
                  use_offload: bool = True, max_microbatches: int = 64,
                  choice=None, mb_size_cap: int = 0, backend="local",
                  sampling: Optional[SamplingParams] = None, seed: int = 0,
                  mesh=None, prefill_chunk: int = 0,
                  max_prefill_tokens_per_tick: int = 0,
                  prefill_mode: str = "auto", fault_plan=None,
                  transport=None, schedule: str = "circular",
                  link_latencies=None, worst_link=None,
                  wire_dtype: str = "fp32",
                  sample_fast_path: bool = True,
                  offload_async: bool = True,
                  prefix_cache: bool = False,
                  slo: Optional[SLOConfig] = None,
                  trace=None,
                  strict: Optional[bool] = None) -> "OfflineEngine":
        """Build an engine whose (N_B, per-microbatch batch, pool split) are
        *derived* from measured stage time + link latency via
        ``repro.core.scheduler.plan_schedule`` — the paper's planner —
        instead of hand-set flags.

        ``m_kv_bytes`` is the per-stage KV budget; ``choice`` may be a
        pre-computed :class:`repro.core.scheduler.ScheduleChoice` (then the
        planner is skipped and the choice is honored as-is).
        ``mb_size_cap`` bounds the per-microbatch batch for reduced/CPU
        runs where the planned batch would not fit the host.

        Prefer :meth:`repro.serving.llm.EngineConfig.plan` — this is the
        low-level entry it resolves to.
        """
        from repro.core import offload as offload_lib
        from repro.core.scheduler import plan_schedule
        if not bandwidth:
            bandwidth = offload_lib.TPU_HOST_DMA_BW
        page_bytes = kvc.kv_bytes_per_page(
            cfg, kvc.PoolConfig(page_size=page_size),
            dtype_bytes=jnp.dtype(rt.compute_dtype).itemsize)
        if page_bytes == 0:
            raise ValueError(
                f"{cfg.name}: from_plan needs at least one paged-attention "
                "layer (pure-recurrent archs have no KV pools to plan)")
        kv_bytes_per_seq = page_bytes * max_pages_per_seq
        if choice is None:
            choice = plan_schedule(
                n_stages=n_stages, stage_time=stage_time, latency=latency,
                link_latencies=link_latencies,
                m_kv_bytes=m_kv_bytes, kv_bytes_per_seq=kv_bytes_per_seq,
                offload_bandwidth=bandwidth, use_offload=use_offload,
                max_microbatches=max_microbatches)
        if choice.offload:
            plan = offload_lib.OffloadPlan.derive(
                m_kv_bytes=m_kv_bytes, page_bytes=page_bytes,
                page_size=page_size, max_pages_per_seq=max_pages_per_seq,
                bandwidth=bandwidth, stage_time=stage_time,
                n_microbatches=choice.n_microbatches)
            pool = plan.pool
        else:
            pool = kvc.PoolConfig(
                page_size=page_size,
                n_local_pages=max(2, int(m_kv_bytes // page_bytes)),
                n_global_pages=0, max_pages_per_seq=max_pages_per_seq)
        mb_size = max(1, choice.per_mb_batch)
        if mb_size_cap:
            mb_size = min(mb_size, mb_size_cap)
        offloader = None
        if choice.offload and pool.n_global_pages:
            offloader = offload_lib.DoubleBufferOffloader(
                pool, choice.n_microbatches, async_swap=offload_async)
        if not prefill_chunk:
            # planner-derived default: a prefill token costs the same model
            # FLOPs as a decode token, so a chunk of ~per-microbatch-batch
            # tokens costs <= one decode tick of stage time and never
            # stretches the stage cadence the planner sized N_B for
            # (floored at 8 so reduced/CPU runs don't degenerate to
            # token-at-a-time prefill)
            prefill_chunk = max(8, mb_size)
            cap = prefill_chunk_cap(cfg, rt, worst_link,
                                    stage_time=stage_time,
                                    wire_dtype=wire_dtype)
            if cap and cap < prefill_chunk:
                # bandwidth-shaped: a chunk payload is C x the decode
                # payload, so on a thin link the FLOPs-derived default
                # would stretch the stage cadence by its serialisation
                # time — shrink the CHUNK (not just the rows) until one
                # chunk's wire time fits a stage tick.  The per-tick
                # admission budget defaults to one chunk, so it follows.
                prefill_chunk = cap
        eng = cls(cfg, params, rt, mb_size=mb_size,
                  num_microbatches=choice.n_microbatches, pool=pool,
                  sampling=sampling, offloader=offloader, seed=seed,
                  backend=backend, n_stages=n_stages, mesh=mesh,
                  prefill_chunk=prefill_chunk,
                  max_prefill_tokens_per_tick=max_prefill_tokens_per_tick,
                  prefill_mode=prefill_mode, fault_plan=fault_plan,
                  transport=transport, schedule=schedule,
                  wire_dtype=wire_dtype, sample_fast_path=sample_fast_path,
                  offload_async=offload_async, prefix_cache=prefix_cache,
                  slo=slo, trace=trace, strict=strict)
        eng.schedule_choice = choice
        return eng

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def submit(self, requests: List[Request]) -> List[SequenceState]:
        cap = self.pool.max_pages_per_seq * self.pool.page_size
        resolved = []
        for r in requests:          # validate all before enqueueing any,
                                    # so a raise never half-admits a batch.
            # ``sampling=None`` resolves to the engine default on a private
            # copy carried by the SequenceState — the caller's (possibly
            # shared) Request object is never written back
            sp = dataclasses.replace(r.sampling if r.sampling is not None
                                     else self.default_sampling)
            sp.validate()
            resolved.append(sp)
            if not r.prompt:
                raise ValueError(
                    f"request {r.request_id}: empty prompt — there is no "
                    "position to prefill or sample the first token from")
            if len(r.prompt) >= cap:
                raise ValueError(
                    f"request {r.request_id}: prompt length {len(r.prompt)} "
                    f">= per-sequence KV capacity {cap} tokens "
                    f"(max_pages_per_seq={self.pool.max_pages_per_seq} x "
                    f"page_size={self.pool.page_size}) — no generation "
                    "budget would remain; raise max_pages_per_seq or "
                    "truncate the prompt")
        now = time.perf_counter()
        seqs = []
        for r, sp in zip(requests, resolved):
            seq = SequenceState(request=r, sampling=sp,
                                submit_step=self.stats.steps,
                                submit_time=now)
            self.queue.append(seq)
            seqs.append(seq)
            if self.recorder is not None:
                # same float as seq.submit_time, so the trace's
                # queue-wait/TTFT math matches the engine's
                self.recorder.request_submit(r.request_id, now,
                                             len(r.prompt))
        self.stats.queue_depth = len(self.queue)
        if self.auditor is not None:
            self.auditor.after_submit()
        return seqs

    def run(self, max_steps: int = 10_000) -> List[SequenceState]:
        """Step until drained (or until ``max_steps``).  Returns finished
        sequences.  Exhausting the step budget with work still pending is
        surfaced: ``stats.aborted`` is set and a warning logged —
        ``pending()`` lists what was left behind."""
        self.stats.aborted = False
        for _ in range(max_steps):
            if not self.step():
                return self.finished
        if self.pending():
            self.stats.aborted = True
            log.warning(
                "OfflineEngine.run(max_steps=%d) exhausted its step budget "
                "with %d request(s) still pending (%d finished) — partial "
                "drain; raise max_steps or keep stepping", max_steps,
                len(self.pending()), len(self.finished))
        return self.finished

    def pending(self) -> List[SequenceState]:
        """Sequences submitted but not finished (queued or in a slot)."""
        return [s for s in self.slots if s is not None] + list(self.queue)

    def status_counts(self) -> Dict[str, int]:
        """Per-status sequence counts across queue, slots, and finished.

        Always writes the result back to ``stats.status_counts`` — the
        cached copy is a *mirror* of this computation, refreshed by every
        caller (``throughput_report()``, metrics snapshots), never a
        source of truth, so it cannot go stale across step/reshard."""
        counts = {s.value: 0 for s in Status}
        for seq in self.pending():
            counts[seq.status.value] += 1
        counts[Status.FINISHED.value] += len(self.finished)
        self.stats.status_counts = counts
        return counts

    # ------------------------------------------------------------------
    # elastic re-sharding (mesh resize mid-run)
    # ------------------------------------------------------------------

    def reshard(self, live_devices: Optional[int] = None, *,
                n_stages: Optional[int] = None, detector=None,
                now: Optional[float] = None) -> dict:
        """Rebuild the pipelined backend with a new stage count mid-run,
        keeping every in-flight request's progress.

        The target is either an explicit ``n_stages`` or derived from the
        live-device count (directly, or from a
        :class:`~repro.distributed.elastic.FailureDetector` at ``now``)
        through :class:`~repro.distributed.elastic.ElasticPlanner` — the
        pipe depth becomes the largest power of two that the live devices,
        ``num_microbatches`` (N_B >= N_S) and the local device count
        admit.

        Sequence: (1) drain the old pipe (both planes) so no tick is lost,
        (2) carry the engine-format cache pytree over — its layout is
        n_stages-independent, stage slicing happens inside the tick jit —
        (3) rebuild :class:`PipelinedBackend` on a fresh ``pod`` mesh
        (params re-split by ``split_scan_params`` at trace time), and
        (4) replay the engine's device-wide page table into the fresh
        backend.  ``seq``/``prefill_pos`` cursors are engine state and
        survive untouched, so no completed token is ever recomputed.

        Offloaded global pools migrate with the rebuild: the old
        backend's per-stage host stores are concatenated into full-period
        arrays (``export_offload_state``, after the drain so the books
        are stable) and re-split for the new stage count
        (``import_offload_state``), so swapped-out parities replay
        byte-identical through the fresh offloaders.

        Returns the planner's resharding plan.  Raises on the local
        backend.
        """
        from repro.distributed.elastic import MeshPlan
        from repro.serving.backend import PipelinedBackend
        if not isinstance(self.backend, PipelinedBackend):
            raise ValueError(
                "reshard: only the pipelined backend has a stage mesh to "
                f"rebuild (backend is {self.backend.name!r})")
        if detector is not None:
            if now is None:
                now = time.monotonic()
            live_devices = len(detector.live(now))
        if n_stages is None:
            if live_devices is None:
                raise ValueError(
                    "reshard: pass live_devices=, detector=, or n_stages=")
            from repro.models.common import make_layer_plan
            plan = self._elastic.plan(live_devices)
            n_periods = make_layer_plan(self.cfg.num_layers,
                                        self.cfg.block_pattern).n_periods
            n_stages = max(1, min(plan.data, self.num_microbatches,
                                  len(jax.devices()), n_periods))
        if n_stages > self.num_microbatches:
            raise ValueError(
                f"reshard: N_B >= N_S requires n_stages <= "
                f"{self.num_microbatches}, got {n_stages}")
        new_plan = MeshPlan(shape=(n_stages, 1), axes=("data", "model"),
                            devices_used=n_stages,
                            devices_spare=max(0, (live_devices or n_stages)
                                              - n_stages))
        reshard_plan = self._elastic.resharding_plan(self._mesh_plan,
                                                     new_plan)

        # (1) drain both planes: every in-flight tick completes and books
        # normally, so nothing is recomputed and recurrent/ring state in
        # the carried caches is consistent
        t_drain0 = time.perf_counter()
        old_stages = self.backend.n_stages
        tokens0 = np.zeros((self.mb_size,), np.int32)
        pos0 = np.zeros((self.mb_size,), np.int32)
        while self.backend.pending():
            for res in self.backend.decode(0, tokens0, pos0,
                                           RowSampling.zeros(self.mb_size),
                                           active=False):
                self._apply_result(res)
        while self.backend.prefill_pending():
            for res in self.backend.prefill_step(None):
                self._apply_prefill_result(res)
        self._activate_ready()          # pipe empty -> nothing is busy

        # offloaded global pools hold per-stage host content keyed to the
        # OLD stage split: concatenate each microbatch's per-stage ranges
        # into full-period host arrays now (pipe drained, caches stable),
        # re-split for the new stage count after the rebuild
        off_state = self.backend.export_offload_state()
        t_rebuild0 = time.perf_counter()
        if self.recorder is not None:
            self.recorder.reshard_span("drain", t_drain0, t_rebuild0,
                                       (("old_stages", old_stages),))

        # (2)+(3) carry caches (host round-trip: the old arrays are
        # committed to the old pod mesh), rebuild on a fresh mesh
        caches = jax.tree.map(lambda x: np.asarray(x), self.backend.caches)
        fault_plan = self.backend.fault_plan
        if fault_plan is not None:
            # a fault planned for a stage that no longer exists cannot
            # happen — prune instead of tripping the new backend's
            # stage-bounds validation mid-run.  Pending tick indices stay
            # plane-local: they are carried below so an event scheduled
            # for absolute tick T still fires at T.
            gone = [e for e in fault_plan.events if e.stage >= n_stages]
            if gone:
                log.warning("reshard: dropping %d pending fault event(s) "
                            "targeting stages >= %d: %s", len(gone),
                            n_stages, gone)
                fault_plan.events = [e for e in fault_plan.events
                                     if e.stage < n_stages]
        if self._mesh is not None:
            log.warning("reshard: the engine's custom mesh is built for "
                        "%d stage(s) — the rebuilt backend uses a default "
                        "mesh over jax.devices()[:%d]",
                        self.backend.n_stages, n_stages)
        old_ticks = (self.backend._decode_ticks, self.backend._prefill_ticks)
        log.info("reshard: %d -> %d stages (%s)", self.backend.n_stages,
                 n_stages, {k: v for k, v in reshard_plan.items()
                            if k not in ("old", "new")})
        self.backend = make_backend(
            "pipelined", self.cfg, self.params, self.rt,
            mb_size=self.mb_size, num_microbatches=self.num_microbatches,
            pool=self.pool, offloader=self._offloader, n_stages=n_stages,
            mesh=None, fault_plan=fault_plan,
            # the link policy survives the rebuild: for_stages retargets
            # per-link specs to the new ring (conservative worst-link
            # envelope when the count changed) and carries the virtual
            # clock so transport accounting stays monotonic
            transport=self.backend.transport.for_stages(n_stages),
            schedule=self.backend.schedule,
            wire_dtype=getattr(self.backend, "wire_dtype", "fp32"),
            recorder=self.recorder)
        # plane tick counters survive the rebuild, so FaultPlan tick
        # indices keep their absolute meaning across a reshard
        self.backend._decode_ticks, self.backend._prefill_ticks = old_ticks
        self.backend.caches = jax.tree.map(jnp.asarray, caches)
        # replay the migrated host stores into the fresh offloaders (the
        # carried caches already hold every RESIDENT parity's bytes; the
        # import covers the swapped-out parities)
        self.backend.import_offload_state(off_state)

        # (4) replay the device-wide page table; per-slot ring/recurrent
        # state rode along inside the cache pytree
        self.backend.set_page_table(self.table)

        from repro.distributed.elastic import StragglerMitigator
        self.straggler = StragglerMitigator(n_stages)
        self._stage_time_total = [0.0] * n_stages
        self._stage_time_count = [0] * n_stages
        self.n_stages = n_stages
        self._mesh_plan = new_plan
        self.stats.reshards += 1
        if self.recorder is not None:
            self.recorder.reshard_span("rebuild", t_rebuild0,
                                       time.perf_counter(),
                                       (("n_stages", n_stages),))
        if self.auditor is not None:
            self.auditor.after_reshard()
        return reshard_plan

    def step(self) -> bool:
        """One engine tick: reap finished, run the prefill phase (one
        budgeted chunk through the prefill plane, or the exact-length
        fallback admission), tick one microbatch through the backend.
        Returns False when fully drained.  The tick is the ``sched.tick``
        span; its phases are the finer ``sched.*`` spans."""
        with obs.span("sched.tick", self.recorder):
            return self._tick()

    def _tick(self) -> bool:
        rec = self.recorder
        t0 = time.perf_counter()
        with obs.span("sched.reap", rec):
            self._reap()
        tp = time.perf_counter()
        if self.chunked_prefill:
            with obs.span("sched.plan", rec):
                chunk = self._build_chunk()
            results = self.backend.prefill_step(chunk)
            with obs.span("sched.prefill_results", rec):
                for res in results:
                    self._apply_prefill_result(res)
                self._activate_ready()
        else:
            with obs.span("sched.plan", rec):
                self._admit()
        tp2 = time.perf_counter()
        self.stats.queue_depth = len(self.queue)
        # drained iff no slot is occupied (active, prefilling, or finished-
        # at-prefill awaiting reap), nothing queued, and neither the decode
        # nor the prefill plane has ticks in flight
        if not any(s is not None for s in self.slots) and not self.queue \
                and not self.backend.pending() \
                and not self.backend.prefill_pending():
            self.stats.prefill_time_s += tp2 - tp
            self.stats.decode_time_s += tp - t0
            self.stats.wall_time_s += time.perf_counter() - t0
            if self.auditor is not None:
                self.auditor.after_step()
            return False
        mb = self.stats.steps % self.num_microbatches
        self._decode_microbatch(mb)
        if self.straggler is not None:
            for s, dt in self.backend.drain_stage_times():
                self.straggler.observe(s, dt)
                self._stage_time_total[s] += dt
                self._stage_time_count[s] += 1
        self.stats.steps += 1
        t1 = time.perf_counter()
        self.stats.prefill_time_s += tp2 - tp
        self.stats.decode_time_s += (tp - t0) + (t1 - tp2)
        self.stats.wall_time_s += t1 - t0
        if self.slo is not None:
            self.slo.observe_tick(t1 - t0)
        if self.auditor is not None:
            self.auditor.after_step()
        return True

    # ------------------------------------------------------------------
    # slot management
    # ------------------------------------------------------------------

    def _mb_of_slot(self, slot: int) -> int:
        return slot // self.mb_size

    def _reap(self) -> None:
        changed = False
        now = time.perf_counter()
        for slot, seq in enumerate(self.slots):
            if seq is not None and seq.is_done():
                seq.status = Status.FINISHED
                seq.finish_step = self.stats.steps
                seq.finish_time = now
                self.finished.append(seq)
                self.stats.finished_requests += 1
                if self.recorder is not None:
                    reason = seq.finish_reason()
                    self.recorder.request_finish(
                        seq.request.request_id, now,
                        reason.value if reason is not None else None)
                self.alloc.release(slot)
                self.slots[slot] = None
                self.active[slot] = False
                self.table[slot] = 0            # park on scratch page 0
                self.cur_pos[slot] = 0
                self.samp_temp[slot] = 0.0      # idle rows decode greedily
                self.samp_top_k[slot] = 0
                self.samp_top_p[slot] = 1.0
                self.samp_keys[slot] = 0
                changed = True
        if changed:
            self.backend.set_page_table(self.table)

    def _admit(self) -> None:
        # microbatches with a tick in flight must not have their cache rows
        # (or page-table rows) rewritten under them — skip until drained
        busy = self.backend.busy_microbatches()
        for slot in range(self.batch):
            if self.slots[slot] is not None or not self.queue:
                continue
            if self._mb_of_slot(slot) in busy:
                continue
            seq = self.queue.popleft()
            seq.status = Status.PREFILLING
            try:
                self._prefill_into_slot(seq, slot)
            except MemoryError:
                seq.status = Status.QUEUED
                self.queue.appendleft(seq)      # retry when pages free up
                break

    # ------------------------------------------------------------------
    # chunked prefill (the default admission path for fully-paged archs)
    # ------------------------------------------------------------------

    def _allocate_slot(self, seq: SequenceState, slot: int,
                       global_pool: Optional[int]) -> None:
        """Allocate the slot's full page budget and bind the sequence to it
        (raises MemoryError with nothing bound on exhaustion).  The caller
        decides when to push the slot's real table row: the chunked path
        parks it until activation (chunks carry their own table rows), the
        exact path pushes it immediately."""
        sp = seq.sampling
        plen = seq.prompt_len
        total_budget = plen + sp.max_new_tokens
        n_pages = -(-min(total_budget,
                         self.pool.max_pages_per_seq * self.pool.page_size)
                    // self.pool.page_size)
        shared: List[int] = []
        if self.prefix_cache is not None:
            # adopt the longest cached full-page prompt prefix: refcounts
            # bump, no re-prefill — the chunk cursor starts past it
            shared = self.prefix_cache.match(seq.request.prompt)
            if shared:
                self.alloc.adopt(slot, shared)
        try:
            pages = self.alloc.allocate(slot, n_pages - len(shared),
                                        global_pool=global_pool)
        except MemoryError:
            # pool pressure: evict cold cached prefixes and retry once
            # before giving the caller its head-of-line retry
            if self.prefix_cache is None or \
                    not self.prefix_cache.evict(n_pages - len(shared)):
                if shared:
                    self.alloc.release(slot)
                raise
            if self.recorder is not None:
                self.recorder.prefix_event(
                    "evict", seq.request.request_id,
                    (n_pages - len(shared)) * self.pool.page_size,
                    time.perf_counter())
            try:
                pages = self.alloc.allocate(slot, n_pages - len(shared),
                                            global_pool=global_pool)
            except MemoryError:
                if shared:
                    self.alloc.release(slot)
                raise
        has_global = any(p >= self.pool.n_local_pages for p in pages)
        seq.global_parity = global_pool if has_global else None
        seq.slot = slot
        seq.prefill_pos = len(shared) * self.pool.page_size
        if shared:
            self.stats.prefix_hits += 1
            self.stats.prefix_hit_tokens += seq.prefill_pos
        now = time.perf_counter()
        self.stats.admitted += 1
        self.stats.queue_wait_s += now - seq.submit_time
        if self.recorder is not None:
            rid = seq.request.request_id
            self.recorder.request_admit(rid, now)
            self.recorder.request_pages(rid, n_pages)
            if shared:
                self.recorder.prefix_event("hit", rid, seq.prefill_pos,
                                           now)
                self.recorder.request_prefix_hit(rid, seq.prefill_pos)
        seq.status = Status.PREFILLING
        seq.budget = min(sp.max_new_tokens,
                         self.pool.max_pages_per_seq * self.pool.page_size
                         - plen)
        self.slots[slot] = seq

    def _tick_prefill_rows(self) -> int:
        """Per-tick admission width: the configured ``prefill_rows``,
        lightened while a pipeline stage is straggling (the §4.3 ring tick
        is set by the slowest stage, so extra admission work must shrink
        with it — ``StragglerMitigator.microbatch_weights`` are mean-1
        inverse EWMAs, and the minimum weight scales the per-tick token
        budget, floored at one chunk).  The chunk's device shapes stay
        fixed at (prefill_rows, prefill_chunk); only fewer rows fill.

        The latency-SLO controller (when configured) composes on the same
        budget: its fraction sheds prefill while smoothed tick time blows
        the inter-token target, unless the oldest waiting request is at
        TTFT risk.  The stricter of the two weights wins — a straggling
        stage caps admission even when TTFT wants more prefill."""
        w = 1.0
        if self.straggler is not None and self.straggler.stragglers():
            w = min(w, min(self.straggler.microbatch_weights()))
        if self.slo is not None:
            now = time.perf_counter()
            waits = [now - s.submit_time for s in self.queue]
            waits += [now - s.submit_time for s in self.prefilling
                      if not s.generated]
            frac = self.slo.budget_frac(max(waits, default=0.0))
            w = min(w, frac)
            if self.recorder is not None:
                self.recorder.slo_budget(
                    frac,
                    int(self.max_prefill_tokens_per_tick * min(1.0, w)),
                    now)
        if w >= 1.0:
            return self.prefill_rows
        budget = int(self.max_prefill_tokens_per_tick * min(1.0, w))
        return max(1, min(self.prefill_rows, budget // self.prefill_chunk))

    def _build_chunk(self) -> Optional[PrefillChunk]:
        """Assemble this tick's prefill work unit: continue partially
        prefilled sequences first (FIFO), then admit queued prompts into
        free slots, up to ``prefill_rows`` rows of ``prefill_chunk`` tokens
        (the ``max_prefill_tokens_per_tick`` budget).  The offloader keys
        global-pool host copies by *microbatch id*, so all rows drawing on
        one pool parity must belong to the same microbatch (one per parity
        can ride along); head-of-line blocking on page exhaustion is
        preserved — the queue front retries after pages free up."""
        if not self.backend.prefill_can_accept():
            return None
        rows_cap = self._tick_prefill_rows()
        rows: List[SequenceState] = []
        # parity -> the single microbatch whose global-pool copy must be
        # resident for this chunk (the offloader stages copies per mb)
        parity_mb: Dict[int, Optional[int]] = {0: None, 1: None}
        for seq in self.prefilling:
            if len(rows) == rows_cap:
                break
            if seq.chunk_inflight:
                continue
            mb = self._mb_of_slot(seq.slot)
            if seq.global_parity is not None:
                if parity_mb[mb % 2] not in (None, mb):
                    continue            # another mb owns this parity slice
                parity_mb[mb % 2] = mb
            rows.append(seq)
        if len(rows) < rows_cap and self.queue:
            free = [s for s in range(self.batch) if self.slots[s] is None]
            for slot in free:
                if not self.queue or len(rows) == rows_cap:
                    break
                mb = self._mb_of_slot(slot)
                gp = mb % 2 if self.pool.n_global_pages else None
                if gp is not None and parity_mb[gp] not in (None, mb):
                    continue            # slot would pull the wrong mb's copy
                seq = self.queue[0]
                try:
                    self._allocate_slot(seq, slot, gp)
                except MemoryError:
                    break               # head-of-line retry next tick
                self.queue.popleft()
                if seq.global_parity is not None:
                    parity_mb[mb % 2] = mb
                self.prefilling.append(seq)
                rows.append(seq)
        if not rows:
            return None

        R, C = self.prefill_rows, self.prefill_chunk
        tokens = np.zeros((R, C), np.int32)
        slots = np.full((R,), -1, np.int32)
        offsets = np.zeros((R,), np.int32)
        n_valid = np.zeros((R,), np.int32)
        lasts = np.full((R,), -1, np.int32)
        tables = np.zeros((R, self.pool.max_pages_per_seq), np.int32)
        for i, seq in enumerate(rows):
            prompt = seq.request.prompt
            take = min(C, len(prompt) - seq.prefill_pos)
            tokens[i, :take] = prompt[seq.prefill_pos:seq.prefill_pos + take]
            slots[i] = seq.slot
            offsets[i] = seq.prefill_pos
            n_valid[i] = take
            if seq.prefill_pos + take == len(prompt):
                lasts[i] = take - 1
            tables[i] = self.alloc.table_row(seq.slot)
            seq.chunk_inflight = True
            if self.recorder is not None:
                self.recorder.request_chunk(seq.request.request_id, take)
        return PrefillChunk(
            tokens=tokens, slots=slots, offsets=offsets, n_valid=n_valid,
            lasts=lasts, tables=tables, seqs=rows,
            residency_mbs=tuple(m for m in parity_mb.values()
                                if m is not None))

    def _apply_prefill_result(self, res: PrefillResult) -> None:
        if res.lost:
            # a stage fault dropped the chunk mid-pipe: no prompt token
            # landed (prefill_pos untouched), so clearing the in-flight
            # flag makes _build_chunk re-emit the identical chunk —
            # prompt-KV writes are offset-keyed, the retry rewrites the
            # same pages and outputs stay bit-identical
            for seq in res.chunk.seqs:
                seq.chunk_inflight = False
            self.stats.prefill_chunks_lost += 1
            if self.recorder is not None:
                self.recorder.fault("recover", time.perf_counter(),
                                    (("plane", "prefill"),
                                     ("rows", len(res.chunk.seqs))))
            return
        for i, seq in enumerate(res.chunk.seqs):
            seq.chunk_inflight = False
            take = int(res.chunk.n_valid[i])
            seq.prefill_pos += take
            self.stats.prefill_tokens += take
            if seq.prefill_pos >= seq.prompt_len:
                self._finish_prefill(seq, res.logits[i])

    def _finish_prefill(self, seq: SequenceState, logits_row) -> None:
        """The sequence's last chunk landed: sample its first token (same
        keying as every decode token) and queue it for activation."""
        self._sample_first_token(seq, seq.slot, logits_row)
        if self.prefix_cache is not None:
            # register this prompt's fully-written blocks for future
            # sharers (existing entries win on a concurrent double-fill)
            self.prefix_cache.insert(seq.request.prompt,
                                     self.alloc.pages_of(seq.slot))
            if self.recorder is not None:
                self.recorder.prefix_event("insert",
                                           seq.request.request_id,
                                           seq.prompt_len,
                                           time.perf_counter())
        self.prefilling.remove(seq)
        if not seq.is_done():               # finished at prefill (eos /
            self._pending_activation.append(seq)    # zero budget): reap
                                                    # without ever decoding

    def _activate_ready(self) -> None:
        """Push real page-table rows and activate completed prefills whose
        microbatch has no decode tick in flight (an in-flight tick still
        writes its bubble rows against the parked table — swapping the row
        under it would clobber the fresh prompt KV at position 0)."""
        if not self._pending_activation:
            return
        busy = self.backend.busy_microbatches()
        held, changed = [], False
        for seq in self._pending_activation:
            if self._mb_of_slot(seq.slot) in busy:
                held.append(seq)
                continue
            self.table[seq.slot] = self.alloc.table_row(seq.slot)
            seq.status = Status.DECODING
            self.active[seq.slot] = True
            changed = True
        self._pending_activation = held
        if changed:
            self.backend.set_page_table(self.table)

    # ------------------------------------------------------------------
    # prefill (exact-length fallback: recurrent / sliding-window archs)
    # ------------------------------------------------------------------

    def _prefill_len(self, n: int) -> int:
        if self.cfg.recurrent_layer_count() > 0:
            # bucket to the next power of two so the per-length jit cache
            # is bounded (log2 entries); state stays exact — pad positions
            # are masked through the recurrences (model.prefill)
            return max(8, 1 << (n - 1).bit_length())
        return max(8, (n + 7) // 8 * 8)

    def _request_key(self, request_id: int) -> np.ndarray:
        """Per-request base PRNG key: ``fold_in(PRNGKey(seed), rid)`` —
        a function of (seed, request_id) only, so token streams reproduce
        across backends, N_B, and admission order."""
        return np.asarray(jax.random.fold_in(self._seed_key, request_id),
                          np.uint32)

    def _sample_first_token(self, seq: SequenceState, slot: int,
                            logits) -> None:
        """Set the slot's sampling state and sample the request's first
        token from its last-position prefill logits — the request's own
        params under its own key (token index 0), the same path as every
        decode token.  Shared by the chunked and exact prefill paths."""
        sp = seq.sampling
        base = self._request_key(seq.request.request_id)
        self.samp_keys[slot] = base
        self.samp_temp[slot] = sp.temperature
        self.samp_top_k[slot] = sp.top_k
        self.samp_top_p[slot] = sp.top_p
        # normalize to a plain single-device array: pipelined backends hand
        # back NamedSharding-committed logits after the first tick, which
        # would fork a second _sample_first compile cache entry
        # repro-audit: allow(host-sync) — once per request at prefill completion, not per tick; de-shards logits for a stable _sample_first cache
        logits = jnp.asarray(np.asarray(logits))
        first_arr, first_lp = _sample_first(
            logits[None], jnp.asarray(base[None]),
            jnp.zeros((1,), jnp.int32),
            jnp.asarray(self.samp_temp[slot:slot + 1]),
            jnp.asarray(self.samp_top_k[slot:slot + 1]),
            jnp.asarray(self.samp_top_p[slot:slot + 1]),
            fast=self.sample_fast_path)
        if sp.logprobs:
            # repro-audit: allow(host-sync) — first-token host booking, once per request at admission
            seq.logprobs = [float(first_lp[0])]
        # repro-audit: allow(host-sync) — first-token host booking, once per request at admission
        seq.generated.append(int(first_arr[0]))
        seq.first_token_time = time.perf_counter()   # engine-side TTFT mark
        if self.recorder is not None:
            # same float as seq.first_token_time: trace TTFT == seq.ttft_s
            rid = seq.request.request_id
            self.recorder.request_first_token(rid, seq.first_token_time)
            self.recorder.request_tokens(rid, 1, seq.first_token_time)
        self.cur_pos[slot] = seq.prompt_len     # position of the first token
        self.stats.decode_tokens += 1

    def _prefill_into_slot(self, seq: SequenceState, slot: int) -> None:
        prompt = seq.request.prompt
        plen = len(prompt)
        gp = self._mb_of_slot(slot) % 2 if self.pool.n_global_pages else None
        self._allocate_slot(seq, slot, gp)      # pages + budget + binding
        self.table[slot] = self.alloc.table_row(slot)
        self.backend.reset_slot(slot)
        self.backend.set_page_table(self.table)

        lp = self._prefill_len(plen)
        toks = np.zeros((lp,), np.int32)
        toks[:plen] = prompt
        logits = self.backend.prefill(
            toks, slot, plen - 1,
            has_global_pages=seq.global_parity is not None)
        self._sample_first_token(seq, slot, logits)
        seq.status = Status.DECODING
        self.active[slot] = True
        self.stats.prefill_tokens += plen

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------

    def _row_sampling(self, lo: int, hi: int) -> RowSampling:
        """Snapshot the per-row sampling state for slots [lo, hi) — copied,
        because pipelined backends hold it until the tick drains."""
        steps = np.zeros((hi - lo,), np.int32)
        for i, slot in enumerate(range(lo, hi)):
            seq = self.slots[slot]
            if seq is not None:
                steps[i] = len(seq.generated)   # index of the token sampled
        return RowSampling(keys=self.samp_keys[lo:hi].copy(), steps=steps,
                           temp=self.samp_temp[lo:hi].copy(),
                           top_k=self.samp_top_k[lo:hi].copy(),
                           top_p=self.samp_top_p[lo:hi].copy())

    def _decode_microbatch(self, mb: int) -> None:
        lo = mb * self.mb_size
        hi = lo + self.mb_size
        mb_active = bool(self.active[lo:hi].any())
        if not mb_active and not self.backend.pending():
            return
        tokens = np.zeros((self.mb_size,), np.int32)
        for i, slot in enumerate(range(lo, hi)):
            seq = self.slots[slot]
            if seq is not None and seq.generated:
                tokens[i] = seq.generated[-1]
        if mb_active:
            # snapshot which rows (and which sequences) this injection is
            # for: with chunked prefill a slot can be reassigned, or become
            # active, while the tick is still in flight — the drained
            # result must never be booked against the new occupant
            self._inject_snap[mb] = (self.active[lo:hi].copy(),
                                     list(self.slots[lo:hi]))
        results = self.backend.decode(mb, tokens, self.cur_pos[lo:hi],
                                      self._row_sampling(lo, hi),
                                      active=mb_active)
        self.stats.swaps = self.backend.swap_count
        with obs.span("sched.decode_results", self.recorder):
            for res in results:
                self._apply_result(res)

    def _apply_result(self, res: DecodeResult) -> None:
        """Book one drained microbatch tick (possibly for an earlier
        microbatch than the one just injected — pipelined backends drain
        with N_S − 1 ticks of latency)."""
        if res.lost:
            # a stage fault dropped the microbatch's tick: nothing was
            # booked (seq/cur_pos cursors only advance on a drained
            # result), so recovery is to discard the injection snapshot
            # and let the round-robin re-inject the microbatch with the
            # same tokens at the same positions on its next turn — the
            # retry rewrites identical position-keyed KV and samples
            # under the same (seed, request_id, token_idx) keys
            self._inject_snap.pop(res.mb, None)
            self.stats.decode_ticks_lost += 1
            if self.recorder is not None:
                self.recorder.fault("recover", time.perf_counter(),
                                    (("plane", "decode"),
                                     ("mb", res.mb)))
            return
        lo = res.mb * self.mb_size
        snap = self._inject_snap.pop(res.mb, None)
        rec = self.recorder
        tnow = time.perf_counter() if rec is not None else 0.0
        for i, slot in enumerate(range(lo, lo + self.mb_size)):
            seq = self.slots[slot]
            if seq is None or seq.is_done():
                continue            # finished at prefill (eos/budget): reap
                                    # next tick, never extend
            if snap is not None and (not snap[0][i] or snap[1][i] is not seq):
                continue            # row wasn't live at injection (still
                                    # prefilling, or slot reassigned)
            seq.generated.append(int(res.tokens[i]))
            if seq.logprobs is not None:
                seq.logprobs.append(float(res.logprobs[i]))
            self.cur_pos[slot] += 1
            self.stats.decode_tokens += 1
            if rec is not None:
                rec.request_tokens(seq.request.request_id, 1, tnow)
            need = self.cur_pos[slot] + 1
            have = len(self.alloc.pages_of(slot)) * self.pool.page_size
            if need > have:
                gp = res.mb % 2 if self.pool.n_global_pages else None
                self.alloc.extend(slot, global_pool=gp)
                self.table[slot] = self.alloc.table_row(slot)
                self.backend.set_page_table(self.table)
                if rec is not None:
                    rec.request_pages(seq.request.request_id, 1)

    # ------------------------------------------------------------------

    def request_trace(self, request_id: int) -> Optional[dict]:
        """Per-request flight-recorder snapshot (queue wait, TTFT,
        per-token inter-token latencies, chunk/page/prefix-hit counts).
        ``None`` when tracing is off or the request has been evicted
        from the recorder's bounded table."""
        if self.recorder is None:
            return None
        return self.recorder.request_trace(request_id)

    def throughput_report(self) -> dict:
        lat_steps = [s.latency_steps for s in self.finished
                     if s.latency_steps is not None]
        lat_s = [s.latency_s for s in self.finished
                 if s.latency_s is not None]
        # per-status counts are O(batch + queue): computed on demand here
        # (status_counts() writes the stats mirror itself), never in the
        # per-tick loop
        self.status_counts()
        rep = {
            "backend": self.backend.name,
            "prefill_tokens": self.stats.prefill_tokens,
            "decode_tokens": self.stats.decode_tokens,
            "total_tokens": self.stats.total_tokens,
            "finished": self.stats.finished_requests,
            "admitted": self.stats.admitted,
            "queue_wait_s": self.stats.queue_wait_s,
            "steps": self.stats.steps,
            "swaps": self.stats.swaps,
            "wall_time_s": self.stats.wall_time_s,
            "prefill_time_s": self.stats.prefill_time_s,
            "decode_time_s": self.stats.decode_time_s,
            "decode_tok_per_s": self.stats.decode_tok_per_s,
            "prefill_tok_per_s": self.stats.prefill_tok_per_s,
            "queue_depth": self.stats.queue_depth,
            "status_counts": self.stats.status_counts,
            "aborted": self.stats.aborted,
            "decode_ticks_lost": self.stats.decode_ticks_lost,
            "prefill_chunks_lost": self.stats.prefill_chunks_lost,
            "reshards": self.stats.reshards,
            "mean_latency_steps":
                float(np.mean(lat_steps)) if lat_steps else 0.0,
            "mean_latency_s": float(np.mean(lat_s)) if lat_s else 0.0,
        }
        if self.prefix_cache is not None:
            rep["prefix_hits"] = self.stats.prefix_hits
            rep["prefix_hit_tokens"] = self.stats.prefix_hit_tokens
            rep["prefix_hit_rate"] = self.prefix_cache.hit_rate
            rep["prefix_cache_pages"] = len(self.prefix_cache)
        if self.straggler is not None:
            # the raw observations behind admission weighting, surfaced:
            # per-stage tick-time EWMAs, lifetime drain-time totals/counts,
            # the mean-1 inverse weights, and which stages are currently
            # flagged (all host lists the mitigation loop already holds)
            rep["stages"] = {
                "ewma_s": list(self.straggler.ewma),
                "total_s": list(self._stage_time_total),
                "counts": list(self._stage_time_count),
                "microbatch_weights": self.straggler.microbatch_weights(),
                "stragglers": self.straggler.stragglers(),
            }
        tstats = self.backend.transport_stats()
        if tstats:
            rep["transport"] = tstats
            vt = tstats.get("virtual_time_s", 0.0)
            if vt > 0:
                # decode tok/s on the simulated network's clock — what
                # the latency_curve benchmark compares across schedules
                rep["virtual_decode_tok_per_s"] = \
                    self.stats.decode_tokens / vt
        return rep
