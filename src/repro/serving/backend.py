"""Pluggable execution backends for the offline serving engine.

The engine (``repro.serving.engine.OfflineEngine``) owns every piece of
*bookkeeping* — request queue, decode slots, page allocator, page table,
positions — while a backend owns the *compute plane*: the device cache
pytree and every jit entry point.  The seam:

  ``prefill_step(chunk)``  — advance the prefill plane one tick,
        optionally injecting a :class:`PrefillChunk` (a fixed-shape batch
        of prompt-token rows with their own page-table rows) — compiled
        once.  Local backends return the chunk's :class:`PrefillResult`
        immediately; pipelined backends route it stage-to-stage through a
        second persistent pipe (overlapping in-flight decode) and return
        it ``N_S − 1`` ticks later.  ``prefill_can_accept`` /
        ``prefill_pending`` expose the pipe state.
  ``prefill(tokens, slot, last_index)``  — the exact-length fallback
        (recurrent / sliding-window archs): run one sequence's (padded)
        prompt into the caches at ``slot``, return last-position logits.
  ``decode(mb, tokens, cur_pos, samp)``  — advance microbatch ``mb`` by one
        token tick; returns zero or more :class:`DecodeResult`.  A result
        may be for an *earlier* microbatch: pipelined backends drain with
        latency, so the engine applies results by the microbatch id they
        carry, not by the one it just injected.  ``samp`` is a per-row
        :class:`repro.serving.sampler.RowSampling` — every slot carries its
        own temperature / top-k / top-p and PRNG key, so one compiled
        decode serves mixed greedy+sampled microbatches.
  cache ownership — ``set_page_table`` / ``reset_slot`` push the engine's
        host-side bookkeeping into the device caches.

Two implementations ship:

``LocalBackend``
    The single-device path: one jitted decode per microbatch tick, one
    jitted prefill per (padded) prompt length.  Decode slices the
    microbatch's ``mb_size`` cache rows (never the full batch), so
    non-microbatch rows are untouched by construction.

``PipelinedBackend``
    DeServe's §4.3 circular schedule as a *persistent stepper* over the
    ``N_S``-stage ``shard_map`` pipeline (``repro.core.pipeline``).  Each
    engine tick injects one microbatch at stage 0 and advances every
    in-flight microbatch one stage; the microbatch leaving the last stage
    drains through the shared epilogue + sampler and is returned to the
    engine ``N_S − 1`` ticks after injection.  Paged KV pools and the
    §4.2 double-buffer offloader run per stage: stage ``s`` swaps its own
    period-slice of the global pools when a microbatch arrives at it.
"""

from __future__ import annotations

import abc
import functools
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import ModelConfig
from repro.models import model as model_lib
from repro.models.common import Runtime
from repro.serving import kv_cache as kvc
from repro.serving.sampler import (RowSampling, fold_in_steps,
                                   sample_batched, token_logprobs)


@dataclass
class DecodeResult:
    """One drained microbatch tick: ``tokens[i]`` is the next token for
    slot ``mb * mb_size + i`` (the engine decides which rows are live).
    ``lost=True`` marks a *fault*: the microbatch's tick was dropped by a
    failed stage — ``tokens``/``logprobs`` are garbage and the engine must
    re-inject the microbatch instead of booking them."""
    mb: int
    tokens: np.ndarray                  # (mb_size,) int32
    logprobs: np.ndarray                # (mb_size,) f32 — model logprob of
                                        # tokens[i] (raw-logits distribution)
    lost: bool = False


@dataclass
class PrefillChunk:
    """One per-tick prefill work unit: up to R rows of C prompt tokens each,
    batched across queued/continuing sequences.  Shapes are fixed by the
    engine (``prefill_rows`` x ``prefill_chunk``) so the chunk jit compiles
    exactly once — padded rows carry ``n_valid == 0``."""
    tokens: np.ndarray                  # (R, C) int32
    slots: np.ndarray                   # (R,) int32 slot per row, -1 = pad
    offsets: np.ndarray                 # (R,) int32 tokens already prefilled
    n_valid: np.ndarray                 # (R,) int32 real tokens this chunk
    lasts: np.ndarray                   # (R,) int32 within-chunk index of the
                                        # final prompt token (-1: not final)
    tables: np.ndarray                  # (R, max_pages) int32 page-table rows
                                        # (the device-wide table keeps
                                        # prefilling slots parked on scratch)
    seqs: list                          # engine-side SequenceState refs —
                                        # opaque to the backend
    residency_mbs: tuple = ()           # microbatch ids (<= one per global-
                                        # pool parity — the offloader keys
                                        # host copies by mb, not parity)
                                        # whose global pages the chunk
                                        # writes; () = all-local


@dataclass
class PrefillResult:
    """A drained prefill chunk: ``logits[i]`` are the last-position logits
    of row ``i`` — meaningful only for rows whose chunk was their last
    (``chunk.lasts[i] >= 0``).  ``lost=True`` marks a dropped chunk tick:
    ``logits`` are garbage and the engine must re-emit the chunk."""
    chunk: PrefillChunk
    logits: np.ndarray                  # (R, V) f32
    lost: bool = False


# cache-view helpers live with the cache layout; re-exported here because
# backends are their main consumer
slot_view = kvc.slot_view
slot_merge = kvc.slot_merge


def named_partial(fn, **static):
    """``functools.partial(fn, **static)`` under ``fn``'s own name, so
    ``jax.jit`` of it lowers as ``jit_<name>`` (``jit__decode_fn``) and
    a profiler trace names the program, where a bare partial lowers as
    ``jit__unknown``."""
    p = functools.partial(fn, **static)
    p.__name__ = fn.__name__
    return p


# ---------------------------------------------------------------------------
# Interface + shared slot-cache machinery
# ---------------------------------------------------------------------------


class ExecutionBackend(abc.ABC):
    """Compute plane behind the engine.  Owns caches and jit entries."""

    name: str = "abstract"

    @abc.abstractmethod
    def prefill(self, tokens: np.ndarray, slot: int, last_index: int,
                has_global_pages: bool = True) -> jax.Array:
        """Prefill one (padded) prompt into ``slot``; returns (V,) logits
        at ``last_index``.  ``has_global_pages=False`` tells the backend
        the slot's allocation is all-local, so no offload residency work
        is needed before the prompt KV is written."""

    @abc.abstractmethod
    def decode(self, mb: int, tokens: np.ndarray, cur_pos: np.ndarray,
               samp: RowSampling, active: bool = True) -> List[DecodeResult]:
        """Advance microbatch ``mb`` one tick (``active=False`` advances
        the pipe without injecting — used to drain).  ``samp`` carries the
        per-row sampling params/keys of the microbatch being injected."""

    @abc.abstractmethod
    def set_page_table(self, table: np.ndarray) -> None:
        """Push the engine's (batch, max_pages) page table to the device."""

    @abc.abstractmethod
    def reset_slot(self, slot: int) -> None:
        """Clear per-slot ring/recurrent state when a slot is reassigned."""

    def busy_microbatches(self) -> set:
        """Microbatches with an in-flight tick (their slots and cache rows
        must not be touched by admission)."""
        return set()

    def pending(self) -> bool:
        """True while ticks are still in flight (engine keeps draining)."""
        return False

    # -- chunked prefill (batched admission) -------------------------------

    def prefill_step(self, chunk: Optional["PrefillChunk"]
                     ) -> List["PrefillResult"]:
        """Advance the prefill plane one engine tick, optionally injecting
        ``chunk``.  Local backends run the chunk synchronously and return
        its result immediately; pipelined backends route it stage-to-stage
        through the pipe (overlapping in-flight decode microbatches) and
        return it ``N_S - 1`` ticks later.  Returns zero or more drained
        :class:`PrefillResult`."""
        if chunk is None:
            return []
        raise NotImplementedError(
            f"{type(self).__name__} does not implement chunked prefill")

    def prefill_can_accept(self) -> bool:
        """True when a new chunk may be injected this tick."""
        return True

    def prefill_pending(self) -> bool:
        """True while prefill chunks are still in flight."""
        return False

    def drain_stage_times(self) -> List[tuple]:
        """(stage, seconds) tick-time observations since the last call —
        non-empty only on staged (pipelined) backends."""
        return []

    def transport_stats(self) -> Dict:
        """Inter-stage transport accounting (virtual clock, wire bytes,
        link stalls) — non-empty only on staged backends whose transport
        keeps books (see ``repro.distributed.transport``)."""
        return {}

    def jit_entries(self) -> Dict[str, object]:
        """Serve-loop jit callables by name, for the strict-mode cache
        probes: each must hold at most one compiled trace over a full
        serve run (a second entry is a silent mid-serve retrace)."""
        return {}

    @property
    def swap_count(self) -> int:
        return 0


class _SlotCacheBackend(ExecutionBackend):
    """Shared prefill / page-table / reset plumbing over engine-format
    paged caches.  Subclasses implement ``decode``."""

    recorder = None             # the engine's TraceRecorder, or None

    def __init__(self, cfg: ModelConfig, params, rt: Runtime, *,
                 mb_size: int, num_microbatches: int, pool: kvc.PoolConfig,
                 place=None):
        """``place`` (tree -> tree of shardings) puts the weights and
        builds the caches where the compute plane keeps them; None leaves
        the weights where they are and the caches on the default device."""
        self.cfg = cfg
        self.params = params if place is None else \
            jax.device_put(params, place(params))
        self.rt = rt
        self.mb_size = mb_size
        self.num_microbatches = num_microbatches
        self.batch = mb_size * num_microbatches
        self.pool = pool
        self.caches = kvc.build_paged_caches(cfg, self.batch, pool, rt,
                                             place=place)
        self._prefill_jits: Dict[int, object] = {}

    # -- cache bookkeeping entry points ------------------------------------

    def set_page_table(self, table: np.ndarray) -> None:
        with obs.span("kv.publish", self.recorder):
            self.caches = self._commit(
                kvc.set_page_table(self.caches, table))

    def reset_slot(self, slot: int) -> None:
        with obs.span("kv.publish", self.recorder):
            self.caches = self._commit(
                kvc.reset_slot(self.caches, self.cfg, slot, self.rt))

    def _commit(self, tree):
        """Place caches a host-side write produced where the tick jits
        expect them (here: where they are)."""
        return tree

    def jit_entries(self) -> Dict[str, object]:
        return {f"_prefill_jits[{lp}]": fn
                for lp, fn in self._prefill_jits.items()}

    # -- prefill -----------------------------------------------------------

    def _prefill_residency(self, mb: int) -> None:
        """Make ``mb``'s global-pool parity resident before prompt KV is
        written (a prefill may allocate overflow pages from the global
        pool while a different microbatch's content is resident — without
        this the next swap would clobber the fresh prompt KV)."""

    def prefill(self, tokens: np.ndarray, slot: int, last_index: int,
                has_global_pages: bool = True) -> jax.Array:
        if has_global_pages:
            self._prefill_residency(slot // self.mb_size)
        lp = len(tokens)
        if lp not in self._prefill_jits:
            # lengths are pow2/8-bucketed (engine._prefill_len) so this
            # dict holds O(log max_len) wrappers, each built once and
            # reused — not a per-call jit
            # repro-audit: allow(retrace-jit) — bounded per-length cache, one wrapper per bucketed length
            self._prefill_jits[lp] = jax.jit(named_partial(
                self._prefill_fn, cfg=self.cfg, rt=self.rt))
        fn = self._prefill_jits[lp]
        logits, caches = fn(self.params, jnp.asarray(tokens)[None],
                            self.caches, slot, last_index)
        self.caches = self._commit(caches)
        return logits

    # -- chunked prefill ---------------------------------------------------

    @staticmethod
    def _chunk_fn(params, caches, tokens, offsets, n_valid, lasts, tables,
                  *, cfg, rt):
        """One prefill chunk over the batch-wide caches.

        Every paged layer's page table is swapped for the chunk's per-row
        table rows (the device-wide table keeps prefilling slots parked on
        the scratch page until activation, so in-flight decode ticks can
        never clobber half-written prompt KV); pools are written in place;
        the parked per-slot table leaves pass through untouched."""
        def swap(c, stacked):
            pt = jnp.broadcast_to(
                tables[None], (c["page_table"].shape[0],) + tables.shape) \
                if stacked else tables
            return {**c, "page_table": pt}
        view = {"scan": [swap(c, True) for c in caches["scan"]],
                "tail": [swap(c, False) for c in caches["tail"]]}
        logits, new = model_lib.prefill_chunk(params, tokens, view, offsets,
                                              n_valid, lasts, cfg, rt)
        keep = lambda n, o: {**n, "page_table": o["page_table"]}
        return logits, {
            "scan": [keep(n, o) for n, o in zip(new["scan"], caches["scan"])],
            "tail": [keep(n, o) for n, o in zip(new["tail"], caches["tail"])]}

    @staticmethod
    def _prefill_fn(params, tokens, caches, slot, last_idx, *, cfg, rt):
        """Prefill one sequence into batch-wide caches at ``slot``: slice
        the slot row from every per-slot leaf, run the model prefill,
        splice back."""
        view = slot_view(caches, slot, 1)
        logits, new_view = model_lib.prefill(
            params, {"tokens": tokens}, cfg, rt, 0, caches=view,
            last_index=jnp.asarray(last_idx).reshape(1))

        # mask ring stale positions beyond the true length
        def clean(c):
            if "pos" in c:
                c = {**c, "pos": jnp.where(c["pos"] <= last_idx,
                                           c["pos"], -1)}
            return c
        new_view = {part: [clean(c) for c in new_view[part]]
                    for part in ("scan", "tail")}
        return logits[0], slot_merge(caches, new_view, slot)


# ---------------------------------------------------------------------------
# LocalBackend — the single-device path
# ---------------------------------------------------------------------------


class LocalBackend(_SlotCacheBackend):
    name = "local"

    def __init__(self, cfg: ModelConfig, params, rt: Runtime, *,
                 mb_size: int, num_microbatches: int, pool: kvc.PoolConfig,
                 offloader=None, sample_fast_path: bool = True,
                 recorder=None):
        super().__init__(cfg, params, rt, mb_size=mb_size,
                         num_microbatches=num_microbatches, pool=pool)
        self.offloader = offloader
        self.recorder = recorder
        if offloader is not None:
            offloader.recorder = recorder
        self._decode_jit = jax.jit(named_partial(
            self._decode_fn, cfg=cfg, rt=rt, mb_size=mb_size,
            sample_fast=sample_fast_path))
        self._chunk_jit = jax.jit(named_partial(
            self._chunk_fn, cfg=cfg, rt=rt))

    def _prefill_residency(self, mb: int) -> None:
        if self.offloader is not None and self.pool.n_global_pages:
            self.caches = self.offloader.ensure_resident(self.caches, mb)

    def jit_entries(self) -> Dict[str, object]:
        out = super().jit_entries()
        out["_decode_jit"] = self._decode_jit
        out["_chunk_jit"] = self._chunk_jit
        return out

    def prefill_step(self, chunk) -> List[PrefillResult]:
        if chunk is None:
            return []
        for mb in chunk.residency_mbs:
            self._prefill_residency(mb)
        with obs.span("step.chunk_dispatch", self.recorder):
            logits, self.caches = self._chunk_jit(
                self.params, self.caches, jnp.asarray(chunk.tokens),
                jnp.asarray(chunk.offsets), jnp.asarray(chunk.n_valid),
                jnp.asarray(chunk.lasts), jnp.asarray(chunk.tables))
        with obs.span("step.chunk_readback", self.recorder):
            # repro-audit: allow(host-sync) — prefill drain: the engine samples the first token from these logits on host, once per chunk
            logits = np.asarray(logits)
        return [PrefillResult(chunk=chunk, logits=logits)]

    def decode(self, mb: int, tokens: np.ndarray, cur_pos: np.ndarray,
               samp: RowSampling, active: bool = True) -> List[DecodeResult]:
        if not active:
            return []
        if self.offloader is not None:
            self.caches = self.offloader.ensure_resident(self.caches, mb)
        with obs.span("step.decode_dispatch", self.recorder):
            toks, lps, self.caches = self._decode_jit(
                self.params, self.caches, jnp.asarray(tokens),
                jnp.asarray(cur_pos), jnp.int32(mb * self.mb_size),
                jnp.asarray(samp.keys), jnp.asarray(samp.steps),
                jnp.asarray(samp.temp), jnp.asarray(samp.top_k),
                jnp.asarray(samp.top_p))
        # §4.3 return link: the host-driven engine books the drained
        # microbatch's token ids, so one transfer per decode call is the
        # loop's single intended sync point — batched (tokens, logprobs)
        # in one device_get rather than two separate np.asarray syncs
        with obs.span("step.decode_readback", self.recorder):
            # repro-audit: allow(host-sync) — intended §4.3 return-link sync, one batched transfer per drain
            toks, lps = jax.device_get((toks, lps))
        return [DecodeResult(mb=mb, tokens=toks, logprobs=lps)]

    @staticmethod
    def _decode_fn(params, caches, tokens, cur_pos, row0, keys, steps, temp,
                   top_k, top_p, *, cfg, rt, mb_size, sample_fast=True):
        """One decode tick over an ``mb_size`` row view of the caches —
        the full batch is never fed through the model, and rows outside
        the microbatch are untouched by construction.  Sampling is per-row
        (``sample_batched``) with per-token keys folded in on device."""
        view = slot_view(caches, row0, mb_size)
        logits, new_view = model_lib.decode_step(
            params, tokens, view, cur_pos, cfg, rt)
        toks = sample_batched(logits, fold_in_steps(keys, steps), temp,
                              top_k, top_p, fast_path=sample_fast)
        return toks, token_logprobs(logits, toks), \
            slot_merge(caches, new_view, row0)

    @property
    def swap_count(self) -> int:
        return self.offloader.swap_count if self.offloader else 0


# ---------------------------------------------------------------------------
# PipelinedBackend — the §4.3 circular schedule as a persistent stepper
# ---------------------------------------------------------------------------


class PipelinedBackend(_SlotCacheBackend):
    name = "pipelined"

    def __init__(self, cfg: ModelConfig, params, rt: Runtime, *,
                 mb_size: int, num_microbatches: int, pool: kvc.PoolConfig,
                 n_stages: int = 2, offload: bool = False, mesh=None,
                 fault_plan=None, transport=None, schedule: str = "circular",
                 wire_dtype: str = "fp32", sample_fast_path: bool = True,
                 offload_async: bool = True, recorder=None):
        from repro.core import pipeline as PL
        from repro.core.offload import DoubleBufferOffloader
        if wire_dtype not in ("fp32", "int8"):
            raise ValueError(f"wire_dtype must be 'fp32'|'int8', got "
                             f"{wire_dtype!r} (top-k has no in-jit codec — "
                             "it stays wire-byte accounting only)")
        self.wire_dtype = wire_dtype
        if num_microbatches < n_stages:
            raise ValueError(
                f"continuous batching over a {n_stages}-stage pipe needs "
                f"N_B >= N_S (got N_B={num_microbatches}); see §4.3 — a "
                "microbatch must drain before its next injection")
        if mesh is None:
            mesh = PL.stage_mesh(n_stages)
        self.mesh = mesh
        # Each stage's weights and caches live on that stage's device from
        # the start (PL.stage_shardings): the period stack is split over
        # "pod", embeddings / final norm / tail are replicated.  A model
        # too large for one chip is then served without ever being whole
        # on one.
        place = PL.stage_shardings(cfg, mesh, n_stages)
        super().__init__(cfg, params, rt, mb_size=mb_size,
                         num_microbatches=num_microbatches, pool=pool,
                         place=place)
        self.n_stages = n_stages
        self.pps, self.leftover = PL.split_layers(cfg, n_stages)
        # Sharding discipline for the persistent tick jits: every array
        # input must carry ONE stable sharding per serve run, or the jit
        # cache key flips and the tick silently recompiles (caught by the
        # strict-mode jit probes).  Host-side table writes are
        # SingleDeviceSharding-uncommitted while tick outputs come back
        # NamedSharding-committed, so: (1) the caches start committed to
        # the stage placement; (2) just before the first tick of each
        # plane, _probe_layout AOT-compiles the tick and commits the state
        # to the compiled OUTPUT shardings — the layout every later tick
        # hands back — so the counted call cache only ever sees the
        # steady layout; (3) every later host-side write re-commits
        # through _commit() to it.
        self._replicated = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        self._cache_shardings = place(self.caches)   # until the probe
        self._act_sharding = None
        self._pf_act_sharding = None
        self._layout_learned = {"decode": False, "prefill": False}
        self.caches = self._commit(self.caches)
        # per-stage input activations: act[s] feeds stage s next tick
        self.act = jax.device_put(
            jnp.zeros((n_stages, mb_size, 1, cfg.d_model),
                      rt.compute_dtype), self._replicated)
        # shift register of in-flight injections: entry for stage s is the
        # (mb, positions-at-injection, RowSampling-at-injection) whose
        # activation sits in act[s]
        self._entries: List[Optional[tuple]] = [None] * n_stages
        self._tick_jit = jax.jit(named_partial(
            PL.pipeline_decode_tick, cfg=cfg, rt=rt,
            n_stages=n_stages, mb_size=mb_size, mesh=mesh,
            wire_dtype=wire_dtype, sample_fast_path=sample_fast_path))
        # prefill pipe: a second persistent stepper with its own activation
        # carry / shift register, so prompt chunks flow stage-to-stage and
        # OVERLAP in-flight decode microbatches instead of pausing them.
        # Shapes (chunk rows x chunk length) are fixed by the engine; the
        # activation buffer and jit are built lazily on the first chunk.
        self._pf_entries: List[Optional[PrefillChunk]] = [None] * n_stages
        self._pf_act = None
        self._pf_tick_jit = jax.jit(named_partial(
            PL.pipeline_prefill_chunk_tick, cfg=cfg, rt=rt,
            n_stages=n_stages, mesh=mesh, wire_dtype=wire_dtype))
        # Probe the decode plane NOW (arg shapes are fixed by n_stages and
        # mb_size): the exact-prefill jits below take the caches as input
        # and always run before the first tick, so the caches must already
        # carry the steady layout or each per-length wrapper retraces after
        # the layout commit.
        _zs = RowSampling.zeros(mb_size)
        self._probe_layout("decode", (
            self.params, self.caches, self.act,
            jnp.zeros((mb_size,), jnp.int32),
            jnp.full((n_stages,), -1, jnp.int32),
            jnp.zeros((n_stages, mb_size), jnp.int32),
            jnp.asarray(_zs.keys), jnp.asarray(_zs.steps),
            jnp.asarray(_zs.temp), jnp.asarray(_zs.top_k),
            jnp.asarray(_zs.top_p), jnp.int32(-1)))

        # fault injection (tests / drills): a FaultPlan consumed one event
        # set per plane tick.  Drops null the shift-register entry (the
        # microbatch/chunk is lost — the engine re-injects it); the
        # drop_stage marker threaded into the tick jit re-masks the same
        # stage's cache writes — redundant under this caller, but it keeps
        # the fault seam explicit for direct users of the tick functions.
        # Delays inflate the stage-time observations that feed straggler
        # mitigation.
        if fault_plan is not None:
            bad = [e for e in fault_plan.events if e.stage >= n_stages]
            if bad:
                raise ValueError(
                    f"fault plan targets stage(s) "
                    f"{sorted({e.stage for e in bad})} but the pipe has "
                    f"only {n_stages} stage(s) — fix the "
                    "kind@plane:tick:stage spec")
        self.fault_plan = fault_plan
        self._decode_ticks = 0          # plane-local tick counters: only
        self._prefill_ticks = 0         # ticks where the pipe advanced
        self._stage_times: List[tuple] = []   # (stage, seconds) since the
                                              # last drain_stage_times()

        # inter-stage links: every shift-register entry crossing a stage
        # boundary — decode ticks AND prefill chunks — travels the
        # configured transport.  InProcessTransport is today's zero-cost
        # shard_map behaviour; SimulatedLinkTransport accounts per-link
        # WAN latency on a virtual clock (outputs stay bit-identical —
        # the links never touch the computation).
        from repro.distributed.transport import (CompressedTransport,
                                                 InProcessTransport,
                                                 make_transport)
        self.transport = make_transport(transport, n_stages)
        # the decode/prefill call sites below pass RAW activation bytes;
        # pricing the packed int8 payload is the transport's job, so a
        # real in-jit codec forces the matching CompressedTransport wrap
        # (or retunes an existing one) — wire accounting then equals the
        # actual ppermute payload: 1 B/element + one f32 scale per row.
        _db = jnp.dtype(rt.compute_dtype).itemsize
        if wire_dtype == "int8" and \
                not isinstance(self.transport, InProcessTransport):
            if isinstance(self.transport, CompressedTransport):
                if self.transport.method != "int8":
                    raise ValueError(
                        f"wire_dtype='int8' but the transport accounts "
                        f"'{self.transport.method}' — use one codec for "
                        "both the wire and the books")
                self.transport.elem_bytes = _db
                self.transport.row_elems = cfg.d_model
                self.transport._wire_cache.clear()
            else:
                self.transport = CompressedTransport(
                    self.transport, method="int8", elem_bytes=_db,
                    row_elems=cfg.d_model).bind(n_stages)
        # the flight recorder rides on the OUTER transport (a compressed
        # wrap forwards to its inner, which accumulates the books — so
        # the recorded ledger carries the re-priced wire bytes)
        self.recorder = recorder
        self.transport.set_recorder(recorder)
        if schedule not in ("circular", "round_flush"):
            raise ValueError(f"schedule must be 'circular'|'round_flush', "
                             f"got {schedule!r}")
        # "round_flush" reproduces the vLLM-PP baseline: the pipe is
        # drained (fill/drain bubbles) every token round instead of
        # running the §4.3 circular schedule — the latency-curve
        # benchmark's comparison point.  Drain ticks run the same jits
        # with bubble entries, so outputs stay bit-identical.
        self.schedule = schedule
        self._last_inject_mb = -1       # round boundary detector
        self._ret_ready: Dict[int, float] = {}  # mb -> virtual time its
                                                # drained return payload
                                                # lands at the injector
        self._dtype_bytes = jnp.dtype(rt.compute_dtype).itemsize

        # §4.2 offloading, per stage: stage s double-buffers its own
        # period-slice of the global pools; the epilogue (leftover periods
        # + tail) forms one extra stage-unit keyed to the draining mb.
        self._stage_off: List = []
        self._epi_off = None
        if offload and pool.n_global_pages:
            self._stage_off = [
                DoubleBufferOffloader(pool, num_microbatches,
                                      async_swap=offload_async)
                for _ in range(n_stages)]
            if self._unit_has_paged(self._epi_view()):
                self._epi_off = DoubleBufferOffloader(
                    pool, num_microbatches, async_swap=offload_async)
            for off in self._stage_off + ([self._epi_off]
                                          if self._epi_off else []):
                off.recorder = recorder

    # -- per-stage offload residency ---------------------------------------

    @staticmethod
    def _unit_has_paged(view: dict) -> bool:
        return any(isinstance(c, dict) and "k_pages" in c
                   for part in ("scan", "tail") for c in view[part])

    def _stage_view(self, s: int) -> dict:
        lo, hi = s * self.pps, (s + 1) * self.pps
        return {"scan": [jax.tree.map(lambda x: x[lo:hi], c)
                         for c in self.caches["scan"]], "tail": []}

    def _epi_view(self) -> dict:
        lo = self.n_stages * self.pps
        scan = [jax.tree.map(lambda x: x[lo:], c)
                for c in self.caches["scan"]] if self.leftover else []
        return {"scan": scan, "tail": self.caches["tail"]}

    def _splice_scan(self, view: dict, lo: int) -> None:
        new_scan = self.caches["scan"]
        if view["scan"]:                # epilogue views may carry tail only
            new_scan = [jax.tree.map(
                lambda full, part: full.at[lo:lo + part.shape[0]].set(
                    part.astype(full.dtype)), c_full, c_new)
                for c_full, c_new in zip(self.caches["scan"], view["scan"])]
        self.caches = self._commit(
            {"scan": new_scan,
             "tail": view["tail"] or self.caches["tail"]})

    def _commit(self, tree):
        """Pin every cache leaf to the steady tick-jit layout (or the
        stage placement before the first tick learned it).  Host-side
        writes (page-table publish, slot reset, offload splice, exact
        prefill) otherwise hand the next tick arrays whose sharding
        differs from the previous tick's outputs — a silent jit cache-key
        flip and recompile.  A no-op for already-committed leaves."""
        return jax.tree.map(jax.device_put, tree, self._cache_shardings)

    def _probe_layout(self, plane: str, args: tuple) -> None:
        """Before the first tick of ``plane``: AOT-compile the tick on the
        bootstrap inputs and commit the persistent state to the compiled
        OUTPUT shardings — the layout every tick hands back.  jax.jit
        wrappers over the same callable share one C++ call cache, so a
        trace keyed on the bootstrap layout could never be evicted; the
        only way to keep steady state at exactly one compile per (shape,
        wire_dtype) config is to never let the bootstrap layout reach a
        counted call.  ``.lower().compile()`` does not populate
        ``_cache_size`` (verified on jax 0.9.0), so the probe itself is
        invisible to the strict-mode jit probes."""
        self._layout_learned[plane] = True
        if plane == "decode":
            out_sh = self._tick_jit.lower(*args).compile().output_shardings
            _, _, self._cache_shardings, self._act_sharding = out_sh
            self.act = jax.device_put(self.act, self._act_sharding)
        else:
            out_sh = (self._pf_tick_jit.lower(*args).compile()
                      .output_shardings)
            _, self._cache_shardings, self._pf_act_sharding = out_sh
            self._pf_act = jax.device_put(self._pf_act,
                                          self._pf_act_sharding)
        self.caches = self._commit(self.caches)

    def _ensure_stage_resident(self, s: int, mb: int) -> None:
        if not self._stage_off:
            return
        view = self._stage_view(s)
        new = self._stage_off[s].ensure_resident(view, mb)
        if new is not view:
            self._splice_scan(new, s * self.pps)

    def _ensure_epi_resident(self, mb: int) -> None:
        if self._epi_off is None:
            return
        view = self._epi_view()
        new = self._epi_off.ensure_resident(view, mb)
        if new is not view:
            self._splice_scan({"scan": new["scan"], "tail": new["tail"]},
                              self.n_stages * self.pps)

    def _prefill_residency(self, mb: int) -> None:
        # a prefill writes every period's pools: all stage units + epilogue
        for s in range(self.n_stages):
            self._ensure_stage_resident(s, mb)
        self._ensure_epi_resident(mb)

    # -- host-store migration (reshard) -------------------------------------

    def _offload_units(self):
        """(offloader, cache view, first scan period) per stage unit."""
        units = [(o, self._stage_view(s), s * self.pps)
                 for s, o in enumerate(self._stage_off)]
        if self._epi_off is not None:
            units.append((self._epi_off, self._epi_view(),
                          self.n_stages * self.pps))
        return units

    def export_offload_state(self) -> dict:
        """Concatenate every offloader's host store into *full-period*
        host arrays, keyed by microbatch — the stage-split-independent
        form ``import_offload_state`` re-splits for a new stage count.

        Per microbatch the state holds, for each paged scan kind, one
        ``(n_periods, n_global, page, heads, head_dim)`` array (periods a
        unit never staged out for that mb stay zero — identical to the
        offloader's own zero-fill-on-first-touch semantics) and for each
        paged tail kind one ``(n_global, ...)`` array.  Currently
        *resident* microbatches are snapshotted from the live pools too:
        the rebuilt backend's offloaders start with empty resident maps,
        so their first ``ensure_resident`` must find every microbatch's
        bytes in the host store.  Call only with both planes drained."""
        units = self._offload_units()
        if not units:
            return {}
        paged_scan = [c for c in self.caches["scan"]
                      if isinstance(c, dict) and "k_pages" in c]
        paged_tail = [c for c in self.caches["tail"]
                      if isinstance(c, dict) and "k_pages" in c]
        n_glob = self.pool.n_global_pages
        state: Dict[int, dict] = {}

        def entry(mb: int) -> dict:
            if mb not in state:
                state[mb] = {
                    "scan": [{
                        "k": np.zeros((c["k_pages"].shape[0], n_glob)
                                      + tuple(c["k_pages"].shape[2:]),
                                      np.dtype(c["k_pages"].dtype)),
                        "v": np.zeros((c["v_pages"].shape[0], n_glob)
                                      + tuple(c["v_pages"].shape[2:]),
                                      np.dtype(c["v_pages"].dtype)),
                    } for c in paged_scan],
                    "tail": [{
                        "k": np.zeros((n_glob,)
                                      + tuple(c["k_pages"].shape[1:]),
                                      np.dtype(c["k_pages"].dtype)),
                        "v": np.zeros((n_glob,)
                                      + tuple(c["v_pages"].shape[1:]),
                                      np.dtype(c["v_pages"].dtype)),
                    } for c in paged_tail],
                }
            return state[mb]

        for o, view, lo in units:
            o.settle()
            stores: Dict[int, List[dict]] = {}
            for parity, mb in o.resident.items():
                if mb is None:
                    continue
                sl = kvc.global_slice(self.pool, parity)
                snap = []
                for c, axis in o._paged_layers(view):
                    idx = (slice(None), sl) if axis == 1 else (sl,)
                    snap.append({"k": np.asarray(c["k_pages"][idx]),
                                 "v": np.asarray(c["v_pages"][idx])})
                stores[mb] = snap
            for mb, layers in o._host.items():
                stores[mb] = [{k: np.asarray(v) for k, v in layer.items()}
                              for layer in layers]
            n_scan = sum(1 for c in view["scan"]
                         if isinstance(c, dict) and "k_pages" in c)
            for mb, layers in stores.items():
                dst = entry(mb)
                for j in range(n_scan):
                    hi = lo + layers[j]["k"].shape[0]
                    dst["scan"][j]["k"][lo:hi] = layers[j]["k"]
                    dst["scan"][j]["v"][lo:hi] = layers[j]["v"]
                for j, layer in enumerate(layers[n_scan:]):
                    dst["tail"][j]["k"][...] = layer["k"]
                    dst["tail"][j]["v"][...] = layer["v"]
        return state

    def import_offload_state(self, state: dict) -> None:
        """Re-split full-period host arrays (``export_offload_state`` of
        the pre-reshard backend) across THIS backend's stage units.  The
        fresh offloaders keep empty resident maps: the first
        ``ensure_resident`` per microbatch pops its imported store and
        writes the pool — by then the departing parity (if any) has been
        staged out, so no carried byte is lost."""
        if not state:
            return
        for s, o in enumerate(self._stage_off):
            lo, hi = s * self.pps, (s + 1) * self.pps
            for mb, full in state.items():
                o._host[mb] = [{"k": f["k"][lo:hi].copy(),
                                "v": f["v"][lo:hi].copy()}
                               for f in full["scan"]]
        if self._epi_off is not None:
            lo = self.n_stages * self.pps
            for mb, full in state.items():
                store = [{"k": f["k"][lo:].copy(),
                          "v": f["v"][lo:].copy()}
                         for f in full["scan"]] if self.leftover else []
                store += [{"k": f["k"].copy(), "v": f["v"].copy()}
                          for f in full["tail"]]
                self._epi_off._host[mb] = store

    # -- fault injection ----------------------------------------------------

    def _take_faults(self, plane: str, tick: int, entries: list):
        """Consume this tick's fault events: drops null the shift-register
        entry (the payload is *lost* — the engine re-injects it) and
        return the dropped stage for the in-jit write mask; delays are
        returned as per-stage synthetic seconds for straggler tracking."""
        drop_stage, delays, lost = -1, {}, []
        if self.fault_plan is not None:
            for ev in self.fault_plan.take(plane, tick):
                if ev.kind == "drop":
                    if entries[ev.stage] is not None:
                        lost.append(entries[ev.stage])
                        entries[ev.stage] = None
                    drop_stage = ev.stage
                else:
                    delays[ev.stage] = delays.get(ev.stage, 0.0) + ev.delay_s
        return drop_stage, delays, lost

    def _record_faults(self, plane: str, lost_mbs: list,
                       delays: dict) -> None:
        """Flight-record this tick's injected faults (host-side stamps;
        callers gate on ``self.recorder is not None``)."""
        rec = self.recorder
        now = time.perf_counter()
        for m in lost_mbs:
            rec.fault("drop", now, (("plane", plane), ("mb", int(m))))
        for s, d in sorted(delays.items()):
            rec.fault("delay", now, (("plane", plane), ("stage", int(s)),
                                     ("delay_s", float(d))))

    def _observe_stages(self, dt: float, delays: dict,
                        stalls=None) -> None:
        # uniform share of the tick's dispatch time per stage, plus any
        # injected synthetic delay (the deterministic signal tests use —
        # dispatch is async, so dt alone is a weak lower bound), plus the
        # measured per-stage link stall from the transport: a stage
        # behind a slow link looks exactly like a straggler to the
        # mitigation loop, shrinking prefill admission the same way
        share = dt / self.n_stages
        for s in range(self.n_stages):
            extra = delays.get(s, 0.0)
            if stalls is not None:
                extra += float(stalls[s])
            self._stage_times.append((s, share + extra))
        if len(self._stage_times) > 4096:       # standalone use: the
            del self._stage_times[:-4096]       # engine drains every step

    def drain_stage_times(self) -> List[tuple]:
        """(stage, seconds) observations since the last call — feed into
        ``StragglerMitigator.observe``."""
        out, self._stage_times = self._stage_times, []
        return out

    # -- the prefill stepper ------------------------------------------------

    def prefill_can_accept(self) -> bool:
        return self._pf_entries[0] is None

    def prefill_pending(self) -> bool:
        return any(e is not None for e in self._pf_entries)

    def prefill_step(self, chunk) -> List[PrefillResult]:
        entries = list(self._pf_entries)
        if chunk is not None:
            assert entries[0] is None, "prefill pipe stage 0 is occupied"
            entries[0] = chunk
        if not any(e is not None for e in entries):
            return []
        tick = self._prefill_ticks
        self._prefill_ticks += 1
        drop_stage, delays, lost = self._take_faults("prefill", tick,
                                                     entries)
        if self.recorder is not None and (lost or delays):
            self._record_faults("prefill", [-1] * len(lost), delays)
        results = [PrefillResult(chunk=c,
                                 logits=np.zeros((c.tokens.shape[0], 1),
                                                 np.float32), lost=True)
                   for c in lost]
        if not any(e is not None for e in entries):
            self._pf_entries = [None] * self.n_stages
            return results
        ref = next(e for e in entries if e is not None)
        rows, clen = ref.tokens.shape
        n_pages_row = ref.tables.shape[1]
        if self._pf_act is None or self._pf_act.shape[1:3] != (rows, clen):
            self._pf_act = jax.device_put(
                jnp.zeros((self.n_stages, rows, clen, self.cfg.d_model),
                          self.rt.compute_dtype),
                self._pf_act_sharding or self._replicated)

        tokens = entries[0].tokens if entries[0] is not None \
            else np.zeros((rows, clen), np.int32)
        offs = np.zeros((self.n_stages, rows), np.int32)
        nval = np.zeros((self.n_stages, rows), np.int32)
        tabs = np.zeros((self.n_stages, rows, n_pages_row), np.int32)
        for s, e in enumerate(entries):
            if e is None:
                continue
            offs[s], nval[s], tabs[s] = e.offsets, e.n_valid, e.tables
            for mb in e.residency_mbs:
                self._ensure_stage_resident(s, mb)
        drained = entries[-1]
        if drained is not None:
            for mb in drained.residency_mbs:
                self._ensure_epi_resident(mb)
        lasts = drained.lasts if drained is not None \
            else np.zeros((rows,), np.int32)

        with obs.span("step.chunk_dispatch", self.recorder):
            tick_args = (jnp.asarray(tokens, jnp.int32), jnp.asarray(offs),
                         jnp.asarray(nval), jnp.asarray(tabs),
                         jnp.asarray(lasts, jnp.int32),
                         jnp.int32(drop_stage))
            if not self._layout_learned["prefill"]:
                self._probe_layout(
                    "prefill",
                    (self.params, self.caches, self._pf_act) + tick_args)
            t0 = time.perf_counter()
            logits, self.caches, self._pf_act = self._pf_tick_jit(
                self.params, self.caches, self._pf_act, *tick_args)
            t1 = time.perf_counter()
        dt = t1 - t0
        # the chunk activation (R, C, D) crosses each occupied boundary
        link = self.transport.tick(
            [e is not None for e in entries],
            rows * clen * self.cfg.d_model * self._dtype_bytes,
            [dt / self.n_stages] * self.n_stages, plane="prefill")
        self._observe_stages(dt, delays, link.stalls)
        if self.recorder is not None:
            # per-stage occupancy: prompt rows in flight at each stage
            # (host ints the stepper already holds)
            self.recorder.pipe_tick(
                "prefill", t0, t1,
                tuple(len(e.seqs) if e is not None else 0
                      for e in entries))
        self._pf_entries = [None] + entries[:-1]
        if drained is None:
            return results
        with obs.span("step.chunk_readback", self.recorder):
            # repro-audit: allow(host-sync) — prefill drain: first-token logits leave the pipe for host-side sampling, once per chunk
            logits = np.asarray(logits)
        return results + [PrefillResult(chunk=drained, logits=logits)]

    # -- the stepper --------------------------------------------------------

    def busy_microbatches(self) -> set:
        return {e[0] for e in self._entries if e is not None}

    def pending(self) -> bool:
        return any(e is not None for e in self._entries)

    def decode(self, mb: int, tokens: np.ndarray, cur_pos: np.ndarray,
               samp: RowSampling, active: bool = True) -> List[DecodeResult]:
        results: List[DecodeResult] = []
        if active and self.schedule == "round_flush" \
                and mb <= self._last_inject_mb:
            # vLLM-PP behaviour: the microbatch counter wrapped — a new
            # token round starts, so drain the pipe completely first
            # (fill/drain bubbles every round).  The drained results ride
            # back with this call; the engine books them by mb id.
            while self.pending():
                results += self._decode_tick(mb, tokens, cur_pos, samp,
                                             active=False)
            self._last_inject_mb = -1
        if active:
            self._last_inject_mb = mb
        return results + self._decode_tick(mb, tokens, cur_pos, samp,
                                           active=active)

    def _decode_tick(self, mb: int, tokens: np.ndarray, cur_pos: np.ndarray,
                     samp: RowSampling, active: bool) -> List[DecodeResult]:
        entries = list(self._entries)
        entries[0] = (mb, np.asarray(cur_pos, np.int32).copy(), samp) \
            if active else None
        if not any(e is not None for e in entries):
            return []
        tick = self._decode_ticks
        self._decode_ticks += 1
        drop_stage, delays, lost = self._take_faults("decode", tick, entries)
        if self.recorder is not None and (lost or delays):
            self._record_faults("decode", [e[0] for e in lost], delays)
        results = [DecodeResult(mb=e[0],
                                tokens=np.zeros((self.mb_size,), np.int32),
                                logprobs=np.zeros((self.mb_size,),
                                                  np.float32), lost=True)
                   for e in lost]
        if not any(e is not None for e in entries):
            self._entries = [None] * self.n_stages
            return results

        mb_assign = np.full((self.n_stages,), -1, np.int32)
        pos_stage = np.zeros((self.n_stages, self.mb_size), np.int32)
        for s, e in enumerate(entries):
            if e is not None:
                mb_assign[s] = e[0]
                pos_stage[s] = e[1]
                self._ensure_stage_resident(s, e[0])
        drained = entries[-1]
        if drained is not None:
            self._ensure_epi_resident(drained[0])
        # sampling params travel with the microbatch: the tick samples the
        # *draining* microbatch with the RowSampling captured at injection
        dsamp = drained[2] if drained is not None \
            else RowSampling.zeros(self.mb_size)

        with obs.span("step.decode_dispatch", self.recorder):
            tick_args = (jnp.asarray(tokens, jnp.int32),
                         jnp.asarray(mb_assign), jnp.asarray(pos_stage),
                         jnp.asarray(dsamp.keys), jnp.asarray(dsamp.steps),
                         jnp.asarray(dsamp.temp), jnp.asarray(dsamp.top_k),
                         jnp.asarray(dsamp.top_p), jnp.int32(drop_stage))
            if not self._layout_learned["decode"]:
                self._probe_layout(
                    "decode",
                    (self.params, self.caches, self.act) + tick_args)
            t0 = time.perf_counter()
            toks, lps, self.caches, self.act = self._tick_jit(
                self.params, self.caches, self.act, *tick_args)
            t1 = time.perf_counter()
        dt = t1 - t0
        # the (mb_size, 1, D) activation crosses each occupied boundary;
        # an injection may not start before its microbatch's previous
        # drain returned over the last link (the §4.3 dependency)
        link = self.transport.tick(
            [e is not None for e in entries],
            self.mb_size * self.cfg.d_model * self._dtype_bytes,
            [dt / self.n_stages] * self.n_stages,
            inject_t=self._ret_ready.get(mb, 0.0)
            if entries[0] is not None else 0.0, plane="decode")
        self._observe_stages(dt, delays, link.stalls)
        if self.recorder is not None:
            # per-stage occupancy: which microbatch sat in each stage
            # slot this tick (-1 = bubble) — host ints from mb_assign
            self.recorder.pipe_tick("decode", t0, t1,
                                    tuple(int(m) for m in mb_assign))
        self._entries = [None] + entries[:-1]
        if drained is None:
            return results
        self._ret_ready[drained[0]] = link.return_ready
        # §4.3 return link: token ids of the draining microbatch ride
        # back to the host injector — one batched (tokens, logprobs)
        # transfer per drained tick, not two separate syncs
        with obs.span("step.decode_readback", self.recorder):
            # repro-audit: allow(host-sync) — intended §4.3 return-link sync, one batched transfer per drain
            toks, lps = jax.device_get((toks, lps))
        return results + [DecodeResult(mb=drained[0], tokens=toks,
                                       logprobs=lps)]

    def transport_stats(self) -> Dict:
        return self.transport.stats()

    def jit_entries(self) -> Dict[str, object]:
        out = super().jit_entries()
        out["_tick_jit"] = self._tick_jit
        out["_pf_tick_jit"] = self._pf_tick_jit
        return out

    @property
    def swap_count(self) -> int:
        n = sum(o.swap_count for o in self._stage_off)
        return n + (self._epi_off.swap_count if self._epi_off else 0)


def make_backend(kind, cfg, params, rt, *, mb_size, num_microbatches, pool,
                 offloader=None, n_stages=2, mesh=None, fault_plan=None,
                 transport=None, schedule="circular", wire_dtype="fp32",
                 sample_fast_path=True, offload_async=True,
                 recorder=None) -> ExecutionBackend:
    """Engine-side factory: ``kind`` is "local", "pipelined", or an already
    constructed :class:`ExecutionBackend` (passed through)."""
    if isinstance(kind, ExecutionBackend):
        return kind
    if kind == "local":
        if fault_plan is not None:
            raise ValueError(
                "fault injection (FaultPlan) requires the pipelined "
                "backend — the local backend has no stages to drop")
        if transport is not None or schedule != "circular" \
                or wire_dtype != "fp32":
            raise ValueError(
                "stage transports / schedules / wire codecs require the "
                "pipelined backend — the local backend has no stage "
                "boundaries for a link to cross")
        return LocalBackend(cfg, params, rt, mb_size=mb_size,
                            num_microbatches=num_microbatches, pool=pool,
                            offloader=offloader,
                            sample_fast_path=sample_fast_path,
                            recorder=recorder)
    if kind == "pipelined":
        return PipelinedBackend(cfg, params, rt, mb_size=mb_size,
                                num_microbatches=num_microbatches, pool=pool,
                                n_stages=n_stages,
                                offload=offloader is not None, mesh=mesh,
                                fault_plan=fault_plan, transport=transport,
                                schedule=schedule, wire_dtype=wire_dtype,
                                sample_fast_path=sample_fast_path,
                                offload_async=offload_async,
                                recorder=recorder)
    raise ValueError(f"unknown backend {kind!r} (want 'local'|'pipelined')")
