"""KV-cache offloading (DeServe §4.2): capacity formulas + the double-buffer
global-pool swapper.

Formula 2 sizes each global pool so that a full swap (out + in, full-duplex)
hides under one pipeline stage time:      M_G = W · T_S
Formula 1 gives the per-microbatch KV capacity with offloading:
      M_B' = (M_KV − 2·M_G) / N_B + M_G
whose floor M_G is *independent of N_B* — the synergy that lets microbatch
scheduling (§4.3) add in-flight microbatches without starving batch size.

Hardware adaptation: on GPU the swap path is PCIe; on TPU v5e it is the
host-DMA path (HBM ↔ host DRAM).  The :class:`DoubleBufferOffloader` below
implements the *schedule* (pool parity, swap-out of the departing microbatch
overlapped with swap-in of the arriving one).  In the default async mode
(``async_swap=True``) the swap-out stores the *enqueued* jax copy — a
lazily-materialised device array (routed to ``pinned_host`` on TPU, in
the pool's own sharding) — so the D2H of buffer A overlaps the next tick's
jit computing into buffer B; nothing
blocks until :meth:`DoubleBufferOffloader.settle` (drain/reshard) or the
value is consumed by a swap-in.  ``async_swap=False`` keeps the old
blocking numpy round-trip for debugging and bit-exactness A/B runs — the
bookkeeping and the schedule are identical either way, which is what the
tests pin.
"""

from __future__ import annotations

import dataclasses
import functools
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.serving.kv_cache import PoolConfig, global_slice

# default bandwidth constants (bytes/s)
PCIE4_BW = 24e9            # paper's setting: PCIe 4.0 x16 effective
TPU_HOST_DMA_BW = 32e9     # v5e host DMA (per chip, conservative)


def global_pool_bytes(bandwidth: float, stage_time: float) -> float:
    """Formula 2: the largest pool a stage-time-long swap can move."""
    return bandwidth * stage_time


def per_microbatch_capacity(m_kv: float, m_g: float, n_b: int) -> float:
    """Formula 1: per-microbatch KV bytes with offloading enabled."""
    m_g = min(m_g, m_kv / 2.0)
    return (m_kv - 2.0 * m_g) / n_b + m_g


def per_microbatch_capacity_no_offload(m_kv: float, n_b: int) -> float:
    return m_kv / n_b


def batch_size_from_capacity(capacity_bytes: float,
                             kv_bytes_per_seq: float) -> int:
    return max(0, int(capacity_bytes // max(kv_bytes_per_seq, 1.0)))


@dataclass
class OffloadPlan:
    """Concrete page accounting for an engine/pipeline stage."""
    pool: PoolConfig
    bandwidth: float
    stage_time: float
    n_microbatches: int
    page_bytes: int                   # bytes per page across paged layers

    @classmethod
    def derive(cls, *, m_kv_bytes: float, page_bytes: int, page_size: int,
               max_pages_per_seq: int, bandwidth: float, stage_time: float,
               n_microbatches: int) -> "OffloadPlan":
        m_g = global_pool_bytes(bandwidth, stage_time)
        m_g = min(m_g, m_kv_bytes / 2.0)
        n_global = int(m_g // page_bytes)
        n_local = max(2, int((m_kv_bytes - 2 * m_g) // page_bytes))
        pool = PoolConfig(page_size=page_size, n_local_pages=n_local,
                          n_global_pages=n_global,
                          max_pages_per_seq=max_pages_per_seq)
        return cls(pool=pool, bandwidth=bandwidth, stage_time=stage_time,
                   n_microbatches=n_microbatches, page_bytes=page_bytes)

    @property
    def m_g_bytes(self) -> float:
        return self.pool.n_global_pages * self.page_bytes

    @property
    def m_kv_bytes(self) -> float:
        return self.pool.n_pages * self.page_bytes

    def capacity_with_offload(self) -> float:
        return per_microbatch_capacity(self.m_kv_bytes, self.m_g_bytes,
                                       self.n_microbatches)

    def capacity_without_offload(self) -> float:
        return per_microbatch_capacity_no_offload(self.m_kv_bytes,
                                                  self.n_microbatches)


# jitted so the snapshot is one fused copy per buffer; static bounds:
# one compile per (pool shape, parity) — a handful total
@functools.partial(jax.jit, static_argnames=("start", "stop", "axis"))
def _snapshot_slice(pages, start: int, stop: int, axis: int):
    return jax.lax.slice_in_dim(pages, start, stop, axis=axis)


# one worker serialises stage-outs in submission order (the double-buffer
# schedule needs no more concurrency: at most one departing microbatch per
# parity is in flight); shared across offloaders — copies are bandwidth-
# bound, more workers would just contend for the same memory bus
_COPY_POOL: Optional[ThreadPoolExecutor] = None


def _copy_pool() -> ThreadPoolExecutor:
    global _COPY_POOL
    if _COPY_POOL is None:
        _COPY_POOL = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="kv-offload")
    return _COPY_POOL


class DoubleBufferOffloader:
    """Functional double-buffer swapper over the engine's cache pytree.

    Microbatch ``m`` owns global pool parity ``m % 2``.  ``ensure_resident``
    swaps the departing microbatch's global-pool content to the host store
    and the arriving one's back in.  ``prefetch_next`` mirrors the paper's
    overlap: with pool ``G_p`` feeding compute for microbatch ``m``, pool
    ``G_{1−p}`` is being refilled for ``m+1`` — on TPU both directions run
    concurrently on the full-duplex host-DMA path.

    ``async_swap=True`` (default): swap-out stores a *future* of the
    snapshot instead of performing it inline — jax arrays are immutable,
    so the slice taken on the copy worker is a correct snapshot of the
    pool at swap-out time while the engaged window only pays the submit.
    The future resolves at the matching swap-in (by which point the copy
    has long landed) or at :meth:`settle`.  Invariants the strict-mode
    auditor pins: ``resident[p]`` is ``None`` or has parity ``p``, the
    host store never keys a currently-resident microbatch, and the swap
    counters are monotone for the offloader's lifetime.
    """

    def __init__(self, pool: PoolConfig, num_microbatches: int,
                 async_swap: bool = True):
        self.pool = pool
        self.num_microbatches = num_microbatches
        self.async_swap = async_swap
        self.resident: Dict[int, Optional[int]] = {0: None, 1: None}
        # mb -> per-layer {"k","v"} store, or a Future of it (async mode)
        self._host: Dict[int, Union[List[dict], Future]] = {}
        self.swap_count = 0
        self.bytes_swapped = 0
        # flight recorder (set by the backend when tracing is on): swap
        # dispatches and swap-in wait windows are recorded host-side
        self.recorder = None

    # -- internal: per-layer global slices ---------------------------------

    def _paged_layers(self, caches):
        for c in caches["scan"]:
            if isinstance(c, dict) and "k_pages" in c:
                yield c, 1            # pool axis after the period axis
        for c in caches["tail"]:
            if isinstance(c, dict) and "k_pages" in c:
                yield c, 0

    def ensure_resident(self, caches, mb: int):
        parity = mb % 2
        if self.resident[parity] == mb or self.pool.n_global_pages == 0:
            return caches
        out_mb = self.resident[parity]
        sl = global_slice(self.pool, parity)
        layers = list(self._paged_layers(caches))
        rec = self.recorder
        if out_mb is not None:
            with obs.span("kv.swap_out", rec,
                          (("mb", out_mb), ("async", self.async_swap))):
                self._host[out_mb] = self._dispatch_stage_out(layers, sl)

        incoming = self._host.pop(mb, None)
        if isinstance(incoming, Future):
            # the wait window: the part of the staged copy the
            # double-buffer failed to hide under the previous tick
            with obs.span("kv.swap_wait", rec, (("mb", mb),)):
                incoming = incoming.result()
        if incoming is None and out_mb is not None:
            # first touch for this microbatch while the pool holds another
            # one's content: zero-fill (hygiene — stale KV is masked by
            # seq_lens anyway, but must never be observable)
            incoming = []
            for c, axis in layers:
                shape = list(c["k_pages"].shape)
                shape[axis] = sl.stop - sl.start
                incoming.append({"k": np.zeros(shape, c["k_pages"].dtype),
                                 "v": np.zeros(shape, c["v_pages"].dtype)})
        out = {"scan": [], "tail": []}
        li = 0
        for part in ("scan", "tail"):
            for c in caches[part]:
                if isinstance(c, dict) and "k_pages" in c:
                    axis = 1 if part == "scan" else 0
                    if incoming is not None:
                        k_new = _on_device(incoming[li]["k"])
                        v_new = _on_device(incoming[li]["v"])
                        c = {**c,
                             "k_pages": jax.lax.dynamic_update_slice_in_dim(
                                 c["k_pages"], k_new.astype(c["k_pages"].dtype),
                                 sl.start, axis=axis),
                             "v_pages": jax.lax.dynamic_update_slice_in_dim(
                                 c["v_pages"], v_new.astype(c["v_pages"].dtype),
                                 sl.start, axis=axis)}
                        self.bytes_swapped += k_new.nbytes + v_new.nbytes
                    li += 1
                out[part].append(c)
        self.resident[parity] = mb
        self.swap_count += 1
        return out

    def _dispatch_stage_out(self, layers, sl) -> Union[List[dict], Future]:
        """Swap-out dispatch: async mode hands the snapshot to the copy
        worker and returns the in-flight :class:`Future` (the tick loop
        never blocks on it — swap-in or :meth:`settle` resolves it);
        sync mode pays the copy here."""
        if self.async_swap:
            return _copy_pool().submit(self._stage_out, layers, sl)
        return self._stage_out(layers, sl)

    def _stage_out(self, layers, sl) -> List[dict]:
        """Snapshot the departing microbatch's global slices into the
        host store.  This is the D2H half of the swap — the part the
        async mode turns from a blocking copy into an enqueued one."""
        store = []
        for c, axis in layers:
            k = _snapshot_slice(c["k_pages"], sl.start, sl.stop, axis)
            v = _snapshot_slice(c["v_pages"], sl.start, sl.stop, axis)
            if self.async_swap:
                host = _pinned_host(k)
                if host is not None:
                    # TPU: enqueue the D2H DMA toward pinned_host now;
                    # it lands while the next tick jit runs
                    k = jax.device_put(k, host)
                    v = jax.device_put(v, host)
                store.append({"k": k, "v": v})
            else:
                # repro-audit: allow(host-sync, offload-sync) — async_swap=False opt-out: the blocking numpy round-trip, kept for debugging and A/B bit-exactness runs
                store.append({"k": np.asarray(k), "v": np.asarray(v)})
            self.bytes_swapped += k.nbytes + v.nbytes
        return store

    def settle(self) -> "DoubleBufferOffloader":
        """Block until every in-flight host-store copy has landed (and
        replace resolved futures with their stores).  This is the
        *outside-the-engaged-window* barrier (drain / reshard /
        shutdown) — the tick loop itself never calls it, so the async
        copies stay overlapped with compute."""
        for mb, layers in list(self._host.items()):
            if isinstance(layers, Future):
                self._host[mb] = layers = layers.result()
            for layer in layers:
                for arr in layer.values():
                    if isinstance(arr, jax.Array):
                        jax.block_until_ready(arr)
        return self


# ---------------------------------------------------------------------------
# TPU memory-kind integration (backend-gated, see DESIGN.md §3)
# ---------------------------------------------------------------------------


def host_memory_available() -> bool:
    """True when the backend supports device↔pinned_host placement (TPU).
    XLA:CPU rejects compile-time host placement for replicated tensors
    (verified: "UNIMPLEMENTED: Side-effect ops cannot be replicated")."""
    return jax.default_backend() == "tpu"


def _pinned_host(x: jax.Array):
    """``x``'s own sharding in pinned host memory, or None where the
    backend has no host memory space."""
    if not host_memory_available():
        return None
    return x.sharding.with_memory_kind("pinned_host")


def _on_device(x):
    """A host-store entry as a device-memory array: the swap-in splices it
    into a pool that lives in device memory."""
    if isinstance(x, jax.Array) and x.sharding.memory_kind == "pinned_host":
        return jax.device_put(x, x.sharding.with_memory_kind("device"))
    return jnp.asarray(x)
