"""Serving stack: allocator, paged caches, engine continuous batching,
offloader rotation, engine == reference greedy decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny
from repro.core.offload import DoubleBufferOffloader
from repro.models import model as M
from repro.serving import kv_cache as kvc
from repro.serving.engine import OfflineEngine
from repro.serving.kv_cache import PageAllocator, PoolConfig
from repro.serving.request import Request, SamplingParams


# ---------------------------------------------------------------- alloc ---

def test_allocator_basic_and_rollback():
    pool = PoolConfig(page_size=4, n_local_pages=5, n_global_pages=2,
                      max_pages_per_seq=8)
    al = PageAllocator(pool)
    assert al.free_local() == 4          # page 0 reserved as scratch
    pages = al.allocate(0, 3)
    assert len(pages) == 3 and 0 not in pages
    assert al.free_local() == 1
    # exceeding local+global capacity rolls back cleanly
    with pytest.raises(MemoryError):
        al.allocate(1, 6, global_pool=0)
    assert al.free_local() == 1 and al.free_global(0) == 2
    al.release(0)
    assert al.free_local() == 4


def test_allocator_global_pool_separation():
    pool = PoolConfig(page_size=4, n_local_pages=2, n_global_pages=3,
                      max_pages_per_seq=8)
    al = PageAllocator(pool)
    p0 = al.allocate(0, 3, global_pool=0)   # 1 local + 2 from G0
    g0 = set(pool.global_range(0))
    g1 = set(pool.global_range(1))
    assert len(set(p0) & g1) == 0
    p1 = al.allocate(1, 2, global_pool=1)
    assert set(p1) <= g1
    al.release(0)
    assert al.free_global(0) == 3


def test_table_row_order_preserved():
    pool = PoolConfig(page_size=4, n_local_pages=8, max_pages_per_seq=4)
    al = PageAllocator(pool)
    pages = al.allocate(7, 2)
    pages += [al.extend(7)]
    row = al.table_row(7)
    assert list(row[:3]) == pages


# ---------------------------------------------------------------- caches ---

def test_build_and_reset_paged_caches(rt):
    cfg = tiny("gemma3-12b")       # local + global kinds
    pool = PoolConfig(page_size=4, n_local_pages=8, n_global_pages=2,
                      max_pages_per_seq=4)
    caches = kvc.build_paged_caches(cfg, batch=3, pool=pool, rt=rt)
    kinds = [("k_pages" in c, "pos" in c) for c in caches["scan"]]
    assert (False, True) in kinds        # local ring present
    assert (True, False) in kinds        # paged pool present
    # reset slot 1: ring pos -> -1 there, untouched elsewhere
    for c in caches["scan"]:
        if "pos" in c:
            c["pos"] = c["pos"].at[:, 1].set(5)
            c["pos"] = c["pos"].at[:, 2].set(7)
    caches = kvc.reset_slot(caches, cfg, 1, rt)
    for c in caches["scan"]:
        if "pos" in c:
            assert bool(jnp.all(c["pos"][:, 1] == -1))
            assert bool(jnp.all(c["pos"][:, 2] == 7))


def test_set_page_table_broadcast(rt):
    cfg = tiny("yi-9b")
    pool = PoolConfig(page_size=4, n_local_pages=8, max_pages_per_seq=4)
    caches = kvc.build_paged_caches(cfg, batch=2, pool=pool, rt=rt)
    table = np.arange(8, dtype=np.int32).reshape(2, 4)
    caches = kvc.set_page_table(caches, table)
    for c in caches["scan"]:
        if "page_table" in c:
            assert c["page_table"].shape[0] == 2 or \
                c["page_table"].shape[1] == 2
            got = np.asarray(c["page_table"])
            assert (got[0] == table).all() if got.ndim == 3 else \
                (got == table).all()


# ---------------------------------------------------------------- engine ---

def _engine(rt, arch="yi-9b", n_mb=2, mb=2, offload=True, max_new=10):
    cfg = tiny(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    pool = PoolConfig(page_size=8, n_local_pages=24,
                      n_global_pages=8 if offload else 0,
                      max_pages_per_seq=8)
    off = DoubleBufferOffloader(pool, n_mb) if offload else None
    sp = SamplingParams(temperature=0.0, max_new_tokens=max_new)
    return OfflineEngine(cfg, params, rt, mb_size=mb, num_microbatches=n_mb,
                         pool=pool, sampling=sp, offloader=off), cfg, params


def _requests(cfg, n, seed=0, lo=3, hi=12, max_new=10):
    rng = np.random.RandomState(seed)
    sp = SamplingParams(temperature=0.0, max_new_tokens=max_new)
    return [Request(i, list(rng.randint(1, cfg.vocab_size,
                                        rng.randint(lo, hi))), sp)
            for i in range(n)]


def test_engine_finishes_all_requests(rt):
    eng, cfg, _ = _engine(rt)
    reqs = _requests(cfg, 9)
    eng.submit(reqs)
    done = eng.run(max_steps=500)
    assert len(done) == 9
    for s in done:
        assert len(s.generated) == 10


def test_engine_matches_reference_greedy(rt):
    eng, cfg, params = _engine(rt, max_new=8)
    reqs = _requests(cfg, 5, seed=3, max_new=8)
    eng.submit(reqs)
    done = {s.request.request_id: s for s in eng.run(max_steps=400)}

    for rid in (0, 2, 4):
        prompt = reqs[rid].prompt
        toks = jnp.asarray(prompt, jnp.int32)[None]
        logits, caches = M.prefill(params, {"tokens": toks}, cfg, rt, 128)
        ref = []
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for i in range(8):
            ref.append(int(tok[0]))
            logits, caches = M.decode_step(
                params, tok, caches,
                jnp.asarray([len(prompt) + i], jnp.int32), cfg, rt)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        assert done[rid].generated == ref, rid


def test_engine_eos_stops_early(rt):
    cfg = tiny("yi-9b")
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    pool = PoolConfig(page_size=8, n_local_pages=24, max_pages_per_seq=8)
    # find what greedy emits first, then make that the eos token
    toks = jnp.asarray([[5, 6, 7]], jnp.int32)
    logits, _ = M.prefill(params, {"tokens": toks}, cfg, rt, 64)
    eos = int(jnp.argmax(logits, -1)[0])
    sp = SamplingParams(temperature=0.0, max_new_tokens=50, eos_token=eos)
    eng = OfflineEngine(cfg, params, rt, mb_size=1, num_microbatches=1,
                        pool=pool, sampling=sp)
    eng.submit([Request(0, [5, 6, 7], sp)])
    done = eng.run(max_steps=200)
    assert len(done) == 1
    assert done[0].generated[-1] == eos
    assert len(done[0].generated) < 50


def test_engine_slot_reuse_no_crosstalk(rt):
    """More requests than slots: recycled slots must produce the same
    output as a fresh engine run of the same request."""
    eng1, cfg, params = _engine(rt, n_mb=1, mb=1, offload=False, max_new=6)
    reqs = _requests(cfg, 4, seed=11, max_new=6)
    eng1.submit(reqs)
    serial = {s.request.request_id: s.generated
              for s in eng1.run(max_steps=400)}
    assert len(serial) == 4
    eng2, _, _ = _engine(rt, n_mb=2, mb=2, offload=True, max_new=6)
    eng2.submit(_requests(cfg, 4, seed=11, max_new=6))
    packed = {s.request.request_id: s.generated
              for s in eng2.run(max_steps=400)}
    assert serial == packed


def test_offloader_roundtrip_preserves_content(rt):
    cfg = tiny("yi-9b")
    pool = PoolConfig(page_size=4, n_local_pages=4, n_global_pages=3,
                      max_pages_per_seq=6)
    caches = kvc.build_paged_caches(cfg, batch=2, pool=pool, rt=rt)
    # write a signature into G0's slice for mb 0
    sl = kvc.global_slice(pool, 0)
    sig = 3.25
    caches["scan"] = [
        {**c, "k_pages": c["k_pages"].at[:, sl.start].set(sig)}
        if "k_pages" in c else c for c in caches["scan"]]
    off = DoubleBufferOffloader(pool, num_microbatches=4)
    caches = off.ensure_resident(caches, 0)        # adopt mb0 (no prior)
    caches = off.ensure_resident(caches, 2)        # swap mb0 out, mb2 in
    for c in caches["scan"]:
        if "k_pages" in c:
            assert not bool(jnp.any(c["k_pages"][:, sl.start] == sig))
    caches = off.ensure_resident(caches, 0)        # swap mb0 back in
    found = False
    for c in caches["scan"]:
        if "k_pages" in c:
            found = True
            assert bool(jnp.all(c["k_pages"][:, sl.start] == sig))
    assert found
    assert off.swap_count == 3
    assert off.bytes_swapped > 0


def test_offloader_async_matches_sync(rt):
    """async_swap stores the enqueued jax copy instead of a blocking
    numpy one — the pool contents after any swap sequence must be
    bit-identical between the two modes, and a swap-in must pop the
    host-store key (the strict auditor's staleness invariant)."""
    cfg = tiny("yi-9b")
    pool = PoolConfig(page_size=4, n_local_pages=4, n_global_pages=3,
                      max_pages_per_seq=6)

    def run(async_swap):
        caches = kvc.build_paged_caches(cfg, batch=2, pool=pool, rt=rt)
        sl = kvc.global_slice(pool, 0)
        caches["scan"] = [
            {**c, "k_pages": c["k_pages"].at[:, sl.start].set(1.5)}
            if "k_pages" in c else c for c in caches["scan"]]
        off = DoubleBufferOffloader(pool, 4, async_swap=async_swap)
        for mb in (0, 2, 0, 2, 0):
            caches = off.ensure_resident(caches, mb)
            assert mb not in off._host        # swap-in popped the key
        off.settle()
        return off, [np.asarray(c["k_pages"]) for part in ("scan", "tail")
                     for c in caches[part]
                     if isinstance(c, dict) and "k_pages" in c]

    off_a, pools_a = run(True)
    off_s, pools_s = run(False)
    assert off_a.swap_count == off_s.swap_count == 5
    assert off_a.bytes_swapped == off_s.bytes_swapped
    for pa, ps in zip(pools_a, pools_s):
        np.testing.assert_array_equal(pa, ps)


def test_sampler_modes():
    from repro.serving.sampler import sample
    logits = jnp.asarray([[1.0, 5.0, 2.0, 0.0]])
    key = jax.random.PRNGKey(0)
    assert int(sample(logits, key, SamplingParams(temperature=0.0))[0]) == 1
    # top-k=1 is greedy regardless of temperature
    sp = SamplingParams(temperature=2.0, top_k=1)
    assert int(sample(logits, key, sp)[0]) == 1
    # top-p very small keeps only the argmax
    sp = SamplingParams(temperature=1.0, top_p=1e-6)
    assert int(sample(logits, key, sp)[0]) == 1
    # plain temperature sampling hits every token eventually
    sp = SamplingParams(temperature=5.0)
    seen = {int(sample(logits, jax.random.PRNGKey(i), sp)[0])
            for i in range(60)}
    assert len(seen) >= 3


def test_offload_backend_gating(rt):
    """On CPU the pinned_host path degrades to device memory and the numpy
    store; the schedule/bookkeeping is identical either way (DESIGN §3)."""
    from repro.core import offload as OF
    assert not OF.host_memory_available()        # CPU container
    x = jnp.arange(8.0)
    # no host memory space: the swap-out keeps the snapshot where it is
    assert OF._pinned_host(x) is None
    # a host-store entry in default memory splices back unchanged
    assert OF._on_device(x) is x
    np.testing.assert_array_equal(OF._on_device(np.ones(3)), np.ones(3))
