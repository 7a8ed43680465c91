"""Execution-backend seam: LocalBackend row isolation, planned engine
construction, submit-time admission control, and Local == Pipelined greedy
equivalence through the N_S-stage shard_map pipe (subprocess, fake devices).
"""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny
from repro.core import scheduler as SC
from repro.models import model as M
from repro.serving.backend import LocalBackend, PipelinedBackend
from repro.serving.engine import OfflineEngine
from repro.serving.kv_cache import PoolConfig
from repro.serving.request import Request, SamplingParams


def _per_slot_rows(caches, lo, hi):
    """Numpy snapshot of every per-slot leaf's rows [lo, hi)."""
    rows = []
    for part, axis in (("scan", 1), ("tail", 0)):
        for c in caches[part]:
            for k in sorted(c):
                if k.endswith("_pages"):
                    continue
                leaf = np.asarray(c[k])
                rows.append(leaf[:, lo:hi] if axis == 1 else leaf[lo:hi])
    return rows


def test_local_decode_touches_only_microbatch_rows(rt):
    """Satellite: decode feeds only the microbatch's mb_size view through
    the model — rows of other microbatches stay bit-identical."""
    cfg = tiny("recurrentgemma-9b")     # recurrent states + ring + paged
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    pool = PoolConfig(page_size=8, n_local_pages=16, max_pages_per_seq=2)
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    eng = OfflineEngine(cfg, params, rt, mb_size=2, num_microbatches=2,
                        pool=pool, sampling=sp)
    rng = np.random.RandomState(0)
    eng.submit([Request(i, list(rng.randint(1, cfg.vocab_size, 4)), sp)
                for i in range(4)])
    assert eng.step()                   # admits all four, decodes mb 0
    before_mb0 = _per_slot_rows(eng.backend.caches, 0, 2)
    assert eng.step()                   # decodes mb 1: rows 2..4
    after_mb0 = _per_slot_rows(eng.backend.caches, 0, 2)
    for a, b in zip(before_mb0, after_mb0):
        np.testing.assert_array_equal(a, b)
    # sanity: mb 1's rows did change
    changed = any(
        not np.array_equal(a, b)
        for a, b in zip(_per_slot_rows(eng.backend.caches, 2, 4),
                        before_mb0))
    assert changed or eng.cur_pos[2] > 0


def test_submit_rejects_over_capacity_prompt(rt):
    """Satellite: a prompt that fills the whole per-sequence page budget
    would be admitted with zero generation budget — reject at submit."""
    cfg = tiny("yi-9b")
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    pool = PoolConfig(page_size=4, n_local_pages=16, max_pages_per_seq=2)
    sp = SamplingParams(temperature=0.0, max_new_tokens=8)
    eng = OfflineEngine(cfg, params, rt, mb_size=1, num_microbatches=1,
                        pool=pool, sampling=sp)
    cap = pool.max_pages_per_seq * pool.page_size            # 8 tokens
    with pytest.raises(ValueError, match="KV capacity"):
        eng.submit([Request(0, list(range(1, cap + 1)), sp)])
    assert not eng.queue                # nothing was admitted
    # one token under capacity is admissible and yields exactly one token
    eng.submit([Request(1, list(range(1, cap)), sp)])
    done = eng.run(max_steps=50)
    assert len(done) == 1 and len(done[0].generated) == 1
    assert done[0].budget == 1


def test_from_plan_honors_schedule_choice(rt):
    """Satellite: a pre-computed ScheduleChoice is honored as-is — N_B,
    per-microbatch batch, and the offload pool split all follow it."""
    cfg = tiny("yi-9b")
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    choice = SC.ScheduleChoice(n_microbatches=3, per_mb_batch=2,
                               per_mb_kv_bytes=0.0, utilisation=1.0,
                               offload=True)
    pb = 2 * cfg.num_layers * 8 * cfg.num_kv_heads * cfg.head_dim * 4
    sp = SamplingParams(temperature=0.0, max_new_tokens=3)
    eng = OfflineEngine.from_plan(
        cfg, params, rt, n_stages=2, stage_time=0.1, latency=0.05,
        m_kv_bytes=64.0 * pb, bandwidth=160.0 * pb, page_size=8,
        max_pages_per_seq=4, choice=choice, sampling=sp)
    assert eng.num_microbatches == choice.n_microbatches
    assert eng.mb_size == choice.per_mb_batch
    assert eng.schedule_choice is choice
    assert eng.pool.n_global_pages > 0          # offload=True -> split pool
    assert eng.backend.name == "local"
    assert eng.backend.offloader is not None
    # and the planned engine actually serves
    rng = np.random.RandomState(1)
    eng.submit([Request(i, list(rng.randint(1, cfg.vocab_size, 4)), sp)
                for i in range(4)])
    assert len(eng.run(max_steps=200)) == 4


def test_from_plan_invokes_planner(rt):
    cfg = tiny("yi-9b")
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    pb = 2 * cfg.num_layers * 8 * cfg.num_kv_heads * cfg.head_dim * 4
    eng = OfflineEngine.from_plan(
        cfg, params, rt, n_stages=2, stage_time=0.1, latency=0.02,
        m_kv_bytes=32.0 * pb, bandwidth=40.0 * pb, page_size=8,
        max_pages_per_seq=4, mb_size_cap=2, max_microbatches=8)
    assert eng.schedule_choice.n_microbatches >= 2      # >= n_stages
    assert eng.schedule_choice.n_microbatches <= 8
    assert eng.mb_size <= 2                             # cap applied


def test_plan_schedule_respects_max_microbatches():
    """Satellite: when the bubble-free N_B* exceeds the cap, the planner
    must stay at or under the cap (host memory bounds the pipe depth)."""
    n_star = SC.optimal_microbatches(4, 0.01, 1.0)
    assert n_star > 16
    choice = SC.plan_schedule(
        n_stages=4, stage_time=0.01, latency=1.0, m_kv_bytes=1e9,
        kv_bytes_per_seq=1e6, use_offload=True, max_microbatches=16)
    assert choice.n_microbatches <= 16
    with pytest.raises(ValueError, match="max_microbatches"):
        SC.plan_schedule(n_stages=4, stage_time=0.01, latency=0.0,
                         m_kv_bytes=1e9, kv_bytes_per_seq=1e6,
                         max_microbatches=2)


def test_pipelined_backend_rejects_shallow_queue(rt):
    """N_B < N_S would re-inject a microbatch before its previous tick
    drained — rejected at construction."""
    cfg = tiny("yi-9b")
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    with pytest.raises(ValueError, match="N_B >= N_S"):
        OfflineEngine(cfg, params, rt, mb_size=1, num_microbatches=1,
                      backend="pipelined", n_stages=2)


# ---------------------------------------------------------------- SPMD ---

EQUIV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp
from equivalence import assert_equivalent, golden_runs, random_prompts
from repro.config import get_arch, reduced_config
from repro.models import model as M
from repro.models.common import Runtime
from repro.serving.kv_cache import PoolConfig
from repro.serving.request import SamplingParams

rt = Runtime(param_dtype=jnp.float32, compute_dtype=jnp.float32)
arch = os.environ["PIPE_ARCH"]
cfg0 = get_arch(arch)
period = len(cfg0.block_pattern)
cfg = reduced_config(cfg0, num_layers=2 * period + (2 if period > 1 else 1))
params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
# offloading ON (n_global_pages > 0) and N_B=3 > 2 pools: microbatches 0
# and 2 contend for global pool parity 0 — the hard case
pool = PoolConfig(page_size=4, n_local_pages=32, n_global_pages=12,
                  max_pages_per_seq=6)
sp = SamplingParams(temperature=0.0, max_new_tokens=6)
# 10 requests > slots: replenishment while the pipe is in flight
prompts = random_prompts(cfg, 10, seed=7, lo=3, hi=10)
runs = golden_runs(cfg, params, rt, prompts, sp, {
    backend: dict(backend=backend, n_stages=2, mb_size=2,
                  num_microbatches=3, pool=pool, offload=True)
    for backend in ("local", "pipelined")}, max_steps=800)
assert_equivalent(runs, base="local")
print("OK")
"""


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["yi-9b", "gemma3-12b"])
def test_local_pipelined_greedy_equivalence(arch):
    """Acceptance: identical greedy token streams per request on
    LocalBackend vs PipelinedBackend, offloading enabled, continuous
    batching replenishing slots while the pipe is in flight."""
    from equivalence import subprocess_env
    r = subprocess.run([sys.executable, "-c", EQUIV_SCRIPT],
                       env=subprocess_env({"PIPE_ARCH": arch}),
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-2000:]}"
    assert "OK" in r.stdout


PLACEMENT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, numpy as np
from repro.config import get_arch, reduced_config
from repro.models import model as M
from repro.serving.kv_cache import PoolConfig
from repro.serving.llm import LLM, EngineConfig

cfg = reduced_config(get_arch("yi-9b"), num_layers=4)
pool = PoolConfig(page_size=4, n_local_pages=8, n_global_pages=0,
                  max_pages_per_seq=4)
llm = LLM(cfg, config=EngineConfig(backend="pipelined", n_stages=2,
                                   mb_size=1, num_microbatches=2, pool=pool,
                                   offload=False))
backend = llm.engine.backend
devs = list(backend.mesh.devices.flat)
for tree in (llm.params, backend.params, backend.caches):
    for leaf in jax.tree.leaves(tree["scan"]):
        # stage s holds periods [2s, 2s+2) of the 4-period stack, only them
        shards = sorted(leaf.addressable_shards, key=lambda s: s.device.id)
        assert [s.device for s in shards] == devs
        assert [s.index[0] for s in shards] == [slice(0, 2), slice(2, 4)]
for leaf in jax.tree.leaves(llm.params["embed"]):
    assert leaf.sharding.is_fully_replicated
# placed as it is built: the same values as a default-device build
ref = M.init_params(cfg, jax.random.PRNGKey(llm.config.seed), llm.rt)
for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(llm.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("OK")
"""


def test_pipelined_weights_and_caches_live_on_their_stage():
    """The pipelined backend never holds the model whole on one device:
    each stage's periods of the weights and caches are built on that
    stage's device, the embedding is replicated."""
    from equivalence import subprocess_env
    r = subprocess.run([sys.executable, "-c", PLACEMENT_SCRIPT],
                       env=subprocess_env({}), capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-2000:]}"
    assert "OK" in r.stdout
