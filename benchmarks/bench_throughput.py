"""Paper Table 4 (the headline): output throughput vs link latency for the
three serving policies, from the calibrated discrete-event simulator —
plus a measured engine comparison of the two execution backends on a
decode-heavy and a prefill-heavy (``--workload prefill_heavy``) workload,
plus the Table-4-shaped ``latency_curve`` on the REAL engine over
simulated WAN links (virtual clock, circular vs round-flush)."""

from repro.core.simulator import PAPER_TABLE4, table4

LATS = (0.0, 0.016, 0.032, 0.064, 0.256)


def _engine_backends(rows, quick: bool, workload: str = "all"):
    """Measured tok/s through the LLM front end on both execution backends
    (reduced config; pipelined runs 2 stages when the host has the
    devices, else a 1-stage pipe — same code path, no fake-device fork).
    Timing comes from the engine's own phase-split clock
    (``stats.prefill_time_s`` / ``stats.decode_time_s``), with warmup
    steps (jit compiles + pipe fill) snapshot-subtracted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import get_arch, reduced_config
    from repro.models import model as M
    from repro.models.common import Runtime
    from repro.serving.kv_cache import PoolConfig
    from repro.serving.llm import LLM, EngineConfig, SamplingParams

    rt = Runtime(param_dtype=jnp.float32, compute_dtype=jnp.float32)
    cfg = reduced_config(get_arch("yi-9b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    pool = PoolConfig(page_size=8, n_local_pages=32, n_global_pages=8,
                      max_pages_per_seq=8)
    n_stages = 2 if len(jax.devices()) >= 2 else 1

    # two workloads: decode-heavy (short prompts, the Table-4 regime) and
    # prefill-heavy (long prompts, short generations — the open-model
    # serving regime chunked admission targets).  Both are recorded in
    # BENCH_throughput.json and gated by benchmarks/check_regression.py.
    workloads = {
        "engine_backend": dict(n_req=6 if quick else 12, prompt_len=8,
                               max_new=16 if quick else 24),
        "engine_prefill": dict(n_req=6 if quick else 12, prompt_len=48,
                               max_new=4),
    }
    if workload == "decode":
        workloads.pop("engine_prefill")
    elif workload == "prefill_heavy":
        workloads.pop("engine_backend")
    for bench, wl in workloads.items():
        print(f"\n-- {bench} (measured, reduced config, "
              f"prompt={wl['prompt_len']} max_new={wl['max_new']}) --")
        sp = SamplingParams(temperature=0.0, max_new_tokens=wl["max_new"])
        for backend in ("local", "pipelined"):
            llm = LLM(cfg, params=params, rt=rt, config=EngineConfig(
                mb_size=2, num_microbatches=2, pool=pool, offload=True,
                backend=backend, n_stages=n_stages, prefill_chunk=16,
                max_prefill_tokens_per_tick=32))
            rng = np.random.RandomState(0)
            # fixed prompt length: one prefill shape.  Warmup is a full
            # pass of the same workload, so every jit variant compiles
            # there and the timed pass is pure steady state.
            prompts = [list(rng.randint(1, cfg.vocab_size, wl["prompt_len"]))
                       for _ in range(wl["n_req"])]
            llm.generate(prompts, sp, max_steps=5000)       # warmup pass
            stats = llm.engine.stats
            warm = (stats.total_tokens, stats.decode_tokens,
                    stats.prefill_tokens, stats.wall_time_s,
                    stats.decode_time_s, stats.prefill_time_s)
            llm.generate(prompts, sp, max_steps=5000)       # timed pass
            rep = llm.stats()
            dt = rep["wall_time_s"] - warm[3]
            tps = (rep["total_tokens"] - warm[0]) / dt
            decode_tps = (rep["decode_tokens"] - warm[1]) / \
                max(rep["decode_time_s"] - warm[4], 1e-9)
            prefill_tps = (rep["prefill_tokens"] - warm[2]) / \
                max(rep["prefill_time_s"] - warm[5], 1e-9)
            print(f"  {backend:10s} {tps:8.1f} tok/s "
                  f"({decode_tps:.1f} decode tok/s, "
                  f"{prefill_tps:.1f} prefill tok/s, {rep['finished']} reqs, "
                  f"{rep['swaps']} swaps, mean latency "
                  f"{rep['mean_latency_steps']:.0f} steps, "
                  f"stages={n_stages if backend == 'pipelined' else 1})")
            rows.append({"bench": bench, "policy": backend,
                         "tps": tps, "decode_tps": decode_tps,
                         "prefill_tps": prefill_tps,
                         "tokens": rep["total_tokens"],
                         "swaps": rep["swaps"],
                         "mean_latency_steps": rep["mean_latency_steps"]})


_BW_SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from repro.config import get_arch, reduced_config
from repro.models import model as M
from repro.models.common import Runtime
from repro.serving.kv_cache import PoolConfig
from repro.distributed.transport import SimulatedLinkTransport
from repro.serving.llm import LLM, EngineConfig, SamplingParams

quick = bool(int(os.environ.get("BW_QUICK", "1")))
rt = Runtime(param_dtype=jnp.float32, compute_dtype=jnp.float32)
cfg = reduced_config(get_arch("yi-9b"))
params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
pool = PoolConfig(page_size=8, n_local_pages=64, n_global_pages=0,
                  max_pages_per_seq=4)
T, n_stages, n_b = 0.016, 2, 4
sp = SamplingParams(temperature=0.0, max_new_tokens=10 if quick else 16)
rng = np.random.RandomState(0)
prompts = [list(rng.randint(1, cfg.vocab_size, 6)) for _ in range(n_b)]
rows = []
for bw in ((8000.0,) if quick else (8000.0, 32000.0)):
    for policy, wire in (("circular", "fp32"), ("circular_int8", "int8")):
        tr = SimulatedLinkTransport.uniform(n_stages, 0.0, bandwidth_bps=bw,
                                            stage_time_s=T)
        llm = LLM(cfg, params=params, rt=rt, config=EngineConfig(
            mb_size=1, num_microbatches=n_b, pool=pool, offload=False,
            backend="pipelined", n_stages=n_stages, transport=tr,
            schedule="circular", wire_dtype=wire, prefill_chunk=8,
            max_prefill_tokens_per_tick=8))
        outs = llm.generate(prompts, sp, max_steps=5000)
        assert all(o.finished for o in outs)
        rep = llm.stats()
        rows.append({"bench": "latency_curve", "policy": policy,
                     "latency": 0.0, "bandwidth": bw,
                     "vtps": rep["virtual_decode_tok_per_s"],
                     "n_b": n_b, "n_stages": n_stages, "wire_dtype": wire,
                     "virtual_time_s": rep["transport"]["virtual_time_s"]})
for bw in {r["bandwidth"] for r in rows}:
    cell = {r["policy"]: r["vtps"] for r in rows if r["bandwidth"] == bw}
    assert cell["circular_int8"] > cell["circular"], (
        f"int8 wire must strictly beat fp32 on a {bw:.0f} B/s pipe: {cell}")
print("BWROWS " + json.dumps(rows))
"""


def _bandwidth_columns(rows, quick: bool):
    """Bandwidth-capped cells: a *thin* ring (bytes/s) instead of a long
    one — the wire codec's regime.  Same circular schedule and depth;
    the only difference between the two policies is the payload packing
    on the link, so ``circular_int8`` strictly beating ``circular`` is
    the wire-speed acceptance.  Needs real stage boundaries (payloads
    only cross between stages), so it runs a 2-stage pipe on two fake
    host devices in a fresh interpreter, whatever this host has.  The
    child runs on the CPU explicitly: its numbers come from the virtual
    clock, and an accelerator belongs to the parent that already holds it."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ, BW_QUICK="1" if quick else "0",
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [
        os.path.join(os.path.dirname(__file__), "..", "src"),
        env.get("PYTHONPATH")]))
    r = subprocess.run([sys.executable, "-c", _BW_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    if r.returncode != 0:
        print("  bandwidth columns FAILED (2-device subprocess):")
        print("  " + r.stderr[-800:].replace("\n", "\n  "))
        raise RuntimeError("bandwidth-capped latency_curve cells failed")
    bw_rows = json.loads(r.stdout.split("BWROWS ", 1)[1])
    rows.extend(bw_rows)
    for bw in sorted({x["bandwidth"] for x in bw_rows}):
        cell = {x["policy"]: x["vtps"] for x in bw_rows
                if x["bandwidth"] == bw}
        ratio = cell["circular_int8"] / cell["circular"]
        print(f"  BW={bw/1000:4.0f}kB/s circular      "
              f"{cell['circular']:7.1f} virtual tok/s (fp32 wire)")
        print(f"  BW={bw/1000:4.0f}kB/s circular_int8 "
              f"{cell['circular_int8']:7.1f} virtual tok/s "
              f"({ratio:.2f}x: packed payload on the thin pipe)")
        rows.append({"bench": "latency_curve", "policy": "wire_speedup",
                     "latency": 0.0, "bandwidth": bw, "ratio": ratio})


def _latency_curve(rows, quick: bool):
    """The Table-4-shaped curve on the REAL engine: decode tok/s vs
    one-way link latency, planner-chosen N_B circular schedule vs the
    round-flush (vLLM-PP) N_B = N_S baseline, through
    ``SimulatedLinkTransport`` on a virtual clock (fixed virtual stage
    time, so the numbers are machine-independent and the run costs CPU
    milliseconds).  Each cell is cross-checked against the discrete-event
    simulator's round-time mechanics (``sim_tps`` — the same
    ``PipelineSimulator._round_time`` code that produces Table 4).
    Bandwidth-capped columns (``_bandwidth_columns``) compare the int8
    wire codec against raw fp32 payloads on a thin pipe.  Recorded in
    BENCH_throughput.json and gated per cell by
    benchmarks/check_regression.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import get_arch, reduced_config
    from repro.core.scheduler import optimal_microbatches
    from repro.core.simulator import PipelineSimulator, SimConfig
    from repro.models import model as M
    from repro.models.common import Runtime
    from repro.serving.kv_cache import PoolConfig
    from repro.distributed.transport import SimulatedLinkTransport
    from repro.serving.llm import LLM, EngineConfig, SamplingParams

    rt = Runtime(param_dtype=jnp.float32, compute_dtype=jnp.float32)
    cfg = reduced_config(get_arch("yi-9b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    pool = PoolConfig(page_size=8, n_local_pages=64, n_global_pages=0,
                      max_pages_per_seq=4)
    n_stages = 2 if len(jax.devices()) >= 2 else 1
    T = 0.016                           # virtual stage time (seconds)
    lats = (0.0, 0.064) if quick else (0.0, 0.016, 0.032, 0.064)
    max_new = 10 if quick else 16
    sp = SamplingParams(temperature=0.0, max_new_tokens=max_new)
    rng = np.random.RandomState(0)

    print(f"\n-- latency_curve (real engine, virtual clock: "
          f"T_S={T*1000:.0f}ms, {n_stages} stage(s)) --")
    for lat in lats:
        # planner-chosen depth, floored so the L=0 cell still has a few
        # microbatches in flight; ONE admission wave (n_req == circular
        # slots) so steady state dominates — a lone tail request cannot
        # hide latency under any schedule and would blur the comparison
        n_b_star = max(4, min(12, optimal_microbatches(n_stages, T, lat)))
        n_req = n_b_star
        prompts = [list(rng.randint(1, cfg.vocab_size, 6))
                   for _ in range(n_req)]
        for policy, n_b, schedule in (
                ("circular", n_b_star, "circular"),
                ("round_flush", n_stages, "round_flush")):
            tr = SimulatedLinkTransport.uniform(n_stages, lat,
                                                stage_time_s=T)
            llm = LLM(cfg, params=params, rt=rt, config=EngineConfig(
                mb_size=1, num_microbatches=n_b, pool=pool, offload=False,
                backend="pipelined", n_stages=n_stages, transport=tr,
                schedule=schedule, prefill_chunk=8,
                max_prefill_tokens_per_tick=8))
            outs = llm.generate(prompts, sp, max_steps=5000)
            assert all(o.finished for o in outs)
            rep = llm.stats()
            vtps = rep["virtual_decode_tok_per_s"]
            # DES cross-check: the simulator's round-time mechanics at
            # this exact (N_S, N_B, T_S, L) — steady state, no prefill
            sim = PipelineSimulator(SimConfig(
                policy="vllm_pp" if schedule == "round_flush"
                else "deserve_pp", n_stages=n_stages, latency=lat))
            sim_tps = n_b / sim._round_time(T, n_b)
            print(f"  L={lat*1000:5.1f}ms {policy:12s} N_B={n_b:2d} "
                  f"{vtps:7.1f} virtual tok/s (sim predicts "
                  f"{sim_tps:7.1f})")
            rows.append({"bench": "latency_curve", "policy": policy,
                         "latency": lat, "vtps": vtps, "sim_tps": sim_tps,
                         "n_b": n_b, "n_stages": n_stages,
                         "virtual_time_s":
                             rep["transport"]["virtual_time_s"]})
    by = {(r["policy"], r["latency"]): r["vtps"] for r in rows
          if r["bench"] == "latency_curve"}
    hi = max(lats)
    ratio = by[("circular", hi)] / by[("round_flush", hi)]
    print(f"  circular/round_flush at {hi*1000:.0f}ms: {ratio:.1f}x "
          "(acceptance floor: 3x)")
    rows.append({"bench": "latency_curve", "policy": "speedup",
                 "latency": hi, "ratio": ratio})
    _bandwidth_columns(rows, quick)


def _online_serving(rows, quick: bool):
    """Poisson multi-tenant online workload through ``OnlineLLM``: every
    request shares a 24-token system prompt, arrivals are seeded
    exponential gaps submitted into the LIVE engine loop, and the prefix
    cache serves the shared pages without re-prefilling them.  Reports
    p50/p99 TTFT and inter-token latency (informational — wall-clock) and
    two gated correctness fields: ``prefix_exact`` (1.0 iff the shared
    prefix was re-prefilled ZERO times — computed prefill tokens exactly
    equal submitted prompt tokens minus cache-hit tokens, and every
    post-warmup request hit all three shared pages) and bit-identity of
    the streamed tokens against offline ``LLM.generate`` on a fresh
    cache-less engine."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import get_arch, reduced_config
    from repro.models import model as M
    from repro.models.common import Runtime
    from repro.serving.kv_cache import PoolConfig
    from repro.serving.llm import LLM, EngineConfig, SamplingParams
    from repro.serving.online import OnlineLLM

    rt = Runtime(param_dtype=jnp.float32, compute_dtype=jnp.float32)
    cfg = reduced_config(get_arch("yi-9b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    pool = PoolConfig(page_size=8, n_local_pages=64, n_global_pages=8,
                      max_pages_per_seq=8)
    n_req = 6 if quick else 12
    max_new = 8 if quick else 16
    rate = 50.0                         # req/s — arrivals overlap decode
    rng = np.random.RandomState(0)
    system = list(rng.randint(1, cfg.vocab_size, 24))   # 3 shared pages
    prompts = [system + list(rng.randint(1, cfg.vocab_size,
                                         rng.randint(4, 16)))
               for _ in range(n_req)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=max_new)

    def build(prefix_cache):
        return LLM(cfg, params=params, rt=rt, config=EngineConfig(
            mb_size=2, num_microbatches=2, pool=pool, offload=True,
            backend="local", prefill_chunk=16,
            max_prefill_tokens_per_tick=32, prefix_cache=prefix_cache))

    # offline reference: same prompts, fresh engine, NO cache — greedy
    # decoding makes the token streams request-id independent, so this is
    # the bit-identity baseline for the online run below
    ref = build(False).generate(prompts, sp)

    online = OnlineLLM(llm=build(True))
    # warm the cache deterministically: one throwaway request prefills +
    # inserts the system pages, so every measured request is a hit
    online.submit(system + list(rng.randint(1, cfg.vocab_size, 4)),
                  SamplingParams(temperature=0.0, max_new_tokens=2)).result()
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_req))
    streams = []
    nxt = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while nxt < n_req and arrivals[nxt] <= now:
            streams.append(online.submit(prompts[nxt], sp))
            nxt += 1
        if not online.step():
            if nxt >= n_req:
                break
            time.sleep(min(0.002, max(
                0.0, arrivals[nxt] - (time.perf_counter() - t0))))
    outs = [s.result() for s in streams]
    rep = online.stats()
    stats = online.engine.stats

    # gated correctness: zero shared-prefix recompute + offline identity
    total_prompt = sum(len(p) for p in prompts) + 24 + 4   # + warmup
    zero_recompute = (
        stats.prefix_hits == n_req
        and stats.prefix_hit_tokens == 24 * n_req
        and stats.prefill_tokens == total_prompt - stats.prefix_hit_tokens)
    identical = all(o.token_ids == r.token_ids and o.finished
                    for o, r in zip(outs, ref))
    prefix_exact = 1.0 if (zero_recompute and identical) else 0.0

    def _pct(vals, q):
        return float(np.percentile(vals, q)) if vals else 0.0
    ttfts = [s.ttft_s for s in streams if s.ttft_s is not None]
    itls = [d for s in streams for d in s.inter_token_s()]
    row = {"bench": "online_serving", "policy": "prefix_cache",
           "n_req": n_req, "arrival_rate": rate,
           "ttft_p50_s": _pct(ttfts, 50), "ttft_p99_s": _pct(ttfts, 99),
           "itl_p50_s": _pct(itls, 50), "itl_p99_s": _pct(itls, 99),
           "prefix_hit_rate": rep.get("prefix_hit_rate", 0.0),
           "prefix_hit_tokens": stats.prefix_hit_tokens,
           "prefix_exact": prefix_exact}
    print(f"\n-- online_serving (Poisson {rate:.0f} req/s, {n_req} reqs, "
          f"shared 24-token system prompt, prefix cache on) --\n"
          f"  TTFT p50={row['ttft_p50_s']*1e3:7.1f}ms "
          f"p99={row['ttft_p99_s']*1e3:7.1f}ms   "
          f"ITL p50={row['itl_p50_s']*1e3:6.1f}ms "
          f"p99={row['itl_p99_s']*1e3:6.1f}ms\n"
          f"  prefix: hit rate {row['prefix_hit_rate']:.2f} "
          f"({stats.prefix_hit_tokens} tokens never re-prefilled), "
          f"exact={prefix_exact:.0f} (zero recompute + offline "
          f"bit-identity)")
    rows.append(row)


def _sampling_epilogue(rows, quick: bool):
    """Fused sampling-epilogue microbench: the top-k partition fast path
    vs the full-vocab sort, both jitted, bit-identical by construction
    (asserted here on every run).  The ratio is gated (>= 1.15x at B<=8)
    by benchmarks/check_regression.py."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.sampler import sample_batched

    B, V = 8, 32768
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    logits = jax.random.normal(ks[0], (B, V), jnp.float32)
    keys = jax.random.key_data(
        jax.random.split(ks[1], B)).astype(jnp.uint32)
    temp = jnp.full((B,), 0.8, jnp.float32)
    top_k = jnp.full((B,), 40, jnp.int32)
    top_p = jnp.full((B,), 0.95, jnp.float32)

    f_fast = jax.jit(lambda l, k: sample_batched(l, k, temp, top_k, top_p,
                                                 fast_path=True))
    f_sort = jax.jit(lambda l, k: sample_batched(l, k, temp, top_k, top_p,
                                                 fast_path=False))
    a = jax.block_until_ready(f_fast(logits, keys))      # compile
    b = jax.block_until_ready(f_sort(logits, keys))
    assert np.array_equal(np.asarray(a), np.asarray(b)), \
        "fast path is not bit-identical to the sort path"

    iters = 100 if quick else 400
    out = {}
    for name, fn in (("fast", f_fast), ("sorted", f_sort)):
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(logits, keys)
        jax.block_until_ready(r)
        out[name] = (time.perf_counter() - t0) / iters
    ratio = out["sorted"] / out["fast"]
    print(f"\n-- sampling epilogue (B={B}, V={V}, top-k on) --\n"
          f"  fast   {out['fast'] * 1e6:8.1f} us/call\n"
          f"  sorted {out['sorted'] * 1e6:8.1f} us/call   "
          f"({ratio:.2f}x speedup)")
    rows.append({"bench": "sampling_fast", "policy": "epilogue",
                 "batch": B, "vocab": V,
                 "t_fast_us": out["fast"] * 1e6,
                 "t_sorted_us": out["sorted"] * 1e6, "ratio": ratio})


def _tracing_overhead(rows, quick: bool):
    """Flight-recorder overhead A/B: the decode-heavy workload with
    ``EngineConfig(trace=...)`` off vs on, same engine build otherwise.
    The recorder only appends host scalars the engine already holds
    (the obs-hot-path lint rule enforces that shape), so tracing must be
    near-free: the ``ratio`` (on/off decode tok/s, best-of-2 per arm) is
    gated at >= 0.95 by benchmarks/check_regression.py — an in-bench A/B,
    no baseline or machine margin involved.  The trace-on run's timeline
    is exported to ``bench_timeline.json`` and schema-validated here (CI
    re-checks the artifact with ``python -m repro.obs.timeline
    --check``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import get_arch, reduced_config
    from repro.models import model as M
    from repro.models.common import Runtime
    from repro.obs.timeline import validate_chrome_trace, write_chrome_trace
    from repro.serving.kv_cache import PoolConfig
    from repro.serving.llm import LLM, EngineConfig, SamplingParams

    rt = Runtime(param_dtype=jnp.float32, compute_dtype=jnp.float32)
    cfg = reduced_config(get_arch("yi-9b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    pool = PoolConfig(page_size=8, n_local_pages=32, n_global_pages=8,
                      max_pages_per_seq=8)
    n_req = 6 if quick else 12
    max_new = 16 if quick else 24
    sp = SamplingParams(temperature=0.0, max_new_tokens=max_new)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, cfg.vocab_size, 8)) for _ in range(n_req)]

    def decode_tps(trace: bool):
        llm = LLM(cfg, params=params, rt=rt, config=EngineConfig(
            mb_size=2, num_microbatches=2, pool=pool, offload=True,
            backend="local", prefill_chunk=16,
            max_prefill_tokens_per_tick=32, trace=trace))
        llm.generate(prompts, sp, max_steps=5000)       # warmup pass
        best = 0.0
        for _ in range(2):                              # best-of-2 per arm
            stats = llm.engine.stats
            warm = (stats.decode_tokens, stats.decode_time_s)
            llm.generate(prompts, sp, max_steps=5000)
            stats = llm.engine.stats
            best = max(best, (stats.decode_tokens - warm[0]) /
                       max(stats.decode_time_s - warm[1], 1e-9))
        return best, llm.engine

    off_tps, _ = decode_tps(False)
    on_tps, eng = decode_tps(True)
    ratio = on_tps / max(off_tps, 1e-9)
    trace = write_chrome_trace(eng.recorder, "bench_timeline.json")
    errs = validate_chrome_trace(trace)
    assert not errs, f"trace-on timeline failed schema check: {errs[:3]}"
    print(f"\n-- tracing_overhead (decode-heavy, trace off vs on) --\n"
          f"  trace off {off_tps:8.1f} decode tok/s\n"
          f"  trace on  {on_tps:8.1f} decode tok/s   "
          f"({ratio:.3f}x — gate floor 0.95)\n"
          f"  timeline: {len(trace['traceEvents'])} events "
          f"({len(eng.recorder.events)} recorded, "
          f"{eng.recorder.dropped} dropped) -> bench_timeline.json")
    rows.append({"bench": "tracing_overhead", "policy": "flight_recorder",
                 "decode_tps_off": off_tps, "decode_tps_on": on_tps,
                 "ratio": ratio, "events": len(trace["traceEvents"])})


def run(quick: bool = False, workload: str = "all"):
    """``workload``: "all" (both engine workloads + Table 4), "decode" /
    "prefill_heavy" (one measured engine workload, no simulator pass),
    "online" (the Poisson online-serving workload through ``OnlineLLM``
    with prefix caching), "latency_curve" (throughput-vs-link-latency
    on the real engine over simulated WAN links, cross-checked against
    the DES), or "tracing" (the flight-recorder overhead A/B +
    ``bench_timeline.json`` export)."""
    rows = []
    if workload == "latency_curve":
        _latency_curve(rows, quick)
        return rows
    if workload == "online":
        _online_serving(rows, quick)
        return rows
    if workload == "tracing":
        _tracing_overhead(rows, quick)
        return rows
    _engine_backends(rows, quick, workload)
    _sampling_epilogue(rows, quick)
    if workload != "all":
        return rows
    _tracing_overhead(rows, quick)
    _online_serving(rows, quick)
    _latency_curve(rows, quick)         # virtual clock — CPU-cheap
    res = table4(sim_seconds=200 if quick else 400,
                 warmup=50 if quick else 100)
    print("\n== Table 4: output throughput (tok/s) vs one-way latency ==")
    hdr = "policy        " + "".join(f"{int(l*1000):>8d}ms" for l in LATS)
    print(hdr + "   (sim | paper)")
    for pol in ("vllm_pp", "deserve_pp", "deserve_opt"):
        line = f"{pol:14s}"
        for lat in LATS:
            line += f"{res[pol][lat].output_tps:10.1f}"
        paper = PAPER_TABLE4.get(pol, {})
        pline = " | paper: " + " ".join(
            f"{paper.get(l, float('nan')):7.1f}" for l in LATS)
        print(line + pline)
        for lat in LATS:
            rows.append({"bench": "table4", "policy": pol, "latency": lat,
                         "tps": res[pol][lat].output_tps,
                         "paper": paper.get(lat)})

    print("\n-- headline speedups (DeServe opt / vLLM pp) --")
    for lat in (0.016, 0.032, 0.064):
        s = res["deserve_opt"][lat].output_tps / \
            res["vllm_pp"][lat].output_tps
        pp = PAPER_TABLE4["deserve_opt"][lat] / PAPER_TABLE4["vllm_pp"][lat]
        print(f"  @{int(lat*1000):3d}ms: {s:5.1f}x   (paper: {pp:.1f}x)")
        rows.append({"bench": "speedup", "latency": lat, "speedup": s,
                     "paper_speedup": pp})
    o = res["deserve_opt"]
    flat = min(o[l].output_tps for l in LATS) / \
        max(o[l].output_tps for l in LATS)
    print(f"  DeServe(opt) flatness across 0-256 ms: {flat:.2f} "
          f"(paper: {442.9/458.5:.2f})")
    rows.append({"bench": "flatness", "value": flat})
    return rows
