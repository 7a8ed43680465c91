"""Offline serving driver: the ``LLM`` front end over a pluggable backend.

Runs the full serving stack end-to-end on a *reduced* config (CPU-sized) or
any registered arch: paged KV cache with local+global pools, double-buffer
offloading, microbatch round-robin, continuous batching, and the §3 profit
accounting on the measured throughput.  Requests carry *per-request*
sampling params — ``--mixed`` serves greedy and sampled requests through
the same engine in one run.

``--backend local`` is the single-device path; ``--backend pipelined``
drives the same engine through the ``--stages``-stage SPMD pipeline (on a
CPU host the pod axis is emulated with forced host devices).  ``--plan``
derives (N_B, per-microbatch batch, pool split) from a *measured* stage
time plus ``--latency`` via the §4.3 planner (``EngineConfig.plan``)
instead of the hand-set flags.

Networked serving (pipelined backend): ``--link-latency 0.064`` puts a
uniform simulated WAN (one-way seconds) on every inter-stage link;
``--deployment us-west,us-west,us-east`` places one stage per region and
derives per-link latencies from the registry's region table
(``DeploymentPlan``) — with ``--plan`` the §4.3 planner then consumes the
plan's **max link latency** instead of the scalar ``--latency`` guess.
Links are accounted on a virtual clock (outputs stay bit-identical; the
report gains ``virtual_decode_tok_per_s``).  ``--schedule round_flush``
runs the vLLM-PP baseline schedule for comparison;
``--transport-compress int8`` turns on the REAL in-jit wire codec
(``EngineConfig(wire_dtype="int8")``: per-row quantization inside both
tick jits, wire accounting equal to the packed payload — outputs shift
within the int8 logit tolerance), while ``--transport-compress topk``
remains wire-byte accounting only (no in-jit top-k path).

Resilience drills (pipelined backend): ``--inject-fault
kind@plane:tick:stage[:delay_s]`` (repeatable) drops or delays a stage
tick mid-run — the engine re-injects the lost work and outputs stay
bit-identical; ``--reshard-at STEP:STAGES`` tears the backend down at
engine step STEP and rebuilds it with STAGES pipeline stages, replaying
the page table so in-flight requests resume without recompute.
``--detect-failures TIMEOUT`` instead drives ``Engine.reshard`` from a
live :class:`~repro.distributed.elastic.FailureDetector` loop — one
heartbeat per stage per engine step, ``--kill-device STEP:DEVICE``
silences a device mid-run and the loop reshards when the detector
declares it dead (no explicit stage target needed).

Online serving: ``--online`` switches the batch ``generate()`` call for a
live loop — seeded Poisson arrivals (``--arrival-rate``) are submitted
into the running engine via :class:`~repro.serving.online.OnlineLLM` and
tokens stream out per tick; the run reports p50/p99 TTFT and inter-token
latency.  ``--prefix-cache`` shares fully-prefilled prompt blocks across
requests with a common prefix (pair with ``--system-prompt N`` to give
every request an N-token shared head); ``--slo-ttft`` / ``--slo-itl``
engage the latency-SLO admission policy that shrinks the per-tick prefill
budget when decode latency drifts past target.  Token streams stay
bit-identical to the offline path in all of these modes.

Observability: ``--trace out.json`` turns on the flight recorder
(``EngineConfig(trace=True)``) and writes a Perfetto-loadable
Chrome-trace timeline at exit — the program's spans (``sched.*``,
``kv.*``, ``step.*``, ``front.*``; the same spans a profiler trace
shows), per-microbatch stage occupancy, per-link transfers on the
virtual clock, prefix-cache and SLO events; ``--metrics`` keeps a
counter/gauge/histogram registry over the run, printing a one-line stats
banner every ``--metrics-every`` engine steps and the full Prometheus
exposition text at exit.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --requests 16 \\
      --backend pipelined --stages 2 --max-new 24 [--plan] [--mixed] \\
      [--link-latency 0.064 | --deployment us-west,us-east] \\
      [--schedule round_flush] [--inject-fault drop@decode:12:1] \\
      [--reshard-at 20:1 | --detect-failures 2 --kill-device 6:1] \\
      [--online --arrival-rate 8 --system-prompt 32 --prefix-cache \\
       --slo-ttft 0.5 --slo-itl 0.05]
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time


def _with_max_new(sp, max_new: int):
    return dataclasses.replace(sp, max_new_tokens=max_new)


def _ensure_host_devices(n: int) -> None:
    """Force >= ``n`` host devices for the pod axis — must run before jax
    initialises its backend (real accelerators ignore the flag)."""
    import re
    if n <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m and int(m.group(1)) >= n:
        return
    if m:                               # present but too small: raise it
        flags = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n}")
        os.environ["XLA_FLAGS"] = flags
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()


def measure_stage_time(cfg, params, rt, n_stages: int) -> float:
    """Wall-time one single-sequence decode step (compile excluded) and
    attribute 1/n_stages of it to each stage — the measurement the §4.3
    planner consumes."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as model_lib

    caches = model_lib.init_caches(cfg, 1, 64, rt)
    fn = jax.jit(lambda p, t, c, cp: model_lib.decode_step(p, t, c, cp,
                                                           cfg, rt))
    tok = jnp.zeros((1,), jnp.int32)
    cur = jnp.ones((1,), jnp.int32)
    logits, caches = fn(params, tok, caches, cur)        # compile + warm
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    logits, caches = fn(params, tok, caches, cur)
    jax.block_until_ready(logits)
    return max(1e-4, (time.perf_counter() - t0) / n_stages)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--backend", default="local",
                    choices=["local", "pipelined"])
    ap.add_argument("--stages", type=int, default=2,
                    help="pipeline stages (pipelined backend / --plan)")
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--mb-size", type=int, default=2)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="tokens per prefill chunk (0 = auto: 32, or the "
                         "planned per-microbatch batch under --plan)")
    ap.add_argument("--max-prefill-tokens", type=int, default=0,
                    help="prefill token budget per engine tick (0 = one "
                         "chunk); rows per chunk = budget // chunk")
    ap.add_argument("--prefill-mode", default="auto",
                    choices=["auto", "chunked", "exact"],
                    help="chunked admission (fully-paged archs) vs the "
                         "exact-length fallback; auto picks per arch")
    ap.add_argument("--mixed", action="store_true",
                    help="serve a mixed workload: greedy, temperature, "
                         "top-k, and top-p requests through one engine")
    ap.add_argument("--inject-fault", action="append", default=[],
                    metavar="kind@plane:tick:stage[:delay_s]",
                    help="drop/delay a pipeline stage tick (repeatable; "
                         "pipelined backend), e.g. drop@decode:12:1 or "
                         "delay@prefill:3:0:0.25 — lost work is "
                         "re-injected, outputs stay bit-identical")
    ap.add_argument("--reshard-at", default="",
                    metavar="STEP:STAGES",
                    help="tear down and rebuild the pipelined backend "
                         "with STAGES stages after engine step STEP "
                         "(page table replayed, no token recomputed)")
    ap.add_argument("--detect-failures", type=float, default=0.0,
                    metavar="TIMEOUT",
                    help="drive Engine.reshard from a live "
                         "FailureDetector loop: one heartbeat per stage "
                         "per engine step, reshard when a device misses "
                         "TIMEOUT steps (pipelined backend)")
    ap.add_argument("--kill-device", action="append", default=[],
                    metavar="STEP:DEVICE",
                    help="stop heartbeating DEVICE after engine step "
                         "STEP (repeatable; the --detect-failures drill "
                         "signal)")
    ap.add_argument("--link-latency", type=float, default=None,
                    help="uniform simulated one-way latency (seconds) on "
                         "every inter-stage link, accounted on a virtual "
                         "clock (pipelined backend); an explicit 0 is a "
                         "zero-cost simulated link, not 'unset'")
    ap.add_argument("--deployment", default="",
                    metavar="REGION[,REGION...]",
                    help="one pipeline stage per region (e.g. "
                         "us-west,us-west,us-east): per-link latencies "
                         "from the registry's region table; overrides "
                         "--stages and, under --plan, --latency")
    ap.add_argument("--schedule", default="circular",
                    choices=["circular", "round_flush"],
                    help="circular = DeServe §4.3 (default); round_flush "
                         "= the vLLM-PP baseline (pipe drained every "
                         "token round) for latency comparisons")
    ap.add_argument("--transport-compress", default="none",
                    choices=["none", "int8", "topk"],
                    help="int8: REAL in-jit activation compression on "
                         "every inter-stage link (wire_dtype='int8' — "
                         "per-row quantize inside the tick jits, "
                         "accounting matches the packed payload); topk: "
                         "wire-byte accounting only (simulated links)")
    ap.add_argument("--heartbeat-clock", default="monotonic",
                    choices=["monotonic", "steps"],
                    help="clock for --detect-failures heartbeats: "
                         "monotonic wall seconds (default; TIMEOUT is in "
                         "seconds) or the engine step index (the "
                         "deterministic shim drills/tests pin — TIMEOUT "
                         "counts steps)")
    ap.add_argument("--online", action="store_true",
                    help="online serving drill: Poisson arrivals submitted "
                         "into a LIVE engine loop (OnlineLLM), tokens "
                         "streamed per tick; reports p50/p99 TTFT and "
                         "inter-token latency")
    ap.add_argument("--arrival-rate", type=float, default=8.0,
                    help="mean Poisson arrival rate for --online, "
                         "requests/second (seeded, deterministic)")
    ap.add_argument("--system-prompt", type=int, default=0,
                    metavar="TOKENS",
                    help="prepend a shared TOKENS-long system prompt to "
                         "every request (the prefix-cache workload shape)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share fully-prefilled prompt blocks across "
                         "requests with a common prefix (refcounted "
                         "paged-KV sharing; needs chunked prefill)")
    ap.add_argument("--slo-ttft", type=float, default=0.0,
                    help="TTFT target (seconds) for the latency-SLO "
                         "admission policy (0 = off)")
    ap.add_argument("--slo-itl", type=float, default=0.0,
                    help="inter-token (per-tick) target (seconds) for the "
                         "latency-SLO admission policy (0 = off)")
    ap.add_argument("--plan", action="store_true",
                    help="derive N_B / batch / pools from measured stage "
                         "time + --latency (OfflineEngine.from_plan)")
    ap.add_argument("--kv-budget-mb", type=float, default=4.0,
                    help="per-stage KV byte budget for --plan")
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (needs real accelerators)")
    ap.add_argument("--latency", type=float, default=0.064,
                    help="assumed one-way link latency (schedule + --plan)")
    ap.add_argument("--trace", default="", metavar="OUT.json",
                    help="flight recorder: record engine/transport/request "
                         "spans and write a Chrome-trace (Perfetto) "
                         "timeline to OUT.json at exit")
    ap.add_argument("--metrics", action="store_true",
                    help="metrics registry over the run: a one-line stats "
                         "banner every --metrics-every engine steps plus "
                         "Prometheus exposition text at exit")
    ap.add_argument("--metrics-every", type=int, default=50,
                    metavar="STEPS",
                    help="engine steps between --metrics banners")
    ap.add_argument("--strict", action="store_true",
                    help="enable the runtime invariant auditor "
                         "(repro.analysis.invariants): page/FSM/transport/"
                         "jit-cache audits after every step, failing at "
                         "the tick that corrupted state")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    deployment = None
    if args.deployment:
        if args.backend != "pipelined":
            raise SystemExit("--deployment requires --backend pipelined")
        from repro.distributed.transport import DeploymentPlan
        deployment = DeploymentPlan.from_regions(
            [r.strip() for r in args.deployment.split(",") if r.strip()])
        args.stages = deployment.n_stages
    if args.backend != "pipelined" and (
            args.link_latency is not None or args.schedule != "circular"
            or args.transport_compress != "none"):
        raise SystemExit("--link-latency / --schedule / "
                         "--transport-compress require --backend pipelined")
    if args.transport_compress == "topk" and deployment is None \
            and args.link_latency is None:
        raise SystemExit("--transport-compress topk is accounting only — "
                         "it needs a simulated link (--link-latency or "
                         "--deployment) to account on")
    detect = args.detect_failures > 0
    if detect and args.backend != "pipelined":
        raise SystemExit("--detect-failures requires --backend pipelined")
    kills = {}
    for spec in args.kill_device:
        try:
            step_s, dev_s = spec.split(":")
            kills[int(dev_s)] = int(step_s)
        except ValueError:
            raise SystemExit(f"--kill-device wants STEP:DEVICE, got {spec!r}")
    if kills and not detect:
        raise SystemExit("--kill-device only matters under "
                         "--detect-failures (nobody is listening for "
                         "missed heartbeats)")

    reshard_at, reshard_stages = 0, 0
    if args.reshard_at:
        try:
            step_s, stages_s = args.reshard_at.split(":")
            reshard_at, reshard_stages = int(step_s), int(stages_s)
        except ValueError:
            raise SystemExit(f"--reshard-at wants STEP:STAGES, "
                             f"got {args.reshard_at!r}")
        if reshard_at < 1 or reshard_stages < 1:
            raise SystemExit("--reshard-at wants STEP >= 1 and "
                             f"STAGES >= 1, got {args.reshard_at!r}")
        if args.backend != "pipelined":
            raise SystemExit("--reshard-at requires --backend pipelined")
    if args.inject_fault and args.backend != "pipelined":
        raise SystemExit("--inject-fault requires --backend pipelined")
    if args.online and (reshard_at or detect):
        raise SystemExit("--online runs its own live loop — it composes "
                         "with faults/SLO/prefix caching but not with the "
                         "--reshard-at / --detect-failures drill loops")
    if args.arrival_rate <= 0:
        raise SystemExit(f"--arrival-rate must be > 0, "
                         f"got {args.arrival_rate}")
    if args.prefix_cache and args.prefill_mode == "exact":
        raise SystemExit("--prefix-cache needs chunked prefill (prefix "
                         "hits resume mid-prompt); drop "
                         "--prefill-mode exact")

    if args.backend == "pipelined":
        _ensure_host_devices(max(args.stages, reshard_stages))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    from repro.config import get_arch, reduced_config
    from repro.core.cost_model import PLATFORMS, min_throughput, \
        profit_per_hour
    from repro.core.scheduler import optimal_microbatches
    from repro.models import model as model_lib
    from repro.models.common import serving_runtime
    from repro.serving.kv_cache import PoolConfig
    from repro.serving.llm import LLM, EngineConfig
    from repro.serving.request import SamplingParams

    from repro.distributed.elastic import FaultPlan
    fault_plan = FaultPlan.parse(args.inject_fault) if args.inject_fault \
        else None

    slo = None
    if args.slo_ttft > 0 or args.slo_itl > 0:
        from repro.serving.engine import SLOConfig
        slo = SLOConfig(ttft_target_s=args.slo_ttft,
                        itl_target_s=args.slo_itl)
        print(f"SLO admission: ttft_target={args.slo_ttft:.3f}s "
              f"itl_target={args.slo_itl:.3f}s (prefill budget shaped "
              "per tick)")

    # int8 is the real in-jit codec: EngineConfig(wire_dtype=) drives the
    # tick jits AND the backend's transport wrap, so the books equal the
    # packed payload.  top-k stays an accounting wrapper built here.
    wire_dtype = "int8" if args.transport_compress == "int8" else "fp32"
    compress = "topk" if args.transport_compress == "topk" else None
    transport = None
    if deployment is not None:
        transport = deployment.transport(compress=compress)
        print(deployment.describe())
    elif args.link_latency is not None:
        from repro.distributed.transport import (CompressedTransport,
                                                 SimulatedLinkTransport)
        transport = SimulatedLinkTransport.uniform(args.stages,
                                                   args.link_latency)
        if compress:
            transport = CompressedTransport(transport, method=compress)
        print(f"links: uniform {args.link_latency * 1000:.0f}ms one-way "
              f"x{args.stages} (virtual clock)"
              + (", topk wire accounting (accounting only)"
                 if compress else ""))
    if wire_dtype == "int8":
        print("wire codec: int8 per-row, in-jit — the ppermute payload "
              "IS the packed payload on every inter-stage link")

    cfg = get_arch(args.arch)
    if not args.full_size:
        cfg = reduced_config(cfg)
    rt = serving_runtime(cfg)
    print(f"arch={cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"params={cfg.param_count()/1e6:.1f}M "
          f"dtype={jnp.dtype(rt.param_dtype).name} backend={args.backend}")

    params = None                       # LLM builds them where they live
    if args.plan:
        params = model_lib.init_params(cfg, jax.random.PRNGKey(args.seed),
                                       rt)
        t_s = measure_stage_time(cfg, params, rt, args.stages)
        # planner latency input: the deployment plan's max ring-link
        # latency (the slowest link sets the bubble budget) beats a
        # uniform --link-latency (an explicit 0 is honoured as a
        # zero-cost link) beats the bare --latency guess
        plan_latency = None if deployment is not None else (
            args.link_latency if args.link_latency is not None
            else args.latency)
        eff_latency = deployment.max_link_latency if deployment is not None \
            else plan_latency
        print(f"planned: measured stage_time={t_s*1000:.1f}ms "
              f"latency={eff_latency*1000:.0f}ms"
              f"{' (deployment max link)' if deployment else ''} "
              f"kv_budget={args.kv_budget_mb:.1f}MB")
        econfig = EngineConfig.plan(
            n_stages=args.stages, stage_time=t_s, latency=plan_latency,
            deployment=deployment, transport=transport,
            schedule=args.schedule,
            m_kv_bytes=args.kv_budget_mb * 1e6, page_size=args.page_size,
            max_pages_per_seq=16, max_microbatches=16, mb_size_cap=4,
            backend=args.backend, seed=args.seed,
            prefill_chunk=args.prefill_chunk,
            max_prefill_tokens_per_tick=args.max_prefill_tokens,
            prefill_mode=args.prefill_mode, fault_plan=fault_plan,
            wire_dtype=wire_dtype, prefix_cache=args.prefix_cache,
            slo=slo, trace=bool(args.trace) or None,
            strict=args.strict or None)
    else:
        pool = PoolConfig(page_size=args.page_size, n_local_pages=64,
                          n_global_pages=16, max_pages_per_seq=16)
        econfig = EngineConfig(mb_size=args.mb_size,
                               num_microbatches=args.microbatches, pool=pool,
                               offload=True, backend=args.backend,
                               n_stages=args.stages, seed=args.seed,
                               prefill_chunk=args.prefill_chunk,
                               max_prefill_tokens_per_tick=args.max_prefill_tokens,
                               prefill_mode=args.prefill_mode,
                               fault_plan=fault_plan, transport=transport,
                               schedule=args.schedule,
                               wire_dtype=wire_dtype,
                               prefix_cache=args.prefix_cache, slo=slo,
                               trace=bool(args.trace) or None,
                               strict=args.strict or None)

    llm = LLM(cfg, config=econfig, params=params, rt=rt)
    engine = llm.engine
    if args.plan:
        print(f"planned: N_B={engine.num_microbatches} "
              f"mb_size={engine.mb_size} pool=(local={engine.pool.n_local_pages}, "
              f"global=2x{engine.pool.n_global_pages}) "
              f"util={engine.schedule_choice.utilisation:.2f}")
    print(f"prefill: {'chunked' if engine.chunked_prefill else 'exact'} "
          f"(chunk={engine.prefill_chunk} tokens, "
          f"budget={engine.max_prefill_tokens_per_tick} tokens/tick, "
          f"rows={engine.prefill_rows})")

    metrics = None
    if args.metrics:
        from repro.obs.metrics import Metrics, update_from_engine
        metrics = Metrics()
        _prev_snap: dict = {}

        def _metrics_banner() -> None:
            snap = update_from_engine(metrics, engine)
            d = Metrics.delta(_prev_snap, snap)
            _prev_snap.clear()
            _prev_snap.update(snap)
            print(f"[metrics] step={engine.stats.steps} "
                  f"tokens+={d.get('repro_tokens_total', 0.0):.0f} "
                  f"finished="
                  f"{snap.get('repro_requests_finished_total', 0.0):.0f}"
                  f"/{args.requests} "
                  f"queue={snap.get('repro_queue_depth', 0.0):.0f} "
                  f"decode tok/s="
                  f"{snap.get('repro_decode_tok_per_s', 0.0):.1f}")

    rng = np.random.RandomState(args.seed)
    system = list(rng.randint(1, cfg.vocab_size, args.system_prompt)) \
        if args.system_prompt > 0 else []
    prompts = [system + list(rng.randint(1, cfg.vocab_size,
                                         rng.randint(4, 24)))
               for _ in range(args.requests)]
    if args.mixed:
        policies = [SamplingParams(temperature=0.0),
                    SamplingParams(temperature=0.8),
                    SamplingParams(temperature=1.0, top_k=20),
                    SamplingParams(temperature=0.9, top_p=0.92)]
        sps = [_with_max_new(policies[i % len(policies)], args.max_new)
               for i in range(args.requests)]
    else:
        sps = SamplingParams(temperature=args.temperature,
                             max_new_tokens=args.max_new)

    if args.online:
        # Online serving drill: seeded Poisson arrivals submitted into a
        # LIVE loop — the engine keeps decoding earlier requests while new
        # ones are admitted; tokens stream out per tick.  Cooperative
        # pump (no thread) so the run is deterministic given the seed.
        from repro.serving.online import OnlineLLM
        online = OnlineLLM(llm=llm)
        gaps = rng.exponential(1.0 / args.arrival_rate,
                               size=args.requests)
        arrivals = np.cumsum(gaps)
        sps_list = sps if isinstance(sps, list) else \
            [sps] * args.requests
        streams = []
        nxt = 0
        _next_banner = [args.metrics_every]
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while nxt < args.requests and arrivals[nxt] <= now:
                streams.append(online.submit(prompts[nxt],
                                             sps_list[nxt]))
                nxt += 1
            busy = online.step()
            if metrics is not None and engine.stats.steps >= \
                    _next_banner[0]:
                _metrics_banner()
                _next_banner[0] = engine.stats.steps + args.metrics_every
            if not busy:
                if nxt >= args.requests:
                    break
                # engine idle before the next arrival: sleep up to it
                time.sleep(min(0.005, max(
                    0.0, arrivals[nxt] - (time.perf_counter() - t0))))
        outs = [s.result() for s in streams]

        def _pct(vals, q):
            return float(np.percentile(vals, q)) if vals else 0.0
        ttfts = [s.ttft_s for s in streams if s.ttft_s is not None]
        itls = [d for s in streams for d in s.inter_token_s()]
        print(f"online: {args.requests} requests over "
              f"{time.perf_counter() - t0:.2f}s (Poisson "
              f"{args.arrival_rate:.1f} req/s); "
              f"TTFT p50={_pct(ttfts, 50)*1e3:.1f}ms "
              f"p99={_pct(ttfts, 99)*1e3:.1f}ms; "
              f"ITL p50={_pct(itls, 50)*1e3:.1f}ms "
              f"p99={_pct(itls, 99)*1e3:.1f}ms")
    elif reshard_at or detect:
        step = 0
        resharded = False
        detector = None
        hb_t0 = time.monotonic()
        if detect:
            from repro.distributed.elastic import FailureDetector
            detector = FailureDetector(timeout=args.detect_failures)
            for d in range(args.stages):        # one device per stage
                detector.beat(d, 0.0)
        for outs in llm.generate_iter(prompts, sps):
            step += 1
            if metrics is not None and step % args.metrics_every == 0:
                _metrics_banner()
            if reshard_at and step == reshard_at:
                rplan = engine.reshard(n_stages=reshard_stages)
                resharded = True
                print(f"resharded at step {step}: {args.stages} -> "
                      f"{reshard_stages} stages "
                      f"(params_move={rplan['params_move']}, "
                      f"batch_reshard={rplan['batch_reshard']})")
            if detect:
                # the live loop: one heartbeat per stage per engine step;
                # a killed device goes silent and the detector — not a
                # drill flag — decides when to reshard and to how many
                # stages.  The default clock is wall (monotonic) seconds,
                # so --detect-failures is a real timeout; --heartbeat-clock
                # steps keeps the old step-index clock for deterministic
                # drills and tests.
                now = (float(step) if args.heartbeat_clock == "steps"
                       else time.monotonic() - hb_t0)
                for d in range(args.stages):
                    if d not in kills or step <= kills[d]:
                        detector.beat(d, now)
                dead = detector.dead(now)
                if dead and not resharded:
                    old = engine.n_stages
                    engine.reshard(detector=detector, now=now)
                    resharded = True
                    print(f"failure detected at step {step} (dead "
                          f"devices {dead}): resharded {old} -> "
                          f"{engine.n_stages} stage(s)")
        if reshard_at and not resharded:
            raise SystemExit(
                f"--reshard-at {args.reshard_at}: the workload finished "
                f"after {step} step(s), before step {reshard_at} — the "
                "drill never resharded; lower STEP or grow the workload")
        if detect and kills and not resharded:
            raise SystemExit(
                f"--detect-failures: the workload finished after {step} "
                "step(s) before any killed device missed its timeout — "
                "kill earlier, shorten the timeout, or grow the workload")
    elif metrics is not None:
        # the banner needs a live loop: step the same workload through
        # generate_iter (the final snapshot carries the request traces)
        step = 0
        for outs in llm.generate_iter(prompts, sps):
            step += 1
            if step % args.metrics_every == 0:
                _metrics_banner()
    else:
        outs = llm.generate(prompts, sps)
    rep = llm.stats()
    if fault_plan is not None:
        print(f"faults: {len(fault_plan.triggered)} triggered, "
              f"{fault_plan.pending()} never reached "
              f"(decode ticks lost {rep['decode_ticks_lost']}, "
              f"prefill chunks lost {rep['prefill_chunks_lost']}, "
              "all re-injected)")
    if "transport" in rep:
        t = rep["transport"]
        line = (f"transport: {t.get('transport')} "
                f"virtual_time={t.get('virtual_time_s', 0.0):.2f}s "
                f"virtual decode tok/s="
                f"{rep.get('virtual_decode_tok_per_s', 0.0):.1f} "
                f"wire={t.get('wire_bytes', 0)}B "
                f"link_stall={t.get('link_stall_s', 0.0):.2f}s")
        if "compression_ratio" in t:
            line += (f" (raw {t['raw_bytes']}B, "
                     f"{t['compression_ratio']:.1f}x on the wire)")
        print(line)
    done = [o for o in outs if o.finished]
    print(f"finished {len(done)}/{args.requests} requests in "
          f"{rep['wall_time_s']:.2f}s "
          f"({rep['decode_tok_per_s']:.1f} decode tok/s, "
          f"{rep['prefill_tok_per_s']:.1f} prefill tok/s on this host; "
          f"mean latency {rep['mean_latency_steps']:.1f} steps / "
          f"{rep['mean_latency_s']:.2f}s)")
    if args.prefix_cache:
        print(f"prefix cache: {rep.get('prefix_hits', 0)} hits, "
              f"{rep.get('prefix_hit_tokens', 0)} prompt tokens served "
              f"from shared blocks (hit rate "
              f"{rep.get('prefix_hit_rate', 0.0):.2f}, "
              f"{rep.get('prefix_cache_pages', 0)} pages retained)")
    reasons = {}
    for o in outs:
        reasons[o.finish_reason] = reasons.get(o.finish_reason, 0) + 1
    print(f"finish reasons: {reasons}")
    print(f"report: {rep}")
    if metrics is not None:
        _metrics_banner()
        print("metrics (Prometheus exposition):")
        print(metrics.prometheus_text(), end="")
    if args.trace:
        from repro.obs.timeline import write_chrome_trace
        trace = write_chrome_trace(engine.recorder, args.trace)
        od = trace["otherData"]
        print(f"trace: wrote {len(trace['traceEvents'])} timeline events "
              f"({od['recorder_events']} recorded, "
              f"{od['recorder_dropped']} dropped) to {args.trace} — open "
              "in https://ui.perfetto.dev")

    n_b = optimal_microbatches(8, 0.08, args.latency)
    print(f"\nschedule report (8-stage pipeline, T_S=80ms, "
          f"L={args.latency*1000:.0f}ms): N_B* = {n_b}")
    for name in ("mining", "ionet", "cloud"):
        p = PLATFORMS[name]
        print(f"  {name:8s} break-even {min_throughput(p.cost_per_hour):8.1f}"
              f" tok/s; at 450 tok/s profit/h = "
              f"${profit_per_hour(450, p.cost_per_hour):+.2f}")


if __name__ == "__main__":
    main()
