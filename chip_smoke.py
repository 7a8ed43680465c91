#!/usr/bin/env python3
"""Smoke run of the served path on a TPU at yi-9b's published widths.

    python3 chip_smoke.py              # one chip (the default)
    python3 chip_smoke.py --chips 4    # the four-stage pipe, on four chips

One chip: yi-9b at its published widths, depth cut to 24 of 48 layers,
bf16, random weights from ``--seed``, through the entry points a user
calls:

  1. ``local``      -- ``LLM.generate`` on 8 seeded prompts of 64-512
                       tokens, 32 new tokens each, mostly greedy plus one
                       top-k and one top-p request; page size 16, chunked
                       prefill, KV offload over the two global pools (the
                       swapped-out pages go to pinned host memory).
  2. ``pipelined``  -- the same requests on the one-stage circular tick
                       path; greedy tokens must equal phase 1's.
  3. ``online``     -- ``OnlineLLM`` streams that share a two-page system
                       prompt under prefix caching; the prefix hits are
                       counted and the streams must equal offline output.

Four chips (``--chips 4``), and no other phase:

  a. the 24-layer model on four stages of 6 layers against ``local`` on
     chip 0: greedy tokens must agree;
  b. full-depth yi-9b (48 layers, 17.7 GB -- more than one chip holds) on
     a four-stage pipe of 12 layers each answers a few requests, and no
     chip's peak memory exceeds its stage's share.  It runs first, so
     that each chip's peak is its stage's alone.

Every phase prints its wall time, its compile time (cold or warm, from
the persistent compile cache) and the peak device bytes: smoke figures
from one run, not benchmark numbers.  The last line of standard output is
one JSON object naming the device.  The script exits non-zero, and prints
no such line, when JAX finds no TPU, when the ``repro`` package is not
beside it, or when any phase fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.abspath(__file__))
SMOKE_LAYERS = 24

# bf16 keeps 8 significant bits, so one rounding moves a value by up to
# 2^-9 of its magnitude.  Two programs that fuse the same residual stream
# differently (the local decode jit and the pipelined tick) round it
# independently at every layer; over ~100 roundings of a 24-layer stack
# that drifts the logits by a few percent of their scale.  A greedy flip
# is a rounding tie, not a wrong result, when the two tokens' logits
# agree to within TIE_TOL of the largest |logit| at that position.
TIE_TOL = 2.0 ** -5

# sentinel of a compiled Pallas TPU kernel in XLA's compiled HLO
KERNEL_MARK = "tpu_custom_call"


class SmokeFailure(RuntimeError):
    pass


@dataclass(frozen=True)
class Sizes:
    """The smoke workload.  The chip runs the defaults; the CPU tests
    shrink them."""
    n_requests: int = 8
    prompt_lo: int = 64
    prompt_hi: int = 512
    max_new: int = 32
    page_size: int = 16
    n_local_pages: int = 96           # < the requests' budget: overflow
    n_global_pages: int = 64          # goes to the two global pools
    prefill_chunk: int = 128
    mb_size: int = 2
    num_microbatches: int = 4         # > 2, so microbatches share a pool
                                      # parity and really swap
    online_requests: int = 4
    system_pages: int = 2
    online_new: int = 16
    online_suffix: int = 64           # longest per-request prompt suffix
    full_requests: int = 4            # four-chip full-depth phase
    full_new: int = 16

    @property
    def max_pages(self) -> int:
        return -(-(self.prompt_hi + self.max_new) // self.page_size)


def _say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _gib(n) -> str:
    return "n/a" if n is None else f"{n / 2**30:.3f} GiB"


# ---------------------------------------------------------------------------
# Measurement helpers
# ---------------------------------------------------------------------------


class CompileLog:
    """Backend compile time and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self._event = BACKEND_COMPILE_EVENT
        self.secs, self.n, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    def _duration(self, event, duration, **_):
        if event == self._event:
            self.secs += duration
            self.n += 1

    def _count(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return self.secs, self.n, self.hits, self.misses


def peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` per device (None where it is not reported)."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


class Phase:
    """Times one phase and prints its smoke line: wall time, compile time
    (cold when any program missed the persistent cache), peak bytes."""

    def __init__(self, name: str, log: CompileLog, devices):
        self.name, self.log, self.devices = name, log, devices

    def __enter__(self):
        _say(f"phase {self.name}: start")
        self.c0 = self.log.snapshot()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        import jax
        if exc_type is not None:
            _say(f"phase {self.name}: FAILED ({exc_type.__name__}: {exc})")
            return False
        wall = time.perf_counter() - self.t0
        secs, n, hits, misses = (b - a for a, b in
                                 zip(self.c0, self.log.snapshot()))
        gc.collect()                    # the phase's engine is gone now
        live = sum(x.nbytes for x in jax.live_arrays())
        kind = "cold" if misses else "warm" if hits else \
            "persistent cache unused"
        peaks = ", ".join(_gib(p) for p in peak_bytes(self.devices))
        _say(f"phase {self.name}: ok; wall {wall:.2f} s; compile {secs:.2f} "
             f"s over {n} programs ({kind}: {hits} persistent-cache hits, "
             f"{misses} misses); peak device bytes so far [{peaks}]; "
             f"{_gib(live)} still live after it (smoke figures, not a "
             "benchmark)")
        return False


# ---------------------------------------------------------------------------
# Workload and checks
# ---------------------------------------------------------------------------


def make_prompts(rng, n: int, lo: int, hi: int, vocab: int) -> list:
    return [rng.randint(1, vocab, rng.randint(lo, hi + 1)).tolist()
            for _ in range(n)]


def sampling_mix(n: int, max_new: int) -> list:
    """Greedy requests, then one top-k and one top-p request."""
    from repro.serving.request import SamplingParams
    sps = [SamplingParams(temperature=0.0, max_new_tokens=max_new,
                          logprobs=True) for _ in range(n)]
    sps[-2] = SamplingParams(temperature=0.8, top_k=40,
                             max_new_tokens=max_new, logprobs=True)
    sps[-1] = SamplingParams(temperature=0.8, top_p=0.9,
                             max_new_tokens=max_new, logprobs=True)
    return sps


def check_outputs(outs, vocab: int, label: str) -> None:
    """Every request finished by length or eos, every token is in the
    vocab, and every recorded logprob is finite."""
    import numpy as np
    for o in outs:
        if not o.finished or o.finish_reason not in ("length", "eos"):
            raise SmokeFailure(f"{label}: request {o.request_id} ended "
                               f"{o.status}/{o.finish_reason}")
        if not o.token_ids or not all(0 <= t < vocab for t in o.token_ids):
            raise SmokeFailure(f"{label}: request {o.request_id} has tokens "
                               f"outside [0, {vocab}): {o.token_ids}")
        if o.logprobs is not None and not (
                len(o.logprobs) == len(o.token_ids)
                and np.all(np.isfinite(o.logprobs))):
            raise SmokeFailure(f"{label}: request {o.request_id} has "
                               f"non-finite logprobs {o.logprobs}")
    _say(f"{label}: {len(outs)}/{len(outs)} requests finished "
         f"({', '.join(sorted({o.finish_reason for o in outs}))}); tokens "
         "in vocab; logprobs finite")


class LogitProbe:
    """Reference logits at the last position of a token sequence: one
    full-sequence prefill of the served weights (sequences padded to a
    multiple of 64, so a few lengths share a compile)."""

    def __init__(self, params, cfg, rt):
        import jax

        from repro.models import model as model_lib
        self.params = params
        self._fwd = jax.jit(lambda p, t, li: model_lib.prefill(
            p, {"tokens": t}, cfg, rt, t.shape[1], last_index=li)[0][0])

    def logits(self, tokens: list):
        """fp32 logits (V,) of the token after ``tokens``."""
        import jax.numpy as jnp
        import numpy as np
        s = -(-len(tokens) // 64) * 64
        t = np.zeros((1, s), np.int32)
        t[0, :len(tokens)] = tokens
        return np.asarray(self._fwd(self.params, jnp.asarray(t),
                                    jnp.asarray([len(tokens) - 1], jnp.int32)))

    def gaps(self, tokens: list, a: int, b: int) -> tuple:
        """(|logit a - logit b|, top-2 gap, max |logit|)."""
        import numpy as np
        lg = self.logits(tokens)
        top = np.sort(lg)[-2:]
        return (float(abs(lg[a] - lg[b])), float(top[1] - top[0]),
                float(np.abs(lg).max()))


def compare_greedy(ref, got, sps, probe: LogitProbe, label: str) -> int:
    """Greedy tokens of ``got`` equal ``ref``'s, or first differ where the
    two tokens are a bf16 rounding tie (``TIE_TOL``).  Returns the number
    of requests that differ."""
    n_greedy = n_div = 0
    for r, g, sp in zip(ref, got, sps):
        if sp.temperature > 0:
            continue
        n_greedy += 1
        if r.token_ids == g.token_ids:
            continue
        i = next((j for j, (x, y) in enumerate(zip(r.token_ids, g.token_ids))
                  if x != y), min(len(r.token_ids), len(g.token_ids)))
        if i >= min(len(r.token_ids), len(g.token_ids)):
            raise SmokeFailure(f"{label}: request {r.request_id} lengths "
                               f"differ: {len(r.token_ids)} vs "
                               f"{len(g.token_ids)}")
        a, b = r.token_ids[i], g.token_ids[i]
        gap, top2, scale = probe.gaps(list(r.prompt) + r.token_ids[:i], a, b)
        tol = TIE_TOL * scale
        _say(f"{label}: request {r.request_id} first differs at generated "
             f"position {i}: {a} vs {b}; reference logit gap between them "
             f"{gap:.6f}, top-2 gap {top2:.6f}, tie tolerance {tol:.6f} "
             f"(2^-5 of max |logit| {scale:.4f})")
        if gap > tol:
            raise SmokeFailure(f"{label}: request {r.request_id} diverges "
                               f"at position {i} by {gap:.6f} > {tol:.6f}")
        n_div += 1
    _say(f"{label}: greedy tokens equal on {n_greedy - n_div}/{n_greedy} "
         f"requests; {n_div} first divergence(s), each a bf16 tie")
    return n_div


def decode_tick_text(backend) -> str:
    """Compiled HLO text of ``backend``'s decode tick, lowered with
    arguments of the served shapes and shardings."""
    import jax.numpy as jnp

    from repro.serving.sampler import RowSampling
    mb = backend.mb_size
    z = RowSampling.zeros(mb)
    samp = (jnp.asarray(z.keys), jnp.asarray(z.steps), jnp.asarray(z.temp),
            jnp.asarray(z.top_k), jnp.asarray(z.top_p))
    jits = backend.jit_entries()
    tok = jnp.zeros((mb,), jnp.int32)
    if backend.name == "local":
        lowered = jits["_decode_jit"].lower(
            backend.params, backend.caches, tok, tok, jnp.int32(0), *samp)
    else:
        n = backend.n_stages
        lowered = jits["_tick_jit"].lower(
            backend.params, backend.caches, backend.act, tok,
            jnp.full((n,), -1, jnp.int32), jnp.zeros((n, mb), jnp.int32),
            *samp, jnp.int32(-1))
    return lowered.compile().as_text()


def check_kernel(backend, label: str, on_tpu: bool) -> None:
    """The compiled decode tick runs the Pallas paged-decode kernel: on
    the chip, a tick that fell back to the XLA reference fails."""
    found = KERNEL_MARK in decode_tick_text(backend)
    _say(f"{label}: '{KERNEL_MARK}' in the compiled decode tick: {found}")
    if on_tpu and not found:
        raise SmokeFailure(f"{label}: the decode tick runs the XLA "
                           "reference, not the Pallas kernel")


def offloaders(backend) -> list:
    if backend.name == "local":
        return [backend.offloader] if backend.offloader else []
    return list(backend._stage_off) + \
        ([backend._epi_off] if backend._epi_off else [])


def check_offload(backend, label: str, on_tpu: bool) -> None:
    """Swaps moved bytes, and on the chip the host store is pinned host
    memory."""
    offs = offloaders(backend)
    moved = sum(o.bytes_swapped for o in offs)
    kinds = set()
    for o in offs:
        o.settle()
        for layers in o._host.values():
            for layer in layers:
                kinds |= {getattr(getattr(x, "sharding", None),
                                  "memory_kind", "numpy")
                          for x in layer.values()}
    _say(f"{label}: offload swaps {sum(o.swap_count for o in offs)}, "
         f"{moved} bytes moved; host store memory kinds {sorted(kinds)}")
    if not moved:
        raise SmokeFailure(f"{label}: no KV bytes were swapped")
    if on_tpu and kinds and kinds != {"pinned_host"}:
        raise SmokeFailure(f"{label}: host store is not in pinned_host "
                           f"memory: {sorted(kinds)}")


def resident_bytes(trees, devices) -> list:
    """Bytes of ``trees``' shards resident on each device."""
    import jax
    per = {d: 0 for d in devices}
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            for sh in leaf.addressable_shards:
                if sh.device in per:
                    per[sh.device] += sh.data.nbytes
    return [per[d] for d in devices]


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------


def engine_config(s: Sizes, backend: str, seed: int, *, n_stages: int = 1,
                  offload: bool = True, **kw):
    from repro.serving.kv_cache import PoolConfig
    from repro.serving.llm import EngineConfig
    pool = PoolConfig(page_size=s.page_size, n_local_pages=s.n_local_pages,
                      n_global_pages=s.n_global_pages if offload else 0,
                      max_pages_per_seq=s.max_pages)
    return EngineConfig(
        mb_size=s.mb_size, num_microbatches=s.num_microbatches, pool=pool,
        offload=offload, backend=backend, n_stages=n_stages, seed=seed,
        prefill_chunk=s.prefill_chunk,
        max_prefill_tokens_per_tick=2 * s.prefill_chunk,
        prefill_mode="chunked", **kw)


def describe(cfg, rt, full_layers: int) -> None:
    import jax.numpy as jnp
    cut = "none" if cfg.num_layers == full_layers else \
        f"depth {cfg.num_layers} of {full_layers} layers"
    _say(f"config {cfg.name}: d_model {cfg.d_model}; {cfg.num_heads} query "
         f"and {cfg.num_kv_heads} KV heads of {cfg.head_dim}; d_ff "
         f"{cfg.d_ff}; vocab {cfg.vocab_size}; rope theta "
         f"{cfg.rope_theta:g}; {cfg.num_layers} layers (cut: {cut}); "
         f"params {jnp.dtype(rt.param_dtype).name}, compute "
         f"{jnp.dtype(rt.compute_dtype).name}; weights random from the seed")


def run_one_chip(cfg, s: Sizes, seed: int, log: CompileLog) -> None:
    """Phases 1-3 on the first device.  Each phase builds its own engine
    and drops it (weights and caches) before the next one starts."""
    import jax
    import numpy as np

    from repro.models.common import serving_runtime
    from repro.serving.request import SamplingParams

    devs = jax.devices()[:1]
    rt = serving_runtime(cfg)
    rng = np.random.RandomState(seed)
    prompts = make_prompts(rng, s.n_requests, s.prompt_lo, s.prompt_hi,
                           cfg.vocab_size)
    sps = sampling_mix(s.n_requests, s.max_new)
    _say(f"workload: {len(prompts)} prompts of {min(map(len, prompts))}-"
         f"{max(map(len, prompts))} tokens, {s.max_new} new tokens each; "
         f"page size {s.page_size}; pool {s.n_local_pages} local + 2x"
         f"{s.n_global_pages} global pages, offload on")
    system = rng.randint(1, cfg.vocab_size,
                         s.system_pages * s.page_size).tolist()
    o_prompts = [system + rng.randint(1, cfg.vocab_size,
                                      rng.randint(1, s.online_suffix + 1)
                                      ).tolist()
                 for _ in range(s.online_requests)]
    o_sp = SamplingParams(temperature=0.0, max_new_tokens=s.online_new)

    with Phase("1 local", log, devs):
        local = _served(cfg, rt, engine_config(s, "local", seed), prompts,
                        sps, "local")
    with Phase("2 pipelined, one stage", log, devs):
        _served(cfg, rt, engine_config(s, "pipelined", seed), prompts, sps,
                "pipelined", ref=local)
    with Phase("3 online, prefix cache", log, devs):
        _online(cfg, rt, engine_config(s, "local", seed, prefix_cache=True),
                o_prompts, o_sp, s)


def _served(cfg, rt, config, prompts, sps, label: str, ref=None):
    """``LLM.generate`` on one engine, checked; greedy tokens compared
    with ``ref`` when given.  Returns the outputs."""
    import jax

    from repro.serving.llm import LLM
    on_tpu = jax.devices()[0].platform == "tpu"
    llm = LLM(cfg, rt=rt, config=config)
    backend = llm.engine.backend
    devs = list(backend.mesh.devices.flat) \
        if backend.name == "pipelined" else jax.devices()[:1]
    held = resident_bytes([backend.params, backend.caches], devs)
    _say(f"{label}: weights and caches placed per device "
         f"[{', '.join(_gib(h) for h in held)}]")
    outs = llm.generate(prompts, sps)
    check_outputs(outs, cfg.vocab_size, label)
    if config.offload:
        check_offload(backend, label, on_tpu)
    check_kernel(backend, label, on_tpu)
    if ref is not None:
        compare_greedy(ref, outs, sps, LogitProbe(llm.params, cfg, rt),
                       f"{label} vs local")
    return outs


def _online(cfg, rt, config, prompts, sp, s: Sizes) -> None:
    """Streams sharing a system prompt: every request after the first
    hits the prefix cache, and the streams equal offline output."""
    from repro.serving.llm import LLM
    from repro.serving.online import OnlineLLM
    llm = LLM(cfg, rt=rt, config=config)
    online = OnlineLLM(llm=llm)
    first = online.submit(prompts[0], sp)
    # its first token means its prompt is prefilled and its blocks are in
    # the prefix cache: every later submission shares them
    first.next_event()
    streams = [first] + [online.submit(p, sp) for p in prompts[1:]]
    streamed = [st.result() for st in streams]
    check_outputs(streamed, cfg.vocab_size, "online")
    stats = llm.engine.stats
    got = (stats.prefix_hits, stats.prefix_hit_tokens)
    want = (len(prompts) - 1,
            (len(prompts) - 1) * s.system_pages * s.page_size)
    _say(f"online: prefix hits {got[0]}, hit tokens {got[1]} (want "
         f"{want[0]}, {want[1]})")
    if got != want:
        raise SmokeFailure(f"online: prefix hits {got} != {want}")
    llm.engine.prefix_cache.clear()
    offline = llm.generate(prompts, sp)
    compare_greedy(offline, streamed, [sp] * len(prompts),
                   LogitProbe(llm.params, cfg, rt), "online vs offline")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------


def run_four_chips(cut_cfg, full_cfg, s: Sizes, seed: int,
                   log: CompileLog) -> None:
    """(b) the full-depth model on a four-stage pipe, then (a) the cut
    model's four-stage tokens against ``local`` on the first device.  (b)
    runs first, so each device's peak is its stage's alone; (a)'s local
    engine then holds the whole cut model on device 0."""
    import jax
    import numpy as np

    from repro.models.common import serving_runtime

    devs = jax.devices()[:4]
    if len(devs) < 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX has "
                           f"{len(jax.devices())}")
    rng = np.random.RandomState(seed)
    full_prompts = make_prompts(rng, s.full_requests, s.prompt_lo,
                                s.prompt_hi, full_cfg.vocab_size)
    prompts = make_prompts(rng, s.n_requests, s.prompt_lo, s.prompt_hi,
                           cut_cfg.vocab_size)
    sps = sampling_mix(s.n_requests, s.max_new)

    with Phase(f"b full depth ({full_cfg.num_layers} layers), 4 stages",
               log, devs):
        _full_depth(full_cfg, serving_runtime(full_cfg), engine_config(
            s, "pipelined", seed, n_stages=4, offload=False), full_prompts,
            s, devs)
    rt = serving_runtime(cut_cfg)
    with Phase(f"a {cut_cfg.num_layers} layers, local on device 0", log,
               devs):
        local = _served(cut_cfg, rt, engine_config(
            s, "local", seed, offload=False), prompts, sps, "local")
    with Phase(f"a {cut_cfg.num_layers} layers, 4 stages", log, devs):
        _served(cut_cfg, rt, engine_config(
            s, "pipelined", seed, n_stages=4, offload=False), prompts, sps,
            "4 stages", ref=local)


def _full_depth(cfg, rt, config, prompts, s: Sizes, devs) -> None:
    """A model no one chip holds answers requests on four stages, and no
    device's peak exceeds its stage's share plus working space."""
    import jax

    from repro.serving.llm import LLM
    from repro.serving.request import SamplingParams
    on_tpu = devs[0].platform == "tpu"
    llm = LLM(cfg, rt=rt, config=config)
    backend = llm.engine.backend
    whole = sum(x.nbytes for x in jax.tree.leaves(llm.params))
    held = resident_bytes([backend.params, backend.caches], devs)
    _say(f"full depth: weights {_gib(whole)} in all; weights and caches "
         f"placed per device [{', '.join(_gib(h) for h in held)}]")
    sp = SamplingParams(temperature=0.0, max_new_tokens=s.full_new,
                        logprobs=True)
    check_outputs(llm.generate(prompts, sp), cfg.vocab_size, "full depth")
    check_kernel(backend, "full depth", on_tpu)
    for d, h, p in zip(devs, held, peak_bytes(devs)):
        ratio = "n/a" if p is None else f"{p / h:.3f}x"
        _say(f"full depth: device {d.id} peak {_gib(p)} for {_gib(h)} "
             f"placed ({ratio})")
        # the stage's share plus working space, never the whole model
        if p is not None and (p > 1.25 * h + 2**30 or p >= whole):
            raise SmokeFailure(f"device {d.id} peaked at {_gib(p)}, more "
                               f"than its stage's {_gib(h)} allows")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases 1-3 on one chip; 4: the four-stage "
                         "pipe and its comparison only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.config import get_arch
        from repro.launch.cache import enable_compile_cache
        from repro.models.common import serving_runtime
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{devs[0].platform!r} ({devs[0].device_kind}) -- no "
              "fallback", file=sys.stderr)
        return 1
    _say(f"device {devs[0].device_kind} x{len(devs)}; jax "
         f"{jax.__version__}; compile cache {cache_dir}")

    full = get_arch("yi-9b")
    cut = dataclasses.replace(full, num_layers=SMOKE_LAYERS)
    log = CompileLog()
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            describe(cut, serving_runtime(cut), full.num_layers)
            run_one_chip(cut, Sizes(), args.seed, log)
        else:
            describe(full, serving_runtime(full), full.num_layers)
            describe(cut, serving_runtime(cut), full.num_layers)
            run_four_chips(cut, full, Sizes(), args.seed, log)
    except Exception as e:                     # every failure is fatal
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    _say(f"all phases passed in {time.perf_counter() - t0:.2f} s "
         f"(compile {log.secs:.2f} s over {log.n} programs; smoke figures)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
