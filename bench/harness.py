"""Drive the program: build the engine a cell describes, run its closed
loop, and record what the window saw.

This is the only module of the benchmark that calls the program.  The
entry the window drives is ``OnlineLLM.submit`` / ``OnlineLLM.step`` in
cooperative mode over an ``LLM`` built from the cell's ``EngineConfig``.
The loop is closed: as many requests are outstanding as the engine has
decode slots, and a new one is submitted when a stream finishes.

The harness's own calls into the program are wrapped in
``jax.profiler.TraceAnnotation`` spans (``online.step``, ``readback``,
``harness.refill``, ``submit``, and ``harness.window`` around the window),
so that idle gaps in a traced run can be laid against them.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench import loadgen, work
from bench.model import program_runtime


@dataclass
class ReqRec:
    gen: loadgen.GenRequest
    stream: object
    submit_t: float
    times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    finish_reason: Optional[str] = None


@dataclass
class Window:
    """Counts over one window, for the metrics and their readers."""
    t0: float
    t1: float
    steps: int = 0
    decode_tokens: int = 0
    prefill_tokens: int = 0
    model_flops: float = 0.0
    paged_roof_s: float = 0.0          # sum over calls of each call's bound
    paged_calls: int = 0
    pages_used: List[float] = field(default_factory=list)   # per tick
    compiles: int = 0                  # programs built inside the window

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class CompileLog:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events."""

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


def engine_config(cell, seed: int):
    """The cell's ``EngineConfig``: every setting from its cell file."""
    from repro.serving.kv_cache import PoolConfig
    from repro.serving.llm import EngineConfig
    e = cell.cell["engine"]
    pipe = {"n_stages": e["n_stages"]} if e["backend"] == "pipelined" \
        and "n_stages" in e else {}
    pool = PoolConfig(page_size=e["page_size"],
                      n_local_pages=e["n_local_pages"],
                      n_global_pages=e["n_global_pages"],
                      max_pages_per_seq=e["max_pages_per_seq"])
    return EngineConfig(
        mb_size=e["mb_size"], num_microbatches=e["num_microbatches"],
        pool=pool, offload=e["offload"], backend=e["backend"],
        seed=seed % 2 ** 31, prefill_chunk=e["prefill_chunk"],
        max_prefill_tokens_per_tick=e["max_prefill_tokens_per_tick"],
        prefill_mode="chunked", strict=False, **pipe)


def placement(cell, devs):
    """Where the cell's backend keeps the weights, as a map from the tree
    of their shapes to their shardings: on the first device, or split
    over the stages as the pipelined backend places them."""
    import jax
    ec = engine_config(cell, 0)
    if ec.backend != "pipelined":
        one = jax.sharding.SingleDeviceSharding(devs[0])
        return lambda tree: one
    from repro.core import pipeline as PL
    return PL.stage_shardings(cell.block.program_config(cell.config),
                              PL.stage_mesh(ec.n_stages), ec.n_stages)


class Stages:
    """The rows each pipeline stage decodes in one backend tick: the
    injected microbatch enters stage 0 and every earlier injection moves
    one stage down (the circular schedule).  The local backend is one
    stage."""

    def __init__(self, n_stages: int):
        self.held: List[Optional[list]] = [None] * n_stages

    def step(self, lens: list) -> List[Optional[list]]:
        """The stages' rows in the backend tick that injected ``lens``
        (empty: nothing live)."""
        self.held = [lens or None] + self.held[:-1]
        return list(self.held)


class Harness:
    """One cell's engine and closed loop."""

    def __init__(self, cell, params, seed: int):
        from repro.serving.llm import LLM
        from repro.serving.online import OnlineLLM
        conf = cell.config
        self.cell, self.conf, self.block = cell, conf, cell.block
        ec = engine_config(cell, seed)
        self.llm = LLM(self.block.program_config(conf),
                       rt=program_runtime(conf), config=ec, params=params)
        self.online = OnlineLLM(llm=self.llm)
        self.engine = self.llm.engine
        self.n_slots = ec.mb_size * ec.num_microbatches
        n_stages = ec.n_stages if ec.backend == "pipelined" else 1
        self.stages = Stages(n_stages)
        # a stage's decode reads the positions its microbatch had when it
        # was injected; the local backend has advanced them by the tick's
        # end, a deeper pipe drains the microbatch only ticks later
        self.lag = 0 if n_stages == 1 else 1
        self.gen = loadgen.requests(cell.traffic, slots=self.n_slots,
                                    seed=seed, vocab=int(conf["vocab_size"]))
        self.live: Dict[int, ReqRec] = {}
        self.all: List[ReqRec] = []
        self.refill = True
        off = getattr(self.engine.backend, "offloader", None)
        self.offloader = off
        self.window: Optional[Window] = None

    # -- the loop -----------------------------------------------------------

    def submit(self) -> None:
        import jax
        from repro.serving.request import SamplingParams
        g = next(self.gen)
        sp = SamplingParams(temperature=g.temperature, top_k=g.top_k,
                            top_p=g.top_p, max_new_tokens=g.max_new_tokens,
                            logprobs=True)
        with jax.profiler.TraceAnnotation("submit"):
            stream = self.online.submit(g.prompt, sp)
        rec = ReqRec(gen=g, stream=stream, submit_t=stream.submit_time)
        self.live[stream.request_id] = rec
        self.all.append(rec)

    def fill(self) -> None:
        while len(self.live) < self.n_slots:
            self.submit()

    def _pages_used_frac(self) -> float:
        a, pool = self.engine.alloc, self.engine.pool
        free = a.free_local() + a.free_global(0) + a.free_global(1)
        return (pool.n_pages - 1 - free) / (pool.n_pages - 1)

    def tick(self) -> None:
        """One engine tick, its readback and the refill; books the window's
        counts when a window is open."""
        import jax
        eng = self.engine
        win = self.window
        before = {rid: (r.stream.seq.prefill_pos if r.stream.seq else 0)
                  for rid, r in self.live.items()} if win else None
        steps0 = eng.stats.steps
        with jax.profiler.TraceAnnotation("online.step"):
            self.online.step()
        with jax.profiler.TraceAnnotation("readback"):
            finished = self._readback()
        decoded = self._decoded(steps0)
        if win is not None:
            self._book(win, before, decoded)
            win.pages_used.append(self._pages_used_frac())
        if self.refill:
            with jax.profiler.TraceAnnotation("harness.refill"):
                for _ in range(finished):
                    self.submit()

    def _readback(self) -> int:
        finished = 0
        for rid, rec in list(self.live.items()):
            st = rec.stream
            while len(rec.tokens) < len(st.tokens()):
                ev = st.next_event()
                rec.times.append(ev.time)
                rec.tokens.append(ev.token)
                if ev.finished:
                    rec.done = True
                    rec.finish_reason = ev.finish_reason
            if rec.done:
                del self.live[rid]
                finished += 1
        return finished

    def _decoded(self, steps0: int) -> Optional[tuple]:
        """After an engine tick: the context of each row the tick injected
        (itself included), and the rows of every stage in the backend tick
        it ran; None where the engine did not tick."""
        eng = self.engine
        if eng.stats.steps == steps0:
            return None
        mb = steps0 % eng.num_microbatches
        lo, hi = mb * eng.mb_size, (mb + 1) * eng.mb_size
        lens = [int(eng.cur_pos[s]) + self.lag for s in range(lo, hi)
                if eng.active[s]]
        return lens, self.stages.step(lens)

    def _book(self, win: Window, before: dict, decoded) -> None:
        """Model operations of this tick, and the paged kernel's work: each
        decoded token's operations once, when its microbatch is injected,
        and each stage's kernel calls in the backend tick that runs them."""
        conf, block = self.conf, self.block
        for rid, pos0 in before.items():
            rec = self.live.get(rid) or next(
                r for r in self.all if r.stream.request_id == rid)
            seq = rec.stream.seq
            if seq is None:
                continue
            pos1 = min(seq.prefill_pos, seq.prompt_len)
            if pos1 > pos0:
                win.model_flops += block.prefill_flops(
                    conf, pos0, pos1, last=pos1 == seq.prompt_len)
        if decoded is None:
            return
        lens, held = decoded
        win.model_flops += sum(block.decode_token_flops(conf, n)
                               for n in lens)
        layers = int(conf["num_hidden_layers"])
        n = len(self.stages.held)
        per = [layers // n] * n
        per[-1] += layers % n          # the epilogue runs after the last
        for rows, k in zip(held, per):
            if not rows:
                continue
            for f, b, count in block.paged_decode_calls(conf, rows, k):
                win.paged_calls += count
                win.paged_roof_s += count * work.roofline_seconds(
                    f, b, self.peaks)[0]

    # -- phases ---------------------------------------------------------------

    def _republish(self) -> None:
        """Publish the engine's page table again, unchanged: the next tick
        then runs on a table that came from the host."""
        self.engine.backend.set_page_table(self.engine.table)

    def ramp(self, log: CompileLog) -> None:
        """Fill every slot and run until each of the first fill's prompts
        has its first token and for at least the mix's ``ramp_min_steps``
        ticks.  The program's tick jits take a second cache entry when the
        page table arrives from the host rather than from the previous
        tick; so the ramp runs a prefill chunk and a decode tick on each
        kind of table, and then goes on until two rounds of the
        microbatches pass without a program being built (compiled or
        loaded from the persistent cache)."""
        self.fill()
        first = list(self.live.values())
        n_min = int(self.cell.traffic.get("ramp_min_steps", 0))
        steps = 0
        for republish in (False, True, False):     # chunks on both tables
            if republish:
                self._republish()
            self.tick()
            steps += 1
        while steps < n_min or not all(r.tokens for r in first):
            self.tick()
            steps += 1
        for republish in (True, False):            # decodes on both
            if republish:
                self._republish()
            self.tick()
            steps += 1
        quiet_need = 2 * self.engine.num_microbatches
        quiet = 0
        built = log.n + log.hits
        while quiet < quiet_need:
            self.tick()
            steps += 1
            now = log.n + log.hits
            quiet = 0 if now != built else quiet + 1
            built = now
        self.ramp_steps = steps

    def run_window(self, seconds: float, log: CompileLog,
                   peaks: dict) -> Window:
        import jax
        eng = self.engine
        self.peaks = peaks
        n0 = log.n + log.hits
        st = eng.stats
        c0 = (st.steps, st.decode_tokens, st.prefill_tokens)
        t0 = time.perf_counter()
        self.window = win = Window(t0=t0, t1=t0)
        with jax.profiler.TraceAnnotation("harness.window"):
            while time.perf_counter() - t0 < seconds:
                self.tick()
        win.t1 = time.perf_counter()
        self.window = None
        c1 = (st.steps, st.decode_tokens, st.prefill_tokens)
        win.steps, win.decode_tokens, win.prefill_tokens = (
            b - a for a, b in zip(c0, c1))
        win.compiles = log.n + log.hits - n0
        return win

    def drain_first_tokens(self, t0: float, t1: float,
                           limit_s: float = 120.0) -> None:
        """Without new submissions, tick until every request submitted in
        [t0, t1] has its first token (tail latencies of the window's own
        requests)."""
        self.refill = False
        end = time.perf_counter() + limit_s
        while time.perf_counter() < end and any(
                not r.tokens for r in self.all if t0 <= r.submit_t <= t1):
            self.tick()

    def close(self) -> None:
        """Drop the engine (its caches and compiled steps' buffers); the
        weights stay with their owner."""
        if self.offloader is not None:
            self.offloader.settle()
        self.online = self.llm = self.engine = self.offloader = None
        gc.collect()


# -- end-to-end metrics -------------------------------------------------------


def percentile(values, p: float) -> Optional[float]:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), p))


def end_to_end(name: str, recs: List[ReqRec], win: Window,
               setup_s: float) -> Optional[float]:
    """The value of one end-to-end metric over the window.  Names:
    ``setup_s``, ``output_tok_s``, ``prompt_tok_s``, ``itl_p<q>_ms``,
    ``ttft_p<q>_s``."""
    t0, t1 = win.t0, win.t1
    if name == "setup_s":
        return setup_s
    if name == "output_tok_s":
        n = sum(1 for r in recs for t in r.times if t0 < t <= t1)
        return n / win.seconds
    if name == "prompt_tok_s":
        return win.prefill_tokens / win.seconds
    if name.startswith("itl_p") and name.endswith("_ms"):
        gaps = [b - a for r in recs for a, b in zip(r.times, r.times[1:])
                if t0 < b <= t1]
        v = percentile(gaps, float(name[5:-3]))
        return None if v is None else v * 1e3
    if name.startswith("ttft_p") and name.endswith("_s"):
        ttft = [r.times[0] - r.submit_t for r in recs
                if t0 <= r.submit_t <= t1 and r.times]
        return percentile(ttft, float(name[6:-2]))
    raise KeyError(f"no end-to-end metric named {name!r}")


def ttft_samples(recs: List[ReqRec], win: Window) -> int:
    return sum(1 for r in recs if win.t0 <= r.submit_t <= win.t1
               and r.times)
