"""Program spans (``repro.obs.span``) on the profiler clock: where the
serving core opens them, what reaches the profiler's host plane and the
recorder's ring, the ``host.gc`` hook, the queue-wait counters, the
tick jits' names in a trace, and served tokens unchanged by any of it."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny
from repro.models import model as M
from repro.obs import (Metrics, TraceRecorder, chrome_trace_events,
                       install_gc_spans, span, validate_chrome_trace)
from repro.obs.metrics import update_from_engine
from repro.serving.kv_cache import PoolConfig
from repro.serving.llm import LLM, EngineConfig
from repro.serving.online import OnlineLLM
from repro.serving.request import SamplingParams
from repro.serving.sampler import RowSampling


def _host_events(log_dir):
    """(name, start_ns, end_ns) of every host-plane event of a trace."""
    import glob
    import os

    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
    return out


def _llm(rt, trace=None, backend="local", **kw):
    cfg = tiny("yi-9b")
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    pool = PoolConfig(page_size=8, n_local_pages=16, max_pages_per_seq=4)
    conf = EngineConfig(mb_size=2, num_microbatches=1, pool=pool,
                        trace=trace, backend=backend, **kw)
    return LLM(cfg, config=conf, params=params, rt=rt), cfg


def _prompts(cfg, n, seed):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, cfg.vocab_size, rng.randint(3, 12)))
            for _ in range(n)]


# ------------------------------------------------------------ the helper ---


def test_span_reaches_profiler_and_ring(tmp_path):
    """One span is one profiler annotation on the host plane, on the
    profiler's clock, and (with a recorder) one ring event on the track
    of its layer prefix; nested spans nest in both."""
    rec = TraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    with span("sched.tick", rec):
        with span("kv.publish", rec, (("rows", 2),)):
            pass
    with span("front.deliver"):                  # no recorder: trace only
        pass
    jax.profiler.stop_trace()
    host = {n: (s, e) for n, s, e in _host_events(str(tmp_path))}
    assert {"sched.tick", "kv.publish", "front.deliver"} <= set(host)
    (ts, te), (ks, ke) = host["sched.tick"], host["kv.publish"]
    assert ts <= ks <= ke <= te
    ring = [(e.name, e.track, e.data) for e in rec.events]
    assert ring == [("kv.publish", "kv", (("rows", 2),)),
                    ("sched.tick", "sched", ())]
    assert all(e.kind == "span" and e.clock == "wall" for e in rec.events)
    outer, inner = rec.events[1], rec.events[0]
    assert outer.t0 <= inner.t0 and \
        inner.t0 + inner.dur <= outer.t0 + outer.dur


def test_span_passes_exceptions_through():
    rec = TraceRecorder()
    with pytest.raises(KeyError):
        with span("sched.plan", rec):
            raise KeyError("x")
    assert [e.name for e in rec.events] == ["sched.plan"]


def test_chrome_export_of_nested_spans_validates():
    """Spans reach the ring as they close, the enclosing one last; the
    export puts them in order of start so each layer's track is
    monotone, and leaves the other events where they were."""
    rec = TraceRecorder()
    with span("sched.tick", rec):
        with span("sched.reap", rec):
            pass
        rec.instant("prefix_hit", "prefix", 1.0)
        with span("sched.plan", rec):
            pass
    trace = chrome_trace_events(rec)
    assert validate_chrome_trace(trace) == []
    xs = [e["name"] for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert xs == ["sched.tick", "sched.reap", "sched.plan"]
    names = [e["name"] for e in trace["traceEvents"]
             if e.get("ph") in ("X", "i")]
    assert names.index("prefix_hit") == 1          # kept its place


def test_gc_spans_wrap_each_collection(tmp_path):
    rec = TraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    remove = install_gc_spans(rec)
    try:
        gc.collect()
        gc.collect(0)
    finally:
        remove()
    gc.collect()                                  # after removal: nothing
    jax.profiler.stop_trace()
    gcs = [e for e in rec.events if e.name == "host.gc"]
    assert [dict(e.data)["generation"] for e in gcs] == [2, 0]
    assert all(e.track == "host" and e.dur >= 0 for e in gcs)
    traced = [n for n, _, _ in _host_events(str(tmp_path))
              if n == "host.gc"]
    assert len(traced) == 2
    remove()                                      # idempotent


# ------------------------------------------------------ the serving core ---


def test_engine_and_front_end_open_their_spans(rt):
    """A cooperative online run with the recorder on: every span of the
    front end, the scheduler, the cache manager and the model step
    reaches the ring, each tick's phases inside its ``sched.tick``."""
    llm, cfg = _llm(rt, trace=True)
    online = OnlineLLM(llm=llm)
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    streams = [online.submit(p, sp) for p in _prompts(cfg, 4, 0)]
    for s in streams:
        s.result()
    ev = [e for e in llm.engine.recorder.events if e.kind == "span"]
    names = {e.name for e in ev}
    assert {"front.inbox", "front.deliver", "sched.tick", "sched.reap",
            "sched.plan", "sched.prefill_results", "sched.decode_results",
            "kv.publish", "step.chunk_dispatch", "step.chunk_readback",
            "step.decode_dispatch", "step.decode_readback"} <= names
    assert {e.track for e in ev} == {"front", "sched", "kv", "step"}
    ticks = [e for e in ev if e.name == "sched.tick"]
    for e in ev:
        if e.track in ("sched", "kv", "step") and e.name != "sched.tick":
            assert any(t.t0 <= e.t0 and e.t0 + e.dur <= t.t0 + t.dur
                       for t in ticks), e.name
    assert validate_chrome_trace(chrome_trace_events(
        llm.engine.recorder)) == []


def test_tokens_identical_with_spans_traced_and_not(rt, tmp_path):
    """Served tokens and log-probabilities are bit-identical whether the
    spans run bare or under the profiler with the recorder on."""
    sp = SamplingParams(temperature=0.8, top_p=0.95, max_new_tokens=6,
                        logprobs=True)
    plain, cfg = _llm(rt, trace=None)
    prompts = _prompts(cfg, 5, 3)
    want = plain.generate(prompts, sp)
    traced, _ = _llm(rt, trace=True)
    jax.profiler.start_trace(str(tmp_path))
    got = traced.generate(prompts, sp)
    jax.profiler.stop_trace()
    assert [o.token_ids for o in got] == [o.token_ids for o in want]
    assert [o.logprobs for o in got] == [o.logprobs for o in want]
    assert traced.engine.recorder.events


@pytest.mark.parametrize("prefill_mode", ["chunked", "exact"])
def test_queue_wait_sums_admission_minus_submit(rt, prefill_mode):
    """``EngineStats.queue_wait_s`` is the sum over admitted requests of
    admission minus submit (the recorder's own stamps of both), and
    ``admitted`` counts them; the metrics export carries both."""
    llm, cfg = _llm(rt, trace=True, prefill_mode=prefill_mode)
    sp = SamplingParams(temperature=0.0, max_new_tokens=3)
    outs = llm.generate(_prompts(cfg, 5, 1), sp)     # 5 > 2 slots: waits
    st = llm.engine.stats
    assert st.admitted == 5
    traces = sorted((o.trace for o in outs), key=lambda t: t["admit_time"])
    want = 0.0
    for t in traces:                              # in admission order
        want += t["admit_time"] - t["submit_time"]
    assert st.queue_wait_s == pytest.approx(want, rel=1e-12, abs=0.0)
    assert st.queue_wait_s > 0
    snap = update_from_engine(Metrics(), llm.engine)
    assert snap["repro_admitted_total"] == 5.0
    assert snap["repro_queue_wait_seconds_total"] == st.queue_wait_s


# ------------------------------------------------------- tick-jit names ---


def _module_name(jitted, *args) -> str:
    return jitted.lower(*args).as_text().split("\n", 1)[0].split()[1]


@pytest.fixture(scope="module")
def backends(rt):
    """A local engine on the chunked path, one on the exact path, and a
    one-stage pipelined engine, each after serving two requests."""
    out = {}
    for name, kw in (("local", {}), ("exact", {"prefill_mode": "exact"}),
                     ("pipelined", {"backend": "pipelined",
                                    "n_stages": 1})):
        llm, cfg = _llm(rt, **kw)
        llm.generate(_prompts(cfg, 2, 5),
                     SamplingParams(temperature=0.0, max_new_tokens=2))
        out[name] = llm.engine
    return out


def _zs(n):
    z = RowSampling.zeros(n)
    return tuple(jnp.asarray(x) for x in (z.keys, z.steps, z.temp, z.top_k,
                                          z.top_p))


def _lower_local_decode(eng):
    be = eng.backend
    n = eng.mb_size
    return _module_name(be._decode_jit, be.params, be.caches,
                        jnp.zeros((n,), jnp.int32),
                        jnp.zeros((n,), jnp.int32), jnp.int32(0), *_zs(n))


def _lower_local_chunk(eng):
    be = eng.backend
    r, c = eng.prefill_rows, eng.prefill_chunk
    rows = jnp.zeros((r,), jnp.int32)
    return _module_name(be._chunk_jit, be.params, be.caches,
                        jnp.zeros((r, c), jnp.int32), rows, rows, rows - 1,
                        jnp.zeros((r, eng.pool.max_pages_per_seq),
                                  jnp.int32))


def _lower_exact_prefill(eng):
    be = eng.backend
    (lp, fn), *_ = be._prefill_jits.items()
    return _module_name(fn, be.params, jnp.zeros((1, lp), jnp.int32),
                        be.caches, 0, 0)


def _lower_pipe_decode(eng):
    be = eng.backend
    n, s = eng.mb_size, be.n_stages
    return _module_name(be._tick_jit, be.params, be.caches, be.act,
                        jnp.zeros((n,), jnp.int32),
                        jnp.full((s,), -1, jnp.int32),
                        jnp.zeros((s, n), jnp.int32), *_zs(n),
                        jnp.int32(-1))


def _lower_pipe_chunk(eng):
    be = eng.backend
    r, c, s = eng.prefill_rows, eng.prefill_chunk, be.n_stages
    act = jnp.zeros((s, r, c, eng.cfg.d_model), jnp.float32)
    return _module_name(be._pf_tick_jit, be.params, be.caches, act,
                        jnp.zeros((r, c), jnp.int32),
                        jnp.zeros((s, r), jnp.int32),
                        jnp.zeros((s, r), jnp.int32),
                        jnp.zeros((s, r, eng.pool.max_pages_per_seq),
                                  jnp.int32),
                        jnp.zeros((r,), jnp.int32), jnp.int32(-1))


@pytest.mark.parametrize("engine,lower,name", [
    ("local", _lower_local_decode, "@jit__decode_fn"),
    ("local", _lower_local_chunk, "@jit__chunk_fn"),
    ("exact", _lower_exact_prefill, "@jit__prefill_fn"),
    ("pipelined", _lower_pipe_decode, "@jit_pipeline_decode_tick"),
    ("pipelined", _lower_pipe_chunk, "@jit_pipeline_prefill_chunk_tick"),
])
def test_tick_jits_lower_under_their_names(backends, engine, lower, name):
    """Each tick jit lowers as ``jit_<function>``, so a profiler trace
    names its program (a bare ``functools.partial`` lowers as
    ``jit__unknown``)."""
    assert lower(backends[engine]) == name
