"""Flight recorder (repro.obs): ring/event mechanics, bitwise transport
ledger reconciliation, Chrome-trace export and its schema check, the
metrics registry, and per-request traces equal — float for float — to
the engine's and the online stream's own latency numbers."""

import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from conftest import tiny
from repro.distributed.transport import SimulatedLinkTransport
from repro.models import model as M
from repro.obs import (Metrics, TraceRecorder, chrome_trace_events, span,
                       validate_chrome_trace, write_chrome_trace)
from repro.obs.metrics import update_from_engine
from repro.serving.engine import OfflineEngine, _resolve_trace
from repro.serving.kv_cache import PoolConfig
from repro.serving.llm import LLM, EngineConfig
from repro.serving.online import OnlineLLM
from repro.serving.request import SamplingParams


# ------------------------------------------------------ recorder core ---


def test_ring_bounds_and_dropped_counter():
    rec = TraceRecorder(capacity=8)
    for i in range(20):
        rec.instant("e", "t", float(i))
    assert len(rec.events) == 8
    assert rec.dropped == 12
    assert rec.summary()["dropped"] == 12
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_request_table_bounded_evicts_finished_first():
    rec = TraceRecorder(max_requests=2)
    rec.request_submit(1, 0.0, 4)
    rec.request_finish(1, 1.0, "eos")
    rec.request_submit(2, 0.5, 4)
    rec.request_submit(3, 0.6, 4)           # full → evicts finished #1
    assert 1 not in rec.requests and {2, 3} <= set(rec.requests)
    rec.request_submit(4, 0.7, 4)           # full of LIVE requests: drop
    assert 4 not in rec.requests
    assert rec.request_trace(4) is None


def test_recorder_event_shapes_export_and_ledger():
    rec = TraceRecorder()
    with span("sched.tick", rec):
        with span("kv.swap_out", rec, (("mb", 2), ("async", True))):
            pass
        with span("kv.swap_wait", rec, (("mb", 2),)):
            pass
    rec.pipe_tick("decode", 0.0, 1.0, (0, -1))
    rec.link_send("decode", 0, 1024, 0.0, 0.5)
    rec.link_send("decode", 1, 64, 0.5, 0.6, return_trip=True)
    rec.tick_stall("decode", 0.25, 1.0)
    rec.stage_busy("decode", 1, 0.0, 0.5)
    rec.prefix_event("hit", 7, 32, 1.0)
    rec.slo_budget(0.5, 16, 1.0)
    rec.fault("drop", 1.0, (("plane", "decode"), ("mb", 1)))
    rec.reshard_span("drain", 0.0, 1.0, (("old_stages", 2),))
    assert rec.link_ledger() == \
        {"wire_bytes": 1088, "sends": 2, "stall_s": 0.25}
    trace = chrome_trace_events(rec)
    assert validate_chrome_trace(trace) == []
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"sched.tick", "tick", "send", "return", "stall", "busy",
            "kv.swap_out", "kv.swap_wait", "prefix_hit", "slo_budget",
            "fault_drop", "reshard_drain"} <= names


def test_engine_config_trace_resolution():
    assert _resolve_trace(None) is None
    assert _resolve_trace(False) is None
    r = _resolve_trace(True)
    assert isinstance(r, TraceRecorder) and r.capacity == 65536
    assert _resolve_trace(128).capacity == 128     # int = ring capacity
    rec = TraceRecorder(capacity=4)
    assert _resolve_trace(rec) is rec              # instance passthrough
    with pytest.raises(ValueError):
        _resolve_trace("yes")


# ------------------------------- transport ledger (bitwise contract) ---


def _drive_transport(rec, n_ticks=40, seed=7):
    """2-stage simulated WAN with bandwidth + jitter, mixed planes and
    occupancy — every book-keeping branch of tick() gets exercised."""
    tr = SimulatedLinkTransport.uniform(2, 0.004, bandwidth_bps=2e6,
                                        jitter_s=0.0005)
    tr.set_recorder(rec)
    rng = np.random.RandomState(seed)
    for i in range(n_ticks):
        occ = [bool(rng.randint(0, 2)), bool(rng.randint(0, 2))]
        if not any(occ):
            occ[int(rng.randint(0, 2))] = True
        tr.tick(occ, int(rng.randint(256, 4096)), [0.002, 0.003],
                inject_t=float(tr.clock.now),
                plane="decode" if i % 3 else "prefill")
    return tr


def test_link_ledger_reconciles_bitwise_with_transport_books():
    rec = TraceRecorder()
    tr = _drive_transport(rec)
    assert rec.dropped == 0
    led = rec.link_ledger()
    assert led["wire_bytes"] == tr.wire_bytes     # exact int sum
    assert led["sends"] == tr.sends
    assert led["stall_s"] == tr.stall_s           # bitwise float equality


def test_exported_timeline_reconciles_through_json(tmp_path):
    rec = TraceRecorder()
    tr = _drive_transport(rec)
    out = tmp_path / "timeline.json"
    write_chrome_trace(rec, str(out))
    trace = json.loads(out.read_text())
    assert validate_chrome_trace(trace) == []
    sends = [e for e in trace["traceEvents"] if e.get("ph") == "b"]
    assert sum(e["args"]["nbytes"] for e in sends) == tr.wire_bytes
    assert len(sends) == tr.sends
    stall = 0.0                 # same floats, same left-to-right order
    for e in trace["traceEvents"]:
        if e.get("ph") == "C" and e["name"] == "stall":
            stall += e["args"]["stall_s"]
    assert stall == tr.stall_s


def test_span_timestamps_monotone_per_track():
    rec = TraceRecorder()
    _drive_transport(rec)
    last = {}
    spans = 0
    for e in rec.events:
        if e.kind != "span":
            continue
        spans += 1
        key = (e.clock, e.track)
        assert e.t0 >= last.get(key, float("-inf")), key
        assert e.dur >= 0.0
        last[key] = e.t0
    assert spans > 0


# -------------------------------------------------- timeline schema ---


def test_validator_catches_malformed_traces():
    assert validate_chrome_trace({"traceEvents": 5}) != []
    assert validate_chrome_trace(3) != []
    errs = validate_chrome_trace([{"ph": "X", "ts": -1.0}])
    assert any("name" in e for e in errs)
    assert any("ts=" in e for e in errs)
    evs = [{"name": "a", "ph": "X", "ts": 10.0, "dur": 1.0,
            "pid": 1, "tid": 1},
           {"name": "b", "ph": "X", "ts": 5.0, "dur": 1.0,
            "pid": 1, "tid": 1}]
    assert any("monotone" in e for e in validate_chrome_trace(evs))
    evs = [{"name": "s", "ph": "b", "ts": 0.0, "cat": "l", "id": 1}]
    assert any("never ended" in e for e in validate_chrome_trace(evs))
    evs = [{"name": "s", "ph": "b", "ts": 5.0, "cat": "l", "id": 1},
           {"name": "s", "ph": "e", "ts": 1.0, "cat": "l", "id": 1}]
    assert any("before its begin" in e
               for e in validate_chrome_trace(evs))


def test_timeline_cli(tmp_path):
    from repro.obs.timeline import main as tl_main
    rec = TraceRecorder()
    rec.span("a", "t", 0.0, 1.0)
    ok = tmp_path / "ok.json"
    write_chrome_trace(rec, str(ok))
    assert tl_main(["--check", str(ok)]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text('{"traceEvents": [{"ph": "X", "ts": -3}]}')
    assert tl_main(["--check", str(bad)]) == 1
    assert tl_main(["--check", str(tmp_path / "missing.json")]) == 2


# ------------------------------------------------------------ metrics ---


def test_metrics_registry_basics():
    m = Metrics()
    c = m.counter("c_total", help="h")
    c.inc()
    c.inc(2)
    assert m.counter("c_total") is c            # idempotent identity
    with pytest.raises(ValueError):
        c.inc(-1)                               # counters never decrease
    with pytest.raises(ValueError):
        c.set_to(1.0)
    with pytest.raises(ValueError):
        m.gauge("c_total")                      # type mismatch
    m.gauge("g", labels={"k": "v"}).set(2.5)
    h = m.histogram("lat_s", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = m.snapshot()
    assert snap["c_total"] == 3.0
    assert snap['g{k="v"}'] == 2.5
    assert snap["lat_s_count"] == 3.0
    assert Metrics.delta({"c_total": 1.0}, snap)["c_total"] == 2.0
    text = m.prometheus_text()
    assert "# TYPE c_total counter" in text
    assert 'g{k="v"} 2.5' in text
    assert "lat_s_bucket" in text and "+Inf" in text
    line = json.loads(m.jsonl_line())
    assert line["c_total"] == 3.0 and "_ts" in line


# ------------------------------------------------- engine integration ---


def _traced_llm(rt, trace=True, **cfg_kw):
    cfg = tiny("yi-9b")
    params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
    pool = PoolConfig(page_size=8, n_local_pages=16, max_pages_per_seq=4)
    econfig = EngineConfig(mb_size=2, num_microbatches=1, pool=pool,
                           trace=trace, **cfg_kw)
    return LLM(cfg, config=econfig, params=params, rt=rt), cfg


def test_trace_off_is_zero_cost(rt):
    llm, cfg = _traced_llm(rt, trace=None)
    assert llm.engine.recorder is None
    outs = llm.generate([[3, 4, 5]],
                        SamplingParams(temperature=0.0, max_new_tokens=3))
    assert outs[0].trace is None


def test_offline_request_traces_match_engine_stamps(rt):
    llm, cfg = _traced_llm(rt)
    eng = llm.engine
    assert eng.recorder is not None
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, cfg.vocab_size, 6)) for _ in range(3)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    outs = llm.generate(prompts, sp)
    assert all(o.finished for o in outs)
    for out in outs:
        tr = out.trace
        assert tr is not None
        assert tr["ttft_s"] == out.ttft_s       # same floats subtracted
        assert len(tr["token_times"]) == len(out.token_ids)
        assert tr["queue_wait_s"] is not None and tr["queue_wait_s"] >= 0
        assert tr["finish_reason"] == out.finish_reason
        assert tr["pages"] >= 1
        assert all(d >= 0 for d in tr["inter_token_s"])
        if eng.chunked_prefill:
            assert tr["chunks"] >= 1
    phases = [e for e in eng.recorder.events
              if e.track == "sched" and e.kind == "span"]
    assert {e.name for e in phases} >= {"sched.tick", "sched.reap",
                                        "sched.plan",
                                        "sched.prefill_results",
                                        "sched.decode_results"}
    wall = chrome_trace_events(eng.recorder)
    assert validate_chrome_trace(wall) == []


def test_online_stream_trace_matches_stream_bitwise(rt):
    """Satellite contract: per-request TTFT / inter-token latencies in
    the trace are the SAME floats RequestStream reports — not close, the
    same subtractions of the same stamps."""
    llm, cfg = _traced_llm(rt)
    online = OnlineLLM(llm=llm)
    rng = np.random.RandomState(1)
    sp = SamplingParams(temperature=0.0, max_new_tokens=5)
    s1 = online.submit(list(rng.randint(1, cfg.vocab_size, 5)), sp)
    s2 = online.submit(list(rng.randint(1, cfg.vocab_size, 7)), sp)
    for s in (s2, s1):                  # drain out of submit order too
        out = s.result()
        tr = out.trace
        assert tr is not None and out.finished
        assert tr["stream_submit_time"] == s.submit_time
        assert tr["delivery_times"] == s._event_times
        assert tr["ttft_s"] == s.ttft_s                  # bitwise
        assert tr["inter_token_s"] == s.inter_token_s()  # bitwise


def test_metrics_snapshot_never_stale(rt):
    """Regression for the status_counts staleness bug: the stats field
    is a mirror that status_counts()/throughput_report() always rewrite,
    so a metrics scrape can never observe a stale copy."""
    llm, cfg = _traced_llm(rt, trace=None)
    eng = llm.engine
    rng = np.random.RandomState(2)
    prompts = [list(rng.randint(1, cfg.vocab_size, 5)) for _ in range(3)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=3)
    llm._submit(prompts, sp)
    m = Metrics()
    snap = update_from_engine(m, eng)
    assert snap['repro_requests{status="queued"}'] == 3.0
    assert eng.stats.status_counts["queued"] == 3       # mirror written
    eng.run(max_steps=200)
    # the mirror was last written pre-run; the report must refresh it
    snap2 = update_from_engine(m, eng)
    assert snap2['repro_requests{status="finished"}'] == 3.0
    assert snap2['repro_requests{status="queued"}'] == 0.0
    assert eng.stats.status_counts["finished"] == 3
    assert snap2["repro_requests_finished_total"] == 3.0
    assert snap2["repro_engine_steps_total"] > 0


def test_stage_report_shape(rt):
    """Satellite contract: StragglerMitigator observations and per-stage
    drain times surface in throughput_report()["stages"]."""
    from repro.distributed.elastic import StragglerMitigator
    llm, _ = _traced_llm(rt, trace=None)
    eng = llm.engine
    assert "stages" not in eng.throughput_report()      # local: no stages
    eng.straggler = StragglerMitigator(2)
    eng._stage_time_total = [0.0, 0.0]
    eng._stage_time_count = [0, 0]
    eng.straggler.observe(0, 0.01)
    eng.straggler.observe(1, 0.05)
    eng._stage_time_total[1] += 0.05
    eng._stage_time_count[1] += 1
    st = eng.throughput_report()["stages"]
    assert set(st) == {"ewma_s", "total_s", "counts",
                       "microbatch_weights", "stragglers"}
    assert len(st["ewma_s"]) == 2 == len(st["microbatch_weights"])
    assert st["counts"] == [0, 1]
    assert st["total_s"][1] == pytest.approx(0.05)
    assert st["ewma_s"] == [0.01, 0.05]         # first observation seeds
    assert isinstance(st["stragglers"], list)
    # ... and the metrics mapping exposes one labelled gauge per stage
    m = Metrics()
    snap = update_from_engine(m, eng)
    assert snap['repro_stage_time_ewma_s{stage="1"}'] == 0.05
    assert 'repro_stage_straggler{stage="0"}' in snap


def test_local_backend_no_retrace_with_tracing_on(rt):
    """Tracing must not add a retrace: with the recorder live, every
    serve jit still holds exactly one compiled trace after mixed
    prefill+decode with slot churn."""
    from repro.analysis.invariants import jit_cache_size
    llm, cfg = _traced_llm(rt)
    rng = np.random.RandomState(11)
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    prompts = [list(rng.randint(1, cfg.vocab_size, rng.randint(3, 10)))
               for _ in range(5)]               # 5 > 2 slots → churn
    outs = llm.generate(prompts, sp)
    assert all(o.finished for o in outs)
    sizes = {k: jit_cache_size(f)
             for k, f in llm.engine.backend.jit_entries().items()}
    bad = {k: v for k, v in sizes.items() if v is not None and v > 1}
    assert not bad, f"tracing caused a retrace: {bad} (all: {sizes})"
    assert any(v == 1 for v in sizes.values()), sizes


PIPE_TRACE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp
import numpy as np
from repro.analysis.invariants import jit_cache_size
from repro.config import get_arch, reduced_config
from repro.distributed.transport import SimulatedLinkTransport
from repro.models import model as M
from repro.models.common import Runtime
from repro.obs import chrome_trace_events, validate_chrome_trace
from repro.serving.engine import OfflineEngine
from repro.serving.kv_cache import PoolConfig
from repro.serving.request import Request, SamplingParams

rt = Runtime(param_dtype=jnp.float32, compute_dtype=jnp.float32)
cfg0 = get_arch("yi-9b")
period = len(cfg0.block_pattern)
cfg = reduced_config(cfg0, num_layers=2 * period + (2 if period > 1 else 1))
params = M.init_params(cfg, jax.random.PRNGKey(0), rt)
pool = PoolConfig(page_size=4, n_local_pages=32, max_pages_per_seq=6)
sp = SamplingParams(temperature=0.0, max_new_tokens=5)
transport = SimulatedLinkTransport.uniform(2, 0.008)
eng = OfflineEngine(cfg, params, rt, mb_size=2, num_microbatches=2,
                    pool=pool, sampling=sp, backend="pipelined",
                    n_stages=2, transport=transport, trace=True,
                    strict=True)
rng = np.random.RandomState(11)
reqs = [Request(i, list(rng.randint(1, cfg.vocab_size,
                                    rng.randint(3, 10))), sp)
        for i in range(6)]
eng.submit(reqs)
done = eng.run(max_steps=600)
assert len(done) == 6, len(done)
rec = eng.recorder
assert rec is not None and rec.dropped == 0
# acceptance: the recorded ledger reconciles BITWISE with the books
led = rec.link_ledger()
assert led["wire_bytes"] == transport.wire_bytes, led
assert led["sends"] == transport.sends, led
assert led["stall_s"] == transport.stall_s, led
# ... including through the exported Chrome-trace JSON
trace = chrome_trace_events(rec)
assert validate_chrome_trace(trace) == []
sends = [e for e in trace["traceEvents"] if e.get("ph") == "b"]
assert sum(e["args"]["nbytes"] for e in sends) == transport.wire_bytes
# tracing must not add a retrace on the pipelined backend either
sizes = {k: jit_cache_size(f)
         for k, f in eng.backend.jit_entries().items()}
bad = {k: v for k, v in sizes.items() if v is not None and v > 1}
assert not bad, sizes
assert any(v == 1 for v in sizes.values()), sizes
print("OK", led)
"""


@pytest.mark.slow
def test_pipelined_tracing_reconciles_bitwise_and_no_retrace():
    """2-stage SimulatedLinkTransport run (fresh interpreter, 2 fake CPU
    devices) with the flight recorder on: the exported timeline's
    per-link transfer slices reconcile bitwise with the transport's
    wire-byte books, and every tick jit still compiles exactly once."""
    from equivalence import subprocess_env
    r = subprocess.run([sys.executable, "-c", PIPE_TRACE_SCRIPT],
                       env=subprocess_env(), capture_output=True,
                       text=True, timeout=560)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-2000:]}"
    assert "OK" in r.stdout
