"""Device idle apportioned to program spans (``bench.spans``): exact
splits of gaps across the innermost host span, JAX's own host events
never taken as spans, the trace reduction's numbers untouched, and on
the CPU the program's spans on the profiler's host plane inside the
harness's ``online.step``."""

import pytest

import bench_cpu
from bench import peaks, spans as S, spec
from bench import trace as T
from test_bench_trace import DEV, HOST, ev, hand_made


def with_program_spans():
    """``hand_made`` with program spans nested in ``online.step`` and a
    JAX host event over them.  Idle [0,100] falls under online.step
    (0-20), sched.reap (20-60) and step.decode_dispatch (60-100); idle
    [400,650] straddles sched.decode_results (400-450), sched.tick's own
    time (450-480), front.deliver (480-500) and readback (500-650)."""
    return hand_made() + [
        ev(HOST, "python", "sched.tick", 20, 460),
        ev(HOST, "python", "sched.reap", 20, 40),
        ev(HOST, "python", "step.decode_dispatch", 60, 90),
        ev(HOST, "python", "sched.decode_results", 380, 70),
        ev(HOST, "python", "front.deliver", 480, 20),
        ev(HOST, "python", "PjitFunction(_decode_fn)", 390, 120),
        ev(DEV, T.OPS_LINE, "%sched.plan = bf16[8] fusion(...)", 100, 1),
    ]


def test_apportion_splits_each_gap_exactly():
    app = S.apportion(with_program_spans())
    by = {k: v * 1e9 for k, v in app["by_span"].items()}
    assert by == pytest.approx({
        "online.step": 20.0, "sched.reap": 40.0,
        "step.decode_dispatch": 40.0, "sched.decode_results": 50.0,
        "sched.tick": 30.0, "front.deliver": 20.0, "readback": 150.0,
        "harness.refill": 150.0, "submit": 50.0})
    assert app["idle_s"] == pytest.approx(550e-9)
    assert sum(app["by_span"].values()) == pytest.approx(app["idle_s"])
    # gaps keep their lengths; each takes the owner of its midpoint
    assert [g[0] for g in app["gaps"]] == ["readback", "submit",
                                           "sched.reap"]
    assert [g[1] for g in app["gaps"]] == pytest.approx(
        [250e-9, 200e-9, 100e-9])


def test_straddling_gap_is_split_at_the_span_edges():
    """One gap [400, 650] across four owners, cut where each ends."""
    evs = with_program_spans()
    app = S.apportion(evs, (400.0, 650.0))
    by = {k: round(v * 1e9, 6) for k, v in app["by_span"].items()}
    assert by == {"sched.decode_results": 50.0, "sched.tick": 30.0,
                  "front.deliver": 20.0, "readback": 150.0}


def test_jax_host_events_are_never_spans():
    evs = with_program_spans()
    names = {e.name for e in S.host_spans(evs)}
    assert "PjitFunction(_decode_fn)" not in names
    assert "harness.window" not in names
    assert {"sched.tick", "online.step", "readback"} <= names
    # a device operation that happens to carry a span's name is no span
    assert all(not e.plane.startswith(T.DEVICE_PREFIX)
               for e in S.host_spans(evs))


def test_layers_and_share():
    app = S.apportion(with_program_spans())
    by = app["by_span"]
    assert S.idle_under(by, "sched.") == pytest.approx(120e-9)
    assert S.idle_under(by, "step.") == pytest.approx(40e-9)
    assert S.idle_under(by, "front.") == pytest.approx(20e-9)
    assert S.idle_under(by, "kv.") == 0.0
    assert S.idle_under(by, "readback") == 0.0        # harness, not program
    assert S.program_share(by) == pytest.approx(180 / 200)
    out = S.summary(app, ticks=2)
    assert out["metrics"] == pytest.approx({
        "front.idle_ms_per_tick": 10e-6, "sched.idle_ms_per_tick": 60e-6,
        "kv.idle_ms_per_tick": 0.0,
        "step.dispatch_idle_ms_per_tick": 20e-6})
    assert S.summary(app, ticks=0)["metrics"]["kv.idle_ms_per_tick"] is None


def test_reduction_reads_as_before_with_program_spans():
    """The trace reduction, and so every accepted metric, reads the same
    numbers whether or not the program's spans are in the trace."""
    a, b = T.reduce(hand_made()), T.reduce(with_program_spans()[:-1])
    assert (a.window_ns, a.devices, a.busy_ns, a.programs, a.ops, a.gaps,
            a.idle_by_span) == (b.window_ns, b.devices, b.busy_ns,
                                b.programs, b.ops, b.gaps, b.idle_by_span)
    assert a.breakdown() == b.breakdown()


def test_cpu_window_puts_program_spans_inside_online_step(tmp_path):
    """A traced window of a tiny cell on the CPU: the program's spans are
    on the host plane, on the clock of the harness's spans, and every one
    of the window's but ``host.gc`` lies inside an ``online.step``."""
    root = bench_cpu.make_root(tmp_path, cells={"tiny.prefill": False})
    cell = spec.resolve("tiny.prefill", root)
    win, deltas, events = S.traced_window(
        cell, 2 ** 31 + 5, 1.0, require_tpu=False,
        peaks=peaks.PEAKS["TPU v5 lite"])
    assert win.steps > 0
    assert deltas["admitted"] > 0 and deltas["queue_wait_s"] >= 0
    spans = S.host_spans(events)
    w, = [e for e in events if e.name == T.WINDOW_SPAN]
    inside = [e for e in spans if e.name in S.PROGRAM_SPANS
              and w.start_ns <= e.start_ns and e.end_ns <= w.end_ns]
    names = {e.name for e in inside}
    assert {"front.inbox", "front.deliver", "sched.tick", "sched.reap",
            "sched.plan", "sched.prefill_results", "sched.decode_results",
            "step.chunk_dispatch", "step.chunk_readback",
            "step.decode_dispatch", "step.decode_readback"} <= names
    steps = [e for e in spans if e.name == S.STEP_SPAN]
    for e in inside:
        if e.name != "host.gc":
            assert any(s.start_ns <= e.start_ns and e.end_ns <= s.end_ns
                       for s in steps), e.name
