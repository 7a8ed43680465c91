"""The trace reduction: device busy union, program and operation times,
and idle gaps labelled by the harness span open on the host."""

import pytest

import bench_cpu  # noqa: F401  (the checkout root on sys.path)
from bench import trace as T

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start, dur, text=""):
    return T.Event(plane, line, name, float(start), float(dur), text)


def hand_made():
    """A window of 1000 ns on the host; on the device two programs whose
    operations leave three gaps: 100 ns under ``online.step`` before the
    first op, 150 ns under ``readback``, 200 ns at the end under
    ``harness.refill``."""
    return [
        ev(HOST, "python", "harness.window", 0, 1000),
        ev(HOST, "python", "online.step", 0, 500),
        ev(HOST, "python", "readback", 500, 300),
        ev(HOST, "python", "harness.refill", 800, 200),
        ev(HOST, "python", "submit", 850, 50),
        ev(DEV, T.MODULES_LINE, "jit__decode_fn(7)", 100, 300),
        ev(DEV, T.MODULES_LINE, "jit__chunk_fn(9)", 550, 250),
        ev(DEV, T.OPS_LINE, "%while.3 = (bf16[8]) while(...)", 100, 300),
        ev(DEV, T.OPS_LINE, "%fusion.1 = bf16[8] fusion(...)", 100, 150),
        ev(DEV, T.OPS_LINE,
           "%paged_decode_attention.9 = bf16[8,8,7,128] custom-call(...)",
           200, 200),
        ev(DEV, T.OPS_LINE, "%fusion.1 = bf16[8] fusion(...)", 650, 150),
        # outside the window: ignored
        ev(DEV, T.OPS_LINE, "fusion.2", 1200, 50),
        ev(DEV, T.MODULES_LINE, "jit__decode_fn(7)", 1200, 50),
    ]


def test_reduce_hand_made_events():
    r = T.reduce(hand_made())
    assert r.window_ns == (0.0, 1000.0)
    assert r.devices == [DEV]
    # ops cover [100, 400] and [650, 800]: 450 ns busy of 1000
    assert r.busy_ns[DEV] == pytest.approx(450.0)
    assert r.busy_s == pytest.approx(450e-9)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.programs == {"jit__decode_fn(7)": [300.0, 1],
                          "jit__chunk_fn(9)": [250.0, 1]}
    assert r.program_s(["jit__decode_fn(7)"]) == (pytest.approx(300e-9), 1)
    # the while loop contains the others: left out of the per-op times
    assert r.ops == {"fusion.1": 300.0, "paged_decode_attention.9": 200.0}
    assert r.kernel_s(T.is_paged_kernel) == (pytest.approx(200e-9), 1)
    assert r.programs_running(T.is_paged_kernel) == ["jit__decode_fn(7)"]
    # gaps: [0,100] online.step, [400,650] spans online.step/readback,
    # labelled at its midpoint 525 -> readback; [800,1000] -> midpoint
    # 900 lies in harness.refill (submit ends at 900: innermost open)
    assert [g[0] for g in r.gaps] == ["readback", "submit", "online.step"]
    assert [g[1] for g in r.gaps] == pytest.approx([250e-9, 200e-9,
                                                    100e-9])
    assert sum(r.idle_by_span.values()) == pytest.approx(550e-9)
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(300e-9)]
    assert len(b["idle_gaps"]) == 3


def test_op_name():
    assert T.op_name("%fusion.181 = bf16[2] fusion(x)") == "fusion.181"
    assert T.op_name("copy-start.2") == "copy-start.2"


def test_union_and_clip():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]
    assert T.clip([[0, 3], [5, 9]], 2, 6) == [[2, 3], [5, 6]]


def test_window_span_is_required():
    with pytest.raises(RuntimeError, match="harness.window"):
        T.reduce([e for e in hand_made() if e.name != "harness.window"])


def test_reads_a_trace_recorded_on_the_cpu(tmp_path):
    """A real profiler trace: the harness's spans come back from the
    .xplane.pb file with their nesting, and the reduction finds the
    window span in it."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("harness.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("online.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = T.read_xplane(T.find_xplane(str(tmp_path)))
    win = [e for e in events if e.name == "harness.window"]
    steps = [e for e in events if e.name == "online.step"]
    assert len(win) == 1 and len(steps) == 3
    assert all(win[0].start_ns <= s.start_ns and s.end_ns <= win[0].end_ns
               for s in steps)
    r = T.reduce(events)
    assert r.window_ns == (win[0].start_ns, win[0].end_ns)
    assert r.devices == []          # the CPU has no device plane
    described = T.describe(events, k=10_000)
    assert any(dict(v["top"]).get("online.step") == 3
               for v in described.values())
