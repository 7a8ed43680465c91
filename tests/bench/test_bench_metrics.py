"""Each per-layer metric's reader, on a hand-made record: what it reads,
and that it reads nothing where its layer has nothing to show."""

import pytest

import bench_cpu  # noqa: F401  (the checkout root on sys.path)
from bench import harness, run, spec
from bench import trace as T

DEV = "/device:TPU:0"


def ev(line, name, start, dur):
    return T.Event(DEV, line, name, float(start), float(dur))


def reduction():
    """Two decode ticks of 100 ns (each running the paged kernel for
    20 ns) and one chunk of 300 ns in a 1000 ns window."""
    events = [T.Event("/host:CPU", "python", "harness.window", 0.0, 1000.0)]
    for t0 in (0, 500):
        events += [ev(T.MODULES_LINE, "jit__unknown(1)", t0, 100),
                   ev(T.OPS_LINE, "%paged_decode_attention.2 = x", t0 + 10,
                      20),
                   ev(T.OPS_LINE, "%fusion.1 = x", t0 + 40, 50)]
    events += [ev(T.MODULES_LINE, "jit__unknown(2)", 150, 300),
               ev(T.OPS_LINE, "%fusion.7 = x", 150, 300),
               ev(T.MODULES_LINE, "jit_convert_element_type(3)", 460, 5),
               ev(T.OPS_LINE, "%convert.1 = x", 460, 5)]
    return T.reduce(events)


def record(trace):
    w = harness.Window(t0=0.0, t1=2.0, steps=4, decode_tokens=20,
                       prefill_tokens=1000, model_flops=197e12 * 0.5,
                       paged_roof_s=10e-9, paged_calls=2,
                       pages_used=[0.5, 0.7])
    return run.Record(window=w, trace=trace,
                      peaks={"bf16_flops": 197e12,
                             "hbm_bytes_per_s": 819e9},
                      chips=1,
                      memory={"peak_bytes": 12, "bytes_limit": 16})


def read(name, rec):
    return spec.load_reader(spec.ROOT, name)(rec)


def test_readers():
    rec = record(reduction())
    assert read("sched.decode_rows_per_tick", rec) == 5.0
    assert read("sched.prefill_tokens_per_tick", rec) == 250.0
    assert read("kv.pages_used_frac", rec) == pytest.approx(60.0)
    assert read("step.decode_tick_ms", rec) == pytest.approx(100e-6)
    assert read("step.prefill_chunk_ms", rec) == pytest.approx(300e-6)
    assert read("step.mfu", rec) == pytest.approx(25.0)
    # 10 ns of roofline over 40 ns of kernel time
    assert read("kernel.paged_decode_roofline", rec) == pytest.approx(25.0)
    # busy: 2 x (20 + 50) + 300 + 5 = 445 of 1000 ns
    assert read("device.idle_frac", rec) == pytest.approx(55.5)
    assert read("device.hbm_peak_frac", rec) == 75.0


def test_prefill_chunk_reads_nothing_when_ambiguous():
    """A second unnamed program beside the decode tick leaves the chunk
    program unknown: the reader reads nothing rather than pick one."""
    red = reduction()
    red.programs["jit__unknown(4)"] = [1000.0, 1]
    assert read("step.prefill_chunk_ms", record(red)) is None


def test_readers_with_nothing_to_read():
    rec = record(None)
    for name in ("step.decode_tick_ms",
                 "step.prefill_chunk_ms", "kernel.paged_decode_roofline",
                 "device.idle_frac"):
        assert read(name, rec) is None, name
    rec.window.steps = 0
    assert read("sched.decode_rows_per_tick", rec) is None
