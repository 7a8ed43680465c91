"""A pipelined cell at a tiny size on four virtual CPU devices, in a fresh
interpreter (the rest of the suite keeps one device): the weights are
placed where the stages keep them, ``Harness._book`` counts what the
local backend counts on the same requests, the reference agrees with the
served tokens, and faults planted in the pipelined tick, two of them in
the hand-off between stages, fail the check.  ``pipe.bubble_frac`` is
read from a synthetic reduction."""

import json
import os
import subprocess
import sys

import pytest

import bench_cpu
from bench import check, run, spec
from bench import trace as T

SCRIPT = r"""
import json, os, sys, pathlib
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.environ["BENCH_TESTS"])
import bench_cpu
import jax, jax.numpy as jnp, numpy as np
from bench import harness, loadgen, model, peaks, run, spec

V5E = peaks.PEAKS["TPU v5 lite"]
tmp = pathlib.Path(os.environ["BENCH_TMP"])
cells = {"tiny.pipe4": False, "tiny.local4": False}
root = bench_cpu.make_root(tmp, cells=cells)
conf = dict(bench_cpu.TINY, num_hidden_layers=4)
json.dump(conf, open(tmp / "bench/configs/tiny-gqa.json", "w"))
for name, backend in (("tiny.pipe4", "pipelined"), ("tiny.local4", "local")):
    path = tmp / "bench/cells" / f"{name}.json"
    c = json.load(open(path))
    c["engine"].update(backend=backend, num_microbatches=4)
    if backend == "pipelined":
        c["engine"].update(n_stages=4)
    json.dump(c, open(path, "w"))
b = json.load(open(tmp / "BENCHMARK.json"))
for w in b["workloads"]:
    w["chips"] = 4
json.dump(b, open(tmp / "BENCHMARK.json", "w"))
out = {}

# the weights, where the stages keep them
cell = spec.resolve("tiny.pipe4", root)
devs = jax.devices()
params = model.make_params(cell.block, conf, 5, place=harness.placement(
    cell, devs))
model.check_layout(cell.block, conf, params)
wq = params["scan"][0]["wq"]
out["layout"] = {
    "wq_shards": sorted((s.device.id, s.index[0].start, s.data.shape[0])
                        for s in wq.addressable_shards),
    "embed_replicated": params["embed"]["tok"].sharding.is_fully_replicated,
    "embed_devices": len(params["embed"]["tok"].sharding.device_set)}

# the same requests to completion through each backend, one window
def book(name):
    cell = spec.resolve(name, root)
    params = model.make_params(cell.block, conf, 5, place=harness.placement(
        cell, devs))
    h = harness.Harness(cell, params, 3)
    rng = np.random.default_rng(1)
    reqs = [loadgen.GenRequest(index=i, prompt=[int(t) for t in rng.integers(
        1, 500, int(n))], max_new_tokens=int(m), temperature=0.0, top_k=0,
        top_p=1.0) for i, (n, m) in enumerate(zip(
            rng.integers(8, 40, 8), rng.integers(4, 12, 8)))]
    h.gen = iter(reqs)
    h.peaks = V5E
    h.refill = False
    h.window = win = harness.Window(t0=0.0, t1=0.0)
    d0 = h.engine.stats.decode_tokens
    h.fill()
    while not all(r.done for r in h.all):
        h.tick()
    h.tick()
    h.window = None
    res = {"decode_tokens": h.engine.stats.decode_tokens - d0,
           "model_flops": win.model_flops, "paged_calls": win.paged_calls,
           "paged_roof_s": win.paged_roof_s,
           "tokens": [r.tokens for r in h.all]}
    h.close()
    return res

out["book"] = {n: book(n) for n in cells}

# a sound run, then one with each fault planted in the pipelined tick
from repro.core import pipeline as PL
orig = PL.pipeline_decode_tick


def swapped(params, caches, act, *a, **kw):      # stages 1 and 2 read
    return orig(params, caches, act[jnp.array([0, 2, 1, 3])], *a, **kw)


def dropped(params, caches, act, *a, **kw):      # no hand-off arrives
    return orig(params, caches, act.at[1:].set(0), *a, **kw)


def state_unchanged(params, caches, *a, **kw):
    toks, lps, _, act = orig(params, caches, *a, **kw)
    return toks, lps, caches, act


def half_batch(*a, **kw):
    toks, lps, caches, act = orig(*a, **kw)
    h = toks.shape[0] // 2
    return toks.at[h:].set(toks[:h]), lps.at[h:].set(lps[:h]), caches, act


def token_altered(*a, cfg, **kw):
    toks, lps, caches, act = orig(*a, cfg=cfg, **kw)
    return (toks + 1) % cfg.vocab_size, lps, caches, act


cell = spec.resolve("tiny.pipe4", root)
for name, fn in (("sound", orig), ("swapped", swapped), ("dropped", dropped),
                 ("state_unchanged", state_unchanged),
                 ("half_batch", half_batch),
                 ("token_altered", token_altered)):
    PL.pipeline_decode_tick = fn
    res = run.run(cell, 2 ** 31 + 5, 1.0, False, require_tpu=False,
                  peaks=V5E, kinds=("int8", "fp8") if fn is orig else ())
    out[name] = {"correct": res["correct"], "readings": res["readings"]}
print("RESULT " + json.dumps(out))
"""

FAULTS = ["swapped", "dropped", "state_unchanged", "half_batch",
          "token_altered"]


@pytest.fixture(scope="module")
def result(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_TESTS=os.path.dirname(os.path.abspath(__file__)),
               BENCH_TMP=str(tmp_path_factory.mktemp("pipe")),
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(bench_cpu.ROOT, "src"), bench_cpu.ROOT]))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    lines = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    assert r.returncode == 0 and lines, r.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_weights_are_placed_by_stage(result):
    """Each device holds one stage's layer of the four; the embedding is
    replicated on all four."""
    lay = result["layout"]
    assert lay["wq_shards"] == [[d, d, 1] for d in range(4)]
    assert lay["embed_replicated"] and lay["embed_devices"] == 4


def test_book_counts_what_the_local_backend_counts(result):
    """The same requests served to completion: the same tokens, decode
    tokens and model operations, and the same paged-kernel work (each
    row's keys and values read once a layer: memory-bound, so the
    roofline seconds add up whatever the rows a call groups)."""
    local, pipe = result["book"]["tiny.local4"], result["book"]["tiny.pipe4"]
    assert pipe["tokens"] == local["tokens"]
    assert pipe["decode_tokens"] == local["decode_tokens"] > 0
    assert pipe["model_flops"] == local["model_flops"] > 0
    assert pipe["paged_roof_s"] == pytest.approx(local["paged_roof_s"],
                                                 rel=1e-9)
    assert pipe["paged_calls"] % 4 == 0 and pipe["paged_calls"] > 0


def test_reference_agrees_with_the_stages(result):
    assert result["sound"]["correct"], result["sound"]


@pytest.mark.parametrize("kind", ["int8", "fp8"])
def test_control_fails_on_the_stages(result, kind):
    """The reference at a lower precision, put in the place of the
    stages' output, fails the tiny cell's limits."""
    r = result["sound"]["readings"]
    assert not check.verdict(r["control"][kind],
                             bench_cpu.DEFAULT_LIMITS)["ok"], r


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_caught(result, fault):
    """Each fault planted in the pipelined tick (stages 1 and 2 reading
    each other's microbatch; no hand-off arriving; the caches returned
    unchanged; half of the microbatch answered from the other half; a
    token altered) comes out not correct under the tiny cell's limits,
    and fails every committed cell's limits."""
    res = result[fault]
    assert not res["correct"], res
    for name, limits in bench_cpu.committed_limits().items():
        assert not check.verdict(res["readings"], limits)["ok"], (name, res)


def test_bubble_frac_on_a_synthetic_reduction():
    """Two stages over a 1000 ns window: stage 0 busy 0-400, stage 1 busy
    300-600; both idle 600-1000.  Stage 0 idles 400-600 while stage 1
    runs (200 ns), stage 1 idles 0-300 while stage 0 runs (300 ns):
    (200 + 300) / 2 / 1000 = 25 %."""
    def ev(dev, start, dur):
        return T.Event(f"/device:TPU:{dev}", T.OPS_LINE, "%fusion.1 = x",
                       float(start), float(dur))
    events = [T.Event("/host:CPU", "python", "harness.window", 0.0, 1000.0),
              ev(0, 0, 250), ev(0, 200, 200), ev(1, 300, 300)]
    red = T.reduce(events)
    assert red.busy_intervals["/device:TPU:0"] == [[0.0, 400.0]]
    reader = spec.load_reader(spec.ROOT, "pipe.bubble_frac")
    rec = run.Record(window=None, trace=red, peaks={}, chips=2, memory={})
    assert reader(rec) == pytest.approx(25.0)
    one = T.reduce([e for e in events if "TPU:1" not in e.plane])
    assert reader(run.Record(window=None, trace=one, peaks={}, chips=1,
                             memory={})) is None


def test_decode_program_is_matched_on_its_own_device():
    """Two stages: the chunk program on device 0 overlaps in time a paged
    kernel call on device 1; only device 1's program runs the kernel."""
    events = [T.Event("/host:CPU", "python", "harness.window", 0.0, 1000.0)]
    for dev, prog, op, t0 in ((0, "jit_chunk(1)", "%fusion.1 = x", 100),
                              (1, "jit_decode(2)",
                               "%paged_decode_attention.3 = x", 100)):
        plane = f"/device:TPU:{dev}"
        events += [T.Event(plane, T.MODULES_LINE, prog, float(t0), 300.0),
                   T.Event(plane, T.OPS_LINE, op, float(t0 + 50), 100.0)]
    red = T.reduce(events)
    assert red.programs_running(T.is_paged_kernel) == ["jit_decode(2)"]
