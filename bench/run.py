"""Run one cell once and print its result line.

    python3 -m bench.run --workload W --seed N --seconds S --trace 0|1

Set-up (counted in ``setup_s``) is everything before the window: JAX and
the chip, the weights (one jitted call from the seed), the engine, and the
ramp of the closed loop, whose first ticks compile every program the
window runs (from the persistent cache after a cell's first run).  Then
the window runs for ``--seconds``.  With ``--trace 1`` the window runs
under the profiler and the result carries the cell's per-layer metrics;
with ``--trace 0`` its end-to-end metrics.  After the window the engine is
dropped and the float32 reference checks a sample of what the window
served.

The last lines of standard error are the numbers compared and their
limits; the last line of standard output is one JSON object.  Without a
TPU, or with fewer chips than the cell asks for, the run prints no result
and exits with 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import Optional  # noqa: E402

from bench import check, spec  # noqa: E402

CACHE_DIR = ".jax_cache"         # inside the checkout, at a fixed path
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class NoChip(RuntimeError):
    pass


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache, every program cached however
    quick its compile: in ``$JAX_COMPILATION_CACHE_DIR`` where that is set
    (JAX reads it itself), else at ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = os.path.join(root, CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def devices_for(cell, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind}); no fallback")
    if len(devs) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips; JAX has "
                     f"{len(devs)}")
    return devs[:cell.chips]


@dataclass
class Record:
    """What a per-layer metric's reader gets."""
    window: object               # harness.Window
    trace: object                # bench.trace.Reduction or None
    peaks: dict
    chips: int
    memory: dict                 # peak_bytes, bytes_limit (fullest chip)


def memory_of(devs) -> dict:
    best = {"peak_bytes": None, "bytes_limit": None}
    for d in devs:
        st = d.memory_stats() or {}
        p = st.get("peak_bytes_in_use")
        if p is not None and (best["peak_bytes"] is None or
                              p > best["peak_bytes"]):
            best = {"peak_bytes": p, "bytes_limit": st.get("bytes_limit")}
    return best


def served_in_window(h, win) -> list:
    """Requests that delivered a token inside the window, with what they
    had delivered by its close."""
    out = []
    for r in h.all:
        if not any(win.t0 < t <= win.t1 for t in r.times):
            continue
        n = sum(1 for t in r.times if t <= win.t1)
        lps = list(r.stream.seq.logprobs or [])[:n]
        out.append(check.Served(prompt=list(r.gen.prompt),
                                tokens=r.tokens[:n], logprobs=lps,
                                greedy=r.gen.greedy))
    return out


def trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def run(cell, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, peaks: Optional[dict] = None,
        kinds=(), t_start: float = T_START, dump_trace: str = "") -> dict:
    """One run of ``cell``; returns the result object (and, under
    ``"readings"``, the numbers of the comparison)."""
    import jax

    from bench import harness, model, peaks as peak_table, reference
    from bench import trace as trace_lib

    stamps = [("imports", time.perf_counter())]
    devs = devices_for(cell, require_tpu)
    kind = devs[0].device_kind
    peaks = peaks or peak_table.peaks_for(kind)
    log = harness.CompileLog()
    conf = cell.config
    stamps.append(("chip", time.perf_counter()))
    params = jax.block_until_ready(model.make_params(
        cell.block, conf, seed, place=harness.placement(cell, devs)))
    model.check_layout(cell.block, conf, params)
    stamps.append(("weights", time.perf_counter()))
    h = harness.Harness(cell, params, seed)
    stamps.append(("engine", time.perf_counter()))
    say(f"{cell.name}: {kind} x{len(devs)}; {h.n_slots} slots "
        f"({h.engine.num_microbatches} microbatches of {h.engine.mb_size})")
    h.ramp(log)
    stamps.append(("ramp", time.perf_counter()))
    say("set-up by phase: " + ", ".join(
        f"{name} {t - prev:.3f} s" for (name, t), prev in
        zip(stamps, [t_start] + [t for _, t in stamps[:-1]])))
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir, profiler_options=trace_options())
    setup_s = time.perf_counter() - t_start
    win = h.run_window(seconds, log, peaks)
    if trace:
        jax.profiler.stop_trace()
    names = [m.name for m in cell.end_to_end]
    if any(n.startswith("ttft_") for n in names):
        h.drain_first_tokens(win.t0, win.t1)
    memory = memory_of(devs)
    recs = h.all
    attempted = [r for r in recs if r.submit_t <= win.t1 and
                 (not r.done or r.times[-1] > win.t0)]
    failed = [r for r in attempted
              if r.finish_reason not in (None, "length", "eos")]
    served = served_in_window(h, win)
    say(f"window {win.seconds:.3f} s: {win.steps} ticks, "
        f"{win.decode_tokens} decoded and {win.prefill_tokens} prefilled "
        f"tokens, {win.compiles} programs built inside it; set-up "
        f"{setup_s:.3f} s after {h.ramp_steps} ramp ticks ({log.n} "
        f"compiles in all, {log.hits} cache hits, {log.misses} misses); "
        f"{len(attempted)} requests attempted, {len(served)} delivered "
        f"tokens in the window; ttft samples "
        f"{harness.ttft_samples(recs, win)}")
    e2e = {m.name: (m, harness.end_to_end(m.name, recs, win, setup_s))
           for m in cell.end_to_end}
    h.close()

    # the comparison, once the program's state is gone
    lim = cell.cell["check"]
    sample = check.sample(served, int(lim["requests"]), seed,
                          length=lambda s: len(s.prompt) + len(s.tokens),
                          greedy=lambda s: s.greedy)
    t_ref = time.perf_counter()
    ref = reference.Reference(cell.block, conf, params)
    numbers = check.readings(ref, sample, kinds)
    say(f"reference over {len(sample)} requests ({numbers['tokens']} "
        f"tokens, {numbers['greedy_tokens']} greedy) took "
        f"{time.perf_counter() - t_ref:.3f} s")
    bad = check.sane(served, int(conf["vocab_size"]))
    verdict = check.verdict(numbers, lim.get("limits", {}))
    correct = verdict["ok"] and bad is None and bool(sample) and \
        numbers["greedy_tokens"] > 0
    if bad:
        say(f"malformed output: {bad}")

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory["peak_bytes"]}
    result = {"correct": correct, "attempted": len(attempted),
              "failed": len(failed)}
    if trace:
        events = trace_lib.read_xplane(trace_lib.find_xplane(tdir))
        if dump_trace:
            with open(dump_trace, "w") as f:
                json.dump(trace_lib.describe(events), f, indent=1)
        red = trace_lib.reduce(events)
        del events
        shutil.rmtree(tdir, ignore_errors=True)
        rec = Record(window=win, trace=red, peaks=peaks, chips=len(devs),
                     memory=memory)
        metrics = {}
        for m in cell.per_layer:
            v = m.reader(rec)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = red.breakdown()
        say("idle by host span: " + ", ".join(
            f"{k} {v:.4f} s" for k, v in sorted(
                red.idle_by_span.items(), key=lambda kv: -kv[1])))
    else:
        result["metrics"] = {n: {"value": v, "unit": m.unit}
                             for n, (m, v) in e2e.items() if v is not None}
        result["device"] = device
    result["check"] = verdict["table"]
    result["readings"] = numbers
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default="",
                    help="write the trace's planes, lines and most frequent "
                         "event names to this JSON file")
    ap.add_argument("--log-compiles", action="store_true",
                    help="log every program JAX builds (to find what a "
                         "ramp left for the window)")
    args = ap.parse_args(argv)

    root = spec.ROOT
    sys.path.insert(0, os.path.join(root, "src"))
    try:
        cell = spec.resolve(args.workload, root)
        import repro  # noqa: F401  the program under test
    except (spec.SpecError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_compile_cache(root)
    if args.log_compiles:
        import jax
        jax.config.update("jax_log_compiles", True)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     dump_trace=args.dump_trace)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result.pop("readings")
    for name, v in result["check"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(f"check correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
