"""Device idle by program span: which layer the host was in while the
device waited.

    python3 -m bench.spans --workload W --seed N --seconds S

Runs one cell's ramp and window as ``bench.run --trace 1`` does, with a
``host.gc`` span around every garbage collection where the program
offers the hook, and prints one JSON line.  Every idle interval of the
first device inside the window is split exactly across the innermost
host span open over each part of it (``apportion``); the line gives that
idle per engine tick by span and by layer, the share of the idle under
the harness's ``online.step`` that a program span owns, the longest
gaps with their owners, and the window's counts.

Program spans are the ``<layer>.<what>`` profiler annotations the
program opens (``repro.obs.span``).  Their names are fixed here and not
imported, so a renamed span reads nothing rather than moving a number.
Other host events (JAX's own dispatch and runtime events) are never
taken as spans.

This is a probe beside the benchmark, not part of its command: it
imports ``bench.run`` and ``bench.harness`` and changes neither.
"""

from __future__ import annotations

import argparse
import heapq
import json
import os
import shutil
import sys
import tempfile
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace as T

PROGRAM_SPANS = (
    "front.inbox", "front.deliver",
    "sched.tick", "sched.reap", "sched.plan", "sched.prefill_results",
    "sched.decode_results",
    "kv.publish", "kv.swap_out", "kv.swap_wait",
    "step.chunk_dispatch", "step.chunk_readback",
    "step.decode_dispatch", "step.decode_readback",
    "host.gc",
)
# the layer of a span is its prefix; the metric each layer would feed
LAYERS = {"front.": "front.idle_ms_per_tick",
          "sched.": "sched.idle_ms_per_tick",
          "kv.": "kv.idle_ms_per_tick",
          "step.": "step.dispatch_idle_ms_per_tick",
          "host.": None}
STEP_SPAN = "online.step"
NO_SPAN = "outside harness spans"


def host_spans(events: Sequence[T.Event]) -> List[T.Event]:
    """The harness's and the program's spans, inside or across the
    window; nothing else of the host planes."""
    names = set(T.HOST_SPANS) | set(PROGRAM_SPANS)
    return [e for e in events if not e.plane.startswith(T.DEVICE_PREFIX)
            and e.name in names and e.name != T.WINDOW_SPAN]


def owners(spans: Sequence[T.Event], lo: float, hi: float
           ) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into pieces, each with the innermost span open over
    it: of the spans covering a piece, the one that started last, and of
    those the one that ends first (spans of one thread nest, so that is
    the innermost; across threads the later start wins).  Pieces no span
    covers are ``NO_SPAN``."""
    edges = []
    for i, s in enumerate(spans):
        a, b = max(s.start_ns, lo), min(s.end_ns, hi)
        if b > a:
            edges.append((a, 1, i))
            edges.append((b, 0, i))
    edges.sort()
    out: List[Tuple[float, float, str]] = []
    heap: List[Tuple[float, float, int]] = []   # (-start, end, index)
    closed = set()
    t = lo
    for x, opening, i in edges:
        while heap and heap[0][2] in closed:
            heapq.heappop(heap)
        if x > t:
            name = spans[heap[0][2]].name if heap else NO_SPAN
            if out and out[-1][2] == name and out[-1][1] == t:
                out[-1] = (out[-1][0], x, name)
            else:
                out.append((t, x, name))
            t = x
        if opening:
            heapq.heappush(heap, (-spans[i].start_ns, spans[i].end_ns, i))
        else:
            closed.add(i)
    if hi > t:
        out.append((t, hi, NO_SPAN))
    return out


def idle_intervals(events: Sequence[T.Event], lo: float, hi: float
                   ) -> List[List[float]]:
    """The first device's idle intervals in [lo, hi]: the complement of
    the union of its ``XLA Ops`` events (the reduction's busy union)."""
    devices = sorted({e.plane for e in events
                      if e.plane.startswith(T.DEVICE_PREFIX)
                      and e.line == T.OPS_LINE})
    if not devices:
        return []
    busy = T.clip(T.union((e.start_ns, e.end_ns) for e in events
                          if e.plane == devices[0] and e.line == T.OPS_LINE
                          and e.end_ns > lo and e.start_ns < hi), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [[s, e] for s, e in zip(edges[::2], edges[1::2]) if e > s]


def apportion(events: Sequence[T.Event],
              window_ns: Optional[Tuple[float, float]] = None) -> dict:
    """Idle seconds of the first device by the innermost host span open
    over each part of each idle interval (exact: a gap that straddles two
    spans is split at their edge), and the idle gaps, longest first, each
    labelled with the owner of its midpoint."""
    if window_ns is None:
        win = [e for e in events if e.name == T.WINDOW_SPAN
               and not e.plane.startswith(T.DEVICE_PREFIX)]
        if len(win) != 1:
            raise RuntimeError(f"expected one {T.WINDOW_SPAN!r} span, "
                               f"found {len(win)}")
        window_ns = (win[0].start_ns, win[0].end_ns)
    lo, hi = window_ns
    idle = idle_intervals(events, lo, hi)
    pieces = owners(host_spans(events), lo, hi)
    by: Dict[str, float] = defaultdict(float)
    gaps = []
    j = 0
    for a, b in idle:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        mid, label = (a + b) / 2, None
        while k < len(pieces) and pieces[k][0] < b:
            s, e, name = pieces[k]
            by[name] += (min(b, e) - max(a, s)) * 1e-9
            if label is None and s <= mid <= e:
                label = name
            k += 1
        gaps.append((label, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return {"window_ns": (lo, hi), "idle_s": sum(b - a for a, b in idle)
            * 1e-9, "by_span": dict(by), "gaps": gaps}


def idle_under(by_span: Dict[str, float], prefix: str) -> float:
    """Idle seconds owned by the program spans whose names start with
    ``prefix``."""
    return sum(v for k, v in by_span.items()
               if k in PROGRAM_SPANS and k.startswith(prefix))


def program_share(by_span: Dict[str, float]) -> Optional[float]:
    """Of the idle under ``online.step`` (its own and its program spans'),
    the share a program span owns."""
    prog = idle_under(by_span, "")
    total = prog + by_span.get(STEP_SPAN, 0.0)
    return prog / total if total else None


def summary(app: dict, ticks: int) -> dict:
    """Per-tick milliseconds by span and by layer, and the metrics the
    layers would feed."""
    by = app["by_span"]
    per = (lambda s: s / ticks * 1e3) if ticks else (lambda s: None)
    out = {"idle_s": app["idle_s"], "ticks": ticks,
           "idle_ms_per_tick": {k: per(v) for k, v in sorted(
               by.items(), key=lambda kv: -kv[1])},
           "layers_ms_per_tick": {p: per(idle_under(by, p))
                                  for p in LAYERS},
           "program_share_of_step_idle": program_share(by),
           "gaps": [[n, s] for n, s in app["gaps"][:10]]}
    out["metrics"] = {m: out["layers_ms_per_tick"][p]
                      for p, m in LAYERS.items() if m}
    return out


def traced_window(cell, seed: int, seconds: float, *,
                  require_tpu: bool = True, peaks: Optional[dict] = None):
    """One cell's ramp, then its window under the profiler with
    ``host.gc`` spans on where the program has the hook.  Returns the
    window, its engine-stat deltas and the trace's events."""
    import jax

    from bench import harness, model, run
    from bench import peaks as peak_table

    devs = run.devices_for(cell, require_tpu)
    peaks = peaks or peak_table.peaks_for(devs[0].device_kind)
    log = harness.CompileLog()
    params = jax.block_until_ready(model.make_params(
        cell.block, cell.config, seed, place=harness.placement(cell, devs)))
    h = harness.Harness(cell, params, seed)
    h.ramp(log)
    try:
        from repro.obs import install_gc_spans
        remove = install_gc_spans()
    except ImportError:                 # a program without the hook
        remove = lambda: None  # noqa: E731
    st = h.engine.stats
    counts = ("admitted", "queue_wait_s")
    c0 = [getattr(st, c, None) for c in counts]
    tdir = tempfile.mkdtemp(prefix="bench-spans-")
    jax.profiler.start_trace(tdir, profiler_options=run.trace_options())
    try:
        win = h.run_window(seconds, log, peaks)
    finally:
        jax.profiler.stop_trace()
        remove()
    deltas = {c: (b - a if a is not None else None)
              for c, a, b in zip(counts, c0,
                                 (getattr(st, c, None) for c in counts))}
    h.close()
    events = T.read_xplane(T.find_xplane(tdir))
    shutil.rmtree(tdir, ignore_errors=True)
    return win, deltas, events


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import run, spec
    root = spec.ROOT
    sys.path.insert(0, os.path.join(root, "src"))
    try:
        cell = spec.resolve(args.workload, root)
        import repro.obs  # noqa: F401  the program under test
    except (spec.SpecError, ImportError) as e:
        print(f"bench.spans: {e}", file=sys.stderr)
        return 2
    run.enable_compile_cache(root)
    try:
        win, deltas, events = traced_window(cell, args.seed, args.seconds)
    except run.NoChip as e:
        print(f"bench.spans: {e}", file=sys.stderr)
        return 2
    red = T.reduce(events)
    app = apportion(events, red.window_ns)
    out = {"workload": cell.name, "seed": args.seed,
           "window_s": win.seconds, "busy_s": red.busy_s,
           "window_trace_s": red.window_s,
           "decoded_tok_s": win.decode_tokens / win.seconds,
           **summary(app, win.steps)}
    if deltas["admitted"]:
        out["metrics"]["sched.queue_wait_ms"] = (
            deltas["queue_wait_s"] / deltas["admitted"] * 1e3)
    out["harness_idle_gaps"] = red.breakdown()["idle_gaps"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
