"""Flight recorder for the serving core: program spans on the profiler
clock with a ring-buffer recorder behind them (:mod:`repro.obs.trace`),
a metrics registry with Prometheus/JSONL
exposition (:mod:`repro.obs.metrics`), and Chrome-trace-event timeline
export for Perfetto (:mod:`repro.obs.timeline`).

Everything here is **host-side bookkeeping**: recording reads only
values the engine already materialises per tick (perf_counter stamps,
the transport's virtual-clock floats, host ints from the sanctioned
return-link syncs).  ``span`` always opens a profiler annotation (under
a microsecond when the profiler is off); the ring behind it is gated
behind ``EngineConfig(trace=...)``.  The ``obs-hot-path``
repro-audit rule enforces that no recording call ever runs inside a
tick-jit body or touches a traced value.
"""

from repro.obs.metrics import Metrics
from repro.obs.trace import Event, TraceRecorder, install_gc_spans, span
from repro.obs.timeline import (chrome_trace_events, validate_chrome_trace,
                                write_chrome_trace)

__all__ = ["TraceRecorder", "Event", "Metrics", "span", "install_gc_spans",
           "chrome_trace_events", "write_chrome_trace",
           "validate_chrome_trace"]
