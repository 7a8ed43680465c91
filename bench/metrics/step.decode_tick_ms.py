"""Model step: device time of one execution of the decode-tick program,
from the trace: its executions' device time in the window over their
number.  The program's jits carry no names of their own (a TPU trace
calls them ``jit__unknown(<id>)``), so the decode tick is found as the
program whose executions run the paged decode-attention kernel."""

from bench.trace import is_paged_kernel


def read(rec):
    if rec.trace is None:
        return None
    s, n = rec.trace.program_s(rec.trace.programs_running(is_paged_kernel))
    return s / n * 1e3 if n else None
