"""Model step: device time of one execution of the prefill-chunk program
(``_chunk_fn``), from the trace: its executions' device time in the
window over their number.  The program's jits carry no names of their own
(``jit__unknown(<id>)`` in a TPU trace): the chunk program is the one
named after ``_chunk_fn`` where a name exists, else the one unnamed
program other than the decode tick.  Where more than one program could be
the chunk program, the reader reads nothing rather than guess."""

from bench.trace import is_paged_kernel

NAME = "_chunk_fn"
UNNAMED = "jit__unknown"


def read(rec):
    t = rec.trace
    if t is None:
        return None
    named = [p for p in t.programs if NAME in p]
    if not named:
        decode = set(t.programs_running(is_paged_kernel))
        named = [p for p in t.programs if p.startswith(UNNAMED)
                 and p not in decode]
        if len(named) != 1:
            return None
    s, n = t.program_s(named)
    return s / n * 1e3 if n else None
