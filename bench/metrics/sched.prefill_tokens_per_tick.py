"""Scheduler: prompt tokens prefilled per engine tick over the window
(``EngineStats.prefill_tokens`` and ``steps`` deltas)."""


def read(rec):
    w = rec.window
    return w.prefill_tokens / w.steps if w.steps else None
