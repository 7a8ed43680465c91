"""Scheduler: tokens decoded per engine tick over the window
(``EngineStats.decode_tokens`` and ``steps`` deltas; a prompt's first
token counts, as the engine counts it)."""


def read(rec):
    w = rec.window
    return w.decode_tokens / w.steps if w.steps else None
