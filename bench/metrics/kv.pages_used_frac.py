"""Cache manager: share of the page pool owned by sequences, from the
allocator's free lists after each tick of the window (page 0, the scratch
page, left out), averaged over the ticks."""


def read(rec):
    used = rec.window.pages_used
    return 100.0 * sum(used) / len(used) if used else None
