"""The least time the chip could take for a piece of work.  A block's own
operations and bytes (``decode_token_flops``, ``prefill_flops``,
``paged_decode_calls``) are in ``bench/blocks/<block>.py``."""

from __future__ import annotations


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
