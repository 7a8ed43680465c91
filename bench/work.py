"""Operations and bytes the dense-GQA decoder needs, from its sizes and
the live lengths.  A multiply-add counts as two operations.  Work that
the program does but the algorithm does not need (padded rows, logits of
a chunk that is not a prompt's last, masked cache positions) is not
counted."""

from __future__ import annotations

from bench.model import matmul_params_per_layer, sizes


def attention_flops(conf: dict, ctx: int) -> int:
    """One token of one layer attending over ``ctx`` positions
    (scores and the weighted sum of values)."""
    s = sizes(conf)
    return 4 * s["h"] * s["dh"] * ctx


def decode_token_flops(conf: dict, ctx: int) -> int:
    """One decoded token whose attention covers ``ctx`` positions
    (itself included), with its logits."""
    s = sizes(conf)
    return s["layers"] * (2 * matmul_params_per_layer(conf) +
                          attention_flops(conf, ctx)) + 2 * s["d"] * s["v"]


def prefill_flops(conf: dict, start: int, stop: int, last: bool) -> int:
    """Prompt positions ``start .. stop-1`` of one sequence under causal
    attention, and the logits of the prompt's last position when
    ``last``."""
    s = sizes(conf)
    n = stop - start
    ctx = (start + 1 + stop) * n // 2            # sum of (p + 1)
    flops = s["layers"] * (2 * matmul_params_per_layer(conf) * n +
                           4 * s["h"] * s["dh"] * ctx)
    return flops + (2 * s["d"] * s["v"] if last else 0)


def paged_decode_call(conf: dict, lens) -> tuple:
    """(FLOPs, bytes) of one paged decode-attention call over rows whose
    caches hold ``lens`` tokens: every live key and value is read once,
    and each row's query and output cross once."""
    import jax.numpy as jnp
    s = sizes(conf)
    item = jnp.dtype(conf["torch_dtype"]).itemsize
    total = int(sum(lens))
    flops = 4 * s["h"] * s["dh"] * total
    kv_bytes = 2 * total * s["hk"] * s["dh"] * item
    qo_bytes = 2 * len(lens) * s["h"] * s["dh"] * item
    return flops, kv_bytes + qo_bytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
