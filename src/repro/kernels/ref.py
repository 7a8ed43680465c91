"""Pure-jnp numerical oracles for every Pallas kernel in this package.

Each oracle is the *definition* of correctness; the kernels must match it to
float tolerance across shape/dtype sweeps (see tests/test_kernels.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# flash-attention oracle: the chunked online-softmax reference.
from repro.models.attention import decode_attention as decode_attention_ref
from repro.models.attention import flash_attention as flash_attention_ref

# RG-LRU oracle: parallel associative-scan form.
from repro.models.rglru import rglru_scan as _rglru_assoc


def rglru_scan_ref(a: jax.Array, b: jax.Array, h0: jax.Array) -> jax.Array:
    return _rglru_assoc(a.astype(jnp.float32), b.astype(jnp.float32),
                        h0.astype(jnp.float32))


def paged_decode_attention_ref(q, k_pages, v_pages, page_table, seq_lens, *,
                               window: int = 0):
    """Gather-then-attend oracle for the paged decode kernel.

    Shapes as in ``repro.kernels.paged_attention.paged_decode_attention``.
    A row with ``seq_len == 0`` has nothing to attend to and reads zeros.
    """
    b, h, dh = q.shape
    page_size = k_pages.shape[1]
    max_pages = page_table.shape[1]
    c = max_pages * page_size
    pt = jnp.clip(page_table, 0, k_pages.shape[0] - 1)
    k = k_pages[pt].reshape(b, c, *k_pages.shape[2:])    # (B, C, Hk, Dh)
    v = v_pages[pt].reshape(b, c, *v_pages.shape[2:])
    pos = jnp.arange(c)[None]                            # logical positions
    slot_pos = jnp.where(pos < seq_lens[:, None], pos, -1).astype(jnp.int32)
    cur = (seq_lens - 1).astype(jnp.int32)
    out = decode_attention_ref(q, k, v, slot_pos, cur, window=window)
    return jnp.where((seq_lens > 0)[:, None, None], out, 0).astype(out.dtype)
