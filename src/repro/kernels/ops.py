"""jit'd dispatch layer over the Pallas kernels.

Routes to the Pallas implementation when the shape is TPU-tileable, and to
the pure-jnp oracle otherwise.  The route depends on the shape only: on a
TPU the kernels run compiled, elsewhere in interpret mode (Python evaluation
of the kernel body) — numerically identical, structurally the same program
(``repro.kernels.default_interpret``).

The routing predicate is conservative: Pallas requires the head dim to be a
multiple of the 128-lane register width for MXU efficiency (64 is accepted:
it packs two heads per register row on v5e).  The paged kernel copies whole
pages, viewed as ``(page_size·Hk, Dh)`` rows, with its own DMAs; a DMA
slice's lane dimension must be a multiple of 128, so the paged route needs
``Dh % 128 == 0`` whatever ``Hk`` is (a ``Dh`` of 64 goes to the oracle),
and a ``page_size`` that is a multiple of 8.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import (flash_attention_pallas,
                                           tuned_flash_blocks)
from repro.kernels.paged_attention import paged_decode_attention as _paged_pl
from repro.kernels.rglru_scan import rglru_scan_pallas


def _aligned(*dims: int) -> bool:
    return all(d % 64 == 0 and d > 0 for d in dims)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_chunk: int = 512, kv_chunk: int = 512, q_offset=0,
                    scheme: str = "masked"):
    """Drop-in replacement for the jnp flash attention (prefill/train)."""
    dh = q.shape[-1]
    sq, skv = q.shape[1], k.shape[1]
    offset_static = isinstance(q_offset, int) and q_offset == 0
    if _aligned(dh) and offset_static and sq >= 8 and skv >= 8:
        g = q.shape[2] // k.shape[2]
        tq, tkv = tuned_flash_blocks(dh, g)
        q_blk = max(8, min(q_chunk, tq))
        kv_blk = max(8, min(kv_chunk, tkv))
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      q_blk=q_blk, kv_blk=kv_blk)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, q_chunk=q_chunk,
                                   kv_chunk=kv_chunk, scheme=scheme)


def decode_attention(q, k_cache, v_cache, slot_pos, cur_pos, *,
                     window: int = 0):
    """Dense-cache decode attention (jnp; the paged pool path is the kernel)."""
    return ref.decode_attention_ref(q, k_cache, v_cache, slot_pos, cur_pos,
                                    window=window)


def paged_decode_attention(q, k_pages, v_pages, page_table, seq_lens, *,
                           window: int = 0, pages_per_block: int = 0):
    """``pages_per_block=0`` autotunes the pages per DMA batch and compute
    step from the pool's shape (see ``tuned_pages_per_block``)."""
    dh = q.shape[-1]
    page_size = k_pages.shape[1]
    if dh % 128 == 0 and dh > 0 and page_size % 8 == 0:
        return _paged_pl(q, k_pages, v_pages, page_table, seq_lens,
                         window=window, pages_per_block=pages_per_block)
    return ref.paged_decode_attention_ref(q, k_pages, v_pages, page_table,
                                          seq_lens, window=window)


def rglru_scan(a, b, h0=None):
    bsz, s, dr = a.shape
    if h0 is None:
        h0 = jnp.zeros((bsz, dr), jnp.float32)
    if dr % 128 == 0 and s >= 8:
        return rglru_scan_pallas(a, b, h0)
    return ref.rglru_scan_ref(a, b, h0)
