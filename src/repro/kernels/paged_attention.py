"""Pallas TPU paged decode-attention kernel.

This is the TPU adaptation of the paper's FlashInfer paged-KV decode path:
the KV cache lives in a *page pool* (``(n_pages, page_size, Hk, Dh)``) and
each sequence owns a list of pages (``page_table`` (B, max_pages)).  The
kernel streams a sequence's pages into VMEM — the page indirection is
resolved by the BlockSpec index_map reading the scalar-prefetched page table
(``PrefetchScalarGridSpec``), so pages travel HBM→VMEM without a gather
materialising the contiguous KV.

Layout/tuning (FlashInfer-style multi-page streaming):

* The pool is viewed as ``(n_pages, page_size, Hk·Dh)`` (a free reshape)
  and each KV block is one head's ``(page_size, Dh)`` slab of a page.  Its
  last two block dimensions are then tile-legal on the TPU (``page_size``
  a multiple of 8, ``Dh`` a multiple of 128); a ``(page_size, 1, Dh)``
  block over the 4-D pool is not, since one head of ``Hk`` is neither a
  multiple of 8 nor the whole axis.
* Grid = ``(B, Hk, n_blocks)`` — one grid dimension per KV head so GQA
  groups never share a softmax scratch, and the innermost dimension walks
  *blocks* of ``pages_per_block`` pages.  Each grid step DMAs
  ``pages_per_block`` pages and runs ONE online-softmax rescale over all of
  them, amortising the rescale and the per-step DMA setup that a
  one-page-per-step walk pays ``pages_per_block`` times.
* ``pages_per_block`` is autotuned per ``(page_size, Dh, G)`` via
  ``tuned_pages_per_block`` (overridable per call).
* The running ``m``/``l`` statistics live in one fused ``(G, 2)`` VMEM
  scratch (column 0 = running max, column 1 = running denominator) — one
  buffer to initialise and one address stream instead of two.
* With a sliding window, blocks entirely below the window are skipped
  before the dot (``pl.when`` on the block-level live predicate), not
  merely masked after it.

Skipped-slot handling: table slots at or beyond ``ceil(seq_len/page_size)``
carry no meaning, and earlier revisions clamped their *page id* to pool
page 0 — issuing a (read-only, masked) DMA against whatever request owns
page 0.  That aliasing assumption is gone: the index map now clamps the
*slot* to the sequence's own last valid page, so masked grid steps only
ever re-read a page the row already owns.  The one residual read outside a
row's pages is the ``seq_len == 0`` row (no valid pages at all), which
reads the page id in its own table slot 0 — the allocator zero-fills
unused table rows, and pool page 0 is the allocator's reserved scratch
page, never user data.

Oracle: ``repro.kernels.ref.paged_decode_attention_ref``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

NEG_INF = -1e30

# Autotuned block choices, keyed (page_size, Dh, G).  Values picked so one
# grid step streams a few hundred KV tokens (amortising the rescale) while
# the K+V block pair stays well inside VMEM at bf16.  Shapes not listed
# fall back to the same ~512-token target with a VMEM-budget cap.
_TUNED_PPB = {
    (8, 64, 1): 8, (8, 64, 2): 8, (8, 64, 4): 8, (8, 64, 8): 4,
    (8, 128, 1): 8, (8, 128, 2): 4, (8, 128, 4): 4, (8, 128, 8): 4,
    (16, 64, 1): 4, (16, 64, 2): 4, (16, 64, 4): 4, (16, 64, 8): 2,
    (16, 128, 1): 4, (16, 128, 2): 4, (16, 128, 4): 2, (16, 128, 8): 2,
    (32, 64, 1): 2, (32, 64, 4): 2, (32, 128, 1): 2, (32, 128, 4): 2,
    (64, 64, 1): 2, (64, 128, 1): 1, (128, 64, 1): 1, (128, 128, 1): 1,
}
_PPB_VMEM_CAP = 128 * 1024        # bytes per K/V block pair (bf16)


def tuned_pages_per_block(page_size: int, dh: int, g: int) -> int:
    """Pages streamed per grid step for a ``(page_size, Dh, G)`` shape."""
    ppb = _TUNED_PPB.get((page_size, dh, g))
    if ppb is None:
        target = 512 if dh <= 64 else 256          # KV tokens per step
        ppb = max(1, target // page_size)
        while ppb > 1 and 2 * ppb * page_size * dh * 2 > _PPB_VMEM_CAP:
            ppb //= 2
    return ppb


def _paged_kernel(pt_ref, len_ref, q_ref, *refs, page_size: int,
                  g: int, window: int, ppb: int, n_blocks: int):
    ks = refs[:ppb]
    vs = refs[ppb:2 * ppb]
    o_ref = refs[2 * ppb]
    acc_ref, ml_ref = refs[2 * ppb + 1], refs[2 * ppb + 2]

    b = pl.program_id(0)
    blk = pl.program_id(2)

    @pl.when(blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ml_ref[:, 0] = jnp.full((g,), NEG_INF, jnp.float32)
        ml_ref[:, 1] = jnp.zeros((g,), jnp.float32)

    seq_len = len_ref[b]                       # tokens in cache (incl. current)
    n_pages = (seq_len + page_size - 1) // page_size
    base = blk * ppb                           # first page slot of this block
    live = base < n_pages
    if window > 0:
        # first in-window token is seq_len - window; blocks whose last page
        # ends before it contribute nothing — skip them before the dot.
        lo_page = jnp.maximum(seq_len - window, 0) // page_size
        live = jnp.logical_and(live, base + ppb > lo_page)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]                        # (G, Dh)
        if ppb == 1:
            k = ks[0][0]                       # (page_size, Dh)
            v = vs[0][0]
        else:
            k = jnp.concatenate([kr[0] for kr in ks], axis=0)
            v = jnp.concatenate([vr[0] for vr in vs], axis=0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / (q_ref.shape[-1] ** 0.5))          # (G, ppb·page)

        span = ppb * page_size
        tok = base * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (g, span), 1)
        mask = tok < seq_len
        if window > 0:
            mask &= tok > seq_len - 1 - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = ml_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        pr = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        ml_ref[:, 1] = ml_ref[:, 1] * alpha + jnp.sum(pr, axis=-1)
        pv = jax.lax.dot_general(pr.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        ml_ref[:, 0] = m_cur

    @pl.when(blk == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(ml_ref[:, 1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "pages_per_block", "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           seq_lens: jax.Array, *, window: int = 0,
                           pages_per_block: int = 0,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Decode attention over a paged KV pool.

    q          (B, H, Dh)         current-token queries
    k/v_pages  (P, page, Hk, Dh)  shared page pool
    page_table (B, max_pages)     page ids per sequence (row-major in time)
    seq_lens   (B,)               tokens present per sequence
    pages_per_block               KV pages streamed per grid step
                                  (0 = autotuned per (page_size, Dh, G))
    -> (B, H, Dh)
    """
    b, h, dh = q.shape
    n_pool, page_size, hk, _ = k_pages.shape
    g = h // hk
    max_pages = page_table.shape[1]

    ppb = pages_per_block or tuned_pages_per_block(page_size, dh, g)
    ppb = max(1, min(ppb, max_pages))
    n_blocks = (max_pages + ppb - 1) // ppb

    qr = q.reshape(b, hk, g, dh)
    # defensive pool-range clamp (matches the oracle); the slot clamp in
    # the index maps below is what keeps skipped steps on the row's pages
    pt = jnp.clip(page_table, 0, n_pool - 1).astype(jnp.int32)

    def _kv_map(j):
        def index_map(bi, hi, blki, pt_ref, len_ref):
            # clamp the slot to this row's own last valid page: masked
            # grid steps re-read a page the row owns instead of page 0
            n_pages = (len_ref[bi] + page_size - 1) // page_size
            last = jnp.minimum(jnp.maximum(n_pages - 1, 0), max_pages - 1)
            slot = jnp.minimum(blki * ppb + j, last)
            return (pt_ref[bi, slot], 0, hi)
        return index_map

    kv_specs = [pl.BlockSpec((1, page_size, dh), _kv_map(j))
                for j in range(ppb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hk, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda bi, hi, pi, pt_ref, len_ref: (bi, hi, 0, 0)),
            *kv_specs,
            *kv_specs,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, dh),
            lambda bi, hi, pi, pt_ref, len_ref: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, dh), jnp.float32),
            pltpu.VMEM((g, 2), jnp.float32),      # fused (m, l) statistics
        ],
    )
    kernel = functools.partial(_paged_kernel, page_size=page_size,
                               g=g, window=window, ppb=ppb,
                               n_blocks=n_blocks)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, g, dh), q.dtype),
        interpret=default_interpret() if interpret is None else interpret,
    )(pt, seq_lens.astype(jnp.int32), qr,
      *([k_pages.reshape(n_pool, page_size, hk * dh)] * ppb),
      *([v_pages.reshape(n_pool, page_size, hk * dh)] * ppb))
    return out.reshape(b, h, dh)
