"""Pallas TPU paged decode-attention kernel.

This is the TPU adaptation of the paper's FlashInfer paged-KV decode path:
the KV cache lives in a *page pool* (``(n_pages, page_size, Hk, Dh)``) and
each sequence owns a list of pages (``page_table`` (B, max_pages)).  The
kernel walks each row's live pages only, copying them from HBM into VMEM
with its own DMAs; no gather materialises the contiguous KV.

* Grid = ``(B,)``: one step per row, no axis over table slots.  The page
  table and ``seq_lens`` are scalar-prefetched into SMEM; the pools stay
  in HBM (``memory_space=pl.ANY``) and are viewed as
  ``(n_pages, page_size·Hk, Dh)``, which merges two adjacent dimensions
  of the stored layout and moves no data.
* A row's live pages run from the first page its window can see
  (``max(seq_len - window, 0) // page_size``, or 0 with no window) to
  ``ceil(seq_len / page_size)``.  A ``fori_loop`` walks them in blocks of
  ``pages_per_block`` pages; each page moves as ONE DMA of all its KV
  heads (``page_size·Hk·Dh`` elements) for K and one for V.  Table slots
  past the row's live pages are never read, nor is any pool page the row
  does not own.
* DMAs are double-buffered: block i+1 is started before block i is
  computed, and a row's last block starts the next row's first one, so
  only the first row of a call waits for its first DMA.  Grid steps
  therefore run in order (``"arbitrary"``), and the buffer slot of the
  next block lives in SMEM across steps.
* A block's keys are ``(pages_per_block·page_size·Hk, Dh)`` rows, token
  major.  All ``H = Hk·G`` queries attend to them in one product, and a
  block-diagonal mask keeps each query to its own KV head; the same mask
  drops the tokens past ``seq_len`` (only the last page is partial) and
  those below the window.  The wasted products cost nothing visible at
  decode's tiny arithmetic intensity.
* The online softmax keeps its running ``m``/``l`` statistics per query
  head in one fused ``(H, 2)`` VMEM scratch (column 0 = running max,
  column 1 = running denominator).
* A row with ``seq_len == 0`` issues no DMA and writes zeros, as the
  oracle defines for it.
* ``pages_per_block`` (pages per DMA batch and compute step) is autotuned
  per shape via ``tuned_pages_per_block`` (overridable per call).

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

# Pages per block, keyed by the bytes of one whole page (every KV head),
# from a sweep on a TPU v5e at the benchmark cells' shapes: 16 KiB pages
# (page 16, Hk 4, Dh 128, bf16) and 32 KiB pages (Hk 8) both run fastest
# at 16 pages a block.  Other pages aim at 256 tokens a block.
_TUNED_PPB = {16 * 1024: 16, 32 * 1024: 16}
_PPB_TARGET_TOKENS = 256
# VMEM for the K and V blocks, both buffers: ppb whole pages x 2 x 2
_PPB_VMEM_CAP = 2 * 1024 * 1024


def tuned_pages_per_block(page_size: int, dh: int, hk: int = 1,
                          itemsize: int = 2) -> int:
    """Pages per DMA batch and compute step for a pool of
    ``(page_size, Hk, Dh)`` pages of ``itemsize``-byte elements."""
    page_bytes = page_size * hk * dh * itemsize
    ppb = _TUNED_PPB.get(page_bytes, max(1, _PPB_TARGET_TOKENS // page_size))
    while ppb > 1 and ppb * page_bytes * 2 * 2 > _PPB_VMEM_CAP:
        ppb //= 2
    return ppb


def _paged_kernel(pt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, slot_ref, acc_ref, ml_ref, *,
                  page_size: int, hk: int, g: int, window: int, ppb: int,
                  max_pages: int):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    rows = page_size * hk                      # buffer rows of one page

    def span(row):
        """First live page of ``row``, its page count and its blocks."""
        seq_len = len_ref[row]
        hi = jnp.minimum((seq_len + page_size - 1) // page_size, max_pages)
        lo = (jnp.maximum(seq_len - window, 0) // page_size if window > 0
              else jnp.int32(0))
        return lo, hi, (hi - lo + ppb - 1) // ppb

    def copies(row, blk, slot, fn):
        """Apply ``fn`` to the K and V copies of each live page of block
        ``blk`` of ``row``, whose buffers are ``slot``."""
        lo, hi, _ = span(row)
        first = lo + blk * ppb

        def each(j, carry):
            page = pt_ref[row, first + j]
            fn(pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot, j],
                                     sems.at[0, slot]))
            fn(pltpu.make_async_copy(v_hbm.at[page], v_buf.at[slot, j],
                                     sems.at[1, slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(ppb, hi - first), each, 0)

    def start(row, blk, slot):
        copies(row, blk, slot, lambda c: c.start())

    def wait(row, blk, slot):
        copies(row, blk, slot, lambda c: c.wait())

    def start_next_row(slot):
        @pl.when(b + 1 < n_rows)
        def _():
            @pl.when(span(b + 1)[2] > 0)
            def _():
                start(b + 1, 0, slot)

    @pl.when(b == 0)
    def _prime():
        slot_ref[0] = 0

        @pl.when(span(0)[2] > 0)
        def _():
            start(0, 0, 0)

    seq_len = len_ref[b]
    lo, _, n_blocks = span(b)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    ml_ref[:, 0] = jnp.full((hk * g,), NEG_INF, jnp.float32)
    ml_ref[:, 1] = jnp.zeros((hk * g,), jnp.float32)

    q = q_ref[0]                               # (H, Dh)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    width = ppb * rows
    col = jax.lax.broadcasted_iota(jnp.int32, (hk * g, width), 1)
    row_head = jax.lax.broadcasted_iota(jnp.int32, (hk * g, width), 0) // g
    same_head = (col % hk) == row_head
    tok_in_block = col // hk
    # token of each buffer row, for the values: the pages past a row's
    # last one in a partial block hold what an earlier block (or nothing)
    # left there, and 0 x NaN would reach the output
    v_tok_in_block = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0) // hk

    def body(i, slot):
        nxt = 1 - slot

        @pl.when(i + 1 < n_blocks)
        def _():
            start(b, i + 1, nxt)

        @pl.when(i + 1 == n_blocks)
        def _():
            start_next_row(nxt)

        wait(b, i, slot)
        k = k_buf[slot].reshape(width, k_buf.shape[-1])   # (T·Hk, Dh)
        v = v_buf[slot].reshape(width, v_buf.shape[-1])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                     # (H, T·Hk)
        base = (lo + i * ppb) * page_size
        v = jnp.where(base + v_tok_in_block < seq_len, v, 0)
        tok = base + tok_in_block
        mask = same_head & (tok < seq_len)
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
        return nxt

    @pl.when(n_blocks == 0)
    def _empty():
        start_next_row(slot_ref[0])

    slot_ref[0] = jax.lax.fori_loop(0, n_blocks, body, slot_ref[0])
    l = jnp.maximum(ml_ref[:, 1], 1e-30)
    o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


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
    pages_per_block               KV pages per DMA batch and compute step
                                  (0 = autotuned per shape)
    -> (B, H, Dh)
    """
    b, h, dh = q.shape
    n_pool, page_size, hk, _ = k_pages.shape
    g = h // hk
    max_pages = page_table.shape[1]

    ppb = pages_per_block or tuned_pages_per_block(
        page_size, dh, hk, k_pages.dtype.itemsize)
    ppb = max(1, min(ppb, max_pages))
    rows = page_size * hk

    # defensive pool-range clamp (matches the oracle)
    pt = jnp.clip(page_table, 0, n_pool - 1).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, dh), lambda bi, pt_ref, len_ref: (bi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, dh),
                               lambda bi, pt_ref, len_ref: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, rows, dh), k_pages.dtype),
            pltpu.VMEM((2, ppb, rows, dh), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),      # (K/V, buffer slot)
            pltpu.SMEM((1,), jnp.int32),          # slot of the next block
            pltpu.VMEM((h, dh), jnp.float32),
            pltpu.VMEM((h, 2), jnp.float32),      # fused (m, l) statistics
        ],
    )
    kernel = functools.partial(_paged_kernel, page_size=page_size, hk=hk,
                               g=g, window=window, ppb=ppb,
                               max_pages=max_pages)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=default_interpret() if interpret is None else interpret,
    )(pt, seq_lens.astype(jnp.int32), q,
      k_pages.reshape(n_pool, rows, dh), v_pages.reshape(n_pool, rows, dh))
