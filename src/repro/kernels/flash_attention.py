"""Pallas TPU flash-attention (prefill/train) kernel.

Tiling: grid = (B·Hk, n_q_blocks, n_kv_blocks), kv innermost (sequential on
TPU), online-softmax state in VMEM scratch.  GQA is handled by folding the
``G = H // Hk`` query-group dimension into the q rows of each block, so the
MXU sees (G·q_blk, Dh) x (Dh, kv_blk) matmuls — hardware-aligned when
``G·q_blk`` and ``kv_blk`` are multiples of 128 and Dh ∈ {64,128,256,512}.

Causality and sliding windows are enforced twice: whole out-of-span kv blocks
are skipped via ``pl.when`` (no FLOPs, no DMA waste — this is the exact-FLOPs
"blockpair" scheme of the jnp reference), and the diagonal blocks are masked
elementwise.

The pure-jnp oracle is ``repro.models.attention.flash_attention``
(re-exported in ``repro.kernels.ref``).
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, ml_ref, *,
                  q_blk: int, kv_blk: int, n_kv: int, g: int, causal: bool,
                  window: int, sq_real: int, skv_real: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ml_ref[:, 0] = jnp.full((g * q_blk,), NEG_INF, jnp.float32)
        ml_ref[:, 1] = jnp.zeros((g * q_blk,), jnp.float32)

    q_start = qi * q_blk
    kv_start = ki * kv_blk

    live = None
    if causal:
        live = kv_start <= q_start + q_blk - 1
    if window > 0:
        w_live = kv_start + kv_blk - 1 > q_start - window
        live = w_live if live is None else jnp.logical_and(live, w_live)

    def _compute():
        q = q_ref[0].reshape(g * q_blk, q_ref.shape[-1])       # (G·qb, Dh)
        k = k_ref[0]                                           # (kvb, Dh)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (G·qb, kvb)
        scale = 1.0 / (q_ref.shape[-1] ** 0.5)
        s = s * scale

        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_blk, kv_blk), 0)
        kv_pos = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_blk, kv_blk), 1)
        mask = (kv_pos < skv_real) & (q_pos < sq_real)
        if causal:
            mask &= kv_pos <= q_pos
        if window > 0:
            mask &= kv_pos > q_pos - window
        mask = jnp.broadcast_to(mask[None], (g, q_blk, kv_blk)).reshape(
            g * q_blk, kv_blk)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = ml_ref[:, 0]                                  # (G·qb,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        ml_ref[:, 1] = ml_ref[:, 1] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (G·qb, Dh)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        ml_ref[:, 0] = m_cur

    if live is None:
        _compute()
    else:
        pl.when(live)(_compute)

    # finalize on the last kv block this q block visits
    if causal:
        last_ki = jnp.minimum(n_kv - 1, (q_start + q_blk - 1) // kv_blk)
    else:
        last_ki = n_kv - 1

    @pl.when(ki == last_ki)
    def _finalize():
        l = jnp.maximum(ml_ref[:, 1], 1e-30)
        out = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        o_ref[0] = out.reshape(g, q_blk, o_ref.shape[-1])


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_blk", "kv_blk", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           q_blk: int = 128, kv_blk: int = 128,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q (B,Sq,H,Dh); k,v (B,Skv,Hk,Dh) -> (B,Sq,H,Dh)."""
    b, sq, h, dh = q.shape
    skv, hk = k.shape[1], k.shape[2]
    g = h // hk
    q_blk = min(q_blk, max(8, sq))
    kv_blk = min(kv_blk, max(8, skv))
    pq, pkv = (-sq) % q_blk, (-skv) % kv_blk

    # (B,S,H,Dh) -> (B·Hk, G, S, Dh)
    qr = q.transpose(0, 2, 1, 3).reshape(b, hk, g, sq, dh)
    qr = qr.reshape(b * hk, g, sq, dh)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hk, skv, dh)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hk, skv, dh)
    if pq:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pkv:
        kr = jnp.pad(kr, ((0, 0), (0, pkv), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, pkv), (0, 0)))
    n_q = (sq + pq) // q_blk
    n_kv = (skv + pkv) // kv_blk

    kernel = functools.partial(
        _flash_kernel, q_blk=q_blk, kv_blk=kv_blk, n_kv=n_kv, g=g,
        causal=causal, window=window, sq_real=sq, skv_real=skv)

    out = pl.pallas_call(
        kernel,
        grid=(b * hk, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, g, q_blk, dh), lambda bh, qi, ki: (bh, 0, qi, 0)),
            pl.BlockSpec((1, kv_blk, dh), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, kv_blk, dh), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, q_blk, dh),
                               lambda bh, qi, ki: (bh, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hk, g, sq + pq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * q_blk, dh), jnp.float32),
            pltpu.VMEM((g * q_blk, 2), jnp.float32),   # fused (m, l) stats
        ],
        interpret=default_interpret() if interpret is None else interpret,
    )(qr, kr, vr)

    out = out[:, :, :sq].reshape(b, hk, g, sq, dh).reshape(b, h, sq, dh)
    return out.transpose(0, 2, 1, 3)


def vmem_bytes(q_blk: int, kv_blk: int, g: int, dh: int,
               dtype_bytes: int = 2) -> int:
    """Static VMEM footprint of one grid step (block inputs + scratch)."""
    blocks = (g * q_blk * dh + 2 * kv_blk * dh + g * q_blk * dh) * dtype_bytes
    scratch = (g * q_blk * dh + g * q_blk * 2) * 4   # acc + fused (m, l)
    return blocks + scratch


# Tuned (q_blk, kv_blk) choices keyed (Dh, G): bigger kv blocks when the
# per-row footprint is small, shrinking as G·Dh grows so q + kv blocks +
# scratch stay inside the ~12 MB VMEM budget (see vmem_bytes).
_TUNED_BLOCKS = {
    (64, 1): (128, 256), (64, 2): (128, 256), (64, 4): (128, 128),
    (64, 8): (64, 128), (128, 1): (128, 256), (128, 2): (128, 128),
    (128, 4): (64, 128), (128, 8): (32, 128), (256, 1): (64, 128),
    (256, 2): (32, 128), (256, 4): (32, 64),
}


def tuned_flash_blocks(dh: int, g: int) -> tuple:
    """(q_blk, kv_blk) for a (Dh, G) head layout (fallback: 128/128)."""
    return _TUNED_BLOCKS.get((dh, g), (128, 128))
