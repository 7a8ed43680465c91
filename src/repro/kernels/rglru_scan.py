"""Pallas TPU kernel for the RG-LRU linear recurrence  h_t = a_t·h_{t-1} + b_t.

The recurrence is bandwidth-bound (2 loads + 1 store per element, 2 FLOPs),
so the kernel's job is streaming: tile (S, Dr) into (s_blk, d_blk) VMEM
blocks, carry ``h`` across sequence blocks in VMEM scratch, and let the VPU
process ``d_blk`` lanes per time step.  Grid = (B, n_d, n_s) with the
sequence dimension innermost (sequential on TPU, carries the scratch).

Oracle: ``repro.models.rglru.rglru_scan`` (associative_scan form).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, carry_ref, *, s_blk: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        carry_ref[...] = h0_ref[...]

    def body(i, _):
        h = a_ref[0, i] * carry_ref[0] + b_ref[0, i]
        carry_ref[0] = h
        o_ref[0, i] = h
        return 0

    jax.lax.fori_loop(0, s_blk, body, 0)


@functools.partial(jax.jit, static_argnames=("s_blk", "d_blk", "interpret"))
def rglru_scan_pallas(a: jax.Array, b: jax.Array, h0: jax.Array, *,
                      s_blk: int = 256, d_blk: int = 512,
                      interpret: Optional[bool] = None) -> jax.Array:
    """a, b (B, S, Dr) f32; h0 (B, Dr) f32 -> all h_t (B, S, Dr) f32."""
    bsz, s, dr = a.shape
    s_blk = min(s_blk, s)
    d_blk = min(d_blk, dr)
    ps, pd = (-s) % s_blk, (-dr) % d_blk
    if ps or pd:
        a = jnp.pad(a, ((0, 0), (0, ps), (0, pd)))
        b = jnp.pad(b, ((0, 0), (0, ps), (0, pd)))
        h0 = jnp.pad(h0, ((0, 0), (0, pd)))
    n_s = (s + ps) // s_blk
    n_d = (dr + pd) // d_blk

    out = pl.pallas_call(
        functools.partial(_rglru_kernel, s_blk=s_blk),
        grid=(bsz, n_d, n_s),
        in_specs=[
            pl.BlockSpec((1, s_blk, d_blk), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, s_blk, d_blk), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, d_blk), lambda bi, di, si: (bi, di)),
        ],
        out_specs=pl.BlockSpec((1, s_blk, d_blk),
                               lambda bi, di, si: (bi, si, di)),
        out_shape=jax.ShapeDtypeStruct((bsz, s + ps, dr + pd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, d_blk), jnp.float32)],
        interpret=default_interpret() if interpret is None else interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32), h0.astype(jnp.float32))
    return out[:, :s, :dr]
