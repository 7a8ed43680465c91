"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU): shape and
dtype sweeps per kernel, as required for every kernel in kernels/."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.rglru_scan import rglru_scan_pallas

FLASH_SWEEP = [
    # (b, sq, skv, h, hk, dh, causal, window, qblk, kvblk)
    (1, 64, 64, 2, 1, 64, True, 0, 32, 32),
    (2, 128, 128, 4, 2, 64, True, 0, 64, 64),
    (1, 96, 96, 8, 8, 128, True, 0, 32, 32),
    (1, 100, 100, 4, 2, 64, True, 0, 32, 32),     # non-multiple seq
    (1, 128, 128, 4, 2, 64, True, 48, 64, 64),    # sliding window
    (1, 64, 64, 2, 2, 64, False, 0, 32, 32),      # non-causal
    (1, 64, 64, 4, 1, 256, True, 0, 32, 32),      # big head dim (MQA)
]


@pytest.mark.parametrize("case", FLASH_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_vs_oracle(case, dtype):
    b, sq, skv, h, hk, dh, causal, window, qb, kb = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, skv, hk, dh), dtype)
    v = jax.random.normal(ks[2], (b, skv, hk, dh), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_blk=qb, kv_blk=kb, interpret=True)
    oracle = ref.flash_attention_ref(q.astype(jnp.float32),
                                     k.astype(jnp.float32),
                                     v.astype(jnp.float32),
                                     causal=causal, window=window,
                                     q_chunk=qb, kv_chunk=kb)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle), rtol=tol, atol=tol)


PAGED_SWEEP = [
    # (b, h, hk, dh, page, max_pages, n_pool, window)
    (2, 4, 2, 64, 8, 4, 16, 0),
    (3, 8, 1, 128, 16, 3, 8, 0),
    (2, 4, 4, 64, 8, 5, 32, 20),
    (1, 16, 2, 64, 8, 8, 64, 0),
    (4, 2, 2, 128, 32, 2, 8, 0),
    (3, 56, 8, 128, 16, 6, 40, 0),       # GQA group 7, eight KV heads
    (2, 16, 1, 256, 8, 5, 16, 20),       # group 16, MQA, head dim 256
    (4, 8, 8, 128, 8, 7, 40, 0),         # group 1 (MHA)
    (3, 32, 4, 128, 16, 5, 24, 24),      # group 8, window inside a page
]


@pytest.mark.parametrize("case", PAGED_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_vs_oracle(case, dtype):
    b, h, hk, dh, page, maxp, npool, win = case
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (b, h, dh), dtype)
    kp = jax.random.normal(ks[1], (npool, page, hk, dh), dtype)
    vp = jax.random.normal(ks[2], (npool, page, hk, dh), dtype)
    pt = jax.random.randint(ks[3], (b, maxp), 0, npool)
    lens = jax.random.randint(ks[4], (b,), 1, maxp * page + 1)
    out = paged_decode_attention(q, kp, vp, pt, lens, window=win,
                                 interpret=True)
    oracle = ref.paged_decode_attention_ref(
        q.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32), pt, lens, window=win)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle), rtol=tol, atol=tol)


RGLRU_SWEEP = [
    (1, 32, 128, 16, 128), (2, 100, 256, 32, 128), (3, 17, 128, 8, 128),
    (1, 257, 512, 64, 256),
]


@pytest.mark.parametrize("case", RGLRU_SWEEP)
def test_rglru_kernel_vs_oracle(case):
    b, s, dr, sblk, dblk = case
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, s, dr)))
    bb = jax.random.normal(ks[1], (b, s, dr))
    h0 = jax.random.normal(ks[2], (b, dr))
    out = rglru_scan_pallas(a, bb, h0, s_blk=sblk, d_blk=dblk,
                            interpret=True)
    oracle = ref.rglru_scan_ref(a, bb, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-4, atol=1e-5)


def test_ops_routing_falls_back_on_unaligned():
    """head dim 24 is not TPU-tileable -> jnp path, still correct."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 16, 2, 24))
    k = jax.random.normal(ks[1], (1, 16, 1, 24))
    v = jax.random.normal(ks[2], (1, 16, 1, 24))
    out = ops.flash_attention(q, k, v, causal=True, q_chunk=8, kv_chunk=8)
    oracle = ref.flash_attention_ref(q, k, v, causal=True, q_chunk=8,
                                     kv_chunk=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-6)


def test_ops_routing_uses_pallas_on_aligned():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 64))
    k = jax.random.normal(ks[1], (1, 64, 1, 64))
    v = jax.random.normal(ks[2], (1, 64, 1, 64))
    out = ops.flash_attention(q, k, v, causal=True)
    oracle = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-4, atol=1e-5)


def test_vmem_budget_static():
    from repro.kernels.flash_attention import vmem_bytes
    # default tiling must fit a v5e 16 MB VMEM comfortably for every
    # assigned head layout
    for g, dh in [(1, 64), (2, 128), (4, 256), (16, 128), (32, 64)]:
        assert vmem_bytes(128, 128, g, dh) < 12 * 2 ** 20, (g, dh)
