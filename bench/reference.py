"""The plain reference of the dense-GQA decoder (Yi's block): RMSNorm,
rotary embedding (half-split rotation, Llama convention), grouped-query
causal attention, SwiGLU MLP, untied head.  float32 throughout, with
every matrix product at ``precision="highest"``.

It reads the benchmark's own weights (``bench.model.make_params``) and
imports nothing of the program.  It runs one sequence at a time and one
layer at a time, upcasting that layer's weights inside the call, so that
it fits beside the served weights on the chip.

``kind`` selects the precision of the matrix products: ``float32`` is the
reference; ``int8`` and ``fp8`` round both operands of every product
(per row of the activations, per output column of the weights, scaled to
the type's largest value) and serve as the control that a comparison has
to catch.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from bench.model import sizes

KINDS = ("float32", "int8", "fp8")
BUCKET = 1024           # sequences are padded to a multiple of this, and
ROWS = 128              # the rows read to a multiple of this, so that a
                        # handful of programs serve every request
Q_BLOCK = 512           # query rows per attention block


def _quant(x, axis: int, kind: str):
    import jax.numpy as jnp
    if kind == "float32":
        return x
    top = {"int8": 127.0, "fp8": 448.0}[kind]
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if kind == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, kind: str):
    """a (S, K) @ w (K, N), both operands rounded to ``kind``."""
    import jax
    import jax.numpy as jnp
    return jnp.dot(_quant(a, -1, kind), _quant(w.astype(jnp.float32), 0,
                                                kind),
                   precision=jax.lax.Precision.HIGHEST)


def _rms(x, gain_offset, eps: float):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gain_offset.astype(
        jnp.float32))


def _rope(x, theta: float):
    """x (S, n, Dh), positions 0..S-1."""
    import jax.numpy as jnp
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal grouped-query attention; q (S, H, Dh), k/v (S, Hk, Dh)."""
    import jax
    import jax.numpy as jnp
    s, h, dh = q.shape
    hk = k.shape[1]
    qg = q.reshape(s, hk, h // hk, dh)
    outs = []
    for i0 in range(0, s, Q_BLOCK):
        i1 = min(s, i0 + Q_BLOCK)
        sc = jnp.einsum("qhgd,khd->hgqk", qg[i0:i1], k[:i1],
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(dh)
        mask = np.arange(i1)[None, :] <= np.arange(i0, i1)[:, None]
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hgqk,khd->qhgd", p, v[:i1],
                               precision=jax.lax.Precision.HIGHEST))
    return jnp.concatenate(outs, 0).reshape(s, h * dh)


@functools.lru_cache(maxsize=None)
def _layer_fn(dims: tuple, kind: str):
    import jax
    h, hk, dh, theta, eps = dims

    def layer(x, scan, i):
        w = jax.tree.map(lambda a: a[i], scan)
        s = x.shape[0]
        a = _rms(x, w["ln1"], eps)
        q = _rope(_mm(a, w["wq"], kind).reshape(s, h, dh), theta)
        k = _rope(_mm(a, w["wk"], kind).reshape(s, hk, dh), theta)
        v = _mm(a, w["wv"], kind).reshape(s, hk, dh)
        x = x + _mm(_attention(q, k, v), w["wo"], kind)
        b = _rms(x, w["ln2"], eps)
        g = jax.nn.silu(_mm(b, w["wg"], kind)) * _mm(b, w["wu"], kind)
        return x + _mm(g, w["wd"], kind)
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, kind: str):
    import jax

    def head(x, rows, final_norm, untok):
        return _mm(_rms(x[rows], final_norm, eps), untok.T, kind)
    return jax.jit(head)


@functools.lru_cache(maxsize=None)
def _embed_fn():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda tok, ids: tok[ids].astype(jnp.float32))


class Reference:
    def __init__(self, conf: dict, params):
        self.s = sizes(conf)
        self.params = params

    def logits(self, tokens, rows, kind: str = "float32"):
        """Logits float32 on the device at positions ``rows`` of the
        sequence ``tokens``: (len(rows) rounded up to ``ROWS``, V), the
        padding rows repeating the last position."""
        import jax.numpy as jnp
        s = self.s
        n = len(tokens)
        padded = np.zeros(-(-n // BUCKET) * BUCKET, np.int32)
        padded[:n] = tokens
        rows = np.asarray(rows, np.int32)
        rows = np.concatenate([rows, np.full(-len(rows) % ROWS, rows[-1],
                                             np.int32)])
        p = self.params
        x = _embed_fn()(p["embed"]["tok"], jnp.asarray(padded))
        layer = _layer_fn((s["h"], s["hk"], s["dh"], s["theta"], s["eps"]),
                          kind)
        scan = p["scan"][0]
        for i in range(s["layers"]):
            x = layer(x, scan, jnp.int32(i))
        return _head_fn(s["eps"], kind)(
            x, jnp.asarray(rows), p["final_norm"], p["embed"]["untok"])
