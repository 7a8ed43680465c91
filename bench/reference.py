"""The plain reference of a served model: the embedding, the block's layers
(``bench/blocks/<block>.py``'s ``reference_layers``), the final RMSNorm
and an untied head.  float32 throughout, with every matrix product at
``precision="highest"``.

It reads the benchmark's own weights (``bench.model.make_params``) and
imports nothing of the program.  It runs one sequence at a time and one
layer at a time on one device (the lowest-numbered one that holds the
embedding), copying that layer's weights there from whichever device
holds them and upcasting them inside the call, so that it fits beside
the served weights on the chip.

``kind`` selects the precision of the matrix products: ``float32`` is the
reference; ``int8`` and ``fp8`` round both operands of every product
(per row of the activations, per output column of the weights, scaled to
the type's largest value) and serve as the control that a comparison has
to catch.
"""

from __future__ import annotations

import functools

import numpy as np

KINDS = ("float32", "int8", "fp8")
BUCKET = 1024           # sequences are padded to a multiple of this, and
ROWS = 128              # the rows read to a multiple of this, so that a
                        # handful of programs serve every request


def quant(x, axis: int, kind: str):
    import jax.numpy as jnp
    if kind == "float32":
        return x
    top = {"int8": 127.0, "fp8": 448.0}[kind]
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if kind == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a, w, kind: str):
    """a (S, K) @ w (K, N), both operands rounded to ``kind``."""
    import jax
    import jax.numpy as jnp
    return jnp.dot(quant(a, -1, kind), quant(w.astype(jnp.float32), 0,
                                              kind),
                   precision=jax.lax.Precision.HIGHEST)


def rms(x, gain_offset, eps: float):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gain_offset.astype(
        jnp.float32))


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, kind: str):
    import jax

    def head(x, rows, final_norm, untok):
        return mm(rms(x[rows], final_norm, eps), untok.T, kind)
    return jax.jit(head)


@functools.lru_cache(maxsize=None)
def _embed_fn():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda tok, ids: tok[ids].astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _take_fn():
    import jax
    return jax.jit(lambda a, i: a[i])


def _shards(a, device) -> list:
    """``a``'s shards, the one on ``device`` first."""
    return sorted(a.addressable_shards, key=lambda sh: sh.device != device)


def whole_on(a, device):
    """``a`` whole on ``device``: itself where it lives there alone, its
    replica there, or a copy of a replica."""
    import jax
    if a.sharding.device_set == {device}:
        return a
    if not a.sharding.is_fully_replicated:
        raise ValueError(f"a leaf of shape {a.shape} is split over devices;"
                         " only stacked leaves may be")
    return jax.device_put(_shards(a, device)[0].data, device)


def row_on(a, i: int, device):
    """Row ``i`` of the stacked leaf ``a`` on ``device``, sliced out of the
    shard that holds it."""
    import jax
    import jax.numpy as jnp
    for sh in _shards(a, device):
        lo, hi, _ = sh.index[0].indices(a.shape[0])
        if lo <= i < hi:
            return jax.device_put(_take_fn()(sh.data, jnp.int32(i - lo)),
                                  device)
    raise IndexError(f"no shard holds row {i} of a leaf of shape {a.shape}")


class Reference:
    def __init__(self, block, conf: dict, params):
        self.block, self.conf, self.params = block, conf, params
        tok = params["embed"]["tok"]
        self.device = min(tok.sharding.device_set, key=lambda d: d.id)
        self.tok = whole_on(tok, self.device)
        self.untok = whole_on(params["embed"]["untok"], self.device)
        self.final_norm = whole_on(params["final_norm"], self.device)
        self.eps = float(conf["rms_norm_eps"])

    def row(self, leaf, i: int):
        return row_on(leaf, i, self.device)

    def logits(self, tokens, rows, kind: str = "float32"):
        """Logits float32 on the device at positions ``rows`` of the
        sequence ``tokens``: (len(rows) rounded up to ``ROWS``, V), the
        padding rows repeating the last position."""
        import jax
        n = len(tokens)
        padded = np.zeros(-(-n // BUCKET) * BUCKET, np.int32)
        padded[:n] = tokens
        rows = np.asarray(rows, np.int32)
        rows = np.concatenate([rows, np.full(-len(rows) % ROWS, rows[-1],
                                             np.int32)])
        x = _embed_fn()(self.tok, jax.device_put(padded, self.device))
        x = self.block.reference_layers(self.conf, self.params, x, kind,
                                        self.row)
        return _head_fn(self.eps, kind)(
            x, jax.device_put(rows, self.device), self.final_norm,
            self.untok)
