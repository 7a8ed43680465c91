"""The dense-GQA block (Yi's decoder layer): RMSNorm, rotary embedding
(half-split rotation, Llama convention), grouped-query causal attention,
SwiGLU MLP; the model's head is untied.

Everything the benchmark knows of this block is here, under the names the
harness calls:

- ``program_config(conf)``: the program's ``ModelConfig`` for a
  configuration file;
- ``param_shapes(conf)``: (shape, fan-in or None for a norm gain) of every
  leaf, in the program's parameter layout (``{"embed": {"tok", "untok"},
  "final_norm", "scan": [one period, each leaf stacked over the layers],
  "tail": []}``; each RMSNorm gain is stored as its offset from 1);
- ``reference_layers(conf, params, x, kind, row)``: the plain float32
  reference over every layer, one layer at a time on one device;
- ``decode_token_flops``, ``prefill_flops`` and ``paged_decode_calls``:
  the operations and bytes the block needs, from its sizes and the live
  lengths.  A multiply-add counts as two operations.  Work that the
  program does but the algorithm does not need (padded rows, logits of a
  chunk that is not a prompt's last, masked cache positions) is not
  counted.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from bench.reference import mm, rms

Q_BLOCK = 512           # query rows per attention block of the reference


def sizes(conf: dict) -> dict:
    """Plain sizes of a dense-GQA configuration file."""
    h = int(conf["num_attention_heads"])
    d = int(conf["hidden_size"])
    return dict(
        d=d, f=int(conf["intermediate_size"]), h=h,
        hk=int(conf["num_key_value_heads"]),
        dh=int(conf.get("head_dim") or d // h),
        v=int(conf["vocab_size"]), layers=int(conf["num_hidden_layers"]),
        theta=float(conf["rope_theta"]), eps=float(conf["rms_norm_eps"]),
        dtype=conf["torch_dtype"])


def program_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.config import ModelConfig
    s = sizes(conf)
    if conf.get("tie_word_embeddings"):
        raise ValueError("the reference serves untied heads only")
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=s["layers"],
        d_model=s["d"], num_heads=s["h"], num_kv_heads=s["hk"],
        head_dim=s["dh"], d_ff=s["f"], vocab_size=s["v"],
        block_pattern=("attn",), rope_theta=s["theta"], norm_eps=s["eps"],
        max_position_embeddings=int(conf["max_position_embeddings"]),
        source=conf["source"])


def param_shapes(conf: dict) -> dict:
    """(shape, fan-in or None for a norm gain) of every leaf."""
    s = sizes(conf)
    d, f, v, n = s["d"], s["f"], s["v"], s["layers"]
    q, kv = s["h"] * s["dh"], s["hk"] * s["dh"]
    layer = {
        "ln1": ((n, d), None), "wq": ((n, d, q), d), "wk": ((n, d, kv), d),
        "wv": ((n, d, kv), d), "wo": ((n, q, d), q), "ln2": ((n, d), None),
        "wg": ((n, d, f), d), "wu": ((n, d, f), d), "wd": ((n, f, d), f),
    }
    return {"embed": {"tok": ((v, d), d), "untok": ((v, d), d)},
            "final_norm": ((d,), None), "scan": [layer], "tail": []}


def matmul_params_per_layer(conf: dict) -> int:
    s = sizes(conf)
    q, kv = s["h"] * s["dh"], s["hk"] * s["dh"]
    return s["d"] * (q + 2 * kv) + q * s["d"] + 3 * s["d"] * s["f"]


# -- the reference ------------------------------------------------------------


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

    def layer(x, w):
        s = x.shape[0]
        a = rms(x, w["ln1"], eps)
        q = _rope(mm(a, w["wq"], kind).reshape(s, h, dh), theta)
        k = _rope(mm(a, w["wk"], kind).reshape(s, hk, dh), theta)
        v = mm(a, w["wv"], kind).reshape(s, hk, dh)
        x = x + mm(_attention(q, k, v), w["wo"], kind)
        b = rms(x, w["ln2"], eps)
        g = jax.nn.silu(mm(b, w["wg"], kind)) * mm(b, w["wu"], kind)
        return x + mm(g, w["wd"], kind)
    return jax.jit(layer)


def reference_layers(conf: dict, params, x, kind: str, row):
    """The embedded sequence ``x`` (S, D) float32 through every layer.
    ``row(leaf, i)`` is layer ``i``'s slice of a stacked leaf on the
    reference's device."""
    s = sizes(conf)
    layer = _layer_fn((s["h"], s["hk"], s["dh"], s["theta"], s["eps"]),
                      kind)
    scan = params["scan"][0]
    for i in range(s["layers"]):
        x = layer(x, {k: row(v, i) for k, v in scan.items()})
    return x


# -- operations and bytes -----------------------------------------------------


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


def paged_decode_calls(conf: dict, lens, layers: int) -> list:
    """(FLOPs, bytes, number of calls) for the paged-kernel calls that one
    microbatch's decode over ``layers`` layers makes: every layer of this
    block runs the kernel once over the same rows."""
    return [paged_decode_call(conf, lens) + (layers,)] if layers else []
