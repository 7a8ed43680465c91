"""Model assembly: init, train forward, prefill, decode — for every arch.

The network is described by ``cfg.block_pattern`` tiled over ``num_layers``
(see ``repro.config``).  Execution scans over *pattern periods* with weights
stacked over periods (HLO size O(period), not O(depth)); the remainder
("tail") layers are unrolled.  The same layer-apply code serves four modes:

  train    — full-sequence forward, no caches, chunked CE loss
  prefill  — full-sequence forward, caches written
  decode   — single-token step against caches
  stage    — a contiguous slice of periods (used by the PP pipeline)

Caches are plain pytrees:
  attention kinds:  {"k": (B,C,Hk,Dh), "v": ..., "pos": (B,C) int32 (-1 empty)}
  rglru:            {"h": (B,Dr) f32, "conv": (B,cw-1,Dr)}
  mlstm:            {"c": (B,H,dh,dh) f32, "n": (B,H,dh) f32, "m": (B,H) f32}
  slstm:            {"c","n","h","m": (B,Dr) f32}
arranged as {"scan": [per-period-position, leading axis n_periods], "tail": [...]}.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ATTN_KINDS, RECURRENT_KINDS, ModelConfig
from repro.models import attention as attn_lib
from repro.models import embedding as embed_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import xlstm as xlstm_lib
from repro.models.common import (DEFAULT_RUNTIME, KeyGen, LayerPlan, Runtime,
                                 dense_init, make_layer_plan, patch_positions3,
                                 rms_norm, swiglu, text_positions3)

LOCAL_ROPE_THETA = 10000.0      # gemma3: local layers keep the small base


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def _init_attn_layer(kg: KeyGen, cfg: ModelConfig, rt: Runtime) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    H, Hk, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pd = rt.param_dtype
    w = {
        "ln1": jnp.zeros((D,), pd),
        "wq": dense_init(kg(), (D, H * Dh), pd),
        "wk": dense_init(kg(), (D, Hk * Dh), pd),
        "wv": dense_init(kg(), (D, Hk * Dh), pd),
        "wo": dense_init(kg(), (H * Dh, D), pd, fan_in=H * Dh),
    }
    if cfg.use_qk_norm:
        w["q_norm"] = jnp.zeros((Dh,), pd)
        w["k_norm"] = jnp.zeros((Dh,), pd)
    if cfg.moe is not None:
        E, dE = cfg.moe.num_experts, cfg.moe.d_expert
        w["ln2"] = jnp.zeros((D,), pd)
        w["moe"] = {
            "router": dense_init(kg(), (D, E), jnp.float32),
            "wg": dense_init(kg(), (E, D, dE), pd, fan_in=D),
            "wu": dense_init(kg(), (E, D, dE), pd, fan_in=D),
            "wd": dense_init(kg(), (E, dE, D), pd, fan_in=dE),
        }
    elif F > 0:
        w["ln2"] = jnp.zeros((D,), pd)
        w["wg"] = dense_init(kg(), (D, F), pd)
        w["wu"] = dense_init(kg(), (D, F), pd)
        w["wd"] = dense_init(kg(), (F, D), pd, fan_in=F)
    return w


def _init_rglru_layer(kg: KeyGen, cfg: ModelConfig, rt: Runtime) -> dict:
    D, F, Dr = cfg.d_model, cfg.d_ff, cfg.d_rnn
    H = cfg.num_heads
    dh = Dr // H
    pd = rt.param_dtype
    # RG-LRU Lambda init: a in [0.9, 0.999] -> lam = softplus^{-1}(-log(a)/c)
    a = np.random.RandomState(0).uniform(0.9, 0.999, (Dr,))
    lam = np.log(np.expm1(-np.log(a) / rglru_lib.RGLRU_C))
    w = {
        "ln1": jnp.zeros((D,), pd),
        "wg": dense_init(kg(), (D, Dr), pd),
        "wx": dense_init(kg(), (D, Dr), pd),
        "conv_w": dense_init(kg(), (cfg.conv_width, Dr), pd, fan_in=cfg.conv_width),
        "conv_b": jnp.zeros((Dr,), pd),
        "gate_a_w": dense_init(kg(), (H, dh, dh), pd, fan_in=dh),
        "gate_a_b": jnp.zeros((Dr,), jnp.float32),
        "gate_x_w": dense_init(kg(), (H, dh, dh), pd, fan_in=dh),
        "gate_x_b": jnp.zeros((Dr,), jnp.float32),
        "lam": jnp.asarray(lam, jnp.float32),
        "wo": dense_init(kg(), (Dr, D), pd, fan_in=Dr),
    }
    if F > 0:
        w["ln2"] = jnp.zeros((D,), pd)
        w["wg_mlp"] = dense_init(kg(), (D, F), pd)
        w["wu"] = dense_init(kg(), (D, F), pd)
        w["wd"] = dense_init(kg(), (F, D), pd, fan_in=F)
    return w


def _init_mlstm_layer(kg: KeyGen, cfg: ModelConfig, rt: Runtime) -> dict:
    D, Dr, H = cfg.d_model, cfg.d_rnn, cfg.num_heads
    dh = Dr // H
    pd = rt.param_dtype
    return {
        "ln1": jnp.zeros((D,), pd),
        "wm": dense_init(kg(), (D, Dr), pd),
        "wz": dense_init(kg(), (D, Dr), pd),
        "wq": dense_init(kg(), (H, dh, dh), pd, fan_in=dh),
        "wk": dense_init(kg(), (H, dh, dh), pd, fan_in=dh),
        "wv": dense_init(kg(), (H, dh, dh), pd, fan_in=dh),
        "w_i": dense_init(kg(), (H, dh), jnp.float32, fan_in=dh),
        "b_i": jnp.zeros((H,), jnp.float32),
        "w_f": dense_init(kg(), (H, dh), jnp.float32, fan_in=dh),
        # positive forget bias: start remembering (xLSTM init, 3..6 per head)
        "b_f": jnp.linspace(3.0, 6.0, H, dtype=jnp.float32),
        "wo": dense_init(kg(), (Dr, D), pd, fan_in=Dr),
    }


def _init_slstm_layer(kg: KeyGen, cfg: ModelConfig, rt: Runtime) -> dict:
    D, Dr, H = cfg.d_model, cfg.d_rnn, cfg.num_heads
    dh = Dr // H
    pd = rt.param_dtype
    b_in = np.zeros((4, Dr), np.float32)
    b_in[1] = 3.0                       # forget-gate positive bias
    return {
        "ln1": jnp.zeros((D,), pd),
        "w_in": dense_init(kg(), (4, D, Dr), pd, fan_in=D),
        "b_in": jnp.asarray(b_in),
        "r": dense_init(kg(), (4, H, dh, dh), pd, fan_in=dh),
        "wo": dense_init(kg(), (Dr, D), pd, fan_in=Dr),
    }


_KIND_INIT = {
    "attn": _init_attn_layer, "local": _init_attn_layer,
    "global": _init_attn_layer, "rglru": _init_rglru_layer,
    "mlstm": _init_mlstm_layer, "slstm": _init_slstm_layer,
}


def init_params(cfg: ModelConfig, key: jax.Array,
                rt: Runtime = DEFAULT_RUNTIME, *, shardings=None) -> dict:
    """Random parameters from ``key``.

    One jit builds every leaf in place: the scanned periods come from a
    vmap over per-period keys, so each stacked leaf is written once rather
    than built layer by layer and then copied by a stack (a transient of
    twice the weights).  ``shardings`` — a function from the parameter
    shape tree to a sharding tree — places each leaf as it is made (the
    pipelined backend's per-stage layout); None keeps the default
    device."""
    if shardings is None:
        return _init_params_jit(cfg, rt)(key)
    build = functools.partial(_init_params, cfg, rt=rt)
    return jax.jit(build, out_shardings=shardings(
        jax.eval_shape(build, key)))(key)


@functools.lru_cache(maxsize=None)
def _init_params_jit(cfg: ModelConfig, rt: Runtime):
    return jax.jit(functools.partial(_init_params, cfg, rt=rt))


def _init_params(cfg: ModelConfig, key: jax.Array, rt: Runtime) -> dict:
    kg = KeyGen(key)
    pd = rt.param_dtype
    plan = make_layer_plan(cfg.num_layers, cfg.block_pattern)
    params: dict = {
        "embed": {"tok": dense_init(kg(), (cfg.vocab_size, cfg.d_model), pd,
                                    fan_in=cfg.d_model)},
        "final_norm": jnp.zeros((cfg.d_model,), pd),
    }
    if cfg.frontend == "audio_frames":
        params["embed"]["frame_proj"] = dense_init(
            kg(), (cfg.d_model, cfg.d_model), pd)
    elif cfg.frontend == "vision_patches":
        params["embed"]["patch_proj"] = dense_init(
            kg(), (cfg.d_model, cfg.d_model), pd)
    if not cfg.tie_embeddings:
        params["embed"]["untok"] = dense_init(
            kg(), (cfg.vocab_size, cfg.d_model), pd, fan_in=cfg.d_model)

    def init_period(draw):
        return [_KIND_INIT[k](draw, cfg, rt) for k in plan.period_kinds]

    params["scan"] = []
    if plan.n_periods:
        # each period replays the keys a layer-by-layer build draws from
        # ``kg``, so a seed gives the same weights however they are built;
        # list over period positions, each leaf stacked over n_periods
        n_draws = _count_draws(init_period)
        keys = jnp.stack([jnp.stack([kg() for _ in range(n_draws)])
                          for _ in range(plan.n_periods)])
        params["scan"] = jax.vmap(lambda ks: init_period(_Replay(ks)))(keys)
    params["tail"] = [_KIND_INIT[k](kg, cfg, rt) for k in plan.tail_kinds]
    return params


class _Replay:
    """A ``KeyGen`` stand-in that hands out the given keys in order."""

    def __init__(self, keys: jax.Array):
        self._keys, self._i = keys, 0

    def __call__(self) -> jax.Array:
        self._i += 1
        return self._keys[self._i - 1]


def _count_draws(init) -> int:
    """How many keys ``init(draw)`` takes from its dispenser."""
    n = 0

    def draw():
        nonlocal n
        n += 1
        return jax.random.PRNGKey(0)
    jax.eval_shape(lambda: init(draw))
    return n


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def _kind_cache(kind: str, cfg: ModelConfig, batch: int, capacity: int,
                rt: Runtime, lead: tuple = ()):
    cd = rt.compute_dtype
    Hk, Dh, Dr, H = cfg.num_kv_heads, cfg.head_dim, cfg.d_rnn, cfg.num_heads
    if kind in ATTN_KINDS:
        c = capacity if (kind != "local" or cfg.window_size == 0) else min(
            cfg.window_size, capacity)
        if rt.kv_dtype == "int8":
            # symmetric per-(token, head) quantization; halves the KV read
            # traffic that dominates the decode roofline (SPerf)
            return {
                "k": jnp.zeros(lead + (batch, c, Hk, Dh), jnp.int8),
                "v": jnp.zeros(lead + (batch, c, Hk, Dh), jnp.int8),
                "k_scale": jnp.zeros(lead + (batch, c, Hk), jnp.bfloat16),
                "v_scale": jnp.zeros(lead + (batch, c, Hk), jnp.bfloat16),
                "pos": jnp.full(lead + (batch, c), -1, jnp.int32),
            }
        return {
            "k": jnp.zeros(lead + (batch, c, Hk, Dh), cd),
            "v": jnp.zeros(lead + (batch, c, Hk, Dh), cd),
            "pos": jnp.full(lead + (batch, c), -1, jnp.int32),
        }
    if kind == "rglru":
        return {
            "h": jnp.zeros(lead + (batch, Dr), jnp.float32),
            "conv": jnp.zeros(lead + (batch, cfg.conv_width - 1, Dr), cd),
        }
    if kind == "mlstm":
        dh = Dr // H
        return {
            "c": jnp.zeros(lead + (batch, H, dh, dh), jnp.float32),
            "n": jnp.zeros(lead + (batch, H, dh), jnp.float32),
            "m": jnp.zeros(lead + (batch, H), jnp.float32),
        }
    if kind == "slstm":
        return {
            "c": jnp.zeros(lead + (batch, Dr), jnp.float32),
            "n": jnp.full(lead + (batch, Dr), 1e-6, jnp.float32),
            "h": jnp.zeros(lead + (batch, Dr), jnp.float32),
            "m": jnp.zeros(lead + (batch, Dr), jnp.float32),
        }
    raise ValueError(kind)


def init_caches(cfg: ModelConfig, batch: int, capacity: int,
                rt: Runtime = DEFAULT_RUNTIME) -> dict:
    plan = make_layer_plan(cfg.num_layers, cfg.block_pattern)
    scan = [_kind_cache(k, cfg, batch, capacity, rt, lead=(plan.n_periods,))
            for k in plan.period_kinds] if plan.n_periods else []
    tail = [_kind_cache(k, cfg, batch, capacity, rt)
            for k in plan.tail_kinds]
    return {"scan": scan, "tail": tail}


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------


def _apply_rope(q, k, positions, cfg: ModelConfig, theta: float):
    from repro.models.common import apply_mrope, apply_rope
    if positions.ndim == 3 and cfg.frontend == "vision_patches":
        return (apply_mrope(q, positions, theta),
                apply_mrope(k, positions, theta))
    pos = positions[0] if positions.ndim == 3 else positions
    return (apply_rope(q, pos, theta, cfg.rope_scaling),
            apply_rope(k, pos, theta, cfg.rope_scaling))


def _write_prefill_paged(cache, k, v, positions):
    """Scatter a prefill's k/v into the shared page pool.

    Positions marked ``-1`` (padding) are redirected to an out-of-bounds
    page index, so jit scatter semantics drop the write — pad tokens never
    touch a live page."""
    n_pages, page_size = cache["k_pages"].shape[:2]
    pos = positions.astype(jnp.int32)                     # (B, S)
    valid = pos >= 0
    logical = jnp.maximum(pos, 0) // page_size
    page = jnp.take_along_axis(cache["page_table"], logical, axis=1)
    page = jnp.where(valid, page, n_pages)                # OOB -> dropped
    off = jnp.maximum(pos, 0) % page_size
    return {
        **cache,
        "k_pages": cache["k_pages"].at[page, off].set(k),
        "v_pages": cache["v_pages"].at[page, off].set(v),
    }


def _quantize_kv(x):
    """(..., Hk, Dh) -> (int8 values, bf16 per-(...,Hk) scales)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _dequant_kv(cache, dtype):
    k = cache["k"]
    if k.dtype != jnp.int8:
        return cache["k"], cache["v"]
    kf = k.astype(jnp.float32) * cache["k_scale"].astype(
        jnp.float32)[..., None]
    vf = cache["v"].astype(jnp.float32) * cache["v_scale"].astype(
        jnp.float32)[..., None]
    return kf.astype(dtype), vf.astype(dtype)


def _write_prefill_cache(cache, k, v, positions):
    """Write a full prefill's k/v into a (possibly smaller ring) cache."""
    if "k_pages" in cache:
        return _write_prefill_paged(cache, k, v, positions)
    quant = cache["k"].dtype == jnp.int8
    if quant:
        k, k_s = _quantize_kv(k)
        v, v_s = _quantize_kv(v)
    C = cache["k"].shape[1]
    S = k.shape[1]
    if S <= C:
        out = {
            "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1),
            "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1),
            "pos": jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], positions.astype(jnp.int32), 0, 1),
        }
        if quant:
            out["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k_scale"], k_s, 0, 1)
            out["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
                cache["v_scale"], v_s, 0, 1)
        return out
    # ring: keep the last C tokens at slot = pos % C; pad positions (-1)
    # scatter out of bounds (dropped) instead of clobbering slot C-1
    b = k.shape[0]
    k_t, v_t = k[:, S - C:], v[:, S - C:]
    pos_t = positions[:, S - C:].astype(jnp.int32)
    slot = jnp.where(pos_t >= 0, jnp.maximum(pos_t, 0) % C, C)
    bidx = jnp.arange(b)[:, None]
    out = {
        "k": cache["k"].at[bidx, slot].set(k_t),
        "v": cache["v"].at[bidx, slot].set(v_t),
        "pos": cache["pos"].at[bidx, slot].set(pos_t),
    }
    if quant:
        out["k_scale"] = cache["k_scale"].at[bidx, slot].set(
            k_s[:, S - C:])
        out["v_scale"] = cache["v_scale"].at[bidx, slot].set(
            v_s[:, S - C:])
    return out


def _attn_layer(kind, w, x, cfg: ModelConfig, rt: Runtime, *, positions,
                mode, cache):
    B = x.shape[0]
    H, Hk, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    window = cfg.window_size if kind == "local" else 0
    theta = LOCAL_ROPE_THETA if (kind == "local" and cfg.window_size) else \
        cfg.rope_theta

    h = rms_norm(x, w["ln1"], cfg.norm_eps)
    S = h.shape[1]
    q = jnp.einsum("bsd,de->bse", h, w["wq"]).reshape(B, S, H, Dh)
    k = jnp.einsum("bsd,de->bse", h, w["wk"]).reshape(B, S, Hk, Dh)
    v = jnp.einsum("bsd,de->bse", h, w["wv"]).reshape(B, S, Hk, Dh)
    if cfg.use_qk_norm:
        q = rms_norm(q, w["q_norm"], cfg.norm_eps)
        k = rms_norm(k, w["k_norm"], cfg.norm_eps)
    q, k = _apply_rope(q, k, positions, cfg, theta)

    new_cache = cache
    if mode == "decode":
        cur = positions[0] if positions.ndim == 3 else positions
        cur = cur[:, 0] if cur.ndim == 2 else cur          # (B,)
        if "k_pages" in cache:                             # paged pool path
            from repro.kernels import ops as kops
            page_size = cache["k_pages"].shape[1]
            pos = cur.astype(jnp.int32)
            logical = pos // page_size
            page = jnp.take_along_axis(cache["page_table"],
                                       logical[:, None], axis=1)[:, 0]
            off = pos % page_size
            kp = cache["k_pages"].at[page, off].set(k[:, 0])
            vp = cache["v_pages"].at[page, off].set(v[:, 0])
            new_cache = {**cache, "k_pages": kp, "v_pages": vp}
            out = kops.paged_decode_attention(
                q[:, 0], kp, vp, cache["page_table"], pos + 1, window=window,
                pages_per_block=rt.attn_pages_per_block)
        else:                                              # dense/ring path
            C = cache["k"].shape[1]
            slot = (cur % C).astype(jnp.int32)
            bidx = jnp.arange(B)
            quant = cache["k"].dtype == jnp.int8
            kw, vw = (k[:, 0], v[:, 0])
            if quant:
                kw, k_s = _quantize_kv(kw)
                vw, v_s = _quantize_kv(vw)
            kc = cache["k"].at[bidx, slot].set(kw)
            vc = cache["v"].at[bidx, slot].set(vw)
            pc = cache["pos"].at[bidx, slot].set(cur.astype(jnp.int32))
            new_cache = {"k": kc, "v": vc, "pos": pc}
            if quant:
                new_cache["k_scale"] = cache["k_scale"].at[bidx, slot].set(k_s)
                new_cache["v_scale"] = cache["v_scale"].at[bidx, slot].set(v_s)
            kf, vf = _dequant_kv(new_cache, q.dtype)
            out = attn_lib.decode_attention(q[:, 0], kf, vf, pc, cur,
                                            window=window)
        out = out[:, None]                                  # (B,1,H,Dh)
    elif mode == "chunk":
        # chunked prefill continuation: write this chunk's KV into the
        # shared pool, then attend the chunk's queries against the row's
        # whole gathered extent (earlier chunks + this one).  Positions
        # carry validity: -1 marks padded tokens (writes dropped, queries
        # fully masked).  Restricted to paged layers — ring/recurrent
        # kinds take the exact-length fallback path.
        if "k_pages" not in cache:
            raise NotImplementedError(
                "chunked prefill supports paged attention layers only; "
                "ring (sliding-window) layers must use exact-length prefill")
        pos2d = positions[0] if positions.ndim == 3 else positions
        new_cache = _write_prefill_paged(cache, k, v, pos2d)
        pt = new_cache["page_table"]                        # (B, P)
        n_ctx = pt.shape[1] * new_cache["k_pages"].shape[1]
        kg = new_cache["k_pages"][pt].reshape(B, n_ctx, Hk, Dh)
        vg = new_cache["v_pages"][pt].reshape(B, n_ctx, Hk, Dh)
        out = attn_lib.chunk_attention(q, kg, vg, jnp.arange(n_ctx), pos2d,
                                       window=window)
    else:
        if rt.use_pallas:
            from repro.kernels import ops as kops
            out = kops.flash_attention(q, k, v, causal=True, window=window,
                                       q_chunk=rt.q_chunk, kv_chunk=rt.kv_chunk)
        else:
            out = attn_lib.flash_attention(
                q, k, v, causal=True, window=window, q_chunk=rt.q_chunk,
                kv_chunk=rt.kv_chunk, scheme=rt.causal_scheme)
        if mode == "prefill":
            pos2d = positions[0] if positions.ndim == 3 else positions
            new_cache = _write_prefill_cache(cache, k, v, pos2d)

    out = jnp.einsum("bse,ed->bsd",
                     out.reshape(B, out.shape[1], H * Dh), w["wo"])
    x = x + out

    if cfg.moe is not None:
        h2 = rms_norm(x, w["ln2"], cfg.norm_eps)
        n = h2.shape[0] * h2.shape[1]
        y = moe_lib.moe_ffn(h2.reshape(n, -1), w["moe"], cfg.moe,
                            token_chunk=rt.moe_chunk if mode == "train"
                            else 0)
        x = x + y.reshape(x.shape)
    elif cfg.d_ff > 0:
        h2 = rms_norm(x, w["ln2"], cfg.norm_eps)
        x = x + swiglu(h2, w["wg"], w["wu"], w["wd"])
    return x, new_cache


def _recurrent_valid(positions, mode):
    """Per-token validity mask for recurrent state updates.

    Prefill positions marked ``-1`` are right-padding (bucketed prefill):
    the recurrence must treat them as identity steps so the carried state
    equals the exact-length result.  Decode/train positions are always
    real — no mask, no masking cost."""
    if mode != "prefill":
        return None
    pos = positions[0] if positions.ndim == 3 else positions
    return pos >= 0


def _rglru_layer(kind, w, x, cfg, rt, *, positions, mode, cache):
    if mode == "chunk":
        raise NotImplementedError(
            "chunked prefill is not supported for recurrent layers")
    h = rms_norm(x, w["ln1"], cfg.norm_eps)
    y, new_state = rglru_lib.rglru_block(h, w, cfg.num_heads, mode=mode,
                                         state=cache,
                                         valid=_recurrent_valid(positions,
                                                                mode))
    x = x + y
    if cfg.d_ff > 0:
        h2 = rms_norm(x, w["ln2"], cfg.norm_eps)
        x = x + swiglu(h2, w["wg_mlp"], w["wu"], w["wd"])
    return x, new_state


def _mlstm_layer(kind, w, x, cfg, rt, *, positions, mode, cache):
    if mode == "chunk":
        raise NotImplementedError(
            "chunked prefill is not supported for recurrent layers")
    h = rms_norm(x, w["ln1"], cfg.norm_eps)
    y, new_state = xlstm_lib.mlstm_block(h, w, cfg.num_heads, mode=mode,
                                         state=cache, chunk=rt.mlstm_chunk,
                                         valid=_recurrent_valid(positions,
                                                                mode))
    return x + y, new_state


def _slstm_layer(kind, w, x, cfg, rt, *, positions, mode, cache):
    if mode == "chunk":
        raise NotImplementedError(
            "chunked prefill is not supported for recurrent layers")
    h = rms_norm(x, w["ln1"], cfg.norm_eps)
    y, new_state = xlstm_lib.slstm_block(h, w, cfg.num_heads, mode=mode,
                                         state=cache,
                                         valid=_recurrent_valid(positions,
                                                                mode))
    return x + y, new_state


_KIND_APPLY = {
    "attn": _attn_layer, "local": _attn_layer, "global": _attn_layer,
    "rglru": _rglru_layer, "mlstm": _mlstm_layer, "slstm": _slstm_layer,
}


def apply_layer(kind, w, x, cfg, rt, *, positions, mode, cache):
    return _KIND_APPLY[kind](kind, w, x, cfg, rt, positions=positions,
                             mode=mode, cache=cache)


# ---------------------------------------------------------------------------
# Layer stack execution
# ---------------------------------------------------------------------------


def run_periods(scan_params, x, cfg: ModelConfig, rt: Runtime, *,
                period_kinds, mode, scan_caches, positions):
    """Scan over stacked periods.  ``scan_params``/``scan_caches`` are lists
    over period positions with a leading period axis."""
    if not scan_params or scan_params[0] is None:
        return x, scan_caches
    have_cache = scan_caches is not None and mode != "train"

    def period_body(carry, xs):
        xc = carry
        if have_cache:
            pw, pc = xs
        else:
            pw, pc = xs, [None] * len(period_kinds)
        new_caches = []
        for i, kind in enumerate(period_kinds):
            xc, nc = apply_layer(kind, pw[i], xc, cfg, rt,
                                 positions=positions, mode=mode, cache=pc[i])
            new_caches.append(nc)
        if mode == "train":
            xc = constrain_activations(
                xc, sequence_parallel=rt.sequence_parallel,
                zero3=(rt.train_style == "zero3"))
        return xc, (new_caches if have_cache else None)

    body = period_body
    if rt.remat and mode == "train":
        body = jax.checkpoint(period_body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    xs = (scan_params, scan_caches) if have_cache else scan_params
    x, new_scan_caches = jax.lax.scan(body, x, xs)
    return x, new_scan_caches


def run_layers(params, x, cfg: ModelConfig, rt: Runtime, *, mode,
               caches, positions):
    plan = make_layer_plan(cfg.num_layers, cfg.block_pattern)
    scan_caches = caches["scan"] if caches is not None else None
    x, new_scan = run_periods(params["scan"], x, cfg, rt,
                              period_kinds=plan.period_kinds, mode=mode,
                              scan_caches=scan_caches, positions=positions)
    new_tail = []
    for i, kind in enumerate(plan.tail_kinds):
        c = caches["tail"][i] if caches is not None else None
        x, nc = apply_layer(kind, params["tail"][i], x, cfg, rt,
                            positions=positions, mode=mode, cache=c)
        new_tail.append(nc)
    new_caches = None
    if caches is not None:
        new_caches = {"scan": new_scan, "tail": new_tail}
    return x, new_caches


# ---------------------------------------------------------------------------
# Inputs / embedding
# ---------------------------------------------------------------------------


def embed_inputs(params, inputs: dict, cfg: ModelConfig, rt: Runtime,
                 *, mode: str):
    """Returns (x (B,S,D), positions) from an input dict.

    inputs: {"tokens": (B,S)} or {"frames": (B,S,D)} (audio) or
    {"tokens": (B,S_text), "patches": (B,P,D)} (vlm).
    """
    cd = rt.compute_dtype
    if cfg.frontend == "audio_frames" and "frames" in inputs:
        x = embed_lib.embed_frames(params["embed"], inputs["frames"], cfg, cd)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        return x, positions
    if cfg.frontend == "vision_patches" and "patches" in inputs:
        xp = embed_lib.embed_patches(params["embed"], inputs["patches"], cfg, cd)
        xt = embed_lib.embed_tokens(params["embed"], inputs["tokens"], cfg, cd)
        B, P = xp.shape[:2]
        St = xt.shape[1]
        x = jnp.concatenate([xp, xt], axis=1)
        p3_patch = patch_positions3(B, P)
        side = max(1, int(np.sqrt(P)))
        text_pos = side + jnp.arange(St)
        p3_text = text_positions3(jnp.broadcast_to(text_pos[None], (B, St)))
        positions = jnp.concatenate([p3_patch, p3_text], axis=2)  # (3,B,S)
        return x, positions
    tokens = inputs["tokens"]
    x = embed_lib.embed_tokens(params["embed"], tokens, cfg, cd)
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.frontend == "vision_patches":
        positions = text_positions3(positions)
    return x, positions


# ---------------------------------------------------------------------------
# Train loss (chunked cross-entropy)
# ---------------------------------------------------------------------------


from repro.models.common import _mesh_axes, constrain_activations


def _logits_constraint(logits):
    """Pin the (B, c, V) loss logits to batch-over-DP, vocab-over-model —
    without this XLA can materialise replicated fp32 logits (hundreds of GB
    at 256k vocab)."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            return logits
        names = set(mesh.axis_names)
    except Exception:
        return logits
    bt = tuple(a for a in ("pod", "data") if a in names) or None
    v = "model" if "model" in names else None
    if bt is None and v is None:
        return logits
    spec = jax.sharding.PartitionSpec(bt if bt and len(bt) > 1 else
                                      (bt[0] if bt else None), None, v)
    return jax.lax.with_sharding_constraint(logits, spec)


def _ce_chunk(xc, labels_c, mask_c, params, cfg):
    logits = embed_lib.unembed(params["embed"], xc, cfg)        # (N,c,V) f32
    logits = _logits_constraint(logits)
    logz = jax.nn.logsumexp(logits, axis=-1)
    # label gather as masked reduction: elementwise over the (possibly
    # vocab-sharded) V axis, so the partitioner never all-gathers logits
    viota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                     logits.ndim - 1)
    ll = jnp.sum(jnp.where(viota == labels_c[..., None], logits, 0.0),
                 axis=-1)
    nll = (logz - ll) * mask_c
    return jnp.sum(nll), jnp.sum(mask_c)


def ce_loss(params, x, labels, cfg: ModelConfig, rt: Runtime,
            mask: Optional[jax.Array] = None):
    """Cross-entropy over (B,S,D) activations, chunked over tokens so the
    (tokens, V) fp32 logits tensor never materialises at full size."""
    B, S, D = x.shape
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    chunk = rt.vocab_chunk
    if chunk <= 0 or S <= chunk:
        total, denom = _ce_chunk(x, labels, mask, params, cfg)
        return total / jnp.maximum(denom, 1.0)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (S + pad) // chunk

    def body(carry, xs):
        xc, lc, mc = xs
        t, d = jax.checkpoint(_ce_chunk, static_argnums=(4,))(
            xc, lc, mc, params, cfg)
        return (carry[0] + t, carry[1] + d), None

    xs = (x.reshape(B, n, chunk, D).swapaxes(0, 1),
          labels.reshape(B, n, chunk).swapaxes(0, 1),
          mask.reshape(B, n, chunk).swapaxes(0, 1))
    (total, denom), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), xs)
    return total / jnp.maximum(denom, 1.0)


def train_loss(params, batch: dict, cfg: ModelConfig,
               rt: Runtime = DEFAULT_RUNTIME):
    """batch: input dict + {"labels": (B,S), optional "loss_mask": (B,S)}."""
    x, positions = embed_inputs(params, batch, cfg, rt, mode="train")
    x = constrain_activations(x, zero3=(rt.train_style == "zero3"))
    x, _ = run_layers(params, x, cfg, rt, mode="train", caches=None,
                      positions=positions)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if x.shape[1] != labels.shape[1]:       # vlm: drop patch positions
        x = x[:, x.shape[1] - labels.shape[1]:]
    return ce_loss(params, x, labels, cfg, rt, mask)


# ---------------------------------------------------------------------------
# Serving entry points
# ---------------------------------------------------------------------------


def prefill(params, inputs: dict, cfg: ModelConfig, rt: Runtime,
            capacity: int, caches=None, last_index=None):
    """Full-sequence prefill.  Returns (last_logits (B,V) f32, caches).

    ``caches`` may be pre-built (e.g. the serving engine's paged pools);
    otherwise dense caches of ``capacity`` slots are created.  When the
    prompt is right-padded, ``last_index`` (B,) selects the true last
    position for the returned logits — and marks the pad positions with
    ``-1`` so cache writes drop them and recurrent layers freeze their
    state across them (bucketed prefill stays state-exact)."""
    x, positions = embed_inputs(params, inputs, cfg, rt, mode="prefill")
    B, S = x.shape[:2]
    if last_index is not None:
        li = jnp.asarray(last_index, jnp.int32).reshape(B)
        pad = jnp.arange(S)[None] > li[:, None]              # (B, S)
        positions = jnp.where(pad[None] if positions.ndim == 3 else pad,
                              -1, positions)
    if caches is None:
        caches = init_caches(cfg, B, capacity, rt)
    x, caches = run_layers(params, x, cfg, rt, mode="prefill", caches=caches,
                           positions=positions)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if last_index is None:
        x_last = x[:, -1]
    else:
        idx = jnp.asarray(last_index, jnp.int32).reshape(B, 1, 1)
        x_last = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (B, 1, x.shape[-1])), axis=1)[:, 0]
    logits = embed_lib.unembed(params["embed"], x_last, cfg)
    return logits, caches


def prefill_chunk(params, tokens: jax.Array, caches, offsets: jax.Array,
                  n_valid: jax.Array, last_in_chunk: jax.Array,
                  cfg: ModelConfig, rt: Runtime = DEFAULT_RUNTIME):
    """One chunk of a (batched) chunked prefill.

    tokens        (B, C) int32 — the next C prompt tokens of B sequences
    offsets       (B,)   int32 — tokens already prefilled per row
    n_valid       (B,)   int32 — real tokens in this chunk per row (0 = the
                                 row is padding; its writes are dropped)
    last_in_chunk (B,)   int32 — within-chunk index of the row's final
                                 prompt token; only meaningful for rows
                                 whose chunk is their last

    ``caches`` must be paged-attention caches whose ``page_table`` rows are
    the rows being prefilled (backends splice the per-request table rows
    in).  Requires every layer kind to be paged ("attn"/"global") — the
    engine gates recurrent / sliding-window archs to exact-length prefill.
    Returns (logits (B, V) f32 at ``last_in_chunk``, new_caches).
    """
    cd = rt.compute_dtype
    B, C = tokens.shape
    iota = jnp.arange(C)[None]
    pos = jnp.where(iota < n_valid[:, None], offsets[:, None] + iota, -1)
    x = embed_lib.embed_tokens(params["embed"], tokens, cfg, cd)
    positions = text_positions3(pos) if cfg.frontend == "vision_patches" \
        else pos
    x, caches = run_layers(params, x, cfg, rt, mode="chunk", caches=caches,
                           positions=positions)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    idx = jnp.clip(last_in_chunk, 0, C - 1).reshape(B, 1, 1)
    x_last = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (B, 1, x.shape[-1])), axis=1)[:, 0]
    logits = embed_lib.unembed(params["embed"], x_last, cfg)
    return logits, caches


def decode_step(params, tokens: jax.Array, caches, cur_pos: jax.Array,
                cfg: ModelConfig, rt: Runtime = DEFAULT_RUNTIME):
    """One decode step.  tokens (B,) int32; cur_pos (B,) absolute positions.

    Returns (logits (B,V) f32, new_caches)."""
    cd = rt.compute_dtype
    x = embed_lib.embed_tokens(params["embed"], tokens[:, None], cfg, cd)
    positions = cur_pos[:, None]
    if cfg.frontend == "vision_patches":
        positions = text_positions3(positions)
    x, caches = run_layers(params, x, cfg, rt, mode="decode", caches=caches,
                           positions=positions)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = embed_lib.unembed(params["embed"], x[:, 0], cfg)
    return logits, caches
