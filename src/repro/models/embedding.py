"""Input frontends: token embedding plus stub modality frontends.

Per the assignment, ``[audio]``/``[vlm]`` archs specify the transformer
*backbone* only — the modality frontend is a stub whose job is to accept
*precomputed* frame/patch embeddings (supplied by ``input_specs()``) and
project them into the backbone's residual stream.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig


def embed_tokens(params: dict, tokens: jax.Array, cfg: ModelConfig,
                 compute_dtype) -> jax.Array:
    """(..., S) int32 -> (..., S, D)."""
    x = jnp.take(params["tok"], tokens, axis=0).astype(compute_dtype)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), compute_dtype)
    return x


def embed_frames(params: dict, frames: jax.Array, cfg: ModelConfig,
                 compute_dtype) -> jax.Array:
    """Audio stub: precomputed EnCodec frame embeddings (B, S, D_in) are
    projected into the residual stream."""
    return jnp.einsum("bsf,fd->bsd", frames.astype(compute_dtype),
                      params["frame_proj"].astype(compute_dtype))


def embed_patches(params: dict, patches: jax.Array, cfg: ModelConfig,
                  compute_dtype) -> jax.Array:
    """Vision stub: precomputed merged-patch embeddings (B, P, D_in) projected
    into the residual stream (the qwen2-vl `merger` MLP, single layer here)."""
    return jnp.einsum("bpf,fd->bpd", patches.astype(compute_dtype),
                      params["patch_proj"].astype(compute_dtype))


def unembed(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """(..., D) -> (..., V) logits in fp32 (softcap applied if configured)."""
    table = params["tok"] if cfg.tie_embeddings else params["untok"]
    # accumulate in fp32: bf16 logits round 64k-way argmaxes into ties
    logits = jnp.einsum("...d,vd->...v", x, table.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap > 0.0:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits
