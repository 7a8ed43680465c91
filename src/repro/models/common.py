"""Shared model components: norms, RoPE (+M-RoPE), MLP, layer plans, init.

Everything is functional: params are plain pytrees of ``jnp`` arrays, and all
entry points are shape-polymorphic over batch/sequence so the same code path
serves smoke tests (tiny), real CPU runs (small) and the 512-device dry-run
(full scale, ``ShapeDtypeStruct`` only).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ATTN_KINDS, RECURRENT_KINDS, ModelConfig


# ---------------------------------------------------------------------------
# Runtime knobs (static; threaded through model functions)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Runtime:
    """Static execution knobs, hashable so it can be a jit static arg."""

    mesh_axes: tuple = ()             # () = single device / no SPMD hints
    use_ep_moe: bool = False          # shard_map all_to_all expert parallelism
    q_chunk: int = 512                # flash-attention query chunk
    kv_chunk: int = 512               # flash-attention kv chunk
    mlstm_chunk: int = 64             # chunkwise mLSTM chunk length
    remat: bool = False               # checkpoint each layer period in training
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    use_pallas: bool = False          # route attention through Pallas kernels
    causal_scheme: str = "masked"     # masked | blockpair (see kernels/ops.py)
    ep_axis: str = "model"            # mesh axis that shards experts
    vocab_chunk: int = 0              # 0 = unchunked loss; else chunk token dim
    sequence_parallel: bool = False   # Megatron-SP residual sharding (train)
    moe_chunk: int = 0                # token-chunked MoE dispatch (0 = off)
    train_style: str = "sp"           # sp (TP+seq-parallel) | zero3 (batch
                                      # over data+model, weights gathered)
    kv_dtype: str = "bf16"            # bf16 | int8 (quantized KV cache)
    attn_pages_per_block: int = 0     # paged-decode KV pages per block
                                      # (0 = autotuned per pool shape)

    def replace(self, **kw) -> "Runtime":
        return dataclasses.replace(self, **kw)


DEFAULT_RUNTIME = Runtime()


def serving_runtime(cfg: ModelConfig) -> Runtime:
    """The dtype the served path uses when the caller gives no ``Runtime``:
    bf16 at published widths (what fits a chip), fp32 for the reduced
    CPU-sized configs (what the equivalence tests compare bitwise)."""
    from repro.config import at_published_widths
    if at_published_widths(cfg):
        return DEFAULT_RUNTIME
    return Runtime(param_dtype=jnp.float32, compute_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Layer plan: scan over pattern periods + unrolled tail
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LayerPlan:
    period_kinds: tuple     # kinds within one period
    n_periods: int          # number of scanned periods
    tail_kinds: tuple       # remainder layers, unrolled

    @property
    def num_layers(self) -> int:
        return len(self.period_kinds) * self.n_periods + len(self.tail_kinds)

    def all_kinds(self) -> tuple:
        return self.period_kinds * self.n_periods + self.tail_kinds


def make_layer_plan(num_layers: int, pattern: tuple) -> LayerPlan:
    period = len(pattern)
    n_periods = num_layers // period
    tail = tuple(pattern[: num_layers % period])
    if n_periods == 0:
        # degenerate (fewer layers than one period): everything is tail
        return LayerPlan(period_kinds=(), n_periods=0, tail_kinds=tail)
    return LayerPlan(period_kinds=tuple(pattern), n_periods=n_periods,
                     tail_kinds=tail)


# ---------------------------------------------------------------------------
# Basic ops
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0.0:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


def swiglu(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, wg)
    u = jnp.einsum("...d,df->...f", x, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, wd)


# ---------------------------------------------------------------------------
# RoPE and M-RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling: float = 1.0) -> jax.Array:
    """Rotary embedding.

    x:        (..., S, H, Dh)
    positions (..., S) integer positions (broadcastable over leading dims)
    """
    dh = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(dh, theta), dtype=jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * freqs / scaling  # (...,S,Dh/2)
    angles = angles[..., None, :]                                        # (...,S,1,Dh/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# M-RoPE sections (pairs per positional component t/h/w), qwen2-vl style.
MROPE_SECTIONS = (16, 24, 24)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float) -> jax.Array:
    """Multimodal RoPE: ``positions3`` is (3, ..., S) for (t, h, w).

    Different contiguous sections of the rotation-frequency spectrum take
    their position from different components.
    """
    dh = x.shape[-1]
    half = dh // 2
    sections = np.asarray(MROPE_SECTIONS, dtype=np.int64)
    sections = (sections * half / sections.sum()).astype(np.int64)
    sections[-1] = half - sections[:-1].sum()
    comp = np.repeat(np.arange(3), sections)                 # (half,) component id
    freqs = jnp.asarray(rope_frequencies(dh, theta), jnp.float32)
    pos = positions3.astype(jnp.float32)                      # (3, ..., S)
    # select per-frequency component: (..., S, half)
    pos_per_freq = jnp.take(pos, jnp.asarray(comp), axis=0)   # (half, ..., S)
    pos_per_freq = jnp.moveaxis(pos_per_freq, 0, -1)          # (..., S, half)
    angles = pos_per_freq * freqs
    angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def text_positions3(positions: jax.Array) -> jax.Array:
    """Text tokens use identical (t, h, w) components."""
    return jnp.broadcast_to(positions[None], (3,) + positions.shape)


def patch_positions3(batch: int, n_patches: int) -> jax.Array:
    """A square patch grid at t=0 with (h, w) raster positions."""
    side = max(1, int(np.sqrt(n_patches)))
    idx = jnp.arange(n_patches)
    h = idx // side
    w = idx % side
    t = jnp.zeros_like(idx)
    p3 = jnp.stack([t, h, w])                                  # (3, P)
    return jnp.broadcast_to(p3[:, None, :], (3, batch, n_patches))


# ---------------------------------------------------------------------------
# Initialisation
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, shape: tuple, dtype, fan_in: Optional[int] = None):
    fan_in = fan_in if fan_in is not None else shape[0]
    scale = 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _mesh_axes(with_sizes: bool = False):
    """Non-manual axis names of the ambient mesh (empty outside any mesh
    context).  Manual axes (e.g. "pod" inside the pipeline's shard_map) must
    never appear in sharding constraints."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            from jax._src import mesh as mesh_lib
            mesh = mesh_lib.thread_resources.env.physical_mesh
            if mesh.empty:
                return {} if with_sizes else frozenset()
        usable = {
            n: s for n, s, t in zip(mesh.axis_names, mesh.axis_sizes,
                                    mesh.axis_types)
            if "anual" not in str(t)}
        return usable if with_sizes else frozenset(usable)
    except Exception:
        return {} if with_sizes else frozenset()


def constrain_activations(x, *, sequence_parallel: bool = False,
                          zero3: bool = False):
    """Pin (B, ..., D) activations to batch-over-DP — without this the
    partitioner can lose the data axis after vocab-sharded embedding gathers
    and replicate multi-GB activation tensors.

    ``sequence_parallel`` additionally shards the sequence dim over "model"
    (Megatron-SP): the layer-boundary residual stash the backward pass keeps
    per scanned period then shards over TP instead of being replicated —
    an O(model)x saving on the dominant training-memory term."""
    sizes = _mesh_axes(with_sizes=True)
    names = frozenset(sizes)
    bt = tuple(a for a in ("pod", "data") if a in names)
    if not bt:
        return x
    rest = [None] * (x.ndim - 1)
    full = 1
    for a in bt:
        full *= sizes[a]
    if zero3 and "model" in names and             x.shape[0] % (full * sizes["model"]) == 0:
        # ZeRO-3 style: batch over *every* axis; weights get gathered per
        # layer instead of activations moving (see EXPERIMENTS.md SPerf).
        # Guarded on divisibility: a 256-batch cannot shard 512 ways.
        bt = bt + ("model",)
    elif sequence_parallel and "model" in names and x.ndim >= 3 and \
            x.shape[1] % 16 == 0:
        rest[0] = "model"
    spec = jax.sharding.PartitionSpec(bt if len(bt) > 1 else bt[0], *rest)
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_expert_dim(x):
    """Pin a (E, ...) expert-major buffer to expert-parallel over "model"."""
    names = _mesh_axes()
    if "model" not in names:
        return x
    spec = jax.sharding.PartitionSpec("model", *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


class KeyGen:
    """Sequential PRNG key dispenser."""

    def __init__(self, key: jax.Array):
        self._key = key

    def __call__(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub
