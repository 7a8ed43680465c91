"""Configuration system for the DeServe reproduction framework.

Every model architecture is described by a :class:`ModelConfig`; every
benchmark/dry-run workload by a :class:`ShapeConfig`; every device topology by a
:class:`MeshConfig`.  Configs are frozen dataclasses so they can be hashed and
used as static arguments to ``jax.jit``.

Layer heterogeneity (local/global attention, recurrent/attention hybrids,
mLSTM/sLSTM mixes) is expressed with a *block pattern*: a tuple of layer-kind
strings that repeats over the depth of the network.  The model runtime scans
over whole pattern periods (weights stacked over periods) and unrolls the
remainder ("tail") layers, which keeps HLO size O(period) instead of O(depth).
"""

from __future__ import annotations

import dataclasses
import importlib
import pkgutil
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

# ---------------------------------------------------------------------------
# Layer kinds
# ---------------------------------------------------------------------------

# Attention-family kinds (consume/produce KV cache):
ATTN_KINDS = ("attn", "local", "global")
# Recurrent-family kinds (carry O(1) state per sequence):
RECURRENT_KINDS = ("rglru", "mlstm", "slstm")

ALL_KINDS = ATTN_KINDS + RECURRENT_KINDS


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts configuration (GShard/Switch-style top-k routing)."""

    num_experts: int
    experts_per_token: int          # top-k
    d_expert: int                   # per-expert FFN hidden size
    capacity_factor: float = 1.25   # per-expert buffer slack for dropless-ish dispatch
    router_jitter: float = 0.0
    normalize_router_weights: bool = True  # qwen3 renormalizes top-k probs


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description.  All sizes are in units of elements, not bytes."""

    name: str
    family: str                      # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads
    block_pattern: tuple = ("attn",)
    window_size: int = 0             # sliding-window size for "local" layers
    moe: Optional[MoEConfig] = None
    rope_theta: float = 10000.0
    rope_scaling: float = 1.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    use_qk_norm: bool = False        # qwen3-style RMSNorm on q/k heads
    frontend: str = "token"          # token | audio_frames | vision_patches
    num_patch_tokens: int = 0        # vlm: patch tokens prepended to the text
    d_rnn: int = 0                   # recurrent width (0 -> d_model)
    conv_width: int = 4              # temporal-conv width in recurrent blocks
    logit_softcap: float = 0.0       # gemma-style tanh soft-capping (0 = off)
    scale_embeddings: bool = False   # gemma-style sqrt(d_model) embedding scale
    max_position_embeddings: int = 131072
    source: str = ""                 # provenance note ([arXiv:...; tier])

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.d_rnn == 0:
            object.__setattr__(self, "d_rnn", self.d_model)
        assert self.num_heads % max(self.num_kv_heads, 1) == 0, (
            f"{self.name}: q heads {self.num_heads} not divisible by kv heads "
            f"{self.num_kv_heads}")
        for k in self.block_pattern:
            assert k in ALL_KINDS, f"unknown layer kind {k!r}"

    # -- derived quantities -------------------------------------------------

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def layer_kinds(self) -> tuple:
        """Layer kind for each of the ``num_layers`` layers (pattern tiled)."""
        p = self.block_pattern
        return tuple(p[i % len(p)] for i in range(self.num_layers))

    def attention_layer_count(self) -> int:
        return sum(1 for k in self.layer_kinds() if k in ATTN_KINDS)

    def recurrent_layer_count(self) -> int:
        return sum(1 for k in self.layer_kinds() if k in RECURRENT_KINDS)

    def is_subquadratic(self) -> bool:
        """True if long-context decode memory does not grow ~linearly with
        full-attention KV for every layer (SSM / hybrid / sliding-window)."""
        kinds = self.layer_kinds()
        full = sum(1 for k in kinds if k in ("attn", "global"))
        return full == 0 or (full / len(kinds)) <= 0.34

    # -- parameter counting (used by the cost model / roofline) -------------

    def param_count(self) -> int:
        """Total parameter count (embedding included once if tied)."""
        D, F, V = self.d_model, self.d_ff, self.vocab_size
        total = V * D                      # embedding
        if not self.tie_embeddings:
            total += D * V                 # unembedding
        total += D                         # final norm
        for kind in self.layer_kinds():
            total += self._layer_params(kind)
        return total

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE activates only top-k experts)."""
        if self.moe is None:
            return self.param_count()
        D = self.d_model
        total = self.param_count()
        # subtract inactive experts
        per_expert = 3 * D * self.moe.d_expert
        inactive = self.moe.num_experts - self.moe.experts_per_token
        total -= inactive * per_expert * self.num_layers_with_moe()
        return total

    def num_layers_with_moe(self) -> int:
        return self.num_layers if self.moe is not None else 0

    def _layer_params(self, kind: str) -> int:
        D, F = self.d_model, self.d_ff
        Dr = self.d_rnn
        H, Hk, Dh = self.num_heads, self.num_kv_heads, self.head_dim
        n = 0
        if kind in ATTN_KINDS:
            n += D * (H * Dh) + 2 * D * (Hk * Dh) + (H * Dh) * D   # qkvo
            n += 2 * D                                             # ln1, ln2
            if self.use_qk_norm:
                n += 2 * Dh
            if self.moe is not None:
                n += D * self.moe.num_experts                       # router
                n += self.moe.num_experts * 3 * D * self.moe.d_expert
            elif F > 0:
                n += 3 * D * F                                      # swiglu
        elif kind == "rglru":
            # gated linear recurrent block (Griffin): two in-proj branches,
            # temporal conv, block-diagonal gate projections, out proj, + mlp
            n += 2 * D * Dr + self.conv_width * Dr
            n += 2 * (Dr * Dr // max(H, 1)) + 2 * Dr               # gates (blockdiag) + Lambda + bias
            n += Dr * D + 2 * D
            if F > 0:
                n += 3 * D * F
        elif kind == "mlstm":
            # up-proj (2x), q/k/v projections in expanded space, gates, down
            n += 2 * D * Dr + 3 * Dr * Dr // max(H, 1) + 3 * Dr + Dr * D + D
        elif kind == "slstm":
            n += 4 * D * Dr + 4 * (Dr * Dr // max(H, 1)) + 4 * Dr + Dr * D + D
        return n

    def kv_bytes_per_token_per_layer(self, dtype_bytes: int = 2) -> int:
        return 2 * self.num_kv_heads * self.head_dim * dtype_bytes

    def kv_bytes_per_token(self, dtype_bytes: int = 2) -> int:
        """KV bytes/token across layers, honouring sliding windows (a local
        layer's cache never exceeds its window)."""
        return self.attention_layer_count() * self.kv_bytes_per_token_per_layer(dtype_bytes)


# ---------------------------------------------------------------------------
# Workload shapes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # train | prefill | decode

    @property
    def tokens_per_step(self) -> int:
        if self.kind == "decode":
            return self.global_batch          # one new token per sequence
        return self.seq_len * self.global_batch


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Mesh / parallelism
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    name: str
    shape: tuple
    axes: tuple

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


SINGLE_POD = MeshConfig("single_pod", (16, 16), ("data", "model"))
MULTI_POD = MeshConfig("multi_pod", (2, 16, 16), ("pod", "data", "model"))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register_arch(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def _load_all_configs() -> None:
    from repro import configs as _pkg
    for m in pkgutil.iter_modules(_pkg.__path__):
        importlib.import_module(f"repro.configs.{m.name}")


def get_arch(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        _load_all_configs()
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown arch {name!r}; available: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> list:
    _load_all_configs()
    return sorted(_REGISTRY)


_WIDTH_FIELDS = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
                 "vocab_size", "d_rnn", "block_pattern", "window_size", "moe")


def at_published_widths(cfg: ModelConfig) -> bool:
    """True when ``cfg`` keeps every width of the registered arch it is
    named after — only its depth may be cut.  ``reduced_config`` renames
    its result, so the CPU-sized configs are never at published widths."""
    try:
        ref = get_arch(cfg.name)
    except KeyError:
        return False
    return all(getattr(cfg, f) == getattr(ref, f) for f in _WIDTH_FIELDS)


def reduced_config(cfg: ModelConfig, *, num_layers: int = 0,
                   d_model: int = 64, vocab: int = 128) -> ModelConfig:
    """A tiny config of the same family for CPU smoke tests.

    Keeps the block pattern (one full period + tail behaviour) and head
    structure ratios, shrinks widths/verbosity.
    """
    period = len(cfg.block_pattern)
    if num_layers == 0:
        num_layers = period + max(1, period // 2)   # one period + a tail
    heads = max(2, min(4, cfg.num_heads))
    kv = max(1, min(heads, cfg.num_kv_heads))
    while heads % kv:
        kv -= 1
    head_dim = max(8, d_model // heads)
    moe = None
    if cfg.moe is not None:
        moe = MoEConfig(num_experts=4,
                        experts_per_token=min(2, cfg.moe.experts_per_token),
                        d_expert=2 * d_model,
                        capacity_factor=2.0,
                        normalize_router_weights=cfg.moe.normalize_router_weights)
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        num_layers=num_layers,
        d_model=d_model,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=head_dim,
        d_ff=0 if cfg.d_ff == 0 else 4 * d_model,
        vocab_size=vocab,
        window_size=min(cfg.window_size, 32) if cfg.window_size else 0,
        moe=moe,
        d_rnn=d_model,
        num_patch_tokens=min(cfg.num_patch_tokens, 4),
        max_position_embeddings=4096,
    )
