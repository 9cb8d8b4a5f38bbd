"""Shared model substrate: config, norms, rotary embeddings, losses.

One ModelConfig covers every assigned architecture (dense / MoE / SSM /
hybrid / enc-dec / VLM-backbone).  A layer has an attention kind and an
FFN kind:

  * ``block_pattern`` — a short cycle of block kinds tiled over
    ``n_layers`` ("attn" | "local" | "global" | "mamba"; e.g. gemma2's
    ("local", "global")), and ``attn_kind`` — how attention layers project
    and cache: "mha" (multi-/grouped-query heads, K and V per KV head) or
    "mla" (latent attention: one compressed latent per position, shared by
    every head, DeepSeek-V2/V3);
  * the FFN is "experts" in every layer past the first ``first_dense``
    ones when ``n_experts`` is set, else "dense".

Layers whose parameters share one structure form a *group*
(:attr:`ModelConfig.layer_groups`): each group is stacked ``[n, ...]`` under
its own key of the parameter tree and scanned on its own.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def pad_vocab(vocab: int, multiple: int = 128) -> int:
    """Pad vocab so embedding/vocab dims divide every mesh axis (Megatron
    convention).  Logits over pad ids are masked to -inf in the loss."""
    return ((vocab + multiple - 1) // multiple) * multiple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int

    # block composition — cycle tiled over n_layers
    block_pattern: Tuple[str, ...] = ("attn",)   # attn|local|global|mamba
    attn_kind: str = "mha"                       # mha|mla

    # attention
    qkv_bias: bool = False
    attn_softcap: Optional[float] = None         # gemma2: 50.0
    final_softcap: Optional[float] = None        # gemma2: 30.0
    window_size: int = 4096                      # for "local" blocks
    rope_theta: float = 10000.0
    # latent attention (attn_kind "mla"): no query down-projection
    kv_lora_rank: int = 0                        # latent width
    qk_nope_dim: int = 0                         # per-head key part off rope
    qk_rope_dim: int = 0                         # rotary key, shared by heads
    v_head_dim: int = 0

    # mlp
    mlp_type: str = "swiglu"                     # swiglu|gelu
    # moe: layers past the first ``first_dense`` hold routed experts
    n_experts: int = 0                           # experts the router scores
    top_k: int = 0
    first_dense: int = 0                         # leading dense-FFN layers
    d_ff_expert: int = 0                         # routed expert width (0: d_ff)
    d_ff_shared: int = 0                         # shared expert width (0: none)
    router_score: str = "softmax"                # softmax|sigmoid
    router_bias: bool = False                    # selection-only score bias
    routed_scale: float = 1.0                    # times the chosen scores
    # the experts this chip holds: [expert_lo, expert_lo + n_held)
    expert_lo: int = 0
    n_held: int = 0                              # 0: every expert
    # ssm (mamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    # zamba2-style shared attention block applied every k mamba blocks
    shared_attn_every: int = 0

    # enc-dec (whisper)
    n_enc_layers: int = 0
    n_audio_frames: int = 1500
    # vlm (internvl2) — patch embeds prepended to token embeds
    n_patches: int = 0

    norm: str = "rmsnorm"                        # rmsnorm|layernorm
    norm_eps: float = 1e-5
    sandwich_norm: bool = False                  # gemma2 pre+post sublayer norms
    scale_embed: bool = False                    # gemma2 sqrt(d) embed scaling
    tie_embeddings: bool = True
    dtype: str = "float32"                       # compute dtype
    remat: bool = False                          # activation checkpointing

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def is_mla(self) -> bool:
        return self.attn_kind == "mla"

    @property
    def attn_scale(self) -> float:
        """Softmax scale: over the query-key width each head scores (MLA's
        latent scores equal its expanded nope + rope dot products)."""
        if self.is_mla:
            return (self.qk_nope_dim + self.qk_rope_dim) ** -0.5
        return self.head_dim ** -0.5

    @property
    def d_expert(self) -> int:
        return self.d_ff_expert or self.d_ff

    @property
    def held(self) -> Tuple[int, int]:
        """(first expert held, experts held)."""
        return self.expert_lo, (self.n_held or self.n_experts)

    @property
    def padded_vocab(self) -> int:
        return pad_vocab(self.vocab_size)

    @property
    def d_inner(self) -> int:                    # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def blocks(self) -> Tuple[str, ...]:
        """The full per-layer kind sequence (pattern tiled to n_layers)."""
        pat = self.block_pattern
        return tuple(pat[i % len(pat)] for i in range(self.n_layers))

    @property
    def ffn_kinds(self) -> Tuple[str, ...]:
        """Per layer: "experts" past the leading dense layers of an expert
        model, else "dense"."""
        return tuple("experts" if self.n_experts and i >= self.first_dense
                     else "dense" for i in range(self.n_layers))

    @property
    def layer_groups(self) -> Tuple[Tuple[str, int, int], ...]:
        """(parameter key, first layer, end) of each run of layers that
        share one parameter structure, in order.  The main group is always
        ``"layers"``; an expert model's leading dense layers form the group
        ``"dense_layers"``."""
        lead = min(self.first_dense, self.n_layers) if self.n_experts else 0
        if lead:
            return (("dense_layers", 0, lead), ("layers", lead, self.n_layers))
        return (("layers", 0, self.n_layers),)

    @property
    def is_enc_dec(self) -> bool:
        return self.n_enc_layers > 0

    @property
    def family(self) -> str:
        """dense | moe | ssm | hybrid | encdec — selects the stack body."""
        if self.is_enc_dec:
            return "encdec"
        if self.shared_attn_every:
            return "hybrid"
        if set(self.blocks) == {"mamba"}:
            return "ssm"
        if self.n_experts:
            return "moe"
        return "dense"

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


MAIN_GROUP = "layers"
# eager site-name prefix of each layer group's layers (``layer{i}/``,
# ``dense{i}/``; ``i`` counts within the group)
GROUP_SITE_PREFIX = {"layers": "layer", "dense_layers": "dense"}


def group_name(key: str, name: str) -> str:
    """Flat name of one group's entry in a per-layer tree (caches, pool
    arrays, scanned quantization state): bare in the main group, else
    ``"<group>/<name>"``."""
    return name if key == MAIN_GROUP else f"{key}/{name}"


def group_items(tree: dict, key: str) -> dict:
    """One group's entries of a flat per-layer tree, under bare names."""
    if key == MAIN_GROUP:
        return {n: v for n, v in tree.items() if "/" not in n}
    pre = key + "/"
    return {n[len(pre):]: v for n, v in tree.items() if n.startswith(pre)}


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, gain: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + gain.astype(jnp.float32))).astype(dt)


def layernorm(x: jnp.ndarray, gain: jnp.ndarray, bias: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * gain + bias).astype(dt)


def apply_norm(cfg: ModelConfig, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["gain"], cfg.norm_eps)
    return layernorm(x, p["gain"], p["bias"], cfg.norm_eps)


def init_norm(cfg: ModelConfig, d: int) -> dict:
    if cfg.norm == "rmsnorm":
        return {"gain": jnp.zeros((d,), jnp.float32)}
    return {"gain": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    freqs = rope_freqs(x.shape[-1], theta)                      # [hd/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., None, :]                          # [..., seq, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    """gemma2 logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  vocab_size: int, mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Token-mean CE.  ``vocab_size`` is the real vocab; padded logit columns
    are excluded from the normalizer."""
    logits = logits.astype(jnp.float32)
    pad = logits.shape[-1] - vocab_size
    if pad > 0:
        neg = jnp.full((pad,), -1e9, jnp.float32)
        logits = logits.at[..., vocab_size:].set(neg)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape: Sequence[int], fan_in: Optional[int] = None) -> jnp.ndarray:
    fan_in = fan_in or shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std)
