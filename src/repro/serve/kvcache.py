"""KV-cache utilities: allocation + INT8 KV quantization.

INT8 KV (Oaken-style, the paper's §1 motivation: 'the KV cache can occupy
more than half of GPU memory') stores K/V as int8 with per-(position, head)
scales — 2x cache capacity, one of the §Perf hillclimb levers for the
decode_32k cells.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig
from repro.models.attention import init_cache, n_attn_layers  # noqa: F401


def quantize_rows(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[..., dh] -> int8 [..., dh] + f32 abs-max scale [..., 1] per row."""
    amax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                               keepdims=True), 1e-6)
    s = amax / 127.0
    xi = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
    return xi.astype(jnp.int8), s.astype(jnp.float32)


def quantize_kv(k: jnp.ndarray, v: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """[..., kv, dh] bf16 -> int8 + f32 scales over the head_dim axis."""
    ki, ks = quantize_rows(k)
    vi, vs = quantize_rows(v)
    return {"k": ki, "k_scale": ks, "v": vi, "v_scale": vs}


def dequantize_kv(cache: Dict[str, jnp.ndarray], dtype=jnp.bfloat16
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    k = (cache["k"].astype(jnp.float32) * cache["k_scale"]).astype(dtype)
    v = (cache["v"].astype(jnp.float32) * cache["v_scale"]).astype(dtype)
    return k, v


def init_int8_cache(cfg: ModelConfig, batch: int, s_max: int) -> dict:
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    n = n_attn_layers(cfg)
    return {
        "k": jnp.zeros((n, batch, s_max, kv, dh), jnp.int8),
        "k_scale": jnp.zeros((n, batch, s_max, kv, 1), jnp.float32),
        "v": jnp.zeros((n, batch, s_max, kv, dh), jnp.int8),
        "v_scale": jnp.zeros((n, batch, s_max, kv, 1), jnp.float32),
        "pos": jnp.asarray(0, jnp.int32),
    }


def cache_bytes(cache) -> int:
    """True buffer bytes of the cache's KV payload: ``size * itemsize`` over
    array leaves, so packed layouts (int4 nibble pages store head_dim/2 int8
    bytes per position) report their physical footprint, not logical element
    counts.  0-dim bookkeeping scalars (``pos``) are excluded — they are not
    KV buffers."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache)
               if hasattr(x, "dtype") and getattr(x, "ndim", 0) > 0)
