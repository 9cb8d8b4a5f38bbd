"""ShapeDtypeStruct stand-ins for every (arch x input-shape) dry-run cell.

No device allocation — ``jax.jit(...).lower(**input_specs(...))`` consumes
these directly.  Modality frontends are stubs per the assignment:
[vlm]/[audio] archs receive precomputed patch/frame embeddings.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig
from repro.models.attention import init_cache, n_attn_layers
from repro.models.ssm import init_ssm_state


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    mode: str  # train | prefill | decode


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def cell_supported(cfg: ModelConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """long_500k only for sub-quadratic archs (DESIGN.md §5)."""
    if shape.name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        return False, "long_500k skipped: unbounded dense-attention KV cache"
    return True, ""


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs_abstract(cfg: ModelConfig, shape: ShapeSpec,
                         act_dtype=jnp.bfloat16) -> Dict[str, Any]:
    b, s = shape.global_batch, shape.seq_len
    batch = {"tokens": _sds((b, s), jnp.int32), "labels": _sds((b, s), jnp.int32)}
    if cfg.n_patches:
        batch["patches"] = _sds((b, cfg.n_patches, cfg.d_model), act_dtype)
    if cfg.is_enc_dec:
        batch["frames"] = _sds((b, cfg.n_audio_frames, cfg.d_model), act_dtype)
    return batch


def prefill_specs_abstract(cfg: ModelConfig, shape: ShapeSpec,
                           act_dtype=jnp.bfloat16) -> Dict[str, Any]:
    b, s = shape.global_batch, shape.seq_len
    batch = {"tokens": _sds((b, s), jnp.int32)}
    if cfg.n_patches:
        batch["patches"] = _sds((b, cfg.n_patches, cfg.d_model), act_dtype)
    if cfg.is_enc_dec:
        batch["frames"] = _sds((b, cfg.n_audio_frames, cfg.d_model), act_dtype)
    return batch


def cache_abstract(cfg: ModelConfig, shape: ShapeSpec,
                   kv_dtype=jnp.bfloat16, int8_kv: bool = False) -> Dict[str, Any]:
    """Abstract KV/SSM cache for the decode cells (cache length = seq_len)."""
    b, s = shape.global_batch, shape.seq_len
    fam = cfg.family

    def shape_of(fn, *a, **kw):
        return jax.eval_shape(lambda: fn(*a, **kw))

    if fam in ("dense", "moe", "encdec"):
        if int8_kv:
            from repro.serve.kvcache import init_int8_cache
            cache = shape_of(init_int8_cache, cfg, b, s)
        else:
            cache = shape_of(init_cache, cfg, b, s, dtype=kv_dtype)
        if fam == "encdec":
            cache["memory"] = _sds((b, cfg.n_audio_frames, cfg.d_model), kv_dtype)
        return cache
    if fam == "ssm":
        cache = shape_of(init_ssm_state, cfg, b, cfg.n_layers)
        cache["pos"] = _sds((), jnp.int32)
        return cache
    # hybrid: ssm states + shared-attn kv
    cache = shape_of(init_ssm_state, cfg, b, cfg.n_layers)
    kvc = shape_of(init_cache, cfg, b, s, dtype=kv_dtype, layers=n_attn_layers(cfg))
    cache.update({"k": kvc["k"], "v": kvc["v"]})
    cache["pos"] = _sds((), jnp.int32)
    return cache


def decode_specs_abstract(cfg: ModelConfig, shape: ShapeSpec,
                          int8_kv: bool = False) -> Dict[str, Any]:
    b = shape.global_batch
    return {"tokens": _sds((b, 1), jnp.int32),
            "cache": cache_abstract(cfg, shape, int8_kv=int8_kv)}


def synthetic_qparams(cfg: ModelConfig, frac: float = 0.02) -> Dict[str, jnp.ndarray]:
    """Static MUXQ outlier masks [L, channels] per site (stand-ins shaped
    like a calibration output; dry-run only — real runs calibrate)."""
    import numpy as np
    rng = np.random.default_rng(0)
    L = cfg.n_layers
    d, f = cfg.d_model, cfg.d_ff

    def m(ch):
        k = max(1, int(frac * ch))
        out = np.zeros((L, ch), bool)
        for i in range(L):
            out[i, rng.choice(ch, k, replace=False)] = True
        return jnp.asarray(out)

    fam = cfg.family
    sites: Dict[str, jnp.ndarray] = {}
    if fam in ("dense", "moe", "encdec", "hybrid"):
        sites["attn_qkv"] = m(d)
        sites["attn_out"] = m(cfg.n_heads * cfg.head_dim)
    if fam in ("dense", "encdec", "hybrid"):
        sites["mlp_up"] = m(d)
        sites["mlp_down"] = m(f)
    if fam == "moe":
        sites["moe_up"] = m(d)
        sites["moe_down"] = m(cfg.d_expert)
        if cfg.d_ff_shared:
            sites["moe_shared_up"] = m(d)
            sites["moe_shared_down"] = m(cfg.d_ff_shared)
    if fam == "encdec":
        sites["cross_q"] = m(d)
        sites["cross_kv"] = m(d)
        sites["cross_out"] = m(cfg.n_heads * cfg.head_dim)
    if fam in ("ssm", "hybrid"):
        sites["ssm_in_zx"] = m(d)
        sites["ssm_in_bcdt"] = m(d)
        sites["ssm_out"] = m(cfg.d_inner)
    return sites
