"""Offline weight pre-quantization (the deployment path).

Transforms a params tree so every quantized-site weight leaf becomes
{"q": int8, "s": f32 per-out-channel scales}.  The serving step then reads
1 byte/weight from HBM and never runs the fp32 quantize pass — in the
baseline decode roofline that pass dominated HBM traffic (EXPERIMENTS.md
§Perf iteration 1).

Packing is policy-aware: pass a :class:`~repro.core.policy.SitePolicy` and
each site is packed at its *resolved* weight bits / granularity (sites whose
policy resolves to ``fp`` keep their original dtype).  For smooth-method
sites (``smoothquant`` / ``muxq_smooth``) the per-channel migration factors
are folded into the weight BEFORE quantization (``Q(s*W)``) so the runtime
only has to apply ``X/s`` — see ``repro.quantize.quantize_model``, which
owns factor computation.

Embeddings / lm_head / norms / biases / router / conv / SSD params stay in
their original dtype (they're outside the paper's target-layer set).
"""
from __future__ import annotations

import re
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantizers as Q
from repro.core.policy import SitePolicy
from repro.models.common import GROUP_SITE_PREFIX, ModelConfig

# site weight leaves eligible for offline int8 (matmul right-hand sides)
_WEIGHT_RE = re.compile(
    r"(attn/(wqkv|wq|wkpe|wo)|cross/(wq|wkv|wo)|mlp/(wi|wo)|moe/(wi|wo)"
    r"|ssm/(in_zx|in_bcdt|out_proj))$")

# weight-path suffix -> the ctx site base name it is consumed under
_SITE_BY_SUFFIX = {
    "attn/wqkv": "attn_qkv", "attn/wo": "attn_out",
    "attn/wq": "attn_q", "attn/wkpe": "attn_kpe",
    "cross/wq": "cross_q", "cross/wkv": "cross_kv", "cross/wo": "cross_out",
    "mlp/wi": "mlp_up", "mlp/wo": "mlp_down",
    "moe/wi": "moe_up", "moe/wo": "moe_down",
    "ssm/in_zx": "ssm_in_zx", "ssm/in_bcdt": "ssm_in_bcdt",
    "ssm/out_proj": "ssm_out",
}


def site_for_path(pathstr: str) -> Optional[str]:
    """ctx site base name for an eligible weight-leaf path, else None."""
    for suffix, site in _SITE_BY_SUFFIX.items():
        if pathstr.endswith(suffix):
            return site
    return None


def _layer_prefix_format(pathstr: str) -> Optional[str]:
    """Eager site-name prefix format for a stacked leaf, e.g. 'layer{}/'.

    Only the decoder stacks (the layer groups 'layers', 'dense_layers') and
    the encoder stack ('enc_layers') have a 1:1 (stack index -> eager site
    prefix) mapping; the hybrid shared block is executed at several
    positions with ONE weight, so per-instance factors cannot be folded
    into it."""
    if pathstr.startswith("enc_layers/"):
        return "enc{}/"
    for key, kind in GROUP_SITE_PREFIX.items():
        if pathstr.startswith(key + "/"):
            return kind + "{}/"
    return None


def stacked_site_factors(pathstr: str, site: str, n_layers: int,
                         smooth_factors: Dict[str, np.ndarray]
                         ) -> Optional[np.ndarray]:
    """[L, in_ch] per-layer smoothing divisors for one stacked weight leaf,
    or None when any layer's factor is missing / the leaf is not foldable."""
    fmt = _layer_prefix_format(pathstr)
    if fmt is None or not smooth_factors:
        return None
    vals = [smooth_factors.get(fmt.format(i) + site) for i in range(n_layers)]
    if any(v is None for v in vals):
        return None
    return np.stack([np.asarray(v, np.float32) for v in vals])


def _pack_cfg(policy: SitePolicy, pathstr: str, site: str, n_layers: int):
    """Resolve the pack-relevant config for one weight leaf.

    Packing must agree with what the *eager* runtime resolves per layer
    (factors and masks are keyed by eager ``layer{i}/site`` names), so
    stacked leaves resolve every layer's eager name and require the
    pack-relevant projection — fp-ness, smooth-ness, weight bits,
    weight granularity — to be uniform across the stack; a layer-targeted
    rule that splits it raises instead of packing silently wrong.
    """
    fmt = _layer_prefix_format(pathstr)
    names = ([fmt.format(i) + site for i in range(n_layers)] if fmt
             else [site])
    cfgs = [policy.resolve(nm) for nm in names]
    keys = {(c.method == "fp", c.method in ("smoothquant", "muxq_smooth"),
             c.weight_bits, c.weight_granularity) for c in cfgs}
    if len(keys) > 1:
        raise ValueError(
            f"weight leaf {pathstr!r}: policy resolves layer-heterogeneous "
            f"pack configs {sorted(keys)}; stacked weight leaves pack "
            "uniformly — make layer-targeted rules agree on fp/smooth/"
            "weight_bits/weight_granularity, or use prequantize=False")
    return cfgs[0], fmt is not None


def _weight_scale(leaf: jnp.ndarray, bits: int, granularity: str) -> jnp.ndarray:
    """Per-(leading dims...) scale with keepdims, reducing the contraction
    axis (-2) — plus the out axis (-1) for per_tensor — so stacked [L, ...]
    leaves quantize per layer (and per expert for MoE)."""
    axes = {"per_channel": (-2,), "per_tensor": (-2, -1),
            "per_token": (-1,)}[granularity]
    amax = jnp.maximum(jnp.max(jnp.abs(leaf.astype(jnp.float32)),
                               axis=axes, keepdims=True), 1e-9)
    return amax / Q.qmax(bits)


def prequantize_params(cfg: ModelConfig, params, weight_bits: int = 8, *,
                       policy: Optional[SitePolicy] = None,
                       smooth_factors: Optional[Dict[str, np.ndarray]] = None):
    """Returns a new tree with eligible weight leaves replaced by
    {"q": int8 [...same shape], "s": f32 [..., 1, out]} dicts.

    Works on stacked [L, ...] leaves: per-(layer, out-channel) scales.
    With ``policy``, each site packs at its resolved weight_bits /
    weight_granularity (fp sites pass through untouched); ``smooth_factors``
    ({eager site: [in_ch] divisor}) are folded (``s*W``) before quantizing
    smooth-method sites.
    """
    def visit(path, leaf):
        pathstr = "/".join(str(getattr(p, "key", p)) for p in path)
        if not _WEIGHT_RE.search(pathstr):
            return leaf
        site = site_for_path(pathstr)
        bits, gran = weight_bits, "per_channel"
        if policy is not None and site is not None:
            scfg, foldable = _pack_cfg(policy, pathstr, site, leaf.shape[0])
            if scfg.method == "fp":
                return leaf
            bits, gran = scfg.weight_bits, scfg.weight_granularity
            if scfg.method in ("smoothquant", "muxq_smooth"):
                # the runtime applies X/s assuming Q(s*W) was packed: a leaf
                # we cannot fold (shared multi-instance weights, missing
                # per-layer factors) must fail loudly, not pack un-smoothed
                S = (stacked_site_factors(pathstr, site, leaf.shape[0],
                                          smooth_factors or {})
                     if foldable else None)
                if S is None:
                    raise ValueError(
                        f"weight leaf {pathstr!r}: method {scfg.method!r} "
                        "needs per-layer smooth factors folded into the "
                        "packed weight, but none cover this leaf (shared/"
                        "multi-instance weights cannot fold a per-instance "
                        "factor) — use prequantize=False for this policy")
                # [L, d] -> [L, ...1..., d, 1] against [L, ..., d, out]
                S = S.reshape(S.shape[0],
                              *([1] * (leaf.ndim - 3)), S.shape[1], 1)
                leaf = (leaf * jnp.asarray(S)).astype(leaf.dtype)
        s = _weight_scale(leaf, bits, gran)
        q, _ = Q.quantize(leaf, bits, scale=s)
        return {"q": q, "s": s.astype(jnp.float32)}

    return jax.tree_util.tree_map_with_path(visit, params)


def prequant_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
