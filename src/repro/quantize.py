"""First-class model quantization: calibrate → plan → prequantize → pack.

The single entrypoint :func:`quantize_model` turns (model cfg, params,
calibration data, :class:`~repro.core.policy.SitePolicy`) into one saveable
:class:`QuantArtifact` bundling everything the runtime needs:

  * the resolved per-site policy table,
  * calibrated static outlier masks (``{eager site: [ch] bool}``),
  * calibrated activation abs-max per site (SmoothQuant raw material),
  * folded smoothing divisors for smooth-method sites,
  * the offline-packed int8 weight tree (``{"q", "s"}`` leaves),
  * kernel-ready packed buffers for fused-backend sites
    (``repro.kernels.dispatch`` format: permutation gather, zero padding,
    per-K-block exponent scales, int8 weights), and
  * stacked ``[L, ch]`` qparams for ``lax.scan``-ed layer loops
    (masks under the bare site name, divisors under ``{site}@smooth``,
    stacked kernel buffers under ``{site}@fused``).

Every consumer — ``ServeEngine``, the launch step builders, benchmarks —
takes the artifact directly; there is no ``(quant, qparams, masks, smooths)``
four-tuple plumbing.  ``save``/``load`` use the atomic bundle machinery in
``repro.checkpoint.ckpt``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Iterable, Optional, Union

import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core import smoothquant as SQ
from repro.core.context import QuantCtx
from repro.core.muxq import QuantConfig
from repro.core.outliers import CalibrationStats
from repro.core.policy import SitePolicy, as_policy
from repro.core.prequant import prequantize_params
from repro.kernels import dispatch
from repro.models.common import GROUP_SITE_PREFIX, group_name

_SMOOTH_METHODS = ("smoothquant", "muxq_smooth")
# v1: no kernel_buffers group, policy configs without a backend field.
# v2 (current): + kernel_buffers group, nested (dict-valued) scan_qparams
# entries flattened with '#'.  Loading accepts 1..=_FORMAT_VERSION.
# v2 bundles may also carry a "pack_target" meta field ("both" when absent):
# "fused" bundles store stub {"q","s"} tree leaves for fused sites, "tree"
# bundles omit kernel_buffers.npz / "@fused" scan entries entirely — both
# load through the normal missing-group path.
# v3 (current): + kv_calib group (int4 KV-page calibration: per-layer
# per-head K/V channel amax, pooled outlier masks, redistribution exponent
# — see repro.serve.kvq).  Absent in v1/v2 bundles and in bundles whose
# calibration never ran; loads as an empty dict either way.
_FORMAT_VERSION = 3

PACK_TARGETS = ("both", "fused", "tree")

# ctx site base name -> weight-leaf path inside one layer's param subtree.
# "mlp_*" has a fallback: in MoE layers the shared expert reuses mlp() (its
# eager sites are layer{i}/mlp_up|down) but its weights live under
# moe/shared/.
_SITE_WEIGHT_PATH = {
    "attn_qkv": ("attn", "wqkv"), "attn_out": ("attn", "wo"),
    "attn_q": ("attn", "wq"), "attn_kpe": ("attn", "wkpe"),
    "cross_q": ("cross", "wq"), "cross_kv": ("cross", "wkv"),
    "cross_out": ("cross", "wo"),
    "mlp_up": ("mlp", "wi"), "mlp_down": ("mlp", "wo"),
    "moe_up": ("moe", "wi"), "moe_down": ("moe", "wo"),
    "ssm_in_zx": ("ssm", "in_zx"), "ssm_in_bcdt": ("ssm", "in_bcdt"),
    "ssm_out": ("ssm", "out_proj"),
}
_SITE_WEIGHT_FALLBACK = {
    "mlp_up": ("moe", "shared", "wi"), "mlp_down": ("moe", "shared", "wo"),
}

_SITE_RE = re.compile(r"^(layer|dense|enc|shared)(\d+)/(.+)$")
# eager site kind -> the parameter tree's stack of those layers
_SITE_ROOT = {"layer": "layers", "dense": "dense_layers", "enc": "enc_layers",
              "shared": "shared"}


def split_site(site: str):
    """'layer3/mlp_up' -> ('layer', 3, 'mlp_up'); bare names -> (None, None, site)."""
    m = _SITE_RE.match(site)
    if m is None:
        return None, None, site
    return m.group(1), int(m.group(2)), m.group(3)


def _site_leaf(params, site: str) -> Optional[jnp.ndarray]:
    """This eager site's per-layer weight leaf ([in_ch, out] or, for MoE
    expert sites, [E, in_ch, out]; contraction axis -2), or None when the
    site has no addressable weight leaf (unknown naming)."""
    kind, idx, base = split_site(site)
    path = _SITE_WEIGHT_PATH.get(base)
    if path is None:
        return None
    root = _SITE_ROOT.get(kind)
    if root is None:
        return None
    leaf = None
    for candidate in (path, _SITE_WEIGHT_FALLBACK.get(base)):
        if candidate is None:
            continue
        try:
            node = params[root]
            for p in candidate:
                node = node[p]
            leaf = node
            break
        except (KeyError, TypeError):
            continue
    if leaf is None:
        return None
    if root != "shared":
        leaf = leaf[idx]                       # stacked [L, ...] -> this layer
    return jnp.asarray(leaf)


def _site_weight(params, site: str) -> Optional[jnp.ndarray]:
    """The 2-D [in_ch, flattened_out] weight consumed at an eager site."""
    leaf = _site_leaf(params, site)
    if leaf is None:
        return None
    # contraction axis is -2; flatten everything else into the out dim
    leaf = jnp.moveaxis(leaf, -2, 0)
    return leaf.reshape(leaf.shape[0], -1)


def _flatten_nested(group: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """One level of dict nesting -> npz-storable flat keys ('{key}#{field}');
    array values pass through.  Inverse of :func:`_unflatten_nested`."""
    flat: Dict[str, np.ndarray] = {}
    for key, val in group.items():
        if isinstance(val, dict):
            for field, arr in val.items():
                flat[f"{key}#{field}"] = np.asarray(arr)
        else:
            flat[key] = np.asarray(val)
    return flat


def _unflatten_nested(flat: Dict[str, np.ndarray]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for key, val in flat.items():
        if "#" in key:
            base, field = key.rsplit("#", 1)
            out.setdefault(base, {})[field] = val
        else:
            out[key] = val
    return out


@dataclasses.dataclass
class QuantArtifact:
    """Everything quantized execution needs, in one saveable object.

    ``params`` is the offline-packed weight tree (int8 ``{"q","s"}`` leaves,
    other leaves untouched) or None for quantize-at-use artifacts.
    ``kernel_buffers`` holds the fused-backend packed buffers
    ({eager site: {field: array}} — ``repro.kernels.dispatch`` format).
    ``scan_qparams`` carries stacked per-layer state for scanned loops
    (dict-valued ``{site}@fused`` entries stack kernel buffers).
    """
    policy: SitePolicy
    masks: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    act_absmax: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    smooth_factors: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    scan_qparams: Dict[str, Any] = dataclasses.field(default_factory=dict)
    kernel_buffers: Dict[str, Dict[str, np.ndarray]] = dataclasses.field(
        default_factory=dict)
    params: Any = None
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # int4 KV-page calibration (repro.serve.kvq.build_kv_calib): k/v_amax
    # [L, kvh, dh], pooled k/v_mask [kvh, dh], exp_factor, outlier_ratio.
    # Empty when calibration never ran an attention forward.
    kv_calib: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)

    @property
    def prequantized(self) -> bool:
        return self.params is not None

    def ctx(self) -> QuantCtx:
        """A QuantCtx wired to this artifact (eager / unscanned paths)."""
        return QuantCtx(self)

    # -- persistence (atomic bundle dir via repro.checkpoint.ckpt) -----------

    def save(self, path: str, pack_target: str = "both") -> str:
        """Persist the bundle.  ``pack_target`` ("both" | "fused" | "tree")
        drops the duplicate per-weight copy the deployment never reads —
        see :func:`apply_pack_target`; the saved bundle records the choice
        in ``meta.json`` and loads through the normal missing-group path."""
        art = apply_pack_target(self, pack_target)
        groups = {
            "masks": art.masks,
            "act_absmax": art.act_absmax,
            "smooth_factors": art.smooth_factors,
            "scan_qparams": _flatten_nested(art.scan_qparams),
            "kernel_buffers": _flatten_nested(art.kernel_buffers),
            "params": ckpt._flatten(art.params) if art.prequantized else {},
            "kv_calib": art.kv_calib,
        }
        meta = {"format_version": _FORMAT_VERSION,
                "policy": art.policy.to_json(),
                "prequantized": art.prequantized,
                **art.meta}
        return str(ckpt.save_bundle(path, groups, meta))

    @classmethod
    def load(cls, path: str) -> "QuantArtifact":
        groups, meta = ckpt.load_bundle(
            path, ["masks", "act_absmax", "smooth_factors", "scan_qparams",
                   "kernel_buffers", "params", "kv_calib"])
        policy = SitePolicy.from_json(meta.pop("policy"))
        version = meta.pop("format_version", None)
        # backward-compatible: v1 bundles (no kernel_buffers group, policies
        # without a backend field) load as all-'fake'-backend artifacts
        if not isinstance(version, int) or not 1 <= version <= _FORMAT_VERSION:
            raise ValueError(f"unsupported artifact format {version!r}")
        prequantized = meta.pop("prequantized", bool(groups["params"]))
        params = ckpt._nest(groups["params"]) if prequantized else None
        return cls(policy=policy, masks=groups["masks"],
                   act_absmax=groups["act_absmax"],
                   smooth_factors=groups["smooth_factors"],
                   scan_qparams=_unflatten_nested(groups["scan_qparams"]),
                   kernel_buffers=_unflatten_nested(groups["kernel_buffers"]),
                   params=params, meta=meta, kv_calib=groups["kv_calib"])


# ---------------------------------------------------------------------------
# Pack targets: drop the per-weight copy the deployment never reads
# ---------------------------------------------------------------------------

def _stacked_leaf_ref(params, site: str):
    """(parent dict, key) addressing the STACKED weight leaf consumed at an
    eager ``layer{i}/``/``enc{i}/`` site, or None when unaddressable.  The
    hybrid shared block is excluded: its instance count is not derivable
    from the leaf shape, so coverage cannot be verified artifact-side."""
    kind, _, base = split_site(site)
    path = _SITE_WEIGHT_PATH.get(base)
    root = _SITE_ROOT.get(kind) if kind != "shared" else None
    if path is None or root is None:
        return None
    for candidate in (path, _SITE_WEIGHT_FALLBACK.get(base)):
        if candidate is None:
            continue
        try:
            node = params[root]
            for p in candidate[:-1]:
                node = node[p]
            node[candidate[-1]]
            return node, candidate[-1], (root,) + tuple(candidate)
        except (KeyError, TypeError):
            continue
    return None


def _replace_leaf(params, path, value):
    """Copy-on-write leaf replacement (the caller's tree stays untouched)."""
    new = dict(params)
    node = new
    for p in path[:-1]:
        node[p] = dict(node[p])
        node = node[p]
    node[path[-1]] = value
    return new


def _defused_policy(policy: SitePolicy) -> SitePolicy:
    """Rewrite every fused-backend config to the fake backend (the 'tree'
    pack target drops the kernel buffers, so fused routing must go too —
    a policy pointing at buffers that no longer exist would refuse to run)."""
    def defuse(c: QuantConfig) -> QuantConfig:
        if c.method != "fp" and getattr(c, "backend", "fake") == "fused":
            return c.replace(backend="fake")
        return c
    return SitePolicy(default=defuse(policy.default),
                      rules=tuple((p, defuse(c)) for p, c in policy.rules))


def apply_pack_target(artifact: "QuantArtifact",
                      pack_target: str) -> "QuantArtifact":
    """Drop the duplicate per-weight copy a single-backend deployment never
    reads (fused sites are otherwise stored twice: int8 ``{"q","s"}`` tree
    leaf AND packed kernel buffer, ~1 byte/weight each).

      * ``"both"``  — keep both copies (the default; the artifact serves
        either backend, e.g. fused production + fake calibration-parity);
      * ``"fused"`` — fused sites keep only the kernel buffers; their
        packed tree leaves shrink to inert ``[L, 1, ..]`` stubs (the tree
        stays scan-shaped, and misrouting a stubbed site to the fake
        backend fails loudly on shape, not silently on garbage).  Only
        stacked leaves whose EVERY layer is fused are stubbed;
      * ``"tree"``  — drop the kernel buffers and ``{site}@fused`` scan
        stacks; the policy's fused backends rewrite to ``fake`` so the
        artifact stays runnable as-is.
    """
    if pack_target not in PACK_TARGETS:
        raise ValueError(f"unknown pack_target {pack_target!r} "
                         f"(expected one of {PACK_TARGETS})")
    if pack_target == "both":
        return artifact
    if pack_target == "tree":
        scan_qp = {k: v for k, v in artifact.scan_qparams.items()
                   if not k.endswith("@fused")}
        return dataclasses.replace(
            artifact, policy=_defused_policy(artifact.policy),
            kernel_buffers={}, scan_qparams=scan_qp,
            meta={**artifact.meta, "pack_target": "tree", "n_fused_sites": 0})

    # "fused": stub the tree copy of every fully-fused stacked leaf
    params = artifact.params
    if params is None or not artifact.kernel_buffers:
        return dataclasses.replace(
            artifact, meta={**artifact.meta, "pack_target": "fused"})
    seen = set()
    for site in artifact.kernel_buffers:
        kind, _, base = split_site(site)
        if (kind, base) in seen or kind not in ("layer", "dense", "enc"):
            continue
        seen.add((kind, base))
        ref = _stacked_leaf_ref(params, site)
        if ref is None:
            continue
        node, key, path = ref
        leaf = node[key]
        if not (isinstance(leaf, dict) and "q" in leaf):
            continue                    # not packed (fp site etc.)
        n = int(leaf["q"].shape[0])
        if not all(f"{kind}{i}/{base}" in artifact.kernel_buffers
                   for i in range(n)):
            continue                    # partial fused coverage: keep copy
        stub = {"q": np.zeros((n,) + (1,) * (leaf["q"].ndim - 1), np.int8),
                "s": np.ones((n,) + (1,) * (leaf["s"].ndim - 1), np.float32)}
        params = _replace_leaf(params, path, stub)
    return dataclasses.replace(
        artifact, params=params,
        meta={**artifact.meta, "pack_target": "fused"})


def _run_calibration(cfg, params, batches, forward):
    """Eager calibration pass.  Returns (matmul-site CalibrationStats,
    kv_calib dict) — the same forwards feed both: the ctx hook sees every
    matmul input, and a KV observer installed over
    ``models.attention.attention`` captures the post-RoPE K/V projections
    for the int4 KV-page calibration (``repro.serve.kvq``)."""
    from repro.core.calibrate import calibrate
    from repro.models import attention as A
    from repro.serve import kvq
    if forward is None:
        from repro.models import transformer as T
        forward = lambda p, b, ctx: T.forward(
            cfg, p, jnp.asarray(b["tokens"]), ctx, scan=False)
    collector = kvq.KVCalibCollector()
    A.set_kv_observer(collector)
    try:
        stats, _, _ = calibrate(forward, params, batches)
    finally:
        A.set_kv_observer(None)
    return stats, kvq.build_kv_calib(collector)


def _scan_key(cfg, kind: str, base: str) -> str:
    """qparams key the scanned model looks up for one eager site base of a
    layer group (``kind``: the group's eager prefix, see
    ``common.GROUP_SITE_PREFIX``).

    In expert layers the shared expert runs through mlp() — its eager
    sites are 'layer{i}/mlp_up|down' but moe() routes the scanned sq under
    'moe_shared_up|down'.  A group other than the main one keys its
    entries '<group>/<site>'."""
    group = {v: k for k, v in GROUP_SITE_PREFIX.items()}[kind]
    if (kind == "layer" and getattr(cfg, "family", None) == "moe"
            and "experts" in cfg.ffn_kinds and base in ("mlp_up", "mlp_down")):
        base = "moe_shared_" + base.split("_", 1)[1]
    return group_name(group, base)


def _groups(cfg):
    """(eager prefix kind, layer count) of each stacked decoder group."""
    if getattr(cfg, "family", None) in ("dense", "moe"):
        return [(GROUP_SITE_PREFIX[k], hi - lo) for k, lo, hi in cfg.layer_groups]
    return [("layer", cfg.n_layers)]


def _stack_qparams(cfg, masks: Dict[str, np.ndarray],
                   factors: Dict[str, np.ndarray],
                   buffers: Optional[Dict[str, dict]] = None
                   ) -> Dict[str, Any]:
    """{site: [L, ch]} stacked state for scanned layer loops, built per
    layer group from eager '<kind>{i}/...' entries that cover every layer
    of the group.  Kernel buffers stack field-wise under '{site}@fused'
    (layers whose packed widths differ are first padded to a uniform K_pad
    with inert blocks)."""
    out: Dict[str, Any] = {}
    buffers = buffers or {}
    for kind, n in _groups(cfg):
        for source, suffix in ((masks, ""), (factors, "@smooth")):
            bases = {split_site(s)[2] for s in source
                     if split_site(s)[0] == kind}
            for base in sorted(bases):
                vals = [source.get(f"{kind}{i}/{base}") for i in range(n)]
                if any(v is None for v in vals):
                    continue            # partial coverage: eager path only
                out[_scan_key(cfg, kind, base) + suffix] = np.stack(
                    [np.asarray(v) for v in vals])
        bases = {split_site(s)[2] for s in buffers
                 if split_site(s)[0] == kind}
        for base in sorted(bases):
            vals = [buffers.get(f"{kind}{i}/{base}") for i in range(n)]
            if any(v is None for v in vals):
                continue                # partial coverage: eager path only
            k_pad = max(dispatch.buffer_k_pad(v) for v in vals)
            vals = [dispatch.pad_buffer_to(v, k_pad) for v in vals]
            out[_scan_key(cfg, kind, base) + "@fused"] = {
                f: np.stack([v[f] for v in vals])
                for f in dispatch.BUFFER_FIELDS}
    return out


def _fused_sites(cfg, params, policy: SitePolicy):
    """Yield (eager site, resolved cfg) for every addressable weight leaf
    whose policy resolves to the fused backend.  Enumerated from the param
    tree (not calibration stats) so maskless fused policies — e.g. uniform
    'naive' int8 — pack without a calibration pass.  The hybrid family's
    shared block packs one buffer per execution instance (``shared{i}/``
    sites share the weight but carry per-instance masks)."""
    k_every = getattr(cfg, "shared_attn_every", 0) or 0
    stacks = (*_groups(cfg),
              ("enc", getattr(cfg, "n_enc_layers", 0) or 0),
              ("shared", sum(1 for i in range(cfg.n_layers)
                             if i % k_every == k_every - 1) if k_every else 0))
    for kind, n in stacks:
        if not n:
            continue
        for base in _SITE_WEIGHT_PATH:
            if _site_leaf(params, f"{kind}0/{base}") is None:
                continue
            for i in range(n):
                site = f"{kind}{i}/{base}"
                scfg = policy.resolve(site)
                if scfg.method != "fp" and dispatch.site_backend(scfg) == "fused":
                    yield site, scfg


def _pack_kernel_buffers(cfg, params, policy: SitePolicy,
                         masks: Dict[str, np.ndarray],
                         factors: Dict[str, np.ndarray]
                         ) -> Dict[str, Dict[str, np.ndarray]]:
    """Kernel-ready packed buffer per fused-backend site (dispatch format).

    Smooth-method sites fold their per-channel divisor into the weight
    (``Q(s*W)``) before packing, mirroring ``prequantize_params``; the
    runtime applies ``X/s``.  muxq-family sites require a calibrated static
    mask — packing bakes the channel permutation offline.

    Fused sites are by default ALSO packed into the ``{"q","s"}`` weight
    tree (both copies are int8, so the bundle carries ~2 bytes/weight for
    them): the fused path never reads the tree leaves, but the same
    artifact then still serves with the backend overridden to ``fake``
    (calibration-parity runs, backends without the kernel).  The
    ``pack_target`` option of :func:`quantize_model` /
    :meth:`QuantArtifact.save` drops the copy a deployment never reads.
    """
    buffers: Dict[str, Dict[str, np.ndarray]] = {}
    for site, scfg in _fused_sites(cfg, params, policy):
        leaf = _site_leaf(params, site)
        mask = masks.get(site)
        if scfg.method in ("muxq", "muxq_smooth") and mask is None:
            raise ValueError(
                f"site {site!r}: fused {scfg.method!r} needs a calibrated "
                "static outlier mask — pass calibration data (the channel "
                "permutation is baked at pack time)")
        if scfg.method in _SMOOTH_METHODS:
            factor = factors.get(site)
            if factor is None:
                raise ValueError(
                    f"site {site!r}: fused {scfg.method!r} needs folded "
                    "smooth factors — pass calibration data")
            leaf = (leaf * jnp.asarray(factor)[..., :, None]).astype(leaf.dtype)
        buffers[site] = dispatch.pack_site_buffer(leaf, mask, scfg)
    return buffers


def quantize_model(cfg, params,
                   calib: Union[None, CalibrationStats, Iterable],
                   policy: Union[QuantConfig, SitePolicy], *,
                   forward=None, prequantize: bool = True,
                   pack_target: str = "both") -> QuantArtifact:
    """calibrate → plan → prequantize → pack, in one call.

    ``calib`` is an iterable of batches (run eagerly through ``forward``,
    default: the transformer LM forward), an already-collected
    :class:`CalibrationStats`, or None when the policy needs no calibration
    (all-dynamic, no smoothing).  ``prequantize=False`` skips weight packing
    (the paper's fake-quant evaluation protocol — benchmark grids).
    ``pack_target`` ("both" | "fused" | "tree") drops the duplicate
    per-weight copy of fused sites that the deployment never reads — see
    :func:`apply_pack_target`.
    """
    policy = as_policy(policy)
    stats: Optional[CalibrationStats] = None
    kv_calib = None
    if isinstance(calib, CalibrationStats):
        stats = calib       # precollected: no forwards run, no KV stats
    elif calib is not None:
        stats, kv_calib = _run_calibration(cfg, params, calib, forward)
    if stats is None and policy.needs_calibration():
        raise ValueError("policy needs static masks / smoothing factors but "
                         "no calibration data or stats were given")

    # plan: resolve every calibrated site against the policy
    masks: Dict[str, np.ndarray] = {}
    absmax: Dict[str, np.ndarray] = {}
    factors: Dict[str, np.ndarray] = {}
    for site, st in (stats.sites.items() if stats else ()):
        scfg = policy.resolve(site)
        if scfg.method == "fp":
            continue
        absmax[site] = np.asarray(st.absmax, np.float32)
        if scfg.outlier_mode == "static":
            masks[site] = np.asarray(st.mask(scfg.outlier_threshold))
        if scfg.method in _SMOOTH_METHODS:
            w2 = _site_weight(params, site)
            if w2 is None:
                if prequantize:
                    raise ValueError(
                        f"cannot fold smoothing for site {site!r}: no "
                        "addressable weight leaf (use prequantize=False)")
                continue
            factors[site] = np.asarray(
                SQ.smoothing_factors(jnp.asarray(st.absmax), w2,
                                     scfg.smooth_alpha), np.float32)

    packed = None
    buffers: Dict[str, Dict[str, np.ndarray]] = {}
    if prequantize:
        packed = prequantize_params(cfg, params, policy=policy,
                                    smooth_factors=factors)
        buffers = _pack_kernel_buffers(cfg, params, policy, masks, factors)

    art = QuantArtifact(
        policy=policy, masks=masks, act_absmax=absmax, smooth_factors=factors,
        scan_qparams=_stack_qparams(cfg, masks, factors, buffers),
        kernel_buffers=buffers, params=packed,
        meta={"n_sites": len(absmax), "n_fused_sites": len(buffers)},
        kv_calib=kv_calib or {})
    return apply_pack_target(art, pack_target)


def save_artifact(artifact: QuantArtifact, path: str) -> str:
    return artifact.save(path)


def load_artifact(path: str) -> QuantArtifact:
    return QuantArtifact.load(path)
