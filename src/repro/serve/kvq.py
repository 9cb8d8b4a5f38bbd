"""KV-quantization subsystem: fp / int8 / int4 page modes behind one seam.

:class:`KVQuantizer` is the single quantize/dequantize entry point for every
KV write and ref-path read in the serving stack — the paged pool
(``serve/pool.py``), the dense decode cache and both paged attention paths
(``models/attention.py``) all route through it instead of open-coding the
int8 math per call site.

Modes:

  * ``fp``   — pages at the pool dtype (parity mode, lossless);
  * ``int8`` — per-(position, head) abs-max int8 over ``head_dim``
    (:func:`repro.serve.kvcache.quantize_kv`, Oaken-style);
  * ``int4`` — MUXQ'd nibble pages: calibrated per-head outlier channels
    along ``head_dim`` are *magnitude-redistributed* (divided by ``2^e``,
    the paper's Eq. 4 decompose) before a symmetric 4-bit quantization, so
    one hot channel no longer dictates the whole head's scale; the read
    path multiplies the outlier channels back by ``2^e`` (Eq. 6
    reconstruct, fused single-multiply form).  K/V pack two values per
    byte (``[..., dh] int4 -> [..., dh//2] int8``) and scales store as
    bf16, so an int4 page costs exactly half an int8 page:
    ``(dh/2 + 2) / (dh + 4)`` bytes per (position, head).

**Calibration.**  The outlier masks come from per-layer, per-head K/V
channel amax gathered by a forward hook over the calibration batches
(:class:`KVCalibCollector`, installed by ``repro.quantize.quantize_model``).
Per-layer masks on a small model are unsystematic, so — following the
bitsandbytes ``GlobalOutlierPooler`` idiom — channel outlier sets are
POOLED across layers (set union per head, capped at ``max_frac`` of
``head_dim`` by pooled amax) into one stable ``[kvh, dh]`` mask per K and
V.  The pooled stats persist as the ``kv_calib`` section of the
``QuantArtifact`` bundle and flow into :class:`Int4KVQuantizer` at pool
construction (``ServeEngine`` -> ``PagePool``).

**Latent pages** (``latent=True``, latent attention): one row per
position holds the normed latent and the rotated rotary key, shared by
every head, under the single key ``k`` (``[..., 1, ps, width]``); fp and
int8 modes only, int8 with one float32 scale per position.

Inside traced model code the mode is discovered from the cache dict's key
set (:func:`from_cache`): int4 pages carry per-layer ``k_redist``/
``v_redist`` rows, int8 pages carry ``k_scale`` without them, fp pages
carry neither, and latent pages have no ``v`` — the same sentinel
convention the scan bodies in ``models/transformer.py`` thread through
``lax.scan``.

**Head-locality.**  Every quantity here is local to one (position, head)
cell (int8 scales) or one head row (int4 redist rows + masks) — nothing
reduces across heads.  Tensor-parallel serving leans on that invariance:
sharding pages, scales and redist rows on the KV-head axis
(``parallel/serve_sharding.py``) commutes with quantize/dequantize, so
int8/int4 streams under a mesh are exactly the single-device streams (the
parity tests in ``tests/test_serve_tp.py`` pin this).

This module deliberately imports nothing from ``repro.models`` or
``repro.kernels`` so the Pallas kernel can share :func:`unpack_int4`
without an import cycle.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

KV_MODES = ("fp", "int8", "int4")

INT4_MAX = 7                   # symmetric [-7, 7]: amax maps to +/-7
DEFAULT_EXP_FACTOR = 2         # MUXQ 2^e magnitude shift (core.muxq default)
DEFAULT_OUTLIER_RATIO = 4.0    # channel amax > ratio * head median => outlier
DEFAULT_MAX_FRAC = 0.25        # cap pooled outliers per head (top-k fallback)
_SCALE_FLOOR = 1e-6            # matches kvcache.quantize_kv's zero-vector floor


# ---------------------------------------------------------------------------
# Nibble packing: two int4 values per int8 byte along head_dim
# ---------------------------------------------------------------------------

def pack_int4(x: jnp.ndarray) -> jnp.ndarray:
    """[..., dh] int8 values in [-8, 7] -> [..., dh//2] int8 bytes.

    Half-split layout: byte ``j`` holds channel ``j`` in its low nibble and
    channel ``j + dh//2`` in its high nibble, so unpacking is one
    concatenate (no lane interleave — TPU-layout-friendly)."""
    dh = x.shape[-1]
    assert dh % 2 == 0, f"head_dim must be even to nibble-pack, got {dh}"
    h = dh // 2
    lo, hi = x[..., :h], x[..., h:]
    return jnp.bitwise_or(jnp.bitwise_and(lo, 0xF),
                          jnp.left_shift(hi, 4)).astype(jnp.int8)


def unpack_int4(p: jnp.ndarray) -> jnp.ndarray:
    """[..., dh//2] int8 bytes -> [..., dh] int8 values (sign-extended).

    Inverse of :func:`pack_int4`; int32 shifts so the same expression works
    inside a Pallas kernel body."""
    p32 = p.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(p32, 28), 28)   # arithmetic >> : sign
    hi = jnp.right_shift(jnp.left_shift(p32, 24), 28)   # extends the nibble
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.int8)


# ---------------------------------------------------------------------------
# The quantizer seam
# ---------------------------------------------------------------------------

class KVQuantizer:
    """One KV page mode's quantize (write) / dequantize (read) pair plus the
    pool-array layout it needs.  ``quantize`` returns a dict whose keys name
    the page arrays the values scatter into; ``dequantize`` accepts the same
    key set (possibly gathered, with extra leading dims)."""

    mode: str = "fp"
    latent: bool = False        # one shared latent row per position, no V
    # symmetric integer ceiling of the mode's codes (None for fp pages);
    # the quality observer (repro.obs.quality) reads this to count
    # saturated codes when it samples live pool pages
    qmax: Optional[int] = None

    def quantize(self, k: jnp.ndarray, v: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        raise NotImplementedError

    def dequantize(self, parts: Dict[str, jnp.ndarray], dtype
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def page_arrays(self, L: int, n_pages: int, ps: int, kvh: int, dh: int
                    ) -> Dict[str, jnp.ndarray]:
        """Zero-initialized pool arrays, all laid out head-major
        [L, n_pages, kvh, ps, ...] (a (page, head) block is a (ps, dh) tile
        the paged-attention kernel loads whole)."""
        raise NotImplementedError

    def pool_state(self, L: int, kvh: int, dh: int) -> Dict[str, jnp.ndarray]:
        """Non-page pool state stacked [L, ...] so it rides the same
        ``lax.scan`` xs as the page arrays (int4: redistribution rows)."""
        return {}

    def bytes_per_token(self, kvh: int, dh: int) -> int:
        """Page bytes one token position costs across K and V (one layer)."""
        raise NotImplementedError

    def kernel_operands(self, cache: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        """Extra keyword operands for ``paged_attention_decode`` beyond the
        packed pages themselves (scales, redistribution rows)."""
        return {}


class FpKVQuantizer(KVQuantizer):
    mode = "fp"

    def __init__(self, dtype=jnp.bfloat16, latent: bool = False):
        self.dtype, self.latent = dtype, latent

    def quantize(self, k, v=None):
        if self.latent:
            return {"k": k.astype(self.dtype)}
        return {"k": k.astype(self.dtype), "v": v.astype(self.dtype)}

    def dequantize(self, parts, dtype):
        if self.latent:
            return parts["k"].astype(dtype), None
        return parts["k"].astype(dtype), parts["v"].astype(dtype)

    def page_arrays(self, L, n_pages, ps, kvh, dh):
        shape = (L, n_pages, kvh, ps, dh)
        out = {"k": jnp.zeros(shape, self.dtype)}
        if not self.latent:
            out["v"] = jnp.zeros(shape, self.dtype)
        return out

    def bytes_per_token(self, kvh, dh):
        return (1 if self.latent else 2) * kvh * dh * jnp.dtype(
            self.dtype).itemsize


class Int8KVQuantizer(KVQuantizer):
    """Per-(position, head) abs-max int8 (delegates to the historical
    ``kvcache.quantize_kv`` math — the serve tests pin its exact scales)."""

    mode = "int8"
    qmax = 127

    def __init__(self, latent: bool = False):
        self.latent = latent

    def quantize(self, k, v=None):
        from repro.serve.kvcache import quantize_kv, quantize_rows
        if self.latent:
            ki, ks = quantize_rows(k)
            return {"k": ki, "k_scale": ks}
        return quantize_kv(k, v)

    def dequantize(self, parts, dtype):
        k = (parts["k"].astype(jnp.float32) * parts["k_scale"]).astype(dtype)
        if self.latent:
            return k, None
        v = (parts["v"].astype(jnp.float32) * parts["v_scale"]).astype(dtype)
        return k, v

    def page_arrays(self, L, n_pages, ps, kvh, dh):
        shape = (L, n_pages, kvh, ps, dh)
        names = ("k",) if self.latent else ("k", "v")
        out = {n: jnp.zeros(shape, jnp.int8) for n in names}
        out.update({f"{n}_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32)
                    for n in names})
        return out

    def bytes_per_token(self, kvh, dh):
        # int8 payload + f32 scale, for K and V (a latent row: once)
        return (1 if self.latent else 2) * kvh * (dh + 4)

    def kernel_operands(self, cache):
        if self.latent:
            return {"k_scale": cache["k_scale"]}
        return {"k_scale": cache["k_scale"], "v_scale": cache["v_scale"]}


class Int4KVQuantizer(KVQuantizer):
    """MUXQ'd int4 nibble pages with calibrated outlier redistribution.

    ``k_redist``/``v_redist`` are ``[kvh, dh]`` (or ``[L, kvh, dh]``, or any
    shape broadcastable against ``[..., kvh, dh]``) multipliers: ``2^e`` on
    calibrated outlier channels, 1 elsewhere.  The write path divides by
    them before quantizing (decompose — the outlier's magnitude no longer
    inflates the head's abs-max scale), the read path multiplies them back
    (reconstruct).  Scales are bf16, keeping the int4 page at exactly half
    the int8 page's bytes."""

    mode = "int4"
    qmax = INT4_MAX
    scale_dtype = jnp.bfloat16

    def __init__(self, k_redist, v_redist):
        self.k_redist = jnp.asarray(k_redist, jnp.float32)
        self.v_redist = jnp.asarray(v_redist, jnp.float32)

    def _q(self, x, redist):
        body = x.astype(jnp.float32) / redist
        amax = jnp.maximum(jnp.max(jnp.abs(body), axis=-1, keepdims=True),
                           _SCALE_FLOOR)
        s = (amax / INT4_MAX).astype(self.scale_dtype)
        xi = jnp.clip(jnp.round(body / s.astype(jnp.float32)),
                      -INT4_MAX, INT4_MAX).astype(jnp.int8)
        return pack_int4(xi), s

    def quantize(self, k, v):
        ki, ks = self._q(k, self.k_redist)
        vi, vs = self._q(v, self.v_redist)
        return {"k": ki, "k_scale": ks, "v": vi, "v_scale": vs}

    def _dq(self, p, s, redist, dtype):
        x = unpack_int4(p).astype(jnp.float32) * s.astype(jnp.float32)
        return (x * redist).astype(dtype)

    def dequantize(self, parts, dtype):
        return (self._dq(parts["k"], parts["k_scale"], self.k_redist, dtype),
                self._dq(parts["v"], parts["v_scale"], self.v_redist, dtype))

    def page_arrays(self, L, n_pages, ps, kvh, dh):
        assert dh % 2 == 0, f"int4 pages need an even head_dim, got {dh}"
        shape = (L, n_pages, kvh, ps, dh // 2)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1] + (1,), self.scale_dtype),
                "v_scale": jnp.zeros(shape[:-1] + (1,), self.scale_dtype)}

    def pool_state(self, L, kvh, dh):
        def stack(r):
            r = jnp.broadcast_to(r, (kvh, dh)) if r.ndim < 3 else r
            return (jnp.broadcast_to(r[None], (L, kvh, dh))
                    if r.ndim == 2 else r)
        return {"k_redist": stack(self.k_redist),
                "v_redist": stack(self.v_redist)}

    def bytes_per_token(self, kvh, dh):
        return 2 * kvh * (dh // 2 + 2)     # nibble payload + bf16 scale

    def kernel_operands(self, cache):
        return {"k_scale": cache["k_scale"], "v_scale": cache["v_scale"],
                "k_redist": cache["k_redist"], "v_redist": cache["v_redist"]}


def redist_from_mask(mask, exp_factor: int = DEFAULT_EXP_FACTOR) -> np.ndarray:
    """[kvh, dh] bool outlier mask -> [kvh, dh] f32 multiplier (2^e / 1)."""
    return np.where(np.asarray(mask, bool),
                    np.float32(2.0 ** exp_factor), np.float32(1.0))


def make_quantizer(mode: str, *, kvh: int, dh: int, dtype=jnp.bfloat16,
                   calib: Optional[Dict[str, np.ndarray]] = None,
                   latent: bool = False) -> KVQuantizer:
    """Quantizer for a pool mode.  ``calib`` is the artifact's ``kv_calib``
    section (see :func:`build_kv_calib`); int4 without calibration runs with
    identity redistribution (plain symmetric int4) — lossier, but the mode
    stays usable for fp-weight serving and uncalibrated tests.  ``latent``
    pages (fp or int8) hold one shared row per position."""
    if mode == "fp":
        return FpKVQuantizer(dtype, latent)
    if mode == "int8":
        return Int8KVQuantizer(latent)
    if mode == "int4" and latent:
        raise ValueError("int4 pages hold per-head K and V rows; latent "
                         "pages take kv_mode 'fp' or 'int8'")
    if mode == "int4":
        e = int(calib["exp_factor"]) if calib and "exp_factor" in calib \
            else DEFAULT_EXP_FACTOR
        if calib and "k_mask" in calib:
            kr = redist_from_mask(calib["k_mask"], e)
            vr = redist_from_mask(calib["v_mask"], e)
        else:
            kr = vr = np.ones((kvh, dh), np.float32)
        return Int4KVQuantizer(kr, vr)
    raise ValueError(f"unknown kv mode {mode!r} (expected one of {KV_MODES})")


def from_cache(cache: Dict[str, jnp.ndarray]) -> KVQuantizer:
    """Classify a (possibly per-layer, traced) cache dict by its key set —
    the single mode sentinel shared by the scan bodies and attention paths:
    redistribution rows mean int4, bare scales mean int8, else fp."""
    if "k_redist" in cache:
        return Int4KVQuantizer(cache["k_redist"], cache["v_redist"])
    latent = "v" not in cache
    if "k_scale" in cache:
        return Int8KVQuantizer(latent)
    return FpKVQuantizer(cache["k"].dtype, latent)


# ---------------------------------------------------------------------------
# Calibration: per-layer per-head K/V channel amax -> pooled outlier masks
# ---------------------------------------------------------------------------

class KVCalibCollector:
    """Forward hook collecting per-layer, per-head K/V channel amax.

    Installed over the eager calibration forwards by
    ``quantize_model`` via ``models.attention.set_kv_observer``; called with
    (site prefix, k, v) where k/v are the post-RoPE ``[b, s, kvh, dh]``
    projections — the exact tensors the paged write path quantizes.  Stats
    accumulate as a running max across batches, keyed by layer prefix."""

    def __init__(self):
        self.k_amax: Dict[str, np.ndarray] = {}
        self.v_amax: Dict[str, np.ndarray] = {}

    def __call__(self, prefix: str, k, v) -> None:
        if isinstance(k, jax.core.Tracer):  # pragma: no cover - guarded misuse
            raise RuntimeError("KVCalibCollector must run eagerly "
                               "(not under jit/scan)")
        if getattr(k, "ndim", 0) != 4 or getattr(v, "ndim", 0) != 4:
            return                          # not [b, s, kvh, dh] self-attn KV
        for store, x in ((self.k_amax, k), (self.v_amax, v)):
            amax = np.max(np.abs(np.asarray(x, np.float32)),
                          axis=(0, 1))      # [kvh, dh]
            prev = store.get(prefix)
            store[prefix] = amax if prev is None else np.maximum(prev, amax)

    def stacked(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """([L, kvh, dh] k_amax, v_amax) in layer order, or None if the
        forward never reached a hooked attention site."""
        if not self.k_amax:
            return None
        keys = sorted(self.k_amax, key=_layer_sort_key)
        return (np.stack([self.k_amax[p] for p in keys]),
                np.stack([self.v_amax[p] for p in keys]))


def _layer_sort_key(prefix: str):
    digits = "".join(c for c in prefix if c.isdigit())
    return (int(digits) if digits else 0, prefix)


def pool_outlier_mask(amax: np.ndarray, *,
                      ratio: float = DEFAULT_OUTLIER_RATIO,
                      max_frac: float = DEFAULT_MAX_FRAC) -> np.ndarray:
    """[L, kvh, dh] per-layer channel amax -> one pooled [kvh, dh] mask.

    Per (layer, head) a channel is an outlier when its amax exceeds
    ``ratio`` times the head's median channel amax (a relative criterion —
    K/V magnitudes are not on the activation |x|>6 scale).  Layer sets are
    then UNIONed per head (the ``GlobalOutlierPooler`` pooling move: small
    models' per-layer outliers are unsystematic; the pooled set is stable).
    If the union exceeds ``max_frac`` of head_dim, keep the top-k channels
    by pooled amax — mirroring ``core.outliers.ChannelStats.mask``."""
    amax = np.asarray(amax, np.float32)
    L, kvh, dh = amax.shape
    med = np.maximum(np.median(amax, axis=-1, keepdims=True), _SCALE_FLOOR)
    mask = (amax > ratio * med).any(axis=0)             # union across layers
    cap = max(1, int(max_frac * dh))
    pooled = amax.max(axis=0)                           # [kvh, dh]
    for head in range(kvh):
        n = int(mask[head].sum())
        if n > cap:
            keep = np.argsort(pooled[head])[-cap:]
            capped = np.zeros(dh, bool)
            capped[keep] = True
            mask[head] = capped
    return mask


def build_kv_calib(collector: KVCalibCollector, *,
                   exp_factor: int = DEFAULT_EXP_FACTOR,
                   ratio: float = DEFAULT_OUTLIER_RATIO,
                   max_frac: float = DEFAULT_MAX_FRAC
                   ) -> Optional[Dict[str, np.ndarray]]:
    """Collector -> the artifact's ``kv_calib`` bundle section: stacked
    per-layer amax (k/v_amax [L, kvh, dh]), pooled masks (k/v_mask
    [kvh, dh]) and the redistribution exponent.  None when the calibration
    forward never exercised a self-attention site."""
    stacked = collector.stacked()
    if stacked is None:
        return None
    k_amax, v_amax = stacked
    return {
        "k_amax": k_amax, "v_amax": v_amax,
        "k_mask": pool_outlier_mask(k_amax, ratio=ratio, max_frac=max_frac),
        "v_mask": pool_outlier_mask(v_amax, ratio=ratio, max_frac=max_frac),
        "exp_factor": np.asarray(exp_factor, np.int32),
        "outlier_ratio": np.asarray(ratio, np.float32),
    }
