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
every head (one "head" of ``latent + rope`` channels), under the single
key ``k``; fp and int8 modes only, int8 with one float32 scale per
position.

**Page layout** (:func:`page_shape`, :func:`scale_shape`).  A position's
row is its heads side by side, ``kvh * w`` wide (``w`` the stored head
width: ``dh``, or ``dh // 2`` nibble-packed).  A page stores ``r`` such
rows side by side in one stored row, ``r`` the fewest positions that fill
whole 128-lane vectors (:func:`page_fold`; 1 for GPT-2's 20 heads of 64,
2 for a 576-wide latent row), so the pages of ``L`` layers are one
row-major array ``[L, n_pages, ps // r, r * kvh * w]`` that the TPU stores
unpadded and as laid out.  Position ``o`` of a page sits in stored row
``o // r``, block ``s = o % r``; head ``h``'s channels in it start at lane
``(h * r + s) * w``, so each head's lanes are one contiguous run
(tensor-parallel serving shards that axis by heads).
Scales, one value per (position, head), are too narrow for a lane-dense
page of their own (``kvh * ps`` of them: 320 for GPT-2 large), so ``g``
pages share a stored row, ``g`` the fewest that fill whole 128-lane
vectors (:func:`scale_fold`: 2 for 320, 8 for a latent page's 16):
``[L, rows, g * kvh * ps]`` (``rows`` covers ``n_pages`` in whole
8-row tiles), page ``p``'s scales at lanes
``(p % g) * kvh * ps + h * ps + o`` of row ``p // g``; under
tensor-parallel serving the row is one such block per head shard.  A
step writes pages and scales in place (:func:`write_pages`) and gathers
only the rows of the pages its table names (:func:`gather_scales`).

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
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

KV_MODES = ("fp", "int8", "int4")

LANES = 128                    # a stored page row fills whole TPU vectors

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
# Page layout: one row-major array per pool array, written in place
# ---------------------------------------------------------------------------

def _lanes(n: int) -> int:
    return -(-n // LANES) * LANES


def page_fold(ps: int, width: int) -> int:
    """Positions per stored page row: the fewest (a divisor of ``ps``)
    whose rows of ``width`` fill whole 128-lane vectors, else the whole
    page in one row."""
    return next((r for r in range(1, ps + 1)
                 if ps % r == 0 and r * width % LANES == 0), ps)


def page_shape(L: int, n_pages: int, ps: int, kvh: int, w: int) -> tuple:
    """Stored page array of ``kvh`` heads of stored width ``w``."""
    r = page_fold(ps, kvh * w)
    return (L, n_pages, ps // r, r * kvh * w)


def scale_fold(n: int) -> int:
    """Pages per stored scale row: the fewest whose ``n`` scales each fill
    whole 128-lane vectors."""
    return LANES // math.gcd(n, LANES)


def scale_shape(L: int, n_pages: int, ps: int, kvh: int,
                shards: int = 1) -> tuple:
    """Stored scales: per layer, ``g`` pages' scales to a row, one such
    block per head shard (see the module docstring)."""
    n = (kvh // shards) * ps
    g = scale_fold(n)
    return (L, -(-n_pages // (8 * g)) * 8, shards * g * n)


def page_size(pages: jnp.ndarray, kvh: int, w: int) -> int:
    """Positions per page of a stored page array of ``kvh`` heads of
    stored width ``w`` (``kvh`` is a shard's heads under tensor-parallel
    serving)."""
    rows, lanes = pages.shape[-2:]
    return rows * (lanes // (kvh * w))


def _i32(a):
    return jnp.asarray(a, jnp.int32)


def _write_rows(pages, rows, layer, page, offset):
    """Write ``rows`` [..., n, kvh, w] into ``pages`` [L, n_pages, R,
    lanes] at (layer, page, offset), which broadcast to the rows' leading
    shape; positions run consecutively along the last leading axis.  One
    scatter of whole stored rows: where a stored row holds ``r > 1``
    positions, each is assembled from this write's rows and, for the
    positions it does not write, the row's current content (rows that
    share a stored row then write the same values)."""
    kvh, w = rows.shape[-2:]
    r = pages.shape[-1] // (kvh * w)
    lead = rows.shape[:-2]
    layer, page, offset = (jnp.broadcast_to(_i32(a), lead)
                           for a in (layer, page, offset))
    rows = rows.astype(pages.dtype)
    if r > 1:
        old = pages[layer, page, offset // r].reshape(lead + (kvh, r, w))
        j = jnp.arange(lead[-1])
        blocks = []
        for s in range(r):
            # the row holding block s: the same stored row, this write's
            # rows for the same page, else the current content
            jj = j + s - offset % r
            src = jnp.clip(jj, 0, lead[-1] - 1)
            ok = ((jj >= 0) & (jj < lead[-1])
                  & (jnp.take_along_axis(page, src, axis=-1) == page))
            mine = jnp.take_along_axis(rows, src[..., None, None], axis=-3)
            blocks.append(jnp.where(ok[..., None, None], mine,
                                    old[..., s, :]))
        rows = jnp.stack(blocks, axis=-2)
    return pages.at[layer, page, offset // r].set(
        rows.reshape(lead + (kvh * r * w,)))


def _write_scales(scales, vals, layer, page, offset, ps: int):
    """Write per-(position, head) ``vals`` [..., kvh, 1] into ``scales``
    [L, rows, g * kvh * ps] (a shard's view)."""
    n = vals.shape[-2] * ps
    g = scales.shape[-1] // n
    layer, page, offset = (_i32(a)[..., None] for a in (layer, page, offset))
    lane = (page % g) * n + jnp.arange(vals.shape[-2]) * ps + offset
    return scales.at[layer, page // g, lane].set(
        vals[..., 0].astype(scales.dtype))


def write_pages(pool: Dict[str, jnp.ndarray], parts: Dict[str, jnp.ndarray],
                layer, page, offset, ps: int) -> Dict[str, jnp.ndarray]:
    """The pool arrays ``parts`` names (:meth:`KVQuantizer.quantize`'s
    ``[..., kvh, w]`` rows and ``[..., kvh, 1]`` scales), each with the
    rows written at (layer, page, offset in the page), which broadcast to
    the rows' leading shape — one scatter per array, in place when the
    array is a loop carry or donated.  ``ps``: positions per page."""
    return {n: (_write_scales(pool[n], v, layer, page, offset, ps)
                if n.endswith("_scale") else
                _write_rows(pool[n], v, layer, page, offset))
            for n, v in parts.items()}


def gather_scales(scales: jnp.ndarray, layer, table: jnp.ndarray, kvh: int,
                  ps: int, rows: int) -> jnp.ndarray:
    """The scales of layer ``layer`` on the pages of ``table`` [b, P], as
    the paged kernel reads them next to a stored page of ``rows`` rows:
    ``[b, P, rows, kvh * r]``, a page row's positions on the sublanes and
    (head, block) on the lanes.  One gather of whole stored rows, then the
    page's part of each."""
    n = kvh * ps
    g = scales.shape[-1] // n
    table = _i32(table)
    got = scales[layer, table // g].reshape(table.shape + (g, n))
    got = jnp.take_along_axis(got, (table % g)[..., None, None], axis=-2)
    got = got.reshape(table.shape + (kvh, rows, n // (kvh * rows)))
    return got.swapaxes(-2, -3).reshape(table.shape + (rows, -1))


def copy_page(arrays: Dict[str, jnp.ndarray], old: int, new: int, kvh: int,
              ps: int, shards: int = 1) -> Dict[str, jnp.ndarray]:
    """The stored pool arrays, each with page ``new`` (in every layer) a
    copy of page ``old``."""
    out = {}
    for n, a in arrays.items():
        if n.endswith("_scale"):        # page p: row p // g, block p % g
            v = a.reshape(a.shape[:2] + (shards, -1, kvh // shards * ps))
            g = v.shape[3]
            v = v.at[:, new // g, :, new % g].set(v[:, old // g, :, old % g])
            out[n] = v.reshape(a.shape)
        else:
            out[n] = a.at[:, new].set(a[:, old])
    return out


def scale_view(scales, kvh: int, ps: int, shards: int = 1):
    """Stored scales ``[L, rows, X]`` (numpy or jnp) as ``[L, rows * g,
    shards, kvh // shards, ps]``: indexed by page (pages past ``n_pages``
    are padding)."""
    L, rows, X = scales.shape
    kl = kvh // shards
    g = X // (shards * kl * ps)
    x = scales.reshape(L, rows, shards, g, kl, ps).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(L, rows * g, shards, kl, ps)


def head_major(pages, kvh: int, ps: int):
    """A stored page array ``[..., n, R, lanes]`` (numpy or jnp) as
    ``[..., n, kvh, ps, w]``; for host-side inspection and tests."""
    *lead, R, lanes = pages.shape
    r = ps // R
    x = pages.reshape(*lead, R, kvh, r, lanes // (kvh * r))
    nd = len(lead)
    x = x.transpose(*range(nd), nd + 1, nd, nd + 2, nd + 3)
    return x.reshape(*lead, kvh, ps, -1)


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
    page_dtype = None           # stored codes (None: the pool dtype)
    scale_dtype = None          # per-(position, head) scales (None: none)
    # symmetric integer ceiling of the mode's codes (None for fp pages);
    # the quality observer (repro.obs.quality) reads this to count
    # saturated codes when it samples live pool pages
    qmax: Optional[int] = None

    def quantize(self, k: jnp.ndarray, v: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        raise NotImplementedError

    def dequantize(self, parts: Dict[str, jnp.ndarray], dtype
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def stored_width(self, dh: int) -> int:
        """Stored channels of one head's row (nibble-packed: ``dh // 2``)."""
        return dh

    def page_arrays(self, L: int, n_pages: int, ps: int, kvh: int, dh: int,
                    shards: int = 1) -> Dict[str, jnp.ndarray]:
        """Zero-initialized pool arrays in the page layout (module
        docstring): pages :func:`page_shape`, scales :func:`scale_shape`
        in ``shards`` head blocks."""
        names = ("k",) if self.latent else ("k", "v")
        pages = page_shape(L, n_pages, ps, kvh, self.stored_width(dh))
        out = {n: jnp.zeros(pages, self.page_dtype) for n in names}
        if self.scale_dtype is not None:
            out.update({f"{n}_scale": jnp.zeros(
                scale_shape(L, n_pages, ps, kvh, shards), self.scale_dtype)
                for n in names})
        return out

    def pool_state(self, L: int, kvh: int, dh: int) -> Dict[str, jnp.ndarray]:
        """Non-page pool state stacked [L, ...] beside the page arrays
        (int4: redistribution rows)."""
        return {}

    def bytes_per_token(self, kvh: int, dh: int) -> int:
        """Page bytes one token position costs across K and V (one layer)."""
        raise NotImplementedError

    def kernel_operands(self, cache: Dict[str, jnp.ndarray], layer,
                        table: jnp.ndarray, kvh: int, ps: int
                        ) -> Dict[str, jnp.ndarray]:
        """Extra keyword operands for ``paged_attention_decode`` beyond the
        stored pages themselves: the scales of ``table``'s pages in layer
        ``layer`` (:func:`gather_scales`; ``kvh`` heads, ``ps`` positions
        a page) and the layer's redistribution rows."""
        rows = cache["k"].shape[-2]
        return {f"{n}_scale": gather_scales(cache[f"{n}_scale"], layer,
                                            table, kvh, ps, rows)
                for n in ("k", "v") if f"{n}_scale" in cache}


class FpKVQuantizer(KVQuantizer):
    mode = "fp"

    def __init__(self, dtype=jnp.bfloat16, latent: bool = False):
        self.dtype, self.latent = dtype, latent
        self.page_dtype = dtype

    def quantize(self, k, v=None):
        if self.latent:
            return {"k": k.astype(self.dtype)}
        return {"k": k.astype(self.dtype), "v": v.astype(self.dtype)}

    def dequantize(self, parts, dtype):
        if self.latent:
            return parts["k"].astype(dtype), None
        return parts["k"].astype(dtype), parts["v"].astype(dtype)

    def bytes_per_token(self, kvh, dh):
        return (1 if self.latent else 2) * kvh * dh * jnp.dtype(
            self.dtype).itemsize


class Int8KVQuantizer(KVQuantizer):
    """Per-(position, head) abs-max int8 (delegates to the historical
    ``kvcache.quantize_kv`` math — the serve tests pin its exact scales)."""

    mode = "int8"
    qmax = 127
    page_dtype = jnp.int8
    scale_dtype = jnp.float32

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

    def bytes_per_token(self, kvh, dh):
        # int8 payload + f32 scale, for K and V (a latent row: once)
        return (1 if self.latent else 2) * kvh * (dh + 4)


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
    page_dtype = jnp.int8
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

    def stored_width(self, dh):
        assert dh % 2 == 0, f"int4 pages need an even head_dim, got {dh}"
        return dh // 2

    def pool_state(self, L, kvh, dh):
        def stack(r):
            r = jnp.broadcast_to(r, (kvh, dh)) if r.ndim < 3 else r
            return (jnp.broadcast_to(r[None], (L, kvh, dh))
                    if r.ndim == 2 else r)
        return {"k_redist": stack(self.k_redist),
                "v_redist": stack(self.v_redist)}

    def bytes_per_token(self, kvh, dh):
        return 2 * kvh * (dh // 2 + 2)     # nibble payload + bf16 scale

    def kernel_operands(self, cache, layer, table, kvh, ps):
        return {**super().kernel_operands(cache, layer, table, kvh, ps),
                "k_redist": self.k_redist, "v_redist": self.v_redist}


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


def from_cache(cache: Dict[str, jnp.ndarray], layer=None) -> KVQuantizer:
    """Classify a (possibly per-layer, traced) cache dict by its key set —
    the single mode sentinel shared by the scan bodies and attention paths:
    redistribution rows mean int4, bare scales mean int8, else fp.  A
    stacked pool's int4 rows are taken at ``layer``."""
    if "k_redist" in cache:
        kr, vr = cache["k_redist"], cache["v_redist"]
        if layer is not None:
            kr, vr = kr[layer], vr[layer]
        return Int4KVQuantizer(kr, vr)
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
