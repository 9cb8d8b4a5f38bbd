"""Matmul contexts — how quantization threads through the model zoo.

Every *projection* matmul site in ``repro.models`` is executed via a ctx
callable ``ctx(name, x, w, mask=None, smooth=None)``.  The paper quantizes
GPT-2's c_attn / attn-c_proj / c_fc / mlp-c_proj; our generalization is
"every dense projection", with embeddings / lm_head / attention score
matmuls left in the compute dtype (matching the paper's target-layer set).

Weight leaves arrive RAW (the ctx owns dtype handling), in one of two forms:
  * a plain array (fp master / bf16) — quantize-at-use, the paper's
    fake-quant protocol;
  * a dict {"q": int8, "s": f32 scales} — OFFLINE pre-quantized weights
    (``repro.core.prequant``): the deployment path.  Avoids the per-step
    fp32 quantize pass over every weight (the dominant HBM traffic in the
    baseline decode roofline — see EXPERIMENTS.md §Perf).

Contexts:
  FpCtx      — plain matmul (FP16 baseline row of Table 1).
  CollectCtx — records per-channel activation stats (calibration pass).
               MUST run eagerly / unscanned: it mutates a host-side dict.
  QuantCtx   — resolves a per-site QuantConfig from a SitePolicy (a single
               QuantConfig means "uniform policy") plus static masks /
               smoothing state, by site name on the eager path or via
               explicit args when running under ``lax.scan`` (host dict
               lookups don't trace).

Execution backends (``repro.kernels.dispatch``): each resolved config's
``backend`` routes the site to ``fp`` passthrough, ``fake`` (everything
below this docstring's original description: quantize-dequantize semantics
and the jnp real-int8 reference paths) or ``fused`` — the packed
single-GEMM MUXQ kernel.  Fused sites consume a kernel-ready buffer instead
of the weight leaf: from the ``fused=`` argument under ``lax.scan``
(stacked ``{site}@fused`` entries of ``scan_qparams``) or from the ctx's
``kernel_buffers`` host dict on the eager path.  The backend chosen per
site is recorded in ``QuantCtx.backend_log`` at trace time.

Smoothing conventions (two distinct vectors ride under one name):
  * ``smooths`` host dict / ``smooth=`` into ``qmatmul``: the *calibrated
    activation abs-max* — SmoothQuant factors are derived live from it and
    the raw weight (quantize-at-use only).
  * ``smooth=`` argument into the ctx (scanned ``{site}@smooth`` qparams)
    and the ``smooth_factors`` dict of a ``QuantArtifact``: the *final
    per-channel divisor* s.  The ctx applies X/s itself; for pre-quantized
    weights ``quantize_model`` already folded s*W into the packed int8
    tensor, so applying the hint-based derivation again would be wrong —
    a smooth-method site with packed weights and no factor raises instead
    of silently serving un-smoothed results.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import quantizers as Q
from repro.core.muxq import QuantConfig, qmatmul
from repro.core.outliers import CalibrationStats
from repro.core.policy import SitePolicy, as_policy
from repro.kernels import dispatch

_SMOOTH_METHODS = ("smoothquant", "muxq_smooth")


def _is_prequant(w) -> bool:
    return isinstance(w, dict) and "q" in w


def _dense_w(w, dtype):
    """Materialize a compute-dtype dense weight from either form."""
    if _is_prequant(w):
        return (w["q"].astype(jnp.float32) * w["s"]).astype(dtype)
    return w.astype(dtype)


def _ragged(x, w, rows):
    """Rows of x through their expert's weight (rows past the groups: 0)."""
    import jax
    return jax.lax.ragged_dot(x, w, rows["group_sizes"])


def _row_expert(rows, m: int) -> jnp.ndarray:
    """[M] held expert of each row of a grouped layout."""
    bm = m // rows["tile_expert"].shape[0]
    return jnp.repeat(rows["tile_expert"], bm, total_repeat_length=m)


def _prequant_matmul(x, w, cfg: QuantConfig, mask=None):
    """x (fp) @ pre-quantized int8 weight: per-token int8 activations,
    INT GEMM, fused dequant.  MUXQ rides as the exact int32 channel
    multiplier on the activation side (DESIGN.md §3.2) — the stored weight
    never changes."""
    xq = x
    if mask is not None and cfg.method in ("muxq", "muxq_smooth"):
        from repro.core.muxq import decompose
        xq = decompose(x, mask, cfg.exp_factor)
    xi, sx = Q.quantize(xq, cfg.act_bits, cfg.act_granularity)
    if mask is not None and cfg.method in ("muxq", "muxq_smooth"):
        mult = jnp.where(mask, jnp.int32(2 ** cfg.exp_factor), jnp.int32(1))
        xi = xi.astype(jnp.int32) * mult
    yi = Q.int_matmul(xi, w["q"])
    return (yi.astype(jnp.float32) * sx * w["s"]).astype(x.dtype)


class FpCtx:
    quantized = False

    def __call__(self, name: str, x: jnp.ndarray, w, mask=None, smooth=None,
                 fused=None):
        return x @ _dense_w(w, x.dtype)

    def emm(self, name: str, x: jnp.ndarray, w, mask=None, smooth=None,
            fused=None):
        """Per-expert matmul: x [e, c, d] @ w [e, d, f] -> [e, c, f]."""
        return jnp.einsum("ecd,edf->ecf", x, _dense_w(w, x.dtype))

    def gmm(self, name: str, x: jnp.ndarray, w, rows, mask=None,
            smooth=None, fused=None):
        """Grouped matmul over expert row tiles: x [M, d] whose rows are
        sorted by expert (``rows``: :func:`repro.models.moe.expert_rows`)
        @ w [e, d, f] -> [M, f]."""
        return _ragged(x, _dense_w(w, x.dtype), rows)


class CollectCtx:
    """Calibration pass: record per-channel |x| stats at every site."""
    quantized = False

    def __init__(self, stats: Optional[CalibrationStats] = None) -> None:
        self.stats = stats or CalibrationStats()

    def __call__(self, name: str, x: jnp.ndarray, w, mask=None, smooth=None,
                 fused=None):
        import jax
        if isinstance(x, jax.core.Tracer):  # pragma: no cover - guarded misuse
            raise RuntimeError("CollectCtx must run eagerly (not under jit/scan)")
        self.stats.update(name, x)
        return x @ _dense_w(w, x.dtype)

    def emm(self, name: str, x: jnp.ndarray, w, mask=None, smooth=None,
            fused=None):
        import jax
        if isinstance(x, jax.core.Tracer):  # pragma: no cover - guarded misuse
            raise RuntimeError("CollectCtx must run eagerly (not under jit/scan)")
        self.stats.update(name, x.reshape(-1, x.shape[-1]))
        return jnp.einsum("ecd,edf->ecf", x, _dense_w(w, x.dtype))

    def gmm(self, name: str, x: jnp.ndarray, w, rows, mask=None,
            smooth=None, fused=None):
        import jax
        if isinstance(x, jax.core.Tracer):  # pragma: no cover - guarded misuse
            raise RuntimeError("CollectCtx must run eagerly (not under jit/scan)")
        # only the rows of routed tokens: padding rows are not activations
        used = np.asarray(rows["row_token"]) < rows["pair_row"].shape[0]
        self.stats.update(name, np.asarray(x)[used] if used.any() else x)
        return _ragged(x, _dense_w(w, x.dtype), rows)


class QuantCtx:
    quantized = True

    def __init__(self, quant,
                 masks: Optional[Dict[str, np.ndarray]] = None,
                 smooths: Optional[Dict[str, np.ndarray]] = None,
                 smooth_factors: Optional[Dict[str, np.ndarray]] = None,
                 kernel_buffers: Optional[Dict[str, dict]] = None) -> None:
        """``quant`` is a QuantConfig (uniform policy), a SitePolicy, or a
        ``repro.quantize.QuantArtifact`` (duck-typed: supplies policy, masks,
        act-absmax, folded smooth factors and packed kernel buffers in one
        object)."""
        if isinstance(quant, (QuantConfig, SitePolicy)):
            self.policy = as_policy(quant)
        else:  # QuantArtifact (duck-typed to avoid a core -> repro.quantize dep)
            self.policy = quant.policy
            masks = quant.masks if masks is None else masks
            smooths = quant.act_absmax if smooths is None else smooths
            smooth_factors = (quant.smooth_factors if smooth_factors is None
                              else smooth_factors)
            kernel_buffers = (getattr(quant, "kernel_buffers", None)
                              if kernel_buffers is None else kernel_buffers)
        self.cfg = self.policy.default          # back-compat accessor
        self.masks = masks or {}
        self.smooths = smooths or {}
        self.smooth_factors = smooth_factors or {}
        self.kernel_buffers = kernel_buffers or {}
        # site -> backend chosen, recorded at trace time (tests/inspection)
        self.backend_log: Dict[str, str] = {}

    # -- per-site state resolution (host dicts: eager path only) ------------

    def _site(self, name, cfg: QuantConfig, mask, smooth
              ) -> Tuple[Optional[jnp.ndarray], Optional[jnp.ndarray],
                         Optional[jnp.ndarray]]:
        """Returns (mask, factor, hint): static outlier mask, final smoothing
        divisor (scan arg or artifact), calibrated act-absmax (legacy)."""
        if mask is None and cfg.outlier_mode == "static":
            m = self.masks.get(name)
            mask = None if m is None else jnp.asarray(m)
        factor = smooth if smooth is not None else self.smooth_factors.get(name)
        if factor is not None:
            factor = jnp.asarray(factor)
        hint = self.smooths.get(name)
        hint = None if hint is None else jnp.asarray(hint)
        return mask, factor, hint

    @staticmethod
    def _smooth_base(cfg: QuantConfig) -> QuantConfig:
        return cfg.replace(
            method="naive" if cfg.method == "smoothquant" else "muxq")

    @staticmethod
    def _observe(name: str, x, cfg: QuantConfig, mask) -> None:
        """Report the to-be-quantized activation to the installed quality
        observer (repro.obs.quality) — eager path only: traced values carry
        no data, and the serve loop must stay observation-free inside jit."""
        obs = dispatch.quality_observer()
        if obs is None:
            return
        import jax
        if isinstance(x, jax.core.Tracer):
            return
        obs.observe_activation(
            name, np.asarray(x), qmax=2 ** (cfg.act_bits - 1) - 1,
            mask=None if mask is None else np.asarray(mask))

    def _fused_buffer(self, name: str, fused):
        """The packed kernel buffer for a fused-backend site: the scanned
        ``fused=`` argument, else the eager host dict."""
        buf = fused if fused is not None else self.kernel_buffers.get(name)
        if buf is None:
            raise RuntimeError(
                f"site {name!r}: backend 'fused' needs packed kernel buffers "
                "— build the artifact via repro.quantize.quantize_model"
                "(..., prequantize=True), or route this site to the 'fake' "
                "backend")
        return buf

    def __call__(self, name: str, x: jnp.ndarray, w, mask=None, smooth=None,
                 fused=None):
        cfg = self.policy.resolve(name)
        backend = dispatch.site_backend(cfg)
        self.backend_log[name] = backend
        if backend == "fp":
            return x @ _dense_w(w, x.dtype)
        mask, factor, hint = self._site(name, cfg, mask, smooth)

        if cfg.method in _SMOOTH_METHODS:
            if factor is not None:
                x = (x / factor).astype(x.dtype)
                cfg = self._smooth_base(cfg)
                if backend == "fake" and not _is_prequant(w):
                    w = (w * factor[:, None]).astype(w.dtype)
            elif backend == "fused" or _is_prequant(w):
                raise RuntimeError(
                    f"site {name!r}: method {cfg.method!r} on the "
                    f"{backend!r} backend needs folded smooth factors "
                    "(build the packed tree via "
                    "repro.quantize.quantize_model)")
            # else: quantize-at-use — qmatmul derives factors from the hint

        self._observe(name, x, cfg, mask)
        if backend == "fused":
            buf = self._fused_buffer(name, fused)
            return dispatch.fused_matmul(
                x, buf, act_bits=cfg.act_bits).astype(x.dtype)
        if _is_prequant(w):
            return _prequant_matmul(x, w, cfg, mask)
        return qmatmul(x, w.astype(x.dtype), cfg, mask=mask, smooth=hint)

    def emm(self, name: str, x: jnp.ndarray, w, mask=None, smooth=None,
            fused=None):
        """Quantized per-expert matmul: vmap the 2-D policy over the expert
        axis (per-expert weight scales, shared outlier mask — DESIGN.md §5)."""
        import jax
        cfg = self.policy.resolve(name)
        backend = dispatch.site_backend(cfg)
        self.backend_log[name] = backend
        if backend == "fp":
            return jnp.einsum("ecd,edf->ecf", x, _dense_w(w, x.dtype))
        mask, factor, hint = self._site(name, cfg, mask, smooth)

        if cfg.method in _SMOOTH_METHODS:
            if factor is not None:
                x = (x / factor).astype(x.dtype)
                cfg = self._smooth_base(cfg)
                if backend == "fake" and not _is_prequant(w):
                    w = (w * factor[None, :, None]).astype(w.dtype)
            elif backend == "fused" or _is_prequant(w):
                raise RuntimeError(
                    f"site {name!r}: method {cfg.method!r} on the "
                    f"{backend!r} backend needs folded smooth factors "
                    "(build the packed tree via "
                    "repro.quantize.quantize_model)")

        self._observe(name, x, cfg, mask)
        if backend == "fused":
            buf = self._fused_buffer(name, fused)
            return dispatch.fused_emm(
                x, buf, act_bits=cfg.act_bits).astype(x.dtype)
        if _is_prequant(w):
            fn = lambda xe, qe, se: _prequant_matmul(xe, {"q": qe, "s": se},
                                                     cfg, mask)
            return jax.vmap(fn)(x, w["q"], w["s"])
        fn = lambda xe, we: qmatmul(xe, we.astype(x.dtype), cfg,
                                    mask=mask, smooth=hint)
        return jax.vmap(fn)(x, w)

    def gmm(self, name: str, x: jnp.ndarray, w, rows, mask=None,
            smooth=None, fused=None):
        """Quantized grouped matmul over expert row tiles (see
        ``FpCtx.gmm``).  Fused sites run the ``muxq_gemm`` kernel's expert
        axis; the other backends run the per-expert :meth:`emm` on every
        row and keep each row's own expert (per-token activation
        quantization makes a row's result independent of the others)."""
        cfg = self.policy.resolve(name)
        backend = dispatch.site_backend(cfg)
        if backend == "fp":
            self.backend_log[name] = backend
            return _ragged(x, _dense_w(w, x.dtype), rows)
        if backend == "fused":
            self.backend_log[name] = backend
            mask, factor, _ = self._site(name, cfg, mask, smooth)
            if cfg.method in _SMOOTH_METHODS:
                if factor is None:
                    raise RuntimeError(
                        f"site {name!r}: method {cfg.method!r} on the fused "
                        "backend needs folded smooth factors")
                x = (x / factor).astype(x.dtype)
                cfg = self._smooth_base(cfg)
            self._observe(name, x, cfg, mask)
            buf = self._fused_buffer(name, fused)
            return dispatch.fused_gmm(x, buf, rows,
                                      act_bits=cfg.act_bits).astype(x.dtype)
        e = rows["group_sizes"].shape[0]
        full = self.emm(name, jnp.broadcast_to(x, (e,) + x.shape), w,
                        mask=mask, smooth=smooth, fused=fused)   # [e, M, f]
        own = _row_expert(rows, x.shape[0])
        y = jnp.take_along_axis(full, own[None, :, None], axis=0)[0]
        used = jnp.arange(x.shape[0]) < jnp.sum(rows["group_sizes"])
        return jnp.where(used[:, None], y, 0).astype(x.dtype)


def as_ctx(quant) -> Tuple[object, Optional[Dict[str, jnp.ndarray]]]:
    """Normalize any quant spec to (ctx, scan_qparams).

    ``quant``: None | QuantConfig | SitePolicy | QuantArtifact.  The second
    element is the stacked {site: [L, ch]} qparams tree for scanned layer
    loops (only a QuantArtifact carries one — eager paths resolve per-site
    state from the ctx's host dicts instead).
    """
    if quant is None:
        return FpCtx(), None
    if isinstance(quant, QuantConfig):
        return (FpCtx(), None) if quant.method == "fp" else (QuantCtx(quant), None)
    if isinstance(quant, SitePolicy):
        return (FpCtx(), None) if quant.is_fp() else (QuantCtx(quant), None)
    # QuantArtifact
    return QuantCtx(quant), getattr(quant, "scan_qparams", None) or None
