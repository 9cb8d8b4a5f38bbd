"""Unified kernel dispatch: every quantized matmul site resolves to an
execution backend.

A site's :class:`~repro.core.muxq.QuantConfig` now names *how to execute*
(``backend``) on top of *what math to apply* (``method``):

  * ``fused`` — the deployable single-GEMM MUXQ path: kernel-ready packed
    buffers (channel permutation, zero padding, per-K-block exponent
    scales, int8 weights — ``repro.kernels.ops.MuxqWeights``) feed the
    Pallas ``muxq_linear`` kernel on TPU; on CPU the same kernel runs in
    interpret mode or via the jnp int8 oracle.
  * ``fake`` — the paper's quantize→dequantize evaluation protocol (and the
    jnp real-int8 reference paths): what ``QuantCtx`` always ran before.
    Kept for calibration, benchmark grids and parity tests.
  * ``fp``   — full-precision passthrough.

This module owns backend selection (:func:`site_backend`), the kernel-ready
per-site buffer format (:func:`pack_site_buffer` — a dict of arrays so a
per-layer stack of buffers is a valid ``lax.scan`` xs pytree), and the
fused execution entry points (:func:`fused_matmul` / :func:`fused_emm`)
that ``repro.core.context.QuantCtx`` routes through.

Under the tensor-parallel serve mesh (``parallel/serve_sharding.py``)
nothing here changes: weights stay replicated inside the shard_map body
(MUXQ's per-token activation quantization needs the full channel vector,
and the packed fused buffers' channel permutation doesn't slice cleanly),
so every backend executes the same full-width GEMM per shard — only the
KV pages shard.

Buffer layout (all arrays; statics derive from shapes — ``bk = K_pad/nb``):

  w_int       int8 [K_pad, N]       packed weight, outlier rows first
              (per-expert sites: [E, K_pad, N])
  sw          f32  [1, N]           per-out-channel weight scales ([E, 1, N])
  block_scale int32 [K_pad/bk]      2^exp on outlier K-blocks, 1 elsewhere
  gather_idx  int32 [K_pad]         source channel per packed slot
  in_scale    f32  [K_pad]          2^-exp outlier run, 0 pad slots, 1 else
"""
from __future__ import annotations

from typing import Dict, Literal, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.kernels.muxq_gemm import muxq_gemm_grouped, pick_bn

Backend = Literal["fused", "fake", "fp"]
FusedImpl = Literal["auto", "pallas", "interpret", "ref"]

BUFFER_FIELDS = ("w_int", "sw", "block_scale", "gather_idx", "in_scale")

# methods whose math the fused kernel can realize: plain int8 (empty outlier
# set) and the MUXQ family (smooth variants fold s*W at pack time, the ctx
# applies X/s before dispatching here)
_FUSED_METHODS = ("naive", "muxq", "smoothquant", "muxq_smooth")

_FUSED_IMPL: FusedImpl = "auto"


def set_fused_impl(impl: FusedImpl) -> FusedImpl:
    """Select how fused-backend sites execute; returns the previous setting.

    ``auto`` (default): compiled Pallas on TPU, the jnp int8 oracle on CPU.
    ``interpret`` forces interpret-mode Pallas (CPU parity tests), ``ref``
    forces the oracle, ``pallas`` forces compiled kernels.
    """
    global _FUSED_IMPL
    if impl not in ("auto", "pallas", "interpret", "ref"):
        raise ValueError(f"unknown fused impl {impl!r}")
    prev, _FUSED_IMPL = _FUSED_IMPL, impl
    return prev


def fused_impl() -> str:
    """The resolved (non-auto) fused execution mode."""
    if _FUSED_IMPL != "auto":
        return _FUSED_IMPL
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# Opt-in quantization-quality observer (repro.obs.quality.QualityObserver).
# When installed, QuantCtx reports eager (non-traced) activations at every
# quantized site; dispatch owns the slot — mirroring _FUSED_IMPL — so the
# core context never imports repro.obs.
_QUALITY_OBSERVER = None


def set_quality_observer(obs):
    """Install (or clear, with None) the process-wide quality observer;
    returns the previous one."""
    global _QUALITY_OBSERVER
    prev, _QUALITY_OBSERVER = _QUALITY_OBSERVER, obs
    return prev


def quality_observer():
    """The installed quality observer, or None (the default: zero cost)."""
    return _QUALITY_OBSERVER


def site_backend(cfg) -> Backend:
    """Execution backend for one resolved site config.

    ``method='fp'`` and ``backend='fp'`` both mean passthrough; a fused
    backend is validated against the method here so misconfiguration fails
    at resolution time, not with a shape error inside a kernel.
    """
    if cfg.method == "fp":
        return "fp"
    backend = getattr(cfg, "backend", "fake")
    if backend == "fp":
        return "fp"
    if backend == "fused":
        if cfg.method not in _FUSED_METHODS:
            raise ValueError(
                f"method {cfg.method!r} has no fused kernel realization "
                f"(supported: {_FUSED_METHODS})")
        return "fused"
    if backend != "fake":
        raise ValueError(f"unknown backend {backend!r}")
    return "fake"


# ---------------------------------------------------------------------------
# Offline: kernel-ready per-site buffers
# ---------------------------------------------------------------------------

def pack_site_buffer(w: jnp.ndarray, mask: Optional[np.ndarray], cfg, *,
                     bk: int = 512,
                     k_pad_to: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Pack one site's weight into the fused-kernel buffer format.

    ``w`` is [in_ch, out] (dense projections) or [E, in_ch, out] (per-expert
    MoE weights, which share one outlier mask — DESIGN.md §5).  ``mask`` may
    be None for maskless methods (naive/smoothquant): the packed buffer then
    has an empty outlier run and the kernel degenerates to a plain
    per-token x per-channel int8 GEMM.
    """
    if cfg.method in ("muxq", "muxq_smooth") and cfg.outlier_mode != "static":
        raise ValueError(
            "fused backend needs a static calibrated outlier mask "
            "(outlier_mode='static'): channels are permuted offline")
    k = w.shape[-2]
    if mask is None:
        mask = np.zeros(k, bool)
    mask = np.asarray(mask, bool)
    assert mask.shape == (k,), (mask.shape, k)

    def pack2d(w2):
        return ops.prepare_weights(w2, mask, cfg.exp_factor, bk=bk,
                                   weight_bits=cfg.weight_bits,
                                   k_pad_to=k_pad_to)

    pick_bn(w.shape[-1])              # raises now, not in the chip compiler
    if w.ndim == 2:
        mw = pack2d(w)
        w_int, sw = mw.w_int, mw.sw
    elif w.ndim == 3:
        mws = [pack2d(w[e]) for e in range(w.shape[0])]
        mw = mws[0]
        w_int = jnp.stack([m.w_int for m in mws])
        sw = jnp.stack([m.sw for m in mws])
    else:
        raise ValueError(f"cannot pack weight of rank {w.ndim}")
    return {"w_int": np.asarray(w_int), "sw": np.asarray(sw),
            "block_scale": np.asarray(mw.block_scale),
            "gather_idx": np.asarray(mw.gather_idx),
            "in_scale": np.asarray(mw.in_scale)}


def buffer_k_pad(buf) -> int:
    return buf["w_int"].shape[-2]


def pad_buffer_to(buf: Dict[str, np.ndarray], k_pad: int) -> Dict[str, np.ndarray]:
    """Extend a packed buffer with whole zero K-blocks (block_scale 1,
    in_scale 0 — mathematically inert) so per-layer buffers of one site can
    stack to a uniform [L, ...] tree for ``lax.scan``."""
    cur = buffer_k_pad(buf)
    if cur == k_pad:
        return buf
    bk = cur // buf["block_scale"].shape[-1]
    extra = k_pad - cur
    assert extra > 0 and extra % bk == 0, (cur, k_pad, bk)
    pad_rows = [(0, 0)] * (buf["w_int"].ndim - 2) + [(0, extra), (0, 0)]
    return {
        "w_int": np.pad(np.asarray(buf["w_int"]), pad_rows),
        "sw": np.asarray(buf["sw"]),
        "block_scale": np.concatenate(
            [np.asarray(buf["block_scale"]),
             np.ones(extra // bk, np.int32)]),
        "gather_idx": np.pad(np.asarray(buf["gather_idx"]), (0, extra)),
        "in_scale": np.pad(np.asarray(buf["in_scale"]), (0, extra)),
    }


def as_muxq_weights(buf) -> ops.MuxqWeights:
    """Rebuild a (possibly traced) runtime MuxqWeights view over a buffer
    dict.  Statics come from shapes, so this works on scanned slices."""
    k_pad = buf["w_int"].shape[-2]
    bk = k_pad // buf["block_scale"].shape[-1]
    return ops.MuxqWeights(
        w_int=buf["w_int"], sw=buf["sw"], block_scale=buf["block_scale"],
        gather_idx=buf["gather_idx"], in_scale=buf["in_scale"],
        bk=bk, k_orig=None)


# ---------------------------------------------------------------------------
# Online: fused execution
# ---------------------------------------------------------------------------

def fused_matmul(x: jnp.ndarray, buf, *, act_bits: int = 8,
                 impl: Optional[str] = None) -> jnp.ndarray:
    """x [..., K] @ packed site buffer -> [..., N] via the fused MUXQ path."""
    impl = impl or fused_impl()
    mw = as_muxq_weights(buf)
    if impl == "ref":
        return ops.muxq_linear_ref(x, mw, act_bits=act_bits)
    return ops.muxq_linear(x, mw, act_bits=act_bits,
                           interpret=(impl == "interpret"))


def fused_emm(x: jnp.ndarray, buf, *, act_bits: int = 8,
              impl: Optional[str] = None) -> jnp.ndarray:
    """Per-expert fused matmul: x [E, C, K] @ buffer with [E, ...] weight
    leaves -> [E, C, N].  Always runs the jnp oracle form — int8
    ``dot_general`` already hits the MXU, and a vmapped interpret-mode
    Pallas call buys nothing on CPU either."""
    del impl

    def one(xe, we, swe):
        mw = as_muxq_weights({"w_int": we, "sw": swe,
                              "block_scale": buf["block_scale"],
                              "gather_idx": buf["gather_idx"],
                              "in_scale": buf["in_scale"]})
        return ops.muxq_linear_ref(xe, mw, act_bits=act_bits)

    return jax.vmap(one)(x, buf["w_int"], buf["sw"])


def fused_gmm(x: jnp.ndarray, buf, rows, *, act_bits: int = 8,
              impl: Optional[str] = None) -> jnp.ndarray:
    """Grouped fused matmul: x [M, K] rows sorted by expert in whole tiles
    (``repro.models.moe.expert_rows``) @ a buffer with [E, ...] weight
    leaves -> [M, N] float32.  The outlier permutation is shared by the
    experts, so the rows are permuted and quantized per token once; the
    int8 GEMM is the ``muxq_gemm`` kernel's expert axis (the tile's expert
    rides scalar prefetch), or the jnp oracle as one integer
    ``ragged_dot``.  Rows of empty tiles are left unwritten by the kernel
    and are never read back."""
    impl = impl or fused_impl()
    mw = as_muxq_weights(buf)
    m = x.shape[0]
    bm = m // rows["tile_expert"].shape[0]
    body = ops._permute_pad_shift(x, mw)
    if impl == "ref":
        x_int, sx = ref.rowwise_quantize_ref(body, act_bits)
        chan = jnp.repeat(mw.block_scale, mw.bk)
        acc = jax.lax.ragged_dot(x_int.astype(jnp.int32) * chan[None],
                                 mw.w_int.astype(jnp.int32),
                                 rows["group_sizes"])
        own = jnp.repeat(rows["tile_expert"], bm, total_repeat_length=m)
        return acc.astype(jnp.float32) * sx * mw.sw[own, 0]
    interpret = impl == "interpret"
    x_int, sx = ops.rowwise_quantize(body, bits=act_bits, bm=min(128, m),
                                     interpret=interpret)
    return muxq_gemm_grouped(
        x_int, mw.w_int, mw.block_scale, sx, mw.sw, rows["tile_expert"],
        rows["n_used"], bm=bm, bk=mw.bk, interpret=interpret)
