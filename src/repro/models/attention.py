"""GQA attention with the features the assigned archs need:

  * grouped-query attention (n_kv_heads <= n_heads), fused QKV projection
  * optional QKV bias (qwen family)
  * sliding-window "local" blocks + attention-logit softcap (gemma2)
  * RoPE
  * full forward (train / prefill, optionally emitting a KV cache) and a
    single-token decode step against a preallocated cache
  * cross-attention (whisper decoder)

All projections run through the quantization ctx (paper's c_attn / c_proj
target set).  ``sq`` is the per-layer site-quant dict {site: outlier mask}
so static MUXQ masks flow through ``lax.scan``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import paged_attention as PA
from repro.models.common import ModelConfig, apply_rope, dense_init, softcap
from repro.parallel import serve_sharding as TP
from repro.parallel.act_sharding import cache_update_mode
from repro.serve import kvq

NEG_INF = -1e9

# Optional KV calibration hook: when set (repro.quantize installs a
# kvq.KVCalibCollector over the eager calibration forwards), every
# full-sequence attention reports its post-RoPE K/V so int4 KV pages can
# calibrate per-head outlier channels.  None in all normal traced paths.
_KV_OBSERVER = None


def set_kv_observer(fn) -> None:
    """Install (or clear, with None) the eager-calibration KV observer,
    called as ``fn(layer_prefix, k, v)`` with [b, s, kvh, dh] tensors."""
    global _KV_OBSERVER
    _KV_OBSERVER = fn


_ROUTING_KEYS = ("pos", "page_table", "start", "write_lo", "write_hi",
                 "n_valid")


def _write_cache(cache: dict, updates: dict) -> dict:
    """New cache dict: every non-routing array passes through, quantized
    writes overwrite — so mode-specific extras (int8/int4 scales, int4
    redistribution rows) survive the step without per-mode plumbing."""
    out = {n: cache[n] for n in cache if n not in _ROUTING_KEYS}
    out.update(updates)
    return out


def init_attention(key, cfg: ModelConfig, cross: bool = False) -> dict:
    h, kv, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    if cross:
        p = {
            "wq": dense_init(k1, (d, h * dh)),
            "wkv": dense_init(k2, (d, 2 * kv * dh)),
            "wo": dense_init(k3, (h * dh, d), fan_in=h * dh),
        }
    else:
        p = {
            "wqkv": dense_init(k1, (d, (h + 2 * kv) * dh)),
            "wo": dense_init(k2, (h * dh, d), fan_in=h * dh),
        }
        if cfg.qkv_bias:
            p["bqkv"] = jnp.zeros(((h + 2 * kv) * dh,), jnp.float32)
    return p


def _split_qkv(cfg: ModelConfig, qkv: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b, s, _ = qkv.shape
    q = qkv[..., : h * dh].reshape(b, s, h, dh)
    k = qkv[..., h * dh: (h + kv) * dh].reshape(b, s, kv, dh)
    v = qkv[..., (h + kv) * dh:].reshape(b, s, kv, dh)
    return q, k, v


def sdpa(cfg: ModelConfig, q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
         bias: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Grouped-query softmax(QK^T/sqrt(d) [softcap] + bias) V.

    q [b, sq, h, dh];  k/v [b, sk, kv, dh] (UNrepeated — the group dim rides
    inside the einsum so the broadcast KV is never materialized; at kv=8,
    h=48 the repeat would 6x the cache read traffic)."""
    b, sq_, h, dh = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, sq_, kv, g, dh)
    scale = cfg.head_dim ** -0.5
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    scores = softcap(scores, cfg.attn_softcap)
    if bias is not None:
        scores = scores + bias[:, :, None]    # [..., sq, sk] -> group-dim bcast
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq_, h, dh)


def causal_bias(sq: int, sk: int, window: int, window_flag,
                q_offset: int = 0) -> jnp.ndarray:
    """[1, 1, sq, sk] additive mask.  ``window_flag`` (python bool or traced
    scalar — scan-friendly) selects sliding-window locality; ``q_offset``
    places the query block inside a longer key range (decode: sq=1,
    q_offset=cache position)."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    causal = kpos <= qpos
    in_window = kpos > qpos - window
    allow = causal & (in_window | ~jnp.asarray(window_flag))
    return jnp.where(allow, 0.0, NEG_INF)[None, None].astype(jnp.float32)


def attention(cfg: ModelConfig, p: dict, ctx, x: jnp.ndarray,
              positions: jnp.ndarray, *, window_flag=False,
              sq: Optional[Dict] = None,
              cache: Optional[dict] = None,
              causal: bool = True) -> Tuple[jnp.ndarray, Optional[dict]]:
    """Full-sequence attention.  If ``cache`` is a dict of preallocated
    [b, s_max, kv, dh] buffers, writes K/V at positions [0, s) and returns
    the updated cache (prefill)."""
    sq = sq or {}
    b, s, d = x.shape
    qkv = ctx("attn_qkv", x, p["wqkv"], mask=sq.get("attn_qkv"),
              smooth=sq.get("attn_qkv@smooth"), fused=sq.get("attn_qkv@fused"))
    if "bqkv" in p:
        qkv = qkv + p["bqkv"].astype(x.dtype)
    q, k, v = _split_qkv(cfg, qkv)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if _KV_OBSERVER is not None and not isinstance(x, jax.core.Tracer):
        # eager calibration only: report the exact post-RoPE K/V the paged
        # write path would quantize, keyed by the layer's site prefix
        _KV_OBSERVER(getattr(ctx, "prefix", ""), k, v)

    if cache is not None:
        cache = dict(cache)
        cache["k"] = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
        cache["pos"] = jnp.asarray(s, jnp.int32)

    bias = causal_bias(s, s, cfg.window_size, window_flag) if causal else None
    o = sdpa(cfg, q, k, v, bias)
    o = o.reshape(b, s, cfg.n_heads * cfg.head_dim)
    out = ctx("attn_out", o, p["wo"], mask=sq.get("attn_out"),
              smooth=sq.get("attn_out@smooth"), fused=sq.get("attn_out@fused"))
    return out, cache


def attention_decode(cfg: ModelConfig, p: dict, ctx, x: jnp.ndarray,
                     cache: dict, *, window_flag=False,
                     sq: Optional[Dict] = None) -> Tuple[jnp.ndarray, dict]:
    """One-token decode: x [b, 1, d]; cache k/v [b, s_max, kv, dh] + pos."""
    sq = sq or {}
    b, one, d = x.shape
    pos = cache["pos"]
    qkv = ctx("attn_qkv", x, p["wqkv"], mask=sq.get("attn_qkv"),
              smooth=sq.get("attn_qkv@smooth"), fused=sq.get("attn_qkv@fused"))
    if "bqkv" in p:
        qkv = qkv + p["bqkv"].astype(x.dtype)
    q, k, v = _split_qkv(cfg, qkv)
    positions = jnp.full((b, 1), pos, jnp.int32)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    # the cache's key set names its page mode (int8: Oaken-style scales;
    # int4: MUXQ'd nibbles + redistribution rows; fp: raw) — one quantize
    # entry point for every mode, shared with the paged pool
    quantizer = kvq.from_cache(cache)
    parts = quantizer.quantize(k, v)

    if cache_update_mode() == "select":
        # elementwise write (shard-local under seq-sharded caches)
        sel = (jnp.arange(cache["k"].shape[1]) == pos)[None, :, None, None]
        written = {n: jnp.where(sel, parts[n].astype(cache[n].dtype),
                                cache[n]) for n in parts}
    else:
        dus = jax.lax.dynamic_update_slice
        written = {n: dus(cache[n], parts[n].astype(cache[n].dtype),
                          (0, pos, 0, 0)) for n in parts}
    new_cache = _write_cache(cache, written)
    new_cache["pos"] = pos + 1
    kk, vv = quantizer.dequantize(written, x.dtype)
    s_max = written["k"].shape[1]
    kpos = jnp.arange(s_max)
    in_window = kpos > pos - cfg.window_size
    allow = (kpos <= pos) & (in_window | ~jnp.asarray(window_flag))
    bias = jnp.where(allow, 0.0, NEG_INF)[None, None, None, :].astype(jnp.float32)
    o = sdpa(cfg, q, kk, vv, bias)
    o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    out = ctx("attn_out", o, p["wo"], mask=sq.get("attn_out"),
              smooth=sq.get("attn_out@smooth"), fused=sq.get("attn_out@fused"))
    return out, new_cache


def attention_decode_paged(cfg: ModelConfig, p: dict, ctx, x: jnp.ndarray,
                           cache: dict, *, window_flag=False,
                           sq: Optional[Dict] = None) -> Tuple[jnp.ndarray, dict]:
    """Pool-wide one-token decode against a *paged* KV pool with a per-slot
    position vector (the continuous-batching step — ``repro.serve``).

    x [b, 1, d].  ``cache`` holds one layer's page pool plus the pool-wide
    routing state:

      k/v          [n_pages, kvh, page_size, dh]  (int8 pages carry
      k/v_scale    [n_pages, kvh, page_size, 1]   per-(pos, head) scales)
      page_table   [b, pages_per_slot] int32 — physical page per logical
                   page; 0 is the reserved scratch page (inactive slots /
                   unallocated tail)
      pos          [b] int32 — per-slot sequence position (may differ per
                   slot: misaligned sequences still batch into ONE step)

    The new K/V is scattered into page ``page_table[b, pos//ps]`` at offset
    ``pos % ps``; attention reads the slot's logical key range via a page
    gather and masks per slot with ``kpos <= pos[b]`` (+ sliding window), so
    no alignment between slots is ever required.

    **Block-sparse reads**: the read budget is the page table's width — the
    scheduler passes ``page_table[:, :bucket]`` where ``bucket`` covers the
    longest live sequence's ``ceil(pos/ps)`` pages, so a short sequence in
    a deep pool never gathers its slot's full logical capacity.  The read
    side lives in :mod:`repro.kernels.paged_attention`: on TPU the Pallas
    kernel (page-table-indexed K/V loads, int8 pages dequantized
    in-kernel), on CPU the jnp gather reference — the fp-page serve tests
    pin the reference bit-exact against the dense cache path."""
    sq = sq or {}
    b, one, d = x.shape
    pos = cache["pos"]                                      # [b]
    page_table = cache["page_table"]                        # [b, P]
    ps = cache["k"].shape[2]
    qkv = ctx("attn_qkv", x, p["wqkv"], mask=sq.get("attn_qkv"),
              smooth=sq.get("attn_qkv@smooth"), fused=sq.get("attn_qkv@fused"))
    if "bqkv" in p:
        qkv = qkv + p["bqkv"].astype(x.dtype)
    q, k, v = _split_qkv(cfg, qkv)
    # tensor-parallel serving: inside the engine's shard_map body each
    # shard keeps only its contiguous run of kv heads (and their grouped q
    # heads — GQA orders q as head = kvh_index * group + g, so both slices
    # are contiguous); quantize, page writes and the kernel then run
    # entirely shard-local, and the outputs psum back below
    shard = TP.active()
    if shard is not None:
        q, k, v = (TP.slice_heads(t, shard) for t in (q, k, v))
    positions = pos[:, None].astype(jnp.int32)              # [b, 1]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    # one quantize entry point for every page mode (fp/int8/int4) — the
    # same kvq seam the pool's prefill writes go through
    quantizer = kvq.from_cache(cache)
    parts = quantizer.quantize(k, v)

    # scatter the new token's K/V into each slot's current page.  Inactive
    # slots all route to scratch page 0 (never read back): duplicate indices
    # there are harmless.
    page_idx = jnp.take_along_axis(page_table, (pos // ps)[:, None], 1)[:, 0]
    offset = pos % ps
    new_cache = _write_cache(cache, {
        n: cache[n].at[page_idx, :, offset].set(
            parts[n][:, 0].astype(cache[n].dtype)) for n in parts})

    # read path: the jnp gather reference on CPU, the Pallas kernel
    # (page-table-indexed loads, in-kernel int8 dequant / int4 nibble
    # unpack + inverse redistribution) on TPU/interpret — both in
    # repro.kernels.paged_attention.  The traced per-layer window flag
    # folds into an effective-window scalar either way.
    win = jnp.where(jnp.asarray(window_flag), cfg.window_size,
                    PA.NO_WINDOW).astype(jnp.int32)
    o = PA.paged_attention_decode(
        q[:, 0], new_cache["k"], new_cache["v"], page_table, pos,
        window=win, softcap=cfg.attn_softcap,
        **quantizer.kernel_operands(new_cache))
    if shard is not None:
        # zero-pad psum gather back to the full head axis (bit-exact:
        # every element = one shard's value + M-1 exact zeros), so the
        # attn_out projection sees the full per-token channel vector the
        # MUXQ per-token act-quant requires
        o = TP.all_heads(o, cfg.n_heads, shard)
    o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    out = ctx("attn_out", o, p["wo"], mask=sq.get("attn_out"),
              smooth=sq.get("attn_out@smooth"), fused=sq.get("attn_out@fused"))
    return out, new_cache


def attention_verify_paged(cfg: ModelConfig, p: dict, ctx, x: jnp.ndarray,
                           cache: dict, *, window_flag=False,
                           sq: Optional[Dict] = None) -> Tuple[jnp.ndarray, dict]:
    """Pool-wide MULTI-token decode against a paged KV pool — the
    speculative-decoding verify step (``repro.serve.scheduler``).

    x [b, k, d]: per slot, the last committed token followed by up to
    ``k - 1`` draft tokens (the scheduler's n-gram proposals).  ``cache``
    is the pooled-decode routing state of :func:`attention_decode_paged`
    plus ``n_valid`` [b] int32 — how many of the k rows are real for each
    slot (1 committed + its draft length; 0 parks an inactive slot).

    Row j of slot b sits at absolute position ``pos[b] + j``.  All k rows'
    K/V scatter into the slot's pages FIRST (rows >= n_valid route to the
    reserved scratch page 0), then ONE kernel call attends the whole
    ``[slot, k]`` query block with a per-row causal mask — so row j reads
    exactly the keys a sequential decode at position ``pos[b] + j`` would
    see, including the rows written this step.  Rejected draft positions
    need no undo: per-slot ``pos`` is the source of truth and their page
    rows are simply overwritten when the slot's position reaches them
    (the scheduler COWs shared pages before the k-token write)."""
    sq = sq or {}
    b, kb, d = x.shape
    pos = cache["pos"]                                      # [b]
    n_valid = cache["n_valid"]                              # [b]
    page_table = cache["page_table"]                        # [b, P]
    ps = cache["k"].shape[2]
    qkv = ctx("attn_qkv", x, p["wqkv"], mask=sq.get("attn_qkv"),
              smooth=sq.get("attn_qkv@smooth"), fused=sq.get("attn_qkv@fused"))
    if "bqkv" in p:
        qkv = qkv + p["bqkv"].astype(x.dtype)
    q, k, v = _split_qkv(cfg, qkv)
    # per-shard head slice under tensor-parallel serving (see
    # attention_decode_paged — same contiguous GQA cut, same psum below)
    shard = TP.active()
    if shard is not None:
        q, k, v = (TP.slice_heads(t, shard) for t in (q, k, v))
    positions = pos[:, None] + jnp.arange(kb, dtype=jnp.int32)[None]  # [b, k]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    quantizer = kvq.from_cache(cache)
    parts = quantizer.quantize(k, v)

    # scatter all k rows into the slot's pages; rows past a slot's valid
    # count (draft padding, parked slots) route to scratch page 0 — one
    # shape-stable scatter, no per-slot control flow
    logical = jnp.clip(positions // ps, 0, page_table.shape[1] - 1)
    page = jnp.take_along_axis(page_table, logical, axis=1)           # [b, k]
    valid = jnp.arange(kb, dtype=jnp.int32)[None] < n_valid[:, None]
    page_idx = jnp.where(valid, page, 0)
    offset = positions % ps
    new_cache = _write_cache(cache, {
        n: cache[n].at[page_idx, :, offset].set(
            parts[n].astype(cache[n].dtype)) for n in parts})

    win = jnp.where(jnp.asarray(window_flag), cfg.window_size,
                    PA.NO_WINDOW).astype(jnp.int32)
    o = PA.paged_attention_decode(
        q, new_cache["k"], new_cache["v"], page_table, pos,
        window=win, softcap=cfg.attn_softcap,
        **quantizer.kernel_operands(new_cache))
    if shard is not None:
        o = TP.all_heads(o, cfg.n_heads, shard)
    o = o.reshape(b, kb, cfg.n_heads * cfg.head_dim)
    out = ctx("attn_out", o, p["wo"], mask=sq.get("attn_out"),
              smooth=sq.get("attn_out@smooth"), fused=sq.get("attn_out@fused"))
    return out, new_cache


def attention_prefill_paged(cfg: ModelConfig, p: dict, ctx, x: jnp.ndarray,
                            cache: dict, *, window_flag=False,
                            sq: Optional[Dict] = None) -> Tuple[jnp.ndarray, dict]:
    """Chunk-of-prompt prefill straight into a *paged* KV pool — the
    chunked counterpart of :func:`attention` + :func:`attention_decode_paged`
    (``repro.serve``'s admission path; there is no dense ``[1, T]`` prefill
    cache anymore).

    x [b, C, d] — per prefilling slot, one chunk of that slot's prompt (C
    is the scheduler's bucketed chunk shape; the tail beyond a slot's valid
    tokens is padding, and slots not advancing this step are all-padding
    rows).  ``cache`` holds one layer's page pool plus routing state:

      k/v          [n_pages, kvh, ps, dh]  (int8 pages carry
      k/v_scale    [n_pages, kvh, ps, 1]   per-(pos, head) scales)
      page_table   [b, pages] int32 — the prefilling slots' page-table
                   rows, sliced to the step's bucketed page budget (rows
                   of idle slots are all scratch page 0)
      start        [b] int32 — absolute position of each slot's chunk's
                   first token
      write_lo/hi  [b] int32 — per-slot absolute position window whose K/V
                   lands in table pages; everything else (chunk padding,
                   positions already covered by prefix-shared pages, idle
                   slots with an empty ``write_lo == write_hi`` window)
                   routes to the reserved scratch page 0 and is never
                   read back

    Each slot's chunk K/V is scattered into its pages FIRST (one
    shape-stable ``[slot, C]`` scatter — the same query-block trick as
    :func:`attention_verify_paged`), then ONE kernel call attends every
    slot's whole logical key range through the page table with a per-slot
    start-offset causal mask — so a query only ever sees keys at positions
    <= its own, which earlier chunks (or the shared prefix) already wrote.
    Slots' write windows are disjoint (each covers only pages that slot
    exclusively owns), so batching N slots into one call is bit-identical
    to running them sequentially.  Masked lanes underflow to exactly 0 in
    the softmax, so fp pages at the compute dtype reproduce the old
    full-prompt dense prefill bit for bit (the parity oracle the serve
    tests pin).

    Back compat: a 1-D ``page_table`` [pages] with scalar
    ``start``/``write_lo``/``write_hi`` (the pre-multi-slot single-request
    form) is normalized to the batched shapes with b=1."""
    sq = sq or {}
    b, C, d = x.shape
    ps = cache["k"].shape[2]
    start = jnp.asarray(cache["start"], jnp.int32)
    write_lo = jnp.asarray(cache["write_lo"], jnp.int32)
    write_hi = jnp.asarray(cache["write_hi"], jnp.int32)
    page_table = cache["page_table"]
    if page_table.ndim == 1:                                # legacy [P] form
        page_table = page_table[None]
    if start.ndim == 0:
        start = jnp.reshape(start, (1,))
        write_lo = jnp.reshape(write_lo, (1,))
        write_hi = jnp.reshape(write_hi, (1,))
    n_pages_budget = page_table.shape[1]
    qkv = ctx("attn_qkv", x, p["wqkv"], mask=sq.get("attn_qkv"),
              smooth=sq.get("attn_qkv@smooth"), fused=sq.get("attn_qkv@fused"))
    if "bqkv" in p:
        qkv = qkv + p["bqkv"].astype(x.dtype)
    q, k, v = _split_qkv(cfg, qkv)
    # per-shard head slice under tensor-parallel serving (see
    # attention_decode_paged — same contiguous GQA cut, same psum below)
    shard = TP.active()
    if shard is not None:
        q, k, v = (TP.slice_heads(t, shard) for t in (q, k, v))
    p_abs = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]   # [b, C]
    positions = p_abs
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    quantizer = kvq.from_cache(cache)
    parts = quantizer.quantize(k, v)

    # scatter every slot's chunk K/V into its pages.  Positions outside a
    # slot's write window (chunk tail padding past the prompt, prefix-shared
    # positions whose pages are mapped read-only, idle slots' empty windows)
    # route to scratch page 0, which is never read back — same trick as the
    # pooled decode's inactive slots, so the write is one shape-stable
    # [slot, C] scatter with no control flow.
    writable = (p_abs >= write_lo[:, None]) & (p_abs < write_hi[:, None])
    logical = jnp.clip(p_abs // ps, 0, n_pages_budget - 1)
    page = jnp.take_along_axis(page_table, logical, axis=1)         # [b, C]
    page_idx = jnp.where(writable, page, 0)
    offset = p_abs % ps
    new_cache = _write_cache(cache, {
        n: cache[n].at[page_idx, :, offset].set(
            parts[n].astype(cache[n].dtype)) for n in parts})

    # read every slot's whole logical key range [0, pages*ps) through the
    # page table with the per-slot start-offset causal mask — the same
    # [slot, sq] query-block kernel as decode/verify, with sq=C and
    # pos=start [b].  On CPU the jnp gather reference reproduces the old
    # gather→dequantize→sdpa op sequence exactly (extra gathered keys past
    # a query's position are NEG_INF-masked and underflow to exactly 0, so
    # fp pages stay bit-exact); on TPU/interpret the flash-style Pallas
    # kernel streams key pages through scalar prefetch with online softmax
    # and in-kernel int8 / int4-nibble dequant + inverse outlier
    # redistribution.
    win = jnp.where(jnp.asarray(window_flag), cfg.window_size,
                    PA.NO_WINDOW).astype(jnp.int32)
    o = PA.paged_attention_decode(
        q, new_cache["k"], new_cache["v"], page_table, start,
        window=win, softcap=cfg.attn_softcap,
        **quantizer.kernel_operands(new_cache))
    if shard is not None:
        o = TP.all_heads(o, cfg.n_heads, shard)
    o = o.reshape(b, C, cfg.n_heads * cfg.head_dim)
    out = ctx("attn_out", o, p["wo"], mask=sq.get("attn_out"),
              smooth=sq.get("attn_out@smooth"), fused=sq.get("attn_out@fused"))
    return out, new_cache


def cross_attention(cfg: ModelConfig, p: dict, ctx, x: jnp.ndarray,
                    memory: jnp.ndarray, sq: Optional[Dict] = None) -> jnp.ndarray:
    """Whisper-style cross attention: queries from decoder x, keys/values
    from encoder memory.  No causal mask, no RoPE on memory."""
    sq = sq or {}
    b, s, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = ctx("cross_q", x, p["wq"], mask=sq.get("cross_q"),
            smooth=sq.get("cross_q@smooth"), fused=sq.get("cross_q@fused"))
    kvm = ctx("cross_kv", memory, p["wkv"], mask=sq.get("cross_kv"),
              smooth=sq.get("cross_kv@smooth"), fused=sq.get("cross_kv@fused"))
    sm = memory.shape[1]
    q = q.reshape(b, s, h, dh)
    k = kvm[..., : kv * dh].reshape(b, sm, kv, dh)
    v = kvm[..., kv * dh:].reshape(b, sm, kv, dh)
    o = sdpa(cfg, q, k, v, None).reshape(b, s, h * dh)
    return ctx("cross_out", o, p["wo"], mask=sq.get("cross_out"),
               smooth=sq.get("cross_out@smooth"),
               fused=sq.get("cross_out@fused"))


def n_attn_layers(cfg: ModelConfig) -> int:
    """Number of KV-cache-bearing attention invocations in the stack."""
    if cfg.shared_attn_every:   # zamba2: shared weights, per-site caches
        return sum(1 for i in range(cfg.n_layers)
                   if i % cfg.shared_attn_every == cfg.shared_attn_every - 1)
    return sum(1 for b in cfg.blocks if b in ("attn", "local", "global", "moe"))


def init_cache(cfg: ModelConfig, batch: int, s_max: int, dtype=jnp.bfloat16,
               layers: Optional[int] = None) -> dict:
    """Preallocated per-layer KV cache (stacked leading layer dim for scan)."""
    kv, dh = cfg.n_kv_heads, cfg.head_dim
    n_attn = layers if layers is not None else n_attn_layers(cfg)
    shape = (n_attn, batch, s_max, kv, dh)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.asarray(0, jnp.int32)}
