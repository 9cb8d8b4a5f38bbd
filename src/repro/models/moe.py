"""Mixture-of-Experts block (llama4-scout 16e top-1 + shared expert,
dbrx 16e top-4, Moonlight/DeepSeek-V3 64e top-6 with sigmoid scores).

Routing (:func:`route`) scores every expert the router knows (softmax, or
sigmoid scores chosen with a selection-only correction bias), keeps each
token's top-k, renormalizes the chosen scores and scales them.

A layer may hold a contiguous share of the experts
(``cfg.expert_lo``/``cfg.n_held``, ``model-configs`` §4): its weights are
``[n_held, ...]``, the router still scores all ``n_experts``, and a pair
routed to an expert held elsewhere adds nothing here.

Two dispatches:

  * **inference** (:func:`moe_held`) — dropless: the (token, expert) pairs
    whose expert is held are sorted by expert into row tiles (each held
    expert's rows padded to whole tiles, at least one tile each), and the
    two expert projections run as grouped matmuls over those tiles
    (``ctx.gmm``: ``ragged_dot`` in floating point, the ``muxq_gemm``
    kernel's expert axis for fused MUXQ sites).  The pool-wide steps pass
    which rows are real tokens; padding rows route nowhere.
  * **training** (``train=True``) — GShard-style capacity routing within
    groups (= sequences), each group with capacity C = ceil(top_k * s / E *
    factor); over-capacity tokens drop (pass through the residual).  The
    dispatch buffer is [g, e, C, d]: g over ('pod','data'), e over 'model'
    (expert parallelism).  The aux load-balance loss (Switch-style) is
    returned for the trainer.

Router math stays in float32 at full precision (tiny, and a flipped top-k
choice changes a token's output wholesale); the expert projections run
through the quantization ctx so MUXQ applies per expert.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, dense_init
from repro.models.mlp import init_mlp, mlp


MOE_SCOPE = "moe"


def init_moe(key, cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_expert, cfg.n_experts
    _, nh = cfg.held
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "router": dense_init(k1, (d, e)),
        "wi": dense_init(k2, (nh, d, 2 * f)),
        "wo": dense_init(k3, (nh, f, d), fan_in=f),
    }
    if cfg.router_bias:
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
    if cfg.d_ff_shared:
        p["shared"] = init_mlp(k4, cfg, cfg.d_ff_shared)
    return p


def route(cfg: ModelConfig, p: dict, x2: jnp.ndarray):
    """x2 [t, d] -> (scores [t, E] f32, gates [t, k] f32, experts [t, k]):
    every expert's score, and each token's chosen experts with the weights
    their outputs are summed with."""
    logits = jnp.dot(x2.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores + p["router_bias"] if "router_bias" in p else scores
    _, experts = jax.lax.top_k(choice, cfg.top_k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return scores, gates * cfg.routed_scale, experts


def _capacity(cfg: ModelConfig, n_tokens: int, factor: float = 1.25) -> int:
    """Per-expert slot count for one training dispatch group."""
    c = int(factor * cfg.top_k * n_tokens / cfg.n_experts)
    return max(8, ((c + 7) // 8) * 8)  # pad to 8 for TPU lanes


def _dispatch_group(cfg: ModelConfig, xf: jnp.ndarray, probs: jnp.ndarray,
                    cap: int, choice=None):
    """Group-local dispatch.  xf [t, d], probs [t, e] ->
    (buf [e*cap, d], slot [t*k], st [t*k], gates [t*k], keep [t*k]).
    ``choice`` = (gates [t, k], experts [t, k]) from :func:`route`; without
    it the top-k of ``probs`` is taken and renormalized."""
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    if choice is None:
        gate_vals, expert_idx = jax.lax.top_k(probs, k)           # [t, k]
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    else:
        gate_vals, expert_idx = choice

    flat_expert = expert_idx.reshape(-1)                          # [t*k]
    flat_token = jnp.repeat(jnp.arange(t), k)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]

    counts = jax.ops.segment_sum(jnp.ones_like(se), se, num_segments=e)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]])
    pos_in_expert = jnp.arange(se.shape[0]) - starts[se]

    keep = pos_in_expert < cap
    slot = se * cap + jnp.where(keep, pos_in_expert, 0)
    src = jnp.where(keep, slot, e * cap)   # OOB for dropped -> mode="drop"
    buf = jnp.zeros((e * cap, d), xf.dtype).at[src].set(xf[st], mode="drop")
    return buf, slot, st, sg, keep


def _combine_group(out_e: jnp.ndarray, slot, st, sg, keep, t: int):
    """out_e [e*cap, d] -> y [t, d]."""
    contrib = (out_e[slot] * sg[:, None].astype(out_e.dtype)
               * keep[:, None].astype(out_e.dtype))
    return jax.ops.segment_sum(contrib, st, num_segments=t)


def _shared(cfg, p, ctx, x, sq):
    """The shared expert, run on every token through ``mlp``."""
    return mlp(cfg, p["shared"], ctx, x, sq={
        "mlp_up": sq.get("moe_shared_up"),
        "mlp_up@smooth": sq.get("moe_shared_up@smooth"),
        "mlp_up@fused": sq.get("moe_shared_up@fused"),
        "mlp_down": sq.get("moe_shared_down"),
        "mlp_down@smooth": sq.get("moe_shared_down@smooth"),
        "mlp_down@fused": sq.get("moe_shared_down@fused")})


def _expert_ffn(cfg, p, ctx, rows, sq, mm):
    """SwiGLU of the expert projections, ``mm(name, x, w, **site)``."""
    def site(name):
        return dict(mask=sq.get(name), smooth=sq.get(name + "@smooth"),
                    fused=sq.get(name + "@fused"))
    h = mm("moe_up", rows, p["wi"], **site("moe_up"))
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(rows.dtype) * up
    return mm("moe_down", h, p["wo"], **site("moe_down"))


# ---------------------------------------------------------------------------
# Inference: held experts' pairs as row tiles, grouped matmuls
# ---------------------------------------------------------------------------

def row_tile(n_pairs: int) -> int:
    """Rows per tile of the grouped matmul: small for decode-sized batches
    (an expert's padding stays under one tile), larger for prefill."""
    return 32 if n_pairs <= 1024 else 128


def expert_rows(experts: jnp.ndarray, keep: jnp.ndarray, n_held: int,
                bm: int) -> Dict[str, jnp.ndarray]:
    """Row layout of the kept (token, expert) pairs.

    ``experts`` [t, k] are held-local expert ids, ``keep`` [t, k] marks the
    pairs computed here.  Kept pairs are sorted by expert; each held expert
    gets its rows padded to whole tiles of ``bm`` rows, at least one tile
    (so every held expert's weights are read once per call).  Returns:

      row_token   [M] int32   token of each row (t for padding rows)
      pair_row    [t, k]      row of each pair (M for pairs not kept)
      group_sizes [n_held]    rows (whole tiles) per held expert
      tile_expert [n_tiles]   held expert of each tile
      n_used      [1] int32   tiles in use (the rest are empty)

    with a static ``n_tiles`` bounding the worst case (every pair kept):
    ceil(t * min(k, n_held) / bm) + n_held, and M = n_tiles * bm."""
    t, k = experts.shape
    n_tiles = -(-t * min(k, n_held) // bm) + n_held
    m = n_tiles * bm
    e = jnp.where(keep, experts, n_held).reshape(-1).astype(jnp.int32)
    counts = jnp.bincount(e, length=n_held + 1)[:n_held].astype(jnp.int32)
    tiles = jnp.maximum(1, -(-counts // bm))
    tile_end = jnp.cumsum(tiles)
    row_lo = jnp.concatenate([(tile_end - tiles) * bm, jnp.asarray([m])])
    first = jnp.concatenate([jnp.cumsum(counts) - counts,
                             jnp.sum(counts, keepdims=True)])
    order = jnp.argsort(e, stable=True)
    se = e[order]
    rank = jnp.arange(t * k, dtype=jnp.int32) - first[se]
    row_sorted = jnp.where(se < n_held, row_lo[se] + rank, m)
    pair_row = jnp.zeros(t * k, jnp.int32).at[order].set(row_sorted)
    token = jnp.arange(t * k, dtype=jnp.int32) // k
    row_token = jnp.full((m,), t, jnp.int32).at[pair_row].set(
        token, mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"),
        n_held - 1).astype(jnp.int32)
    return {"row_token": row_token, "pair_row": pair_row.reshape(t, k),
            "group_sizes": (tiles * bm).astype(jnp.int32),
            "tile_expert": tile_expert,
            "n_used": tile_end[-1:].astype(jnp.int32)}


def moe_held(cfg: ModelConfig, p: dict, ctx, x: jnp.ndarray,
             sq: Optional[Dict] = None, valid: Optional[jnp.ndarray] = None):
    """Dropless inference over the experts this layer holds.

    x [b, s, d]; ``valid`` [b, s] bool marks real tokens (None: all).
    Returns (out [b, s, d], held rows: int32 count of valid tokens' pairs
    that landed on a held expert, scores [b*s, E])."""
    sq = sq or {}
    b, s, d = x.shape
    t = b * s
    lo, nh = cfg.held
    x2 = x.reshape(t, d)
    with jax.named_scope(MOE_SCOPE):
        scores, gates, experts = route(cfg, p, x2)
        local = experts - lo
        keep = (local >= 0) & (local < nh)
        if valid is not None:
            keep = keep & valid.reshape(t, 1)
        lay = expert_rows(local, keep, nh, row_tile(t * min(cfg.top_k, nh)))
        rows = jnp.concatenate([x2, jnp.zeros((1, d), x2.dtype)])[
            lay["row_token"]]
        out = _expert_ffn(cfg, p, ctx, rows, sq,
                          lambda n, r, w, **kw: ctx.gmm(n, r, w, lay, **kw))
        y = jnp.zeros((t, d), jnp.float32)
        weight = jnp.where(keep, gates, 0.0)
        for j in range(cfg.top_k):
            y = y + jnp.take(out, lay["pair_row"][:, j], axis=0, mode="fill",
                             fill_value=0).astype(jnp.float32) * weight[:, j:j + 1]
        y = y.astype(x.dtype)
    if "shared" in p:
        y = y + _shared(cfg, p, ctx, x2[None], sq)[0]
    return y.reshape(b, s, d), jnp.sum(keep, dtype=jnp.int32), scores


def _aux_loss(cfg, scores):
    """Switch load-balance loss over [tokens, E] scores."""
    e = cfg.n_experts
    top1 = jnp.argmax(scores, axis=-1).reshape(-1)
    assign_frac = jax.ops.segment_sum(
        jnp.ones_like(top1, jnp.float32), top1, num_segments=e) / top1.shape[0]
    prob_frac = scores.reshape(-1, e).mean(axis=0)
    return e * jnp.sum(assign_frac * prob_frac)


def moe(cfg: ModelConfig, p: dict, ctx, x: jnp.ndarray,
        sq: Optional[Dict] = None, *, train: bool = False
        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [b, s, d] -> (out, aux_loss).

    Inference (the default) is :func:`moe_held`'s dropless dispatch, so
    ``decode_step`` reproduces ``forward``.  ``train=True`` sizes the
    dispatch buffer with the classic capacity factor and DROPS
    over-capacity tokens (throughput compromise: the [g, e, C, d]
    all-to-all buffer stays small); groups = batch rows when s > 1, one
    flat group for s == 1."""
    sq = sq or {}
    if not train:
        y, _, scores = moe_held(cfg, p, ctx, x, sq)
        return y, _aux_loss(cfg, scores)
    b, s, d = x.shape
    e = cfg.n_experts
    lo, nh = cfg.held

    grouped = s > 1
    if grouped:
        g, tg = b, s
        xg = x                                                     # [g, tg, d]
    else:
        g, tg = 1, b * s
        xg = x.reshape(1, tg, d)

    scores, gates, experts = route(cfg, p, xg.reshape(g * tg, d))
    probs = scores.reshape(g, tg, e)
    cap = _capacity(cfg, tg)

    buf, slot, st, sg_, keep = jax.vmap(
        lambda xf, pr, gt, ex: _dispatch_group(cfg, xf, pr, cap, (gt, ex)))(
        xg, probs, gates.reshape(g, tg, -1), experts.reshape(g, tg, -1))
    buf = buf.reshape(g, e, cap, d)
    spec_fn = _expert_sharding()
    if spec_fn is not None:
        spec = spec_fn(buf.shape)
        if spec is not None:
            buf = jax.lax.with_sharding_constraint(buf, spec)
    # only the held experts' slots are computed here; the rest add nothing
    held = buf[:, lo:lo + nh]

    # ---- expert FFN (quantized), groups folded into the expert rows -------
    bswap = held.swapaxes(0, 1).reshape(nh, g * cap, d)
    out_sw = _expert_ffn(cfg, p, ctx, bswap, sq, ctx.emm)
    out_h = out_sw.reshape(nh, g, cap, d).swapaxes(0, 1)          # [g,nh,cap,d]
    out_e = jnp.pad(out_h, ((0, 0), (lo, e - lo - nh), (0, 0), (0, 0)))

    out_flat = out_e.reshape(g, e * cap, d)
    yg = jax.vmap(lambda oe, sl, stt, gg, kk: _combine_group(oe, sl, stt, gg, kk, tg)
                  )(out_flat, slot, st, sg_, keep).astype(x.dtype)
    yf = yg.reshape(b * s, d)
    if "shared" in p:
        yf = yf + _shared(cfg, p, ctx, xg.reshape(1, b * s, d), sq)[0]
    return yf.reshape(b, s, d), _aux_loss(cfg, probs)


_EXPERT_SHARDING: Optional[Callable] = None


def set_expert_sharding(spec_fn: Optional[Callable]) -> None:
    """Install a callable shape -> NamedSharding|None for the [g, e, C, d]
    dispatch buffer (g over dp, e over 'model').  None disables the
    constraint (single-device runs)."""
    global _EXPERT_SHARDING
    _EXPERT_SHARDING = spec_fn


def _expert_sharding():
    return _EXPERT_SHARDING
