"""The model stack: init + forward + decode for all five families.

Families (cfg.family):
  dense   — attn(+per-layer window flag)+MLP       (qwen*, gemma2, internvl2)
  moe     — attn + mixture-of-experts FFN          (llama4-scout, dbrx)
  ssm     — Mamba2 blocks only                     (mamba2)
  hybrid  — Mamba2 + a *shared* attn+MLP block
            applied every k layers                 (zamba2)
  encdec  — encoder + decoder w/ cross-attn        (whisper)

Layers whose parameters share one structure form a group
(``cfg.layer_groups``: an expert model's leading dense layers, then the
rest), each stacked [n, ...] under its own key; :func:`_run_groups` is the
one layer loop every decoder-only path runs, group after group.

Two execution paths share one (stacked, [L, ...]-leading) param layout:
  * ``scan=True``  — ``lax.scan`` over layers: tiny HLO, fast XLA compiles
    at 512 devices, remat-friendly.  Per-layer data (window flags, MUXQ
    outlier masks) ride along as scanned xs.
  * ``scan=False`` — python loop with per-layer site names
    (``layer{i}/attn_qkv`` …) so the eager calibration pass can attribute
    activation stats to individual layers.

The hybrid family always uses the python loop (38 compact blocks — HLO is
small; the shared block's 6 KV caches don't fit scan's uniform-xs shape).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.context import FpCtx
from repro.parallel import serve_sharding as TP
from repro.parallel.act_sharding import constrain
from repro.models import attention as A
from repro.models import mlp as M
from repro.models import moe as E
from repro.models import ssm as S
from repro.models.common import (GROUP_SITE_PREFIX, ModelConfig, apply_norm,
                                 cross_entropy, dense_init, group_items,
                                 group_name, init_norm, softcap)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, kind: str, decoder: bool = False) -> dict:
    """One layer's weights; ``kind`` is its FFN kind ("dense" |
    "experts") or "mamba"."""
    ks = jax.random.split(key, 8)
    if kind == "mamba":
        return {"ln1": init_norm(cfg, cfg.d_model), "ssm": S.init_ssm(ks[0], cfg)}
    p = {"ln1": init_norm(cfg, cfg.d_model), "attn": A.init_attention(ks[0], cfg),
         "ln2": init_norm(cfg, cfg.d_model)}
    if cfg.sandwich_norm:
        p["ln1b"] = init_norm(cfg, cfg.d_model)
        p["ln2b"] = init_norm(cfg, cfg.d_model)
    if kind == "experts":
        p["moe"] = E.init_moe(ks[1], cfg)
    else:
        p["mlp"] = M.init_mlp(ks[1], cfg)
    if decoder:
        p["cross"] = A.init_attention(ks[2], cfg, cross=True)
        p["ln3"] = init_norm(cfg, cfg.d_model)
    return p


def _stacked_layers(key, cfg: ModelConfig, kinds, decoder: bool = False) -> dict:
    """Init each layer then stack leaves to [L, ...].  All kinds in ``kinds``
    must share a param structure (one layer group)."""
    keys = jax.random.split(key, len(kinds))
    layers = [_init_layer(keys[i], cfg, kinds[i], decoder) for i in range(len(kinds))]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def init_params(cfg: ModelConfig, key) -> dict:
    k_embed, k_layers, k_enc, k_shared, k_head = jax.random.split(key, 5)
    params: Dict[str, Any] = {
        "embed": jax.random.normal(k_embed, (cfg.padded_vocab, cfg.d_model), jnp.float32) * 0.02,
        "ln_f": init_norm(cfg, cfg.d_model),
    }
    fam = cfg.family
    if fam == "encdec":
        params["enc_layers"] = _stacked_layers(k_enc, cfg, ["dense"] * cfg.n_enc_layers)
        params["enc_ln_f"] = init_norm(cfg, cfg.d_model)
        params["layers"] = _stacked_layers(k_layers, cfg, ["dense"] * cfg.n_layers, decoder=True)
    elif fam == "hybrid":
        params["layers"] = _stacked_layers(k_layers, cfg, ["mamba"] * cfg.n_layers)
        params["shared"] = _init_layer(k_shared, cfg, "dense")
    elif fam == "ssm":
        params["layers"] = _stacked_layers(k_layers, cfg, ["mamba"] * cfg.n_layers)
    else:
        groups = cfg.layer_groups
        keys = [k_layers] if len(groups) == 1 else jax.random.split(
            k_layers, len(groups))
        for k, (name, lo, hi) in zip(keys, groups):
            params[name] = _stacked_layers(k, cfg, cfg.ffn_kinds[lo:hi])
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.padded_vocab))
    return params


def layer_slice(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


# ---------------------------------------------------------------------------
# Block bodies (single layer)
# ---------------------------------------------------------------------------

def _ffn(cfg, lp, ctx, h, sq, *, train=False, valid=None,
         held_rows=False):
    """The layer's FFN: (out, stat).  ``stat`` is the MoE aux loss (f32),
    or with ``held_rows`` the routed rows that landed on held experts
    (int32; 0 in a dense layer)."""
    if "moe" in lp:
        if held_rows:
            m, n, _ = E.moe_held(cfg, lp["moe"], ctx, h, sq, valid)
            return m, n
        return E.moe(cfg, lp["moe"], ctx, h, sq=sq, train=train)
    zero = jnp.zeros((), jnp.int32 if held_rows else jnp.float32)
    return M.mlp(cfg, lp["mlp"], ctx, h, sq=sq), zero


def _layer(cfg, lp, ctx, x, attn, sq, **ffn_kw):
    """One pre-norm decoder layer.  ``attn(p, ctx, h) -> (out, cache)``
    runs the layer's attention (and its cache write); returns
    (x, cache, FFN stat)."""
    x = constrain(x)
    h = apply_norm(cfg, lp["ln1"], x)
    a, cache = attn(lp["attn"], ctx, h)
    if cfg.sandwich_norm:
        a = apply_norm(cfg, lp["ln1b"], a)
    x = x + a
    h = apply_norm(cfg, lp["ln2"], x)
    m, stat = _ffn(cfg, lp, ctx, h, sq, **ffn_kw)
    if cfg.sandwich_norm:
        m = apply_norm(cfg, lp["ln2b"], m)
    return x + m, cache, stat


def _dense_block(cfg, lp, ctx, x, positions, window_flag, sq, cache=None,
                 prefix: str = "", causal: bool = True, train: bool = False):
    def attn(p, nctx, h):
        return A.attention(cfg, p, nctx, h, positions, window_flag=window_flag,
                           sq=sq, cache=cache, causal=causal)
    x, cache, aux = _layer(cfg, lp, _Named(ctx, prefix), x, attn, sq,
                           train=train)
    return x, aux, cache


def _decoder_block(cfg, lp, ctx, x, positions, memory, sq, cache=None):
    """Whisper decoder: self-attn + cross-attn + mlp."""
    x = constrain(x)
    nctx = _Named(ctx, "")
    h = apply_norm(cfg, lp["ln1"], x)
    a, cache = A.attention(cfg, lp["attn"], nctx, h, positions, sq=sq, cache=cache)
    x = x + a
    h = apply_norm(cfg, lp["ln3"], x)
    c = A.cross_attention(cfg, lp["cross"], nctx, h, memory, sq=sq)
    x = x + c
    h = apply_norm(cfg, lp["ln2"], x)
    x = x + M.mlp(cfg, lp["mlp"], nctx, h, sq=sq)
    return x, cache


def _mamba_block(cfg, lp, ctx, x, sq, want_state=False):
    x = constrain(x)
    h = apply_norm(cfg, lp["ln1"], x)
    o, st = S.ssm_block(cfg, lp["ssm"], ctx, h, sq=sq,
                        conv_state=jnp.zeros(()) if want_state else None)
    return x + o, st


class _Named:
    """Prefixes site names (``layer{i}/``) for the eager calibration path;
    no-op prefix under scan."""
    def __init__(self, ctx, prefix: str):
        self.ctx, self.prefix = ctx, prefix
        self.quantized = getattr(ctx, "quantized", False)

    def __call__(self, name, x, w, mask=None, smooth=None, fused=None):
        return self.ctx(self.prefix + name, x, w, mask=mask, smooth=smooth,
                        fused=fused)

    def emm(self, name, x, w, mask=None, smooth=None, fused=None):
        return self.ctx.emm(self.prefix + name, x, w, mask=mask,
                            smooth=smooth, fused=fused)

    def gmm(self, name, x, w, rows, mask=None, smooth=None, fused=None):
        return self.ctx.gmm(self.prefix + name, x, w, rows, mask=mask,
                            smooth=smooth, fused=fused)


def _run_groups(cfg, params, x, ctx, qparams, cache, attn, stat, *,
                scan=True, named=False, **ffn_kw):
    """The one decoder layer loop: every layer group in order, each scanned
    over its own stacked weights, quantization state and cache arrays.

    ``attn(p, ctx, h, c, flag, sq) -> (out, c)`` runs one layer's
    attention against its per-layer cache dict ``c`` (None without a
    cache); ``cache`` is the flat per-layer tree (``common.group_name``
    keys) or None; ``stat`` the initial FFN stat (see :func:`_ffn`).
    ``scan=False`` unrolls the loop, with eager ``layer{i}/`` site names
    when ``named``.  Returns (x, summed stat, new cache tree or None)."""
    flags = _window_flags(cfg)
    new_cache = {} if cache is not None else None

    def one(x, lp, nctx, flag, sq, c):
        return _layer(cfg, lp, nctx, x,
                      lambda p, cx, h: attn(p, cx, h, c, flag, sq), sq,
                      **ffn_kw)

    def add(st, s_):
        return None if st is None else st + s_

    for key, lo, hi in cfg.layer_groups:
        lps, sqs = params[key], group_items(qparams or {}, key)
        cs = None if cache is None else group_items(cache, key)
        fl = flags[lo:hi]
        if scan:
            def body(carry, xs):
                x, st = carry
                lp, flag, sq, c = xs
                x, c, s_ = one(x, lp, _Named(ctx, ""), flag, sq, c)
                return (x, add(st, s_)), c
            if cfg.remat:
                body = jax.checkpoint(body)
            (x, stat), cs = jax.lax.scan(body, (x, stat), (lps, fl, sqs, cs))
        else:
            outs = []
            for i in range(hi - lo):
                name = f"{GROUP_SITE_PREFIX[key]}{i}/" if named else ""
                x, c, s_ = one(x, layer_slice(lps, i), _Named(ctx, name),
                               fl[i], _sq_for_layer(sqs, i),
                               None if cs is None else layer_slice(cs, i))
                stat = add(stat, s_)
                outs.append(c)
            if cs is not None:
                cs = jax.tree.map(lambda *t: jnp.stack(t), *outs)
        if cs is not None:
            new_cache.update({group_name(key, n): v for n, v in cs.items()})
    return x, stat, new_cache


def _keep(c, new):
    """The per-layer arrays of ``c`` from an attention's updated cache."""
    return None if c is None else {n: new[n] for n in c}


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed(cfg: ModelConfig, params, tokens, extra) -> jnp.ndarray:
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    if cfg.scale_embed:
        x = x * math.sqrt(cfg.d_model)
    if cfg.n_patches and extra is not None and "patches" in extra:
        x = jnp.concatenate([extra["patches"].astype(x.dtype), x], axis=1)
    return x


def _window_flags(cfg: ModelConfig) -> jnp.ndarray:
    return jnp.asarray([b == "local" for b in cfg.blocks])


def _sq_for_layer(qparams, i=None):
    """qparams: {site: [L, ch] | {field: [L, ...]}} -> per-layer slice
    (``{site}@fused`` kernel buffers are dict-valued, hence the tree map)."""
    if qparams is None:
        return {}
    if i is None:
        return qparams  # already sliced by scan
    return jax.tree.map(lambda v: v[i], qparams)


def forward(cfg: ModelConfig, params, tokens, ctx=None, *, extra=None,
            scan: bool = True, cache: Optional[dict] = None,
            qparams: Optional[Dict[str, jnp.ndarray]] = None,
            train: bool = False) -> Dict[str, Any]:
    """Full-sequence forward.

    Returns {"logits": [b, s, V], "aux": moe-aux-loss, "cache": updated}.
    ``cache`` (optional) is a stacked prefill KV cache to fill.
    ``qparams``: {site: [L, channels]} static MUXQ outlier masks.
    ``train=True`` enables the capacity-factor MoE dispatch (over-capacity
    tokens drop); the inference default is dropless so prefill routing
    matches per-token decode routing exactly.
    """
    ctx = ctx or FpCtx()
    fam = cfg.family
    x = _embed(cfg, params, tokens, extra)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    aux_total = jnp.float32(0)
    new_cache = None

    if fam == "encdec":
        memory = _encode(cfg, params, extra["frames"].astype(x.dtype), ctx, scan=scan)
        x, new_cache = _run_decoder(cfg, params, x, positions, memory, ctx,
                                    scan=scan, cache=cache, qparams=qparams)
        if new_cache is not None:
            new_cache["memory"] = memory
    elif fam == "hybrid":
        x, new_cache = _run_hybrid(cfg, params, x, positions, ctx,
                                   cache=cache, qparams=qparams)
    elif fam == "ssm":
        x, new_cache = _run_ssm(cfg, params, x, ctx, scan=scan,
                                cache=cache, qparams=qparams)
    else:
        x, aux_total, new_cache = _run_dense(cfg, params, x, positions, ctx,
                                             scan=scan, cache=cache,
                                             qparams=qparams, train=train)

    x = apply_norm(cfg, params["ln_f"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    logits = softcap(logits, cfg.final_softcap)
    return {"logits": logits, "aux": aux_total, "cache": new_cache}


def _run_dense(cfg, params, x, positions, ctx, *, scan, cache, qparams,
               train=False):
    layers = None if cache is None else {n: v for n, v in cache.items()
                                         if n != "pos"}

    def attn(p, nctx, h, c, flag, sq):
        a, new = A.attention(cfg, p, nctx, h, positions, window_flag=flag,
                             sq=sq, cache=c)
        return a, _keep(c, new)

    x, aux_total, nc = _run_groups(cfg, params, x, ctx, qparams, layers, attn,
                                   jnp.float32(0), scan=scan, named=True,
                                   train=train)
    if nc is not None:
        nc["pos"] = jnp.asarray(x.shape[1], jnp.int32)
    return x, aux_total, nc


def _run_ssm(cfg, params, x, ctx, *, scan, cache, qparams):
    want_state = cache is not None
    if not scan:
        states = []
        for i in range(cfg.n_layers):
            lp = layer_slice(params["layers"], i)
            x, st = _mamba_block(cfg, lp, _Named(ctx, f"layer{i}/"), x,
                                 _sq_for_layer(qparams, i), want_state=want_state)
            if st is not None:
                states.append(st)
        nc = None
        if want_state:
            nc = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
            nc["pos"] = jnp.asarray(x.shape[1], jnp.int32)
        return x, nc

    def body(x, xs):
        lp, sq = xs
        x, st = _mamba_block(cfg, lp, ctx, x, sq, want_state=want_state)
        return x, (st if st is not None else jnp.zeros(()))

    if cfg.remat:
        body = jax.checkpoint(body)
    x, ys = jax.lax.scan(body, x, (params["layers"], qparams or {}))
    nc = None
    if want_state:
        nc = dict(ys)
        nc["pos"] = jnp.asarray(x.shape[1], jnp.int32)
    return x, nc


def _run_hybrid(cfg, params, x, positions, ctx, *, cache, qparams):
    """zamba2: mamba stack + shared attn+MLP block every k layers.
    Python loop (see module docstring)."""
    k_every = cfg.shared_attn_every
    want_state = cache is not None
    states, sks, svs = [], [], []
    shared_i = 0
    for i in range(cfg.n_layers):
        lp = layer_slice(params["layers"], i)
        x, st = _mamba_block(cfg, lp, _Named(ctx, f"layer{i}/"), x,
                             _sq_for_layer(qparams, i), want_state=want_state)
        if st is not None:
            states.append(st)
        if i % k_every == k_every - 1:
            c_i = None
            if cache is not None:
                c_i = {"k": cache["k"][shared_i], "v": cache["v"][shared_i]}
            x, _, c_i = _dense_block(cfg, params["shared"], ctx, x, positions,
                                     False, _sq_for_layer(qparams, i),
                                     cache=c_i, prefix=f"shared{shared_i}/")
            if c_i is not None:
                sks.append(c_i["k"]); svs.append(c_i["v"])
            shared_i += 1
    nc = None
    if want_state:
        nc = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        nc.update({"k": jnp.stack(sks), "v": jnp.stack(svs),
                   "pos": jnp.asarray(x.shape[1], jnp.int32)})
    return x, nc


def _encode(cfg, params, frames, ctx, *, scan=True):
    """Whisper encoder over precomputed frame embeddings (frontend stub)."""
    b, s, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = frames

    if not scan:
        for i in range(cfg.n_enc_layers):
            lp = layer_slice(params["enc_layers"], i)
            x, _, _ = _dense_block(cfg, lp, ctx, x, positions, False, {},
                                   prefix=f"enc{i}/", causal=False)
        return apply_norm(cfg, params["enc_ln_f"], x)

    def body(x, lp):
        x, _, _ = _dense_block(cfg, lp, ctx, x, positions, False, {}, causal=False)
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return apply_norm(cfg, params["enc_ln_f"], x)


def _run_decoder(cfg, params, x, positions, memory, ctx, *, scan, cache, qparams):
    if not scan:
        ks, vs = [], []
        for i in range(cfg.n_layers):
            lp = layer_slice(params["layers"], i)
            c_i = None if cache is None else {"k": cache["k"][i], "v": cache["v"][i]}
            x, c_i = _decoder_block(cfg, lp, _Named(ctx, f"layer{i}/"), x,
                                    positions, memory, _sq_for_layer(qparams, i),
                                    cache=c_i)
            if c_i is not None:
                ks.append(c_i["k"]); vs.append(c_i["v"])
        nc = None
        if cache is not None:
            nc = {"k": jnp.stack(ks), "v": jnp.stack(vs),
                  "pos": jnp.asarray(x.shape[1], jnp.int32)}
        return x, nc

    def body(x, xs):
        lp, sq, c_k, c_v = xs
        c_i = None if c_k is None else {"k": c_k, "v": c_v}
        x, c_i = _decoder_block(cfg, lp, ctx, x, positions, memory, sq, cache=c_i)
        y = (c_i["k"], c_i["v"]) if c_i is not None else (jnp.zeros(()), jnp.zeros(()))
        return x, y

    if cfg.remat:
        body = jax.checkpoint(body)
    xs = (params["layers"], qparams or {},
          cache["k"] if cache is not None else None,
          cache["v"] if cache is not None else None)
    x, ys = jax.lax.scan(body, x, xs)
    nc = None
    if cache is not None:
        nc = {"k": ys[0], "v": ys[1], "pos": jnp.asarray(x.shape[1], jnp.int32)}
    return x, nc


# ---------------------------------------------------------------------------
# Decode (one token against a cache)
# ---------------------------------------------------------------------------

def decode_step(cfg: ModelConfig, params, tokens, cache, ctx=None, *,
                qparams=None, scan: bool = True) -> Tuple[jnp.ndarray, dict]:
    """tokens [b, 1] -> (logits [b, 1, V], updated cache).  The cache comes
    from ``forward(..., cache=init_cache(...))`` (prefill) or zeros.
    ``scan=False`` unrolls the layer loop (dry-run marginal-cost variants)."""
    ctx = ctx or FpCtx()
    fam = cfg.family
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    if cfg.scale_embed:
        x = x * math.sqrt(cfg.d_model)
    pos = cache["pos"]

    if fam in ("dense", "moe"):
        # any per-layer cache arrays beyond k/v (int8 scales, int4 scales +
        # redistribution rows) ride the layer loop generically and come
        # back stacked — the attention step passes unrecognized keys through
        layers = {n: cache[n] for n in cache if n != "pos"}

        def attn(p, nctx, h, c, flag, sq):
            a, new = A.attention_decode(cfg, p, nctx, h, {**c, "pos": pos},
                                        window_flag=flag, sq=sq)
            return a, _keep(c, new)

        x, _, new_cache = _run_groups(cfg, params, x, ctx, qparams, layers,
                                      attn, jnp.float32(0), scan=scan)
        new_cache["pos"] = pos + 1

    elif fam == "ssm":
        state_tree = {k: cache[k] for k in ("conv_x", "conv_bc", "ssm")}

        def body(x, xs):
            lp, sq, st_in = xs
            h = apply_norm(cfg, lp["ln1"], x)
            o, st = S.ssm_decode(cfg, lp["ssm"], ctx, h, st_in, sq=sq)
            return x + o, st

        if scan:
            xs = (params["layers"], qparams or {}, state_tree)
            x, sts = jax.lax.scan(body, x, xs)
        else:
            st_l = []
            for i in range(cfg.n_layers):
                x, st_i = body(x, (layer_slice(params["layers"], i),
                                   _sq_for_layer(qparams, i),
                                   jax.tree.map(lambda t: t[i], state_tree)))
                st_l.append(st_i)
            sts = jax.tree.map(lambda *xs_: jnp.stack(xs_), *st_l)
        new_cache = dict(sts)
        new_cache["pos"] = pos + 1

    elif fam == "hybrid":
        k_every = cfg.shared_attn_every
        states, sks, svs = [], [], []
        shared_i = 0
        nctx = _Named(ctx, "")
        for i in range(cfg.n_layers):
            lp = layer_slice(params["layers"], i)
            h = apply_norm(cfg, lp["ln1"], x)
            st_in = {k: cache[k][i] for k in ("conv_x", "conv_bc", "ssm")}
            o, st = S.ssm_decode(cfg, lp["ssm"], nctx, h, st_in,
                                 sq=_sq_for_layer(qparams, i))
            x = x + o
            states.append(st)
            if i % k_every == k_every - 1:
                c_i = {"k": cache["k"][shared_i], "v": cache["v"][shared_i], "pos": pos}
                h = apply_norm(cfg, params["shared"]["ln1"], x)
                a, c_i = A.attention_decode(cfg, params["shared"]["attn"], nctx, h, c_i)
                x = x + a
                h = apply_norm(cfg, params["shared"]["ln2"], x)
                x = x + M.mlp(cfg, params["shared"]["mlp"], nctx, h)
                sks.append(c_i["k"]); svs.append(c_i["v"])
                shared_i += 1
        new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        new_cache.update({"k": jnp.stack(sks), "v": jnp.stack(svs), "pos": pos + 1})

    elif fam == "encdec":
        memory = cache["memory"]

        def body(x, xs):
            lp, sq, c_k, c_v = xs
            c_i = {"k": c_k, "v": c_v, "pos": pos}
            nctx = _Named(ctx, "")
            h = apply_norm(cfg, lp["ln1"], x)
            a, c_i = A.attention_decode(cfg, lp["attn"], nctx, h, c_i, sq=sq)
            x = x + a
            h = apply_norm(cfg, lp["ln3"], x)
            x = x + A.cross_attention(cfg, lp["cross"], nctx, h, memory, sq=sq)
            h = apply_norm(cfg, lp["ln2"], x)
            x = x + M.mlp(cfg, lp["mlp"], nctx, h, sq=sq)
            return x, (c_i["k"], c_i["v"])

        if scan:
            xs = (params["layers"], qparams or {}, cache["k"], cache["v"])
            x, (ks, vs) = jax.lax.scan(body, x, xs)
        else:
            ks_l, vs_l = [], []
            for i in range(cfg.n_layers):
                x, (k_i, v_i) = body(x, (layer_slice(params["layers"], i),
                                         _sq_for_layer(qparams, i),
                                         cache["k"][i], cache["v"][i]))
                ks_l.append(k_i); vs_l.append(v_i)
            ks, vs = jnp.stack(ks_l), jnp.stack(vs_l)
        new_cache = {"k": ks, "v": vs, "pos": pos + 1, "memory": memory}
    else:  # pragma: no cover
        raise ValueError(fam)

    x = apply_norm(cfg, params["ln_f"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    logits = softcap(logits, cfg.final_softcap)
    return logits, new_cache


def _paged_step(cfg, params, tokens, kv, routing, valid, ctx, qparams,
                attn_fn, stats):
    """The paged steps' shared body: embed, the layer loop over the pool's
    per-layer arrays with ``routing`` (page table and positions) beside
    each layer's pages, final norm and LM head.  ``valid`` [b, s] marks
    real tokens (the MoE layers route nothing else)."""
    ctx = ctx or FpCtx()
    if cfg.family not in ("dense", "moe"):
        raise ValueError(f"paged steps support dense/moe, not {cfg.family}")
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    if cfg.scale_embed:
        x = x * math.sqrt(cfg.d_model)

    def attn(p, nctx, h, c, flag, sq):
        a, new = attn_fn(cfg, p, nctx, h, {**c, **routing}, window_flag=flag,
                         sq=sq)
        return a, _keep(c, new)

    experts = bool(cfg.n_experts)
    x, held, new_kv = _run_groups(cfg, params, x, ctx, qparams, kv, attn,
                                  jnp.int32(0) if experts else None,
                                  valid=valid, held_rows=experts)
    x = apply_norm(cfg, params["ln_f"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    # under tensor-parallel serving each shard computes its contiguous
    # vocab-column slice and a zero-pad psum reassembles the replicated
    # logits (bit-exact; plain full matmul when no shard context is active)
    logits = TP.tp_logits(x, head.astype(x.dtype))
    logits = softcap(logits, cfg.final_softcap)
    if stats:
        counts = {}
        if experts:
            calls = sum(k == "experts" for k in cfg.ffn_kinds)
            counts = {"moe_rows_held": held,
                      "moe_expert_calls": jnp.int32(calls)}
        return logits, new_kv, counts
    return logits, new_kv


def decode_step_paged(cfg: ModelConfig, params, tokens, kv: dict,
                      page_table, pos, ctx=None, *, qparams=None,
                      stats=False) -> Tuple[jnp.ndarray, dict]:
    """One-token decode for the WHOLE slot pool against a paged KV pool
    (``repro.serve.pool``), with a per-slot position vector.

    tokens [b, 1]; ``kv`` = {"k"/"v": [L, n_pages, kvh, ps, dh]} (int8 pages
    add "k_scale"/"v_scale" [L, n_pages, kvh, ps, 1]); ``page_table``
    [b, page_budget] int32; ``pos`` [b] int32.  Returns
    (logits [b, 1, V], updated kv dict).  Unlike :func:`decode_step` the
    position is per slot, so misaligned sequences decode in ONE traced step
    — the continuous-batching scheduler's invariant.

    ``page_table``'s width IS the read budget: the scheduler slices the
    pool table to the bucketed live-page maximum, so attention gathers
    ``budget * ps`` key positions per slot instead of the slot's full
    logical capacity (block-sparse decode reads).  The only requirement is
    ``pos[b] // ps < budget`` for every live slot — the write page and all
    read pages must sit inside the sliced table.  Dense/MoE only (the
    families ``ServeEngine`` serves).

    With ``stats=True`` returns (logits, kv, counts); an expert model's
    counts are ``moe_rows_held``, the routed rows of live slots that
    landed on held experts summed over the expert layers, and
    ``moe_expert_calls``, the expert layers run (the same for the verify
    and prefill steps)."""
    routing = {"page_table": page_table, "pos": pos}
    valid = page_table[:, :1] > 0        # idle and prefilling slots: scratch
    return _paged_step(cfg, params, tokens, kv, routing, valid, ctx, qparams,
                       A.attention_decode_paged, stats)


def decode_verify_paged(cfg: ModelConfig, params, tokens, kv: dict,
                        page_table, pos, n_valid, ctx=None, *,
                        qparams=None, stats=False
                        ) -> Tuple[jnp.ndarray, dict]:
    """Speculative-decoding VERIFY step: score a ``[slot, k]`` block of
    draft tokens for the whole pool in ONE traced call
    (``repro.serve.scheduler``'s n-gram speculation path).

    tokens [b, k]: per slot, the last committed token followed by up to
    ``k - 1`` proposed draft tokens (rows past ``n_valid[b]`` are
    padding); ``kv`` / ``page_table`` / ``pos`` as in
    :func:`decode_step_paged` — ``pos`` stays the FIRST row's position;
    ``n_valid`` [b] int32 counts each slot's real rows (0 parks a slot).

    Returns (logits [b, k, V], updated kv dict): ``logits[b, j]`` is the
    model's next-token distribution after consuming ``tokens[b, :j+1]`` —
    exactly what ``decode_step_paged`` would emit at that position, so
    greedy acceptance of the longest agreeing draft prefix reproduces
    sequential argmax decode bit for bit on fp pages.  Rejected rows'
    page writes need no undo: per-slot ``pos`` is the source of truth and
    they are overwritten when the position reaches them.  Shapes are
    static per (k bucket, page bucket) pair — the scheduler buckets both
    — so verify compiles once per pair, never per draft length."""
    routing = {"page_table": page_table, "pos": pos, "n_valid": n_valid}
    valid = jnp.arange(tokens.shape[1])[None] < n_valid[:, None]
    return _paged_step(cfg, params, tokens, kv, routing, valid, ctx, qparams,
                       A.attention_verify_paged, stats)


def prefill_chunk_paged(cfg: ModelConfig, params, tokens, kv: dict,
                        page_table, start, write_lo, write_hi, ctx=None, *,
                        qparams=None, stats=False) -> Tuple[jnp.ndarray, dict]:
    """One chunk per prefilling slot, for SEVERAL slots at once, prefilled
    straight into the paged KV pool in ONE traced call (``repro.serve``) —
    the serving engine's only prefill path; there is no dense ``[1, T]``
    prefill cache.

    tokens [b, C] (C = the scheduler's bucketed chunk shape; ids past a
    slot's valid tokens are padding, and slots not advancing this step are
    all-padding rows); ``kv`` = {"k"/"v": [L, n_pages, kvh, ps, dh]} (int8
    pages add "k_scale"/"v_scale"); ``page_table`` [b, pages] int32 is the
    prefilling slots' table rows sliced to the bucketed page budget;
    ``start`` / ``write_lo`` / ``write_hi`` are traced int32 [b] vectors
    (per-slot chunk start position and the absolute position window whose
    K/V is written to pages — idle slots carry an empty window; see
    :func:`repro.models.attention.attention_prefill_paged`, which also
    keeps the legacy 1-slot scalar/1-D form working).

    Returns (logits [b, C, V], updated kv dict).  Because a chunk's queries
    only attend to positions <= their own — already in pages from earlier
    chunks or the shared prefix — chunks need NO hidden-state carry between
    them: the scheduler can interleave one batched multi-slot chunk step
    per step with the pooled decode.  Slots' page write windows are
    disjoint, so the batched call is bit-identical to prefilling the same
    chunks one slot at a time.  Shapes are static per (chunk bucket, page
    bucket) pair, so the step compiles once per pair, never per prompt
    length or per number of advancing slots.  Dense/MoE only (the families
    ``ServeEngine`` serves)."""
    routing = {"page_table": page_table, "start": start,
               "write_lo": write_lo, "write_hi": write_hi}
    c = tokens.shape[1]
    valid = (jnp.asarray(start, jnp.int32).reshape(-1, 1)
             + jnp.arange(c)[None]) < jnp.asarray(write_hi,
                                                   jnp.int32).reshape(-1, 1)
    return _paged_step(cfg, params, tokens, kv, routing, valid, ctx, qparams,
                       A.attention_prefill_paged, stats)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def lm_loss(cfg: ModelConfig, params, batch, ctx=None, *, scan=True,
            qparams=None, aux_weight: float = 0.01,
            train: bool = True) -> Tuple[jnp.ndarray, Dict]:
    """batch: {"tokens": [b,s], "labels": [b,s], optional "mask", "patches",
    "frames"}.  ``train`` (default True — this is the trainer's loss)
    selects capacity-factor MoE dispatch; pass False for dropless eval."""
    extra = {k: batch[k] for k in ("patches", "frames") if k in batch}
    out = forward(cfg, params, batch["tokens"], ctx, extra=extra or None,
                  scan=scan, qparams=qparams, train=train)
    logits = out["logits"]
    if cfg.n_patches and "patches" in batch:   # vlm: loss over text positions
        logits = logits[:, -batch["tokens"].shape[1]:]
    loss = cross_entropy(logits, batch["labels"], cfg.vocab_size,
                         batch.get("mask"))
    total = loss + aux_weight * out["aux"]
    return total, {"ce": loss, "aux": out["aux"]}
