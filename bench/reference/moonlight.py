"""Moonlight-16B-A3B (DeepSeek-V3's block) as the repository serves a chip's
share of it, written out plainly for the benchmark.

It follows the reference contract set out at the top of ``gpt2.py``
(``model_config``, ``init_weights``, ``forward``, ``work_model``).

Published model (``config.json`` of moonshotai/Moonlight-16B-A3B,
``model_type`` deepseek_v3): RMSNorm (eps ``rms_norm_eps``) before
attention and before the FFN, untied input and output embeddings, and

  * latent attention: no query down-projection; per head a query of
    ``qk_nope_head_dim`` + ``qk_rope_head_dim`` values; one latent of
    ``kv_lora_rank`` values per position, RMS-normed, from which
    ``kv_b_proj`` expands each head's key part and value; one rotary key of
    ``qk_rope_head_dim`` values shared by every head; softmax scale
    (nope + rope) ** -0.5; rotary base ``rope_theta``;
  * the first ``first_k_dense_replace`` layers: a SwiGLU MLP of
    ``intermediate_size``;
  * every later layer: a router over ``experts_routed_over`` experts
    (sigmoid scores; each token's ``num_experts_per_tok`` experts chosen by
    score plus a correction bias, ``topk_method`` noaux_tc with one group;
    the chosen scores normalized and multiplied by
    ``routed_scaling_factor``), SwiGLU experts of ``moe_intermediate_size``,
    and ``n_shared_experts`` shared experts as one SwiGLU of
    ``n_shared_experts * moe_intermediate_size`` on every token.

A chip's share (``model-configs`` §4): the file's ``n_routed_experts``
experts held, from ``experts_held_from`` on; the router keeps every
output, and the pairs routed to experts held elsewhere add nothing, here
as in the program.  The vocabulary is the file's ``vocab_size`` ids.

Here attention runs in the expanded form (the latent through
``kv_b_proj`` into per-head keys and values), which the program never
does: it attends the cached latent in the absorbed form.  ``bits``, the
control, rounds every projection the program quantizes (queries and
latent, rotary key, attention output, dense MLP, experts, shared experts)
per token on its input and per output channel on its weight, and each
cached latent row per position (symmetric, absmax); the router and
``kv_b_proj`` stay in float32, as in the program.

Departures: rotary embedding on the two halves of the rotary part instead
of interleaved pairs (equal up to a fixed permutation of the 64 rotary
weight columns of the query and of the rotary key); the latent's RMSNorm
at eps 1e-6 (the published code's default for that norm); the byte-level
tokenizer; random weights from ``weight_seed``.  Nothing here imports the
program's model code or takes its arrays.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LATENT_EPS = 1e-6
QUERY_BLOCK = 512       # attention in blocks of queries: memory, not math


def sizes(spec):
    h = spec["num_attention_heads"]
    lead = spec["first_k_dense_replace"]
    return dict(
        L=spec["num_hidden_layers"], lead=lead,
        moe=spec["num_hidden_layers"] - lead, d=spec["hidden_size"], h=h,
        r=spec["kv_lora_rank"], nope=spec["qk_nope_head_dim"],
        rope=spec["qk_rope_head_dim"], v=spec["v_head_dim"],
        f=spec["intermediate_size"], fe=spec["moe_intermediate_size"],
        fs=spec["n_shared_experts"] * spec["moe_intermediate_size"],
        E=spec["experts_routed_over"], held=spec["n_routed_experts"],
        lo=spec["experts_held_from"], k=spec["num_experts_per_tok"],
        scale=spec["routed_scaling_factor"], V=spec["vocab_size"],
        Vp=-(-spec["vocab_size"] // 128) * 128, eps=spec["rms_norm_eps"],
        theta=float(spec["rope_theta"]))


def work_model(spec):
    """Moonlight's work as the program runs it: latent attention in the
    absorbed form (``kv_b_proj``'s two halves applied per token, to the
    query and to the attended latent; every head scores the 512 + 64
    latent row and sums its 512 latent values; int8 pages hold the row and
    a float32 scale per position and layer, fp pages the row in bf16), the
    dense layer's MLP, and in each expert layer the router, the held
    experts (a token's share: experts per token times held over routed;
    a call may read every held expert) and the shared experts."""
    s = sizes(spec)
    d, h, r, nope, rope, v = s["d"], s["h"], s["r"], s["nope"], s["rope"], s["v"]

    def site(name, k, n, layers, quantized=True, share=1.0, matrices=1):
        return dict(name=name, k=k, n=n, layers=layers, token_share=share,
                    matrices=matrices, quantized=quantized)

    L, lead, moe = s["L"], s["lead"], s["moe"]
    share = s["k"] * s["held"] / s["E"]
    linear = [site("attn_q", d, h * (nope + rope) + r, L),
              site("attn_kpe", d, rope, L),
              site("attn_kv_b", r, h * (nope + v), L, quantized=False),
              site("attn_out", h * v, d, L)]
    if lead:
        linear += [site("mlp_up", d, 2 * s["f"], lead),
                   site("mlp_down", s["f"], d, lead)]
    if moe:
        linear += [site("router", d, s["E"], moe, quantized=False),
                   site("moe_up", d, 2 * s["fe"], moe, share=share,
                        matrices=s["held"]),
                   site("moe_down", s["fe"], d, moe, share=share,
                        matrices=s["held"])]
        if s["fs"]:
            linear += [site("moe_shared_up", d, 2 * s["fs"], moe),
                       site("moe_shared_down", s["fs"], d, moe)]
    return {
        "linear": linear,
        "attention": [{"layers": L, "heads": h, "qk_dim": r + rope,
                       "v_dim": r,
                       "kv_bytes": {"int8": r + rope + 4,
                                    "fp": 2 * (r + rope)},
                       "window": None}],
        "head": {"width": d, "vocab": s["V"]},
    }


def model_config(spec):
    """The program's ModelConfig for these sizes."""
    from repro.models.common import ModelConfig
    s = sizes(spec)
    return ModelConfig(
        name=spec["name"], n_layers=s["L"], d_model=s["d"], n_heads=s["h"],
        n_kv_heads=spec["num_key_value_heads"], d_ff=s["f"],
        vocab_size=s["V"], block_pattern=("attn",), attn_kind="mla",
        kv_lora_rank=s["r"], qk_nope_dim=s["nope"], qk_rope_dim=s["rope"],
        v_head_dim=s["v"], rope_theta=s["theta"], n_experts=s["E"],
        top_k=s["k"], first_dense=s["lead"], d_ff_expert=s["fe"],
        d_ff_shared=s["fs"], router_score=spec["scoring_func"],
        router_bias=spec["topk_method"] == "noaux_tc",
        routed_scale=s["scale"],
        expert_lo=s["lo"], n_held=s["held"], mlp_type="swiglu",
        norm="rmsnorm", norm_eps=s["eps"],
        tie_embeddings=spec["tie_word_embeddings"], dtype=spec["dtype"])


def init_weights(spec, seed: int, dtype):
    """Seeded weights in the program's layout (one stack per layer group:
    ``dense_layers`` then ``layers``), cast to ``dtype``: embedding N(0,
    0.02), projections N(0, 1/fan_in), RMSNorm gains (applied as 1 + gain)
    N(0, 0.1), the router's correction bias N(0, 0.05)."""
    s = sizes(spec)
    d, h, r, nope, rope, v = s["d"], s["h"], s["r"], s["nope"], s["rope"], s["v"]

    def make(key):
        ks = iter(jax.random.split(key, 32))

        def normal(shape, std):
            return (jax.random.normal(next(ks), shape, jnp.float32) * std
                    ).astype(dtype)

        def proj(n, k, m):
            return normal((n, k, m), k ** -0.5)

        def attn(n):
            return {"wq": proj(n, d, h * (nope + rope) + r),
                    "wkpe": proj(n, d, rope),
                    "kv_norm": {"gain": normal((n, r), 0.1)},
                    "wkv_b": proj(n, r, h * (nope + v)),
                    "wo": proj(n, h * v, d)}

        def layer(n):
            return {"ln1": {"gain": normal((n, d), 0.1)}, "attn": attn(n),
                    "ln2": {"gain": normal((n, d), 0.1)}}

        out = {"embed": normal((s["Vp"], d), 0.02),
               "ln_f": {"gain": normal((d,), 0.1)},
               "lm_head": normal((d, s["Vp"]), d ** -0.5)}
        if s["lead"]:
            n = s["lead"]
            out["dense_layers"] = dict(layer(n), mlp={
                "wi": proj(n, d, 2 * s["f"]), "wo": proj(n, s["f"], d)})
        n, e = s["moe"], s["held"]
        moe = {"router": proj(n, d, s["E"]),
               "router_bias": normal((n, s["E"]), 0.05),
               "wi": normal((n, e, d, 2 * s["fe"]), d ** -0.5),
               "wo": normal((n, e, s["fe"], d), s["fe"] ** -0.5)}
        if s["fs"]:
            moe["shared"] = {"wi": proj(n, d, 2 * s["fs"]),
                             "wo": proj(n, s["fs"], d)}
        out["layers"] = dict(layer(n), moe=moe)
        return out

    return jax.jit(make)(jax.random.PRNGKey(seed))


def _rmsnorm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * (
        1.0 + gain)


def _rope(x, positions, theta):
    """x [b, s, h, n]: rotate the first half against the second."""
    n = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = positions[:, None].astype(jnp.float32) * freqs[None]   # [s, n/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : n // 2], x[..., n // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fake_quant(x, axis, bits):
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def _linear(x, w, bits):
    if bits is not None:
        x = _fake_quant(x, -1, bits)     # per token
        w = _fake_quant(w, -2, bits)     # per output channel
    return x @ w


def _swiglu(x, wi, wo, bits):
    gate, up = jnp.split(_linear(x, wi, bits), 2, -1)
    return _linear(jax.nn.silu(gate) * up, wo, bits)


def _attend(q, k, v, scale):
    """Causal softmax attention, q/k [b, n, h, dq], v [b, n, h, dv], in
    blocks of queries once the sequence is longer than one block."""
    n = q.shape[1]
    kpos = jnp.arange(n)

    def block(qb, lo):
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        qpos = lo + jnp.arange(qb.shape[1])
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)

    if n <= QUERY_BLOCK or n % QUERY_BLOCK:
        return block(q, 0)
    nb = n // QUERY_BLOCK
    qs = q.reshape(q.shape[0], nb, QUERY_BLOCK, *q.shape[2:]).swapaxes(0, 1)
    out = jax.lax.map(lambda a: block(a[0], a[1]),
                      (qs, jnp.arange(nb) * QUERY_BLOCK))
    return out.swapaxes(0, 1).reshape(*q.shape[:3], v.shape[-1])


def expert_layer(spec, m, x, bits=None):
    """An expert layer's FFN on normed x [b, n, d]: the held experts' part
    of the routed output, and the shared experts."""
    s = sizes(spec)
    scores = jax.nn.sigmoid(x @ m["router"])                     # [b, n, E]
    _, idx = jax.lax.top_k(scores + m["router_bias"], s["k"])
    gates = jnp.take_along_axis(scores, idx, -1)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20) * s["scale"]
    held = s["lo"] + jnp.arange(s["held"])
    weight = jnp.sum(gates[..., None] * (idx[..., None] == held), -2)
    gate, up = jnp.split(jnp.einsum(
        "bnd,edf->bnef", _q_in(x, bits), _q_w(m["wi"], bits)), 2, -1)
    out = jnp.einsum("bnef,efd->bned", _q_in(jax.nn.silu(gate) * up, bits),
                     _q_w(m["wo"], bits))
    y = jnp.einsum("bned,bne->bnd", out, weight)
    if "shared" in m:
        y = y + _swiglu(x, m["shared"]["wi"], m["shared"]["wo"], bits)
    return y


def forward(spec, params, tokens, bits=None):
    """float32 logits [b, s, vocab] of ``tokens`` [b, s] (position 0 first)."""
    s = sizes(spec)
    h, r, nope, rope, v = s["h"], s["r"], s["nope"], s["rope"], s["v"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    b, n = tokens.shape
    positions = jnp.arange(n)

    def attention(x, lp):
        a = lp["attn"]
        qc = _linear(x, a["wq"], bits)
        q = qc[..., : h * (nope + rope)].reshape(b, n, h, nope + rope)
        c = _rmsnorm(qc[..., h * (nope + rope):], a["kv_norm"]["gain"],
                     LATENT_EPS)
        k_pe = _rope(_linear(x, a["wkpe"], bits)[:, :, None], positions,
                     s["theta"])
        if bits is not None:         # the cached latent row, per position
            row = _fake_quant(jnp.concatenate([c, k_pe[:, :, 0]], -1), -1,
                              bits)
            c, k_pe = row[..., :r], row[..., None, r:]
        kv = (c @ a["wkv_b"]).reshape(b, n, h, nope + v)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], positions,
                                                  s["theta"])], -1)
        k = jnp.concatenate([kv[..., :nope],
                             jnp.broadcast_to(k_pe, (b, n, h, rope))], -1)
        o = _attend(q, k, kv[..., nope:], (nope + rope) ** -0.5)
        return _linear(o.reshape(b, n, h * v), a["wo"], bits)

    def layer(x, lp):
        x = x + attention(_rmsnorm(x, lp["ln1"]["gain"], s["eps"]), lp)
        u = _rmsnorm(x, lp["ln2"]["gain"], s["eps"])
        if "moe" in lp:
            return x + expert_layer(spec, lp["moe"], u, bits), None
        return x + _swiglu(u, lp["mlp"]["wi"], lp["mlp"]["wo"], bits), None

    with jax.default_matmul_precision("highest"):
        x = p["embed"][tokens]
        for group in ("dense_layers", "layers"):
            if group in p:
                x, _ = jax.lax.scan(layer, x, p[group])
        x = _rmsnorm(x, p["ln_f"]["gain"], s["eps"])
        return x @ p["lm_head"][:, : s["V"]]


def _q_in(x, bits):
    return x if bits is None else _fake_quant(x, -1, bits)


def _q_w(w, bits):
    return w if bits is None else _fake_quant(w, -2, bits)
