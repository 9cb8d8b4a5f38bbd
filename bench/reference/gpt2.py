"""GPT-2 as the repository serves it, written out plainly for the benchmark.

A configuration file names its reference by the key ``"reference"``; the
harness loads ``bench/reference/<name>.py`` and uses four functions:

  model_config(spec)             the program's ModelConfig for the file's sizes
  init_weights(spec, seed, dtype) seeded weights in the program's parameter
                                 layout, made on the device in one jitted call
  forward(spec, params, tokens, bits=None)
                                 float32 logits [b, s, vocab] in plain jnp at
                                 "highest" matmul precision: no kernels, no
                                 cache, no paging, one full causal pass
  work_model(spec)               the model's work as plain data, from which
                                 ``bench/work.py`` counts a window's
                                 operations and bytes (``mfu`` and the
                                 kernels' roofline shares):
      "linear": one entry per projection site: {"name", "k", "n" (its
          K x N), "layers" (how many layers hold it), "token_share" (the
          share of each layer's tokens that pass through it: 1 for a dense
          projection, top_k * held / n_experts for routed experts held on
          this chip), "matrices" (weight matrices one call may read: 1, or
          the experts held), "quantized" (int8 in a MUXQ configuration)}
      "attention": one entry per kind of attention layer: {"layers",
          "heads" (query heads), "qk_dim" and "v_dim" (the widths a query
          scores keys over and sums values over, per head; a latent shared
          by all heads counts at its own width), "kv_bytes" ({kv_mode:
          cached bytes per position and layer} for each page mode the
          configuration may use), "window" (None, or the positions a query
          at p sees: min(p + 1, window))}
      "head": {"width", "vocab"}: the LM head over the vocabulary held

``bits=8`` or ``bits=4`` is the control: every linear layer's input is
rounded per token and its weight per output channel to that many bits,
and every cached key and value per position and head (symmetric, absmax
scale): the arithmetic of a serving path one precision lower.

Published GPT-2 (Radford et al. 2019, ``config.json`` of
openai-community/gpt2-large): pre-LayerNorm blocks, GELU in its tanh form
("gelu_new"), biases on every projection, tied input and output
embeddings, learned position embeddings.  Departures, both the
repository's model's own (``src/repro/configs/gpt2.py``): rotary position
embeddings (theta 10000, the two halves of each head rotated) in place of
learned positions, and no bias on the attention output projection.
Nothing here imports the program's model code or takes its arrays.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROPE_THETA = 10000.0


def sizes(spec):
    d = spec["n_embd"]
    return dict(L=spec["n_layer"], d=d, h=spec["n_head"],
                dh=d // spec["n_head"], f=spec.get("n_inner") or 4 * d,
                V=spec["vocab_size"], Vp=-(-spec["vocab_size"] // 128) * 128,
                eps=spec["layer_norm_epsilon"])


def work_model(spec):
    """GPT-2's work: four dense projections in every layer (QKV fused, all
    quantized under MUXQ), full multi-head attention over K/V pages (bf16
    values for ``fp``; int8 values and a float32 scale per head and
    position for ``int8``; packed int4 values and a bfloat16 scale for
    ``int4``), and the LM head over the whole vocabulary."""
    s = sizes(spec)
    L, d, h, dh, f = s["L"], s["d"], s["h"], s["dh"], s["f"]
    dense = dict(layers=L, token_share=1.0, matrices=1, quantized=True)
    return {
        "linear": [dict(name="attn_qkv", k=d, n=3 * d, **dense),
                   dict(name="attn_out", k=d, n=d, **dense),
                   dict(name="mlp_up", k=d, n=f, **dense),
                   dict(name="mlp_down", k=f, n=d, **dense)],
        "attention": [{"layers": L, "heads": h, "qk_dim": dh, "v_dim": dh,
                       "kv_bytes": {"fp": 2 * h * 2 * dh,
                                    "int8": 2 * h * (dh + 4),
                                    "int4": 2 * h * (dh // 2 + 2)},
                       "window": None}],
        "head": {"width": d, "vocab": s["V"]},
    }


def model_config(spec):
    """The program's ModelConfig for these sizes (GPT-2 family settings)."""
    from repro.models.common import ModelConfig
    s = sizes(spec)
    return ModelConfig(
        name=spec["name"], n_layers=s["L"], d_model=s["d"], n_heads=s["h"],
        n_kv_heads=s["h"], d_ff=s["f"], vocab_size=s["V"],
        block_pattern=("attn",), qkv_bias=True, mlp_type="gelu",
        norm="layernorm", norm_eps=s["eps"], tie_embeddings=True,
        rope_theta=ROPE_THETA, dtype=spec["dtype"])


def init_weights(spec, seed: int, dtype):
    """Seeded weights in the program's stacked layout, cast to ``dtype``.

    Embedding N(0, 0.02) as GPT-2 initialises it; projections N(0,
    1/fan_in); biases N(0, 0.02) and LayerNorm gains 1 + N(0, 0.1), so
    that every bias and gain takes part in the comparison."""
    s = sizes(spec)
    L, d, f, Vp = s["L"], s["d"], s["f"], s["Vp"]
    qkv = 3 * d

    def make(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, std, mean=0.0):
            x = jax.random.normal(next(ks), shape, jnp.float32) * std + mean
            return x.astype(dtype)

        def norm(shape):
            return {"gain": normal(shape, 0.1, 1.0), "bias": normal(shape, 0.02)}

        return {
            "embed": normal((Vp, d), 0.02),
            "ln_f": norm((d,)),
            "layers": {
                "ln1": norm((L, d)),
                "attn": {"wqkv": normal((L, d, qkv), d ** -0.5),
                         "wo": normal((L, d, d), d ** -0.5),
                         "bqkv": normal((L, qkv), 0.02)},
                "ln2": norm((L, d)),
                "mlp": {"wi": normal((L, d, f), d ** -0.5),
                        "wo": normal((L, f, d), f ** -0.5),
                        "bi": normal((L, f), 0.02),
                        "bo": normal((L, d), 0.02)},
            },
        }

    return jax.jit(make)(jax.random.PRNGKey(seed))


def _layernorm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["gain"] + p["bias"]


def _rope(x, positions):
    """x [b, s, h, dh]: rotate the first half against the second."""
    dh = x.shape[-1]
    freqs = 1.0 / ROPE_THETA ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions[:, None].astype(jnp.float32) * freqs[None]   # [s, dh/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fake_quant(x, axis, bits):
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def _linear(x, w, bits):
    if bits is not None:
        x = _fake_quant(x, -1, bits)     # per token
        w = _fake_quant(w, 0, bits)      # per output channel
    return x @ w


def forward(spec, params, tokens, bits=None):
    """float32 logits [b, s, vocab] of ``tokens`` [b, s] (position 0 first)."""
    s = sizes(spec)
    h, dh, eps = s["h"], s["dh"], s["eps"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    b, n = tokens.shape
    positions = jnp.arange(n)
    causal = jnp.tril(jnp.ones((n, n), bool))

    def layer(x, lp):
        a = _layernorm(x, lp["ln1"], eps)
        qkv = _linear(a, lp["attn"]["wqkv"], bits) + lp["attn"]["bqkv"]
        q, k, v = (t.reshape(b, n, h, dh) for t in jnp.split(qkv, 3, -1))
        q, k = _rope(q, positions), _rope(k, positions)
        if bits is not None:
            k, v = _fake_quant(k, -1, bits), _fake_quant(v, -1, bits)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = x + _linear(o.reshape(b, n, h * dh), lp["attn"]["wo"], bits)
        m = _layernorm(x, lp["ln2"], eps)
        u = jax.nn.gelu(_linear(m, lp["mlp"]["wi"], bits) + lp["mlp"]["bi"],
                        approximate=True)
        x = x + _linear(u, lp["mlp"]["wo"], bits) + lp["mlp"]["bo"]
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, p["embed"][tokens], p["layers"])
        x = _layernorm(x, p["ln_f"], eps)
        return x @ p["embed"][: s["V"]].T
