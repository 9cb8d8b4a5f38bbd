"""A test-size reference of another architecture than GPT-2, written out
plainly for the benchmark's tests: grouped-query attention (fewer K/V
heads than query heads), a SwiGLU MLP (gate and up in one matrix, then
down), RMSNorm, and sliding-window layers beside full ones, as the
repository's dense model runs them (``block_pattern`` "local" / "attn").

It follows the reference contract of ``bench/reference/gpt2.py``
(``model_config``, ``init_weights``, ``forward``, ``work_model``), so the
harness serves a configuration naming it (``"reference": "gqa_swiglu"``)
through the normal path, and checks and counts it from this file alone.
Nothing here imports the program's model code or takes its arrays.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SLIDING = "sliding_attention"


def sizes(spec):
    d, h = spec["hidden_size"], spec["num_attention_heads"]
    return dict(L=spec["num_hidden_layers"], d=d, h=h,
                kv=spec["num_key_value_heads"], dh=d // h,
                f=spec["intermediate_size"], V=spec["vocab_size"],
                Vp=-(-spec["vocab_size"] // 128) * 128,
                eps=spec["rms_norm_eps"], theta=spec["rope_theta"],
                window=spec["sliding_window"],
                local=[t == SLIDING for t in spec["layer_types"]])


def work_model(spec):
    """Four dense projection sites (QKV over the K/V heads only, the
    attention output, gate and up, down), a group of sliding-window layers
    and one of full layers whose K/V pages hold the K/V heads, and the LM
    head over the whole vocabulary."""
    s = sizes(spec)
    L, d, h, kv, dh, f = s["L"], s["d"], s["h"], s["kv"], s["dh"], s["f"]
    dense = dict(layers=L, token_share=1.0, matrices=1, quantized=True)
    n_local = sum(s["local"])
    kv_bytes = {"fp": 2 * kv * 2 * dh, "int8": 2 * kv * (dh + 4),
                "int4": 2 * kv * (dh // 2 + 2)}
    groups = [{"layers": n, "heads": h, "qk_dim": dh, "v_dim": dh,
               "kv_bytes": kv_bytes, "window": w}
              for n, w in ((n_local, s["window"]), (L - n_local, None)) if n]
    return {
        "linear": [dict(name="attn_qkv", k=d, n=(h + 2 * kv) * dh, **dense),
                   dict(name="attn_out", k=h * dh, n=d, **dense),
                   dict(name="mlp_up", k=d, n=2 * f, **dense),
                   dict(name="mlp_down", k=f, n=d, **dense)],
        "attention": groups,
        "head": {"width": d, "vocab": s["V"]},
    }


def model_config(spec):
    """The program's ModelConfig for these sizes."""
    from repro.models.common import ModelConfig
    s = sizes(spec)
    return ModelConfig(
        name=spec["name"], n_layers=s["L"], d_model=s["d"], n_heads=s["h"],
        n_kv_heads=s["kv"], d_ff=s["f"], vocab_size=s["V"],
        block_pattern=tuple("local" if x else "attn" for x in s["local"]),
        window_size=s["window"], mlp_type="swiglu", norm="rmsnorm",
        norm_eps=s["eps"], tie_embeddings=True, rope_theta=s["theta"],
        dtype=spec["dtype"])


def init_weights(spec, seed: int, dtype):
    """Seeded weights in the program's stacked layout, cast to ``dtype``:
    embedding N(0, 0.02), projections N(0, 1/fan_in), RMSNorm gains
    (applied as 1 + gain) N(0, 0.1)."""
    s = sizes(spec)
    L, d, h, kv, dh, f = s["L"], s["d"], s["h"], s["kv"], s["dh"], s["f"]

    def make(key):
        ks = iter(jax.random.split(key, 8))

        def normal(shape, std):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * std).astype(dtype)

        return {
            "embed": normal((s["Vp"], d), 0.02),
            "ln_f": {"gain": normal((d,), 0.1)},
            "layers": {
                "ln1": {"gain": normal((L, d), 0.1)},
                "attn": {"wqkv": normal((L, d, (h + 2 * kv) * dh), d ** -0.5),
                         "wo": normal((L, h * dh, d), (h * dh) ** -0.5)},
                "ln2": {"gain": normal((L, d), 0.1)},
                "mlp": {"wi": normal((L, d, 2 * f), d ** -0.5),
                        "wo": normal((L, f, d), f ** -0.5)},
            },
        }

    return jax.jit(make)(jax.random.PRNGKey(seed))


def _rmsnorm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * (1.0 + gain)


def _rope(x, positions, theta):
    """x [b, s, heads, dh]: rotate the first half against the second."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions[:, None].astype(jnp.float32) * freqs[None]
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
    """float32 logits [b, s, vocab] of ``tokens`` [b, s] (position 0
    first); ``bits`` rounds every linear input, weight and cached K/V as
    ``gpt2.forward`` does."""
    s = sizes(spec)
    h, kv, dh, eps = s["h"], s["kv"], s["dh"], s["eps"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    b, n = tokens.shape
    positions = jnp.arange(n)
    qpos, kpos = positions[:, None], positions[None, :]
    causal = kpos <= qpos
    local = causal & (kpos > qpos - s["window"])

    def layer(x, xs):
        lp, is_local = xs
        a = _rmsnorm(x, lp["ln1"]["gain"], eps)
        qkv = _linear(a, lp["attn"]["wqkv"], bits)
        q = qkv[..., : h * dh].reshape(b, n, kv, h // kv, dh)
        k = qkv[..., h * dh: (h + kv) * dh].reshape(b, n, kv, dh)
        v = qkv[..., (h + kv) * dh:].reshape(b, n, kv, dh)
        q = _rope(q.reshape(b, n, h, dh), positions, s["theta"]).reshape(
            b, n, kv, h // kv, dh)
        k = _rope(k, positions, s["theta"])
        if bits is not None:
            k, v = _fake_quant(k, -1, bits), _fake_quant(v, -1, bits)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / math.sqrt(dh)
        mask = jnp.where(is_local, local, causal)
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
        o = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(scores, -1), v)
        x = x + _linear(o.reshape(b, n, h * dh), lp["attn"]["wo"], bits)
        m = _rmsnorm(x, lp["ln2"]["gain"], eps)
        gate, up = jnp.split(_linear(m, lp["mlp"]["wi"], bits), 2, -1)
        x = x + _linear(jax.nn.silu(gate) * up, lp["mlp"]["wo"], bits)
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, p["embed"][tokens],
                            (p["layers"], jnp.asarray(s["local"])))
        x = _rmsnorm(x, p["ln_f"]["gain"], eps)
        return x @ p["embed"][: s["V"]].T
