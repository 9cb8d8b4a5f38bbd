"""Operation and byte counts from a reference's description of its model
(``work_model``): GPT-2 large's against hand counts (36 layers, width
1280, 20 heads of 64, MLP 5120, vocabulary 50257) and against the numbers
the GPT-2 formulas it replaced gave; toy descriptions of other
architectures against hand counts; and the descriptions' operations
against XLA's own count of the reference's forward pass."""
from __future__ import annotations

import importlib.util
import json

import pytest

import bench_testlib as L
import work


def _ref(path):
    m = importlib.util.spec_from_file_location(path.stem + "_ref", path)
    mod = importlib.util.module_from_spec(m)
    m.loader.exec_module(mod)
    return mod


GPT2 = _ref(L.BENCH / "reference" / "gpt2.py")
GQA = _ref(L.DATA / "gqa_swiglu.py")


def _spec(name):
    return json.loads((L.BENCH / "configs" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def large():
    return GPT2.work_model(_spec("gpt2-large-muxq"))


def test_sizes():
    s = GPT2.sizes(_spec("gpt2-large-muxq"))
    assert (s["L"], s["d"], s["h"], s["dh"], s["f"], s["V"], s["Vp"]) == \
        (36, 1280, 20, 64, 5120, 50257, 50304)


def test_projection_weights(large):
    # per layer: 1280*3840 + 1280*1280 + 1280*5120 + 5120*1280 = 19,660,800
    assert work.linear_weights(large["linear"]) == 707_788_800


@pytest.mark.parametrize("quantized,want", [
    # int8: 36 * (19,706,880 weights+scales + 32 rows * 55,056)
    (True, 772_872_192),
    # bf16: 36 * (39,321,600 + 32 rows * 40,960)
    (False, 1_462_763_520),
])
def test_decode_call_bytes(large, quantized, want):
    assert work.linear_bytes(large["linear"], calls=1, rows=32,
                             quantized=quantized,
                             dtype_bytes=4 if quantized else 2) == want


@pytest.mark.parametrize("mode,want", [("int8", 97_920), ("fp", 184_320),
                                       ("int4", 48_960)])
def test_kv_bytes_per_token(large, mode, want):
    # 36 layers * K and V * 20 heads * (64 int8 + a float32 scale; 64
    # bf16; 32 bytes of nibbles + a bfloat16 scale)
    assert work.kv_bytes_per_token(large, mode) == want


def test_attention_ops(large):
    # 4 * 36 layers * 20 heads * 64 * 512 keys
    assert work.attention_ops(large, 512) == 94_371_840


def test_chunk_keys():
    # queries at 32..63 see 33..64 keys: 32*32 + (1+..+32)
    assert work.chunk_keys(32, 64) == 1552
    assert work.chunk_keys(1, 1) == 1


def test_window_by_hand():
    """A one-layer toy: d 2, one head of 2, MLP 4, vocabulary 5."""
    s = GPT2.work_model({"n_layer": 1, "n_embd": 2, "n_head": 1, "n_inner": 4,
                         "vocab_size": 5, "layer_norm_epsilon": 1e-5})
    w = work.window(s, kv_mode="int8", quantized=True, dtype_bytes=4,
                    decode_steps=1, decode_slot_steps=2, prefill_steps=1,
                    prefill_tokens=3, prefills=1, decode_keys=[10, 20],
                    chunks=[(3, 3)])
    # weights 2*6 + 2*2 + 2*4 + 4*2 = 32; 5 tokens
    assert w["linear_ops"] == 2 * 32 * 5
    # per call 32 + 4*(6+2+4+2) = 88; per row 30 + 14 + 22 + 16 = 82
    assert w["linear_bytes"] == 2 * 88 + 5 * 82
    # keys 10 + 20 + (0 + 1 + 2 + 3); 4 ops per key per head dim pair
    assert w["attention_ops"] == 4 * 2 * 36
    # 12 bytes a position (K and V: 2 bytes + a 4-byte scale each)
    assert w["attention_bytes"] == 12 * (10 + 20 + 3)
    # LM head: 2 decode tokens and 1 finished prompt
    assert w["model_ops"] == 320 + 288 + 2 * 2 * 5 * 3
    # every site is quantized: the int8 work is all of it
    assert (w["quantized_ops"], w["quantized_bytes"]) == \
        (w["linear_ops"], w["linear_bytes"])


def test_roofline_takes_the_slower_bound():
    assert work.roofline_s(393e12, 1.0, 393e12, 819e9) == pytest.approx(1.0)
    assert work.roofline_s(1.0, 819e9, 393e12, 819e9) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# GPT-2's description gives the numbers of the formulas it replaced
# ---------------------------------------------------------------------------

# a fixed window: 1200 decoded tokens over contexts 65-764, 112 chunks
DECODE_KEYS = [65 + (37 * i) % 700 for i in range(1200)]
CHUNKS = [(32, 32 * (1 + j % 16)) for j in range(110)] + [(5, 261), (29, 413)]

# work.window of the GPT-2 formulas (bench/work.py before descriptions)
# on that window, both GPT-2 large configurations
BEFORE = {
    "gpt2-large-muxq": {"linear_ops": 6729655910400.0,
                        "linear_bytes": 171176575104.0,
                        "attention_ops": 257478451200.0,
                        "attention_bytes": 51471256320.0,
                        "model_ops": 7145254945280.0},
    "gpt2-large-bf16": {"linear_ops": 6729655910400.0,
                        "linear_bytes": 329761751040.0,
                        "attention_ops": 257478451200.0,
                        "attention_bytes": 96887070720.0,
                        "model_ops": 7145254945280.0},
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_gpt2_counts_match_the_replaced_formulas(name):
    spec = _spec(name)
    w = work.window(GPT2.work_model(spec), kv_mode=spec["serve"]["kv_mode"],
                    quantized=bool(spec["quant"]),
                    dtype_bytes=4 if spec["dtype"] == "float32" else 2,
                    decode_steps=114, decode_slot_steps=1200,
                    prefill_steps=114,
                    prefill_tokens=sum(n for n, _ in CHUNKS), prefills=29,
                    decode_keys=DECODE_KEYS, chunks=CHUNKS)
    for key, want in BEFORE[name].items():
        assert w[key] == pytest.approx(want, rel=1e-12, abs=0), key
    # the muxq_gemm roofline's work: every site in MUXQ, none in bf16
    quantized = bool(spec["quant"])
    assert w["quantized_ops"] == (w["linear_ops"] if quantized else 0)
    assert w["quantized_bytes"] == (w["linear_bytes"] if quantized else 0)


# ---------------------------------------------------------------------------
# other architectures, counted by hand
# ---------------------------------------------------------------------------

def _site(name, k, n, layers=1, share=1.0, matrices=1, quantized=True):
    return {"name": name, "k": k, "n": n, "layers": layers,
            "token_share": share, "matrices": matrices, "quantized": quantized}


def _group(layers, heads, qk, v, int8_bytes, window=None):
    return {"layers": layers, "heads": heads, "qk_dim": qk, "v_dim": v,
            "kv_bytes": {"int8": int8_bytes}, "window": window}


def _window(model, decode_keys=(), chunks=(), **kw):
    args = dict(kv_mode="int8", quantized=True, dtype_bytes=2,
                decode_steps=0, decode_slot_steps=0, prefill_steps=0,
                prefill_tokens=0, prefills=0)
    args.update(kw)
    return work.window(model, decode_keys=decode_keys, chunks=chunks, **args)


def test_gqa_with_a_three_matrix_mlp():
    """Two layers, width 8, 4 query heads and 2 K/V heads of 2, SwiGLU of
    width 16 (gate, up, down): the cache and the QKV site hold the K/V
    heads only."""
    model = {"linear": [_site("q", 8, 8, 2), _site("kv", 8, 8, 2),
                        _site("o", 8, 8, 2), _site("gate", 8, 16, 2),
                        _site("up", 8, 16, 2), _site("down", 16, 8, 2)],
             "attention": [_group(2, 4, 2, 2, int8_bytes=2 * 2 * (2 + 4))],
             "head": {"width": 8, "vocab": 10}}
    # per layer 3 * 64 + 3 * 128 = 576 weights
    assert work.linear_weights(model["linear"]) == 1152
    # K and V of 2 heads, 2 int8 values and a float32 scale each: 24 B
    assert work.kv_bytes_per_token(model, "int8") == 48
    # scores and values of 4 query heads of 2 in 2 layers:
    # 2 * 2 * 4 * (2 + 2) = 64 per key
    assert work.attention_ops(model, 7) == 64 * 7
    w = _window(model, decode_keys=[7], decode_steps=1, decode_slot_steps=1)
    assert w["linear_ops"] == 2 * 1152
    # a call: 1152 int8 weights and a float32 scale for each of the 64
    # outputs a layer; the row, in each layer: 8+8+8+8+8+16 int8 in,
    # 64 bf16 out, a scale for each of the 6 sites
    assert w["linear_bytes"] == 1152 + 2 * 64 * 4 + 2 * (56 + 2 * 64 + 6 * 4)
    assert w["attention_bytes"] == 48 * 7
    assert w["model_ops"] == 2 * 1152 + 64 * 7 + 2 * 8 * 10


def test_window_caps_keys_in_decode_and_chunks():
    """One full layer and one with a window of 4, one head of 1."""
    model = {"linear": [],
             "attention": [_group(1, 1, 1, 1, int8_bytes=10),
                           _group(1, 1, 1, 1, int8_bytes=10, window=4)],
             "head": {"width": 1, "vocab": 1}}
    # chunk of 3 ending at 8: queries at 5, 6, 7 see 6, 7, 8 keys, 4 each
    # in the window; it reads positions 4-7 for the last query down to
    # 2-5 for the first: 2..7, 6 of 8
    assert work.chunk_keys(3, 8, 4) == 12
    assert work.chunk_positions(3, 8, 4) == 6
    # a chunk inside the window, and one straddling it (keys 3, 4, 4)
    assert work.chunk_keys(3, 3, 4) == work.chunk_keys(3, 3) == 6
    assert work.chunk_keys(3, 5, 4) == 11
    assert work.chunk_positions(3, 3, 4) == 3
    w = _window(model, decode_keys=[2, 10], chunks=[(3, 8)])
    # full layer: 2 + 10 + (6 + 7 + 8) keys; windowed: 2 + 4 + 12
    assert w["attention_ops"] == 4 * (33 + 18)
    # full layer reads 2 + 10 + 8 positions; windowed 2 + 4 + 6
    assert w["attention_bytes"] == 10 * (20 + 12)
    assert work.attention_ops(model, 10) == 4 * (10 + 4)


def test_routed_experts_count_their_token_share():
    """64 experts of 4 x 6, 6 per token, 8 of them held here, in 3 layers:
    6 * 8 / 64 = 0.75 of a token's expert work lands here, and a call may
    read all 8 held experts' weights."""
    experts = _site("experts", 4, 6, layers=3, share=6 * 8 / 64, matrices=8)
    shared = _site("shared", 4, 6, layers=3)
    router = _site("router", 4, 64, layers=3, quantized=False)
    model = {"linear": [experts, shared, router], "attention": [],
             "head": {"width": 4, "vocab": 8}}
    # per token: 3 * (0.75 * 24 + 24 + 256)
    assert work.linear_weights(model["linear"]) == 3 * (18 + 24 + 256)
    w = _window(model, decode_steps=1, decode_slot_steps=10, dtype_bytes=2)
    assert w["linear_ops"] == 2 * 3 * 298 * 10
    # int8 experts a call: 8 * (24 + 6 scales of 4); per row 0.75 * (4 in
    # + 6 * 2 out + a scale of 4)
    experts_b = 3 * (8 * (24 + 24) + 10 * 0.75 * (4 + 12 + 4))
    shared_b = 3 * ((24 + 24) + 10 * (4 + 12 + 4))
    # the router is not quantized: bf16 weights and rows, no scales
    router_b = 3 * (256 * 2 + 10 * (4 * 2 + 64 * 2))
    assert w["linear_bytes"] == experts_b + shared_b + router_b
    assert w["quantized_ops"] == 2 * 3 * (18 + 24) * 10
    assert w["quantized_bytes"] == experts_b + shared_b
    # unquantized, the same sites read bf16 weights and rows
    bf16 = _window(model, decode_steps=1, decode_slot_steps=10,
                   quantized=False, dtype_bytes=2)
    assert (bf16["quantized_ops"], bf16["quantized_bytes"]) == (0, 0)
    assert bf16["linear_bytes"] == 3 * (8 * 48 + 10 * 0.75 * (8 + 12)) + \
        3 * (48 + 10 * 20) + router_b


def test_latent_attention_widths():
    """Absorbed latent attention: every head scores a shared latent of
    512 + 64 rotary values and sums the 512-wide latent, which one int8
    copy with a float32 scale holds per position (16 heads, as in
    Moonlight-16B-A3B)."""
    mla = {"linear": [],
           "attention": [_group(1, 16, 512 + 64, 512, int8_bytes=576 + 4)],
           "head": {"width": 1, "vocab": 1}}
    # 2 * 16 * (576 + 512) per key
    assert work.attention_ops(mla, 1) == 34_816
    assert work.kv_bytes_per_token(mla, "int8") == 580
    w = _window(mla, decode_keys=[100], chunks=[(2, 2)])
    assert w["attention_ops"] == 34_816 * (100 + 1 + 2)
    assert w["attention_bytes"] == 580 * (100 + 2)
    with pytest.raises(ValueError, match="fp"):
        _window(mla, decode_keys=[1], kv_mode="fp")


def test_sliced_vocabulary_head():
    """An LM head over the eighth of a 163,840 vocabulary held here."""
    model = {"linear": [], "attention": [],
             "head": {"width": 2048, "vocab": 163_840 // 8}}
    assert work.head_ops(model) == 2 * 2048 * 20_480
    w = _window(model, decode_keys=[5, 6], prefills=1)
    assert w["model_ops"] == 3 * 2 * 2048 * 20_480


# ---------------------------------------------------------------------------
# the description against XLA's count of the reference's forward pass
# ---------------------------------------------------------------------------

# One layer (XLA counts a scan's body once), every query against all T
# keys as the reference computes them.  XLA counts the elementwise work
# too (norms, rotary, softmax, the activation, masking), which the
# description leaves out: about 1% at these widths.  Dropping a single
# projection site would leave the description 6% or more short, so a gap
# of up to 3% is elementwise work and more is a count that is wrong.
XLA_TOLERANCE = 0.03
XLA_CASES = {
    "gpt2": (GPT2, {"name": "xla", "n_layer": 1, "n_embd": 256, "n_head": 4,
                    "vocab_size": 512, "layer_norm_epsilon": 1e-5}),
    "gqa_swiglu": (GQA, {"name": "xla", "hidden_size": 256,
                         "num_hidden_layers": 1, "num_attention_heads": 8,
                         "num_key_value_heads": 2, "intermediate_size": 512,
                         "vocab_size": 512, "rms_norm_eps": 1e-6,
                         "rope_theta": 10000.0, "sliding_window": 128,
                         "layer_types": ["full_attention"],
                         "dtype": "float32"}),
}


@pytest.mark.parametrize("case", sorted(XLA_CASES))
def test_description_against_xla(case):
    import jax
    import jax.numpy as jnp
    ref, spec = XLA_CASES[case]
    b, t = 2, 128
    params = jax.eval_shape(lambda: ref.init_weights(spec, 0, jnp.float32))
    tokens = jax.ShapeDtypeStruct((b, t), jnp.int32)
    cost = jax.jit(lambda p, x: ref.forward(spec, p, x)).lower(
        params, tokens).compile().cost_analysis()
    xla = (cost[0] if isinstance(cost, list) else cost)["flops"]
    model = ref.work_model(spec)
    ours = b * t * (2 * work.linear_weights(model["linear"])
                    + work.attention_ops(model, t) + work.head_ops(model))
    assert 0 <= (xla - ours) / xla <= XLA_TOLERANCE, (xla, ours)
