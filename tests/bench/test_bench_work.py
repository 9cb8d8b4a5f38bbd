"""Operation and byte counts against hand counts at GPT-2 large's shapes
(36 layers, width 1280, 20 heads of 64, MLP 5120, vocabulary 50257)."""
from __future__ import annotations

import json

import pytest

import bench_testlib as L
import work


@pytest.fixture(scope="module")
def large():
    import importlib.util
    spec = json.loads((L.BENCH / "configs" / "gpt2-large-muxq.json").read_text())
    path = L.BENCH / "reference" / "gpt2.py"
    m = importlib.util.spec_from_file_location("gpt2_ref", path)
    mod = importlib.util.module_from_spec(m)
    m.loader.exec_module(mod)
    return mod.sizes(spec)


def test_sizes(large):
    assert (large["L"], large["d"], large["h"], large["dh"], large["f"],
            large["V"], large["Vp"]) == (36, 1280, 20, 64, 5120, 50257, 50304)


def test_projection_weights(large):
    # per layer: 1280*3840 + 1280*1280 + 1280*5120 + 5120*1280 = 19,660,800
    assert work.linear_weights(large) == 707_788_800


@pytest.mark.parametrize("quantized,want", [
    # int8: 36 * (19,706,880 weights+scales + 32 rows * 55,056)
    (True, 772_872_192),
    # bf16: 36 * (39,321,600 + 32 rows * 40,960)
    (False, 1_462_763_520),
])
def test_decode_call_bytes(large, quantized, want):
    kw = (dict(weight_bytes=1, act_bytes=1, out_bytes=4, scale_bytes=4)
          if quantized else dict(weight_bytes=2, act_bytes=2, out_bytes=2))
    assert work.linear_bytes(large, calls=1, rows=32, **kw) == want


@pytest.mark.parametrize("mode,want", [("int8", 97_920), ("fp", 184_320)])
def test_kv_bytes_per_token(large, mode, want):
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
    s = {"L": 1, "d": 2, "h": 1, "dh": 2, "f": 4, "V": 5}
    w = work.window(s, kv_mode="int8", quantized=True, out_bytes=4,
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


def test_roofline_takes_the_slower_bound():
    assert work.roofline_s(393e12, 1.0, 393e12, 819e9) == pytest.approx(1.0)
    assert work.roofline_s(1.0, 819e9, 393e12, 819e9) == pytest.approx(1.0)
