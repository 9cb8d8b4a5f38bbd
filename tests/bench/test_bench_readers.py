"""Each per-layer metric's reader, on a hand-made traced window, and the
discovery of configuration, traffic and metric files by name alone."""
from __future__ import annotations

import json
import types

import pytest

import bench_testlib as L
import run

PEAKS = json.loads((L.BENCH / "peaks.json").read_text())["TPU v5 lite"]


def _ctx(**kw):
    device = {"window_s": 2.0, "busy_s": 1.5, "chips": 1,
              "ops": {"paged_attention": [0.5, 10], "muxq_gemm": [0.1, 40],
                      "fusion": [0.9, 10]},
              "modules": {"jit_decode": [1.2, 4], "jit_prefill": [0.3, 1]},
              "idle_gaps": []}
    base = dict(
        counters={"decode_steps": 4, "decode_slot_steps": 100,
                  "prefill_steps": 1, "prefill_chunk_tokens": 48,
                  "prefills": 2, "kv_bytes_read": 8e6,
                  "attn_pages_bucket": 640, "attn_pages_live": 128},
        events=[{"name": "STEP", "args": {"chunk_bucket": 32}},
                {"name": "STEP", "args": {"chunk_bucket": 0}}],
        device=device, peaks=PEAKS, spec={"quant": {"method": "muxq"}},
        n_slots=32,
        work={"linear_ops": 393e9, "linear_bytes": 819e9 * 0.06,
              "quantized_ops": 393e9, "quantized_bytes": 819e9 * 0.05,
              "attention_ops": 0.0, "attention_bytes": 819e9 * 0.1,
              "model_ops": 393e12 * 0.02})
    base.update(kw)
    return types.SimpleNamespace(**base)


def _read(name, ctx):
    return run.load_module(L.BENCH / "metrics" / f"{name}.py").read(ctx)


@pytest.mark.parametrize("name,want", [
    ("sched.decode_batch_mean", 25.0),
    # 48 valid rows of 32 slots x 32 rows
    ("sched.prefill_valid_share", 100.0 * 48 / 1024),
    ("step.decode_ms", 300.0),
    ("step.prefill_ms", 300.0),
    ("pool.kv_read_mb_per_decode", 2.0),
    # the quantized sites' bytes bound (50 ms) over 100 ms of kernel time
    ("muxq_gemm_roofline", 50.0),
    ("paged_attention_roofline", 20.0),
    ("device.idle_share", 25.0),
    # 2% of the int8 peak's operations for each of the window's 2 seconds
    ("mfu", 1.0),
    # 128 live pages of 640 in the kernel's grid
    ("paged_attention.live_page_share", 20.0),
])
def test_reader(name, want):
    assert _read(name, _ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["muxq_gemm_roofline",
                                  "paged_attention_roofline", "mfu",
                                  "step.decode_ms", "device.idle_share"])
def test_reader_without_device_events_returns_nothing(name):
    empty = {"window_s": 2.0, "busy_s": 0.0, "chips": 0, "ops": {},
             "modules": {}, "idle_gaps": []}
    assert _read(name, _ctx(device=empty)) is None


@pytest.mark.parametrize("name,kw", [
    # no dispatch of the paged kernel in the window
    ("paged_attention.live_page_share",
     {"counters": {"attn_pages_bucket": 0, "attn_pages_live": 0}}),
    # a kernel in the trace but no quantized site in the description
    ("muxq_gemm_roofline",
     {"work": {"quantized_ops": 0.0, "quantized_bytes": 0.0}}),
])
def test_reader_with_nothing_to_count_returns_nothing(name, kw):
    assert _read(name, _ctx(**kw)) is None


def test_a_non_gpt2_model_is_counted_from_its_own_description(
        tmp_path, monkeypatch):
    """A test-size model of another architecture, grouped-query attention
    (4 query heads, 2 K/V heads of 16), SwiGLU and one sliding-window layer
    of 24 positions beside a full one (``tests/bench/data/gqa_swiglu.py``),
    served, traced and checked on the CPU: ``mfu`` and both rooflines read
    the work its reference describes, which matches a count by hand,
    query by query, over the window's own contexts and chunks.  The CPU's
    profile has no TPU plane, so the v5e's peaks and kernel times stand
    in for the chip's."""
    import tracefile
    import work
    root = L.root_of(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-gqa-muxq", "source": "test",
                             "file": "configs/tiny-gqa-muxq.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-gqa-chat",
                               "config": "tiny-gqa-muxq",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    kernels = {"paged_attention": 0.25, "muxq_gemm": 0.5}
    reduce, count = tracefile.reduce, work.window

    def on_the_chip(records, window_s):
        out = reduce(records, window_s)
        out["busy_s"] = window_s / 2
        out["ops"].update({k: [t, 10] for k, t in kernels.items()})
        return out

    seen = []

    def window(model, **kw):
        seen.append((model, kw, count(model, **kw)))
        return seen[-1][2]
    monkeypatch.setattr(tracefile, "reduce", on_the_chip)
    monkeypatch.setattr(work, "window", window)
    monkeypatch.setattr(run, "device_info", lambda chips, tpu: (
        {"platform": "cpu", "kind": "cpu", "count": 1}, PEAKS))
    res = L.run_cell(tmp_path, "tiny-gqa-chat", trace=True)
    assert res["correct"], res["check"]
    (model, kw, w), = seen

    d, h, kv, dh, f, vocab, win = 64, 4, 2, 16, 96, 512, 24
    tokens = kw["decode_slot_steps"] + kw["prefill_tokens"]
    # two layers of QKV over 4 + 2 + 2 heads, output, gate and up, down
    weights = 2 * (d * (h + 2 * kv) * dh + h * dh * d + d * 2 * f + f * d)
    assert w["linear_ops"] == w["quantized_ops"] == 2 * weights * tokens
    keys, positions = {}, {}
    for cap in (None, win):
        def seen_by(p):     # the positions the query at p attends
            return range(0 if cap is None else max(0, p + 1 - cap), p + 1)
        keys[cap] = sum(min(k, cap or k) for k in kw["decode_keys"])
        positions[cap] = keys[cap]
        for n, end in kw["chunks"]:
            keys[cap] += sum(len(seen_by(p)) for p in range(end - n, end))
            positions[cap] += len({j for p in range(end - n, end)
                                   for j in seen_by(p)})
    assert keys[win] < keys[None] and positions[win] < positions[None]
    # one layer of each: scores and values of 4 heads of 16 per key; K and
    # V of the 2 K/V heads, int8 with a float32 scale, per position
    assert w["attention_ops"] == 2 * h * 2 * dh * (keys[None] + keys[win])
    assert w["attention_bytes"] == 2 * kv * (dh + 4) * (positions[None]
                                                        + positions[win])
    sampled = len(kw["decode_keys"]) + kw["prefills"]
    assert w["model_ops"] == pytest.approx(
        w["linear_ops"] + w["attention_ops"] + 2 * d * vocab * sampled)

    got = {k: v["value"] for k, v in res["metrics"].items()}
    window_s = res["device"]["window_s"]
    assert got["mfu"] == pytest.approx(
        100 * w["model_ops"] / window_s / PEAKS["int8_ops_per_s"])
    assert got["paged_attention_roofline"] == pytest.approx(100 * max(
        w["attention_ops"] / PEAKS["bf16_flops_per_s"],
        w["attention_bytes"] / PEAKS["hbm_bytes_per_s"]) / 0.25)
    assert got["muxq_gemm_roofline"] == pytest.approx(100 * max(
        w["quantized_ops"] / PEAKS["int8_ops_per_s"],
        w["quantized_bytes"] / PEAKS["hbm_bytes_per_s"]) / 0.5)
    assert 0 < got["paged_attention.live_page_share"] <= 100


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a mix and a metric added as files, with entries
    appended to BENCHMARK.json and no existing file edited."""
    root = L.root_of(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / "bench" / "traffic" / "tiny-burst.json").write_text(json.dumps(
        {"backlog": 512, "block": 4,
         "prompt_tokens": {"median": 20, "sigma": 0.2, "min": 16, "max": 24},
         "output_tokens": {"median": 9, "sigma": 0.1, "min": 8, "max": 10},
         "shared_prefix_tokens": 0}))
    # MUXQ: at test width bf16's mean gap does not separate from its
    # control's, so only the MUXQ configuration's check is steady here
    spec = json.loads((L.DATA / "tiny-muxq.json").read_text())
    spec["name"] = "tiny-muxq-b"
    (root / "configs" / "tiny-muxq-b.json").write_text(json.dumps(spec))
    (root / "bench" / "metrics" / "sched.completed.py").write_text(
        "def read(run):\n    return run.counters['completed']\n")
    bench["configs"].append({"name": "tiny-muxq-b", "source": "test",
                             "file": "configs/tiny-muxq-b.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-burst", "config": "tiny-muxq-b",
                               "traffic": "tiny-burst", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "sched.completed", "unit": "requests",
                               "better": "higher", "source": "program_counter",
                               "layer": "scheduler", "moves": "output_tok_s",
                               "workloads": ["tiny-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    _, cell, spec2, traffic = run.load_cell(root, "tiny-burst")
    assert spec2["name"] == "tiny-muxq-b" and traffic["block"] == 4
    names = [n for n, _, _ in run.per_layer_readers(root, bench)]
    assert "sched.completed" in names
    res = L.run_cell(tmp_path, "tiny-burst", trace=True)
    assert res["correct"]
    assert res["metrics"]["sched.completed"]["value"] > 0
    # a bf16 cell runs no muxq_gemm: its reader finds nothing to read
    assert "muxq_gemm_roofline" not in res["metrics"]
    assert res["metrics"]["sched.decode_batch_mean"]["unit"] == "slots"
