"""Moonlight-16B-A3B's share in the benchmark: its reference's description
of the work against hand counts and against XLA's count of the reference's
forward, its cell's mix, the test-size cell driven through the harness
with ``mfu``, both rooflines and the two ``moe.*`` readers reading it, and
the readers on hand-made windows."""
from __future__ import annotations

import copy
import importlib.util
import json
import types

import pytest

import bench_testlib as L
import gen
import hosttrace
import run
import tracefile
import work


def _ref():
    m = importlib.util.spec_from_file_location(
        "moonlight_ref", L.BENCH / "reference" / "moonlight.py")
    mod = importlib.util.module_from_spec(m)
    m.loader.exec_module(mod)
    return mod


REF = _ref()
SPEC = json.loads((L.BENCH / "configs" / "moonlight-16b-a3b-muxq.json")
                  .read_text())
TINY = json.loads((L.DATA / "tiny-moonlight-muxq.json").read_text())
PEAKS = json.loads((L.BENCH / "peaks.json").read_text())["TPU v5 lite"]


def _read(name, ctx):
    return run.load_module(L.BENCH / "metrics" / f"{name}.py").read(ctx)


# ---------------------------------------------------------------------------
# the description, by hand
# ---------------------------------------------------------------------------

def test_held_parameters_per_layer():
    """What one chip holds of a layer (8 of 64 experts, width 2048):
    latent attention 13.8 M, 8 experts of 8.65 M, the shared experts
    17.3 M and the router 0.13 M; the dense layer 83.0 M."""
    d, h, r = 2048, 16, 512
    mla = d * (h * 192 + r) + d * 64 + r * h * 256 + h * 128 * d
    expert = 3 * d * 1408
    layer = mla + 8 * expert + 3 * d * 2816 + d * 64
    assert round(mla / 1e6, 1) == 13.8 and round(expert / 1e6, 2) == 8.65
    assert round(layer / 1e6, 1) == 100.4
    assert round((mla + 3 * d * 11264) / 1e6, 1) == 83.0
    model = REF.work_model(SPEC)
    per_token = work.linear_weights(model["linear"])
    # every site but the routed experts, which a token reaches 6 * 8 / 64
    # = 0.75 times (and the router, which is counted but holds 0.13 M)
    assert per_token == pytest.approx(
        13 * mla + 3 * d * 11264 + 12 * (0.75 * expert + 3 * d * 2816
                                         + d * 64))


def test_latent_cache_and_attention_counts():
    model = REF.work_model(SPEC)
    # the 576-wide latent row in int8 with a float32 scale, or in bf16,
    # per position in each of the 13 layers
    assert work.kv_bytes_per_token(model, "int8") == 13 * 580
    assert work.kv_bytes_per_token(model, "fp") == 13 * 1152
    # every head scores the 576-wide row and sums its 512 latent values
    assert work.attention_ops(model, 1) == 2 * 13 * 16 * (576 + 512)
    assert work.head_ops(model) == 2 * 2048 * 20480
    quantized = {s["name"] for s in model["linear"] if s["quantized"]}
    assert quantized == {"attn_q", "attn_kpe", "attn_out", "mlp_up",
                         "mlp_down", "moe_up", "moe_down", "moe_shared_up",
                         "moe_shared_down"}
    experts = [s for s in model["linear"] if s["name"].startswith("moe_")
               and "shared" not in s["name"]]
    assert {(s["token_share"], s["matrices"]) for s in experts} == {(0.75, 8)}


def test_configuration_keeps_the_published_numbers():
    """Every number the file changes from the published config is in
    BENCHMARK.json's ``reduced`` and stated beside the published one."""
    bench = json.loads((L.REPO / "BENCHMARK.json").read_text())
    conf = {c["name"]: c for c in bench["configs"]}["moonlight-16b-a3b-muxq"]
    assert set(conf["reduced"]) == set(SPEC["published"]) == set(
        SPEC["reduced"])
    assert SPEC["published"] == {"num_hidden_layers": 27,
                                 "n_routed_experts": 64,
                                 "vocab_size": 163840}
    assert SPEC["experts_routed_over"] == 64
    cfg = REF.model_config(SPEC)
    assert (cfg.n_experts, cfg.held, cfg.top_k) == (64, (0, 8), 6)
    assert cfg.first_dense == 1 and cfg.is_mla and cfg.vocab_size == 20480


# ---------------------------------------------------------------------------
# the description against XLA's count of the reference's forward
# ---------------------------------------------------------------------------

def test_description_against_xla():
    """One dense and one expert layer (XLA counts a scan's body once),
    every query against all T keys.  The reference computes every held
    expert for every token and weighs the unchosen by 0, so the case holds
    every expert and chooses every one (token share = experts held), the
    one case where the description's expected share is what XLA sees.  The
    reference runs attention in the expanded form (192-wide keys, 128-wide
    values per head), the program in the absorbed form the description
    counts (576 and 512 shared): attention is counted here at the
    reference's widths.  Up to 3% more in XLA is elementwise work, as in
    ``test_bench_work.py``."""
    import jax
    import jax.numpy as jnp
    spec = dict(SPEC, hidden_size=256, num_attention_heads=4,
                kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
                v_head_dim=32, intermediate_size=512,
                moe_intermediate_size=128, n_shared_experts=2,
                experts_routed_over=4, n_routed_experts=4,
                num_experts_per_tok=4, num_hidden_layers=2, vocab_size=512)
    b, t = 2, 128
    params = jax.eval_shape(lambda: REF.init_weights(spec, 0, jnp.float32))
    tokens = jax.ShapeDtypeStruct((b, t), jnp.int32)
    cost = jax.jit(lambda p, x: REF.forward(spec, p, x)).lower(
        params, tokens).compile().cost_analysis()
    xla = (cost[0] if isinstance(cost, list) else cost)["flops"]
    model = REF.work_model(spec)
    expanded = 2.0 * 2 * 4 * (32 + 16 + 32)        # per key: 2 layers
    ours = b * t * (2 * work.linear_weights(model["linear"])
                    + expanded * t + work.head_ops(model))
    assert 0 <= (xla - ours) / xla <= 0.03, (xla, ours)


# ---------------------------------------------------------------------------
# the cell's mix
# ---------------------------------------------------------------------------

def test_chat_full_is_chat_unscaled():
    full = gen.load(L.BENCH / "traffic" / "chat-full.json")
    chat = gen.load(L.BENCH / "traffic" / "chat.json")
    assert full["scale"] == 1.0
    for key in ("prompt_tokens", "output_tokens", "backlog", "block",
                "shared_prefix_tokens", "open_after_finished"):
        assert full[key] == chat[key]
    b = gen.bounds(full)
    assert (b["prompt_min"], b["prompt_max"]) == (256, 2048)
    assert (b["output_min"], b["output_max"]) == (16, 1024)
    # the longest context fits a slot of the configuration
    assert b["prompt_max"] + b["output_max"] <= SPEC["serve"]["s_max"]
    reqs = gen.requests(full, 2 ** 31 + 77)
    assert max(r["prompt_tokens"] for r in reqs) <= 2048


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def test_rows_per_held_expert_reader():
    steps = [{"name": "STEP", "args": {"moe_rows_held": 30,
                                       "moe_expert_calls": 12}},
             {"name": "STEP", "args": {"moe_rows_held": 66,
                                       "moe_expert_calls": 12}},
             {"name": "CHUNK", "args": {"moe_rows_held": 1000}}]
    ctx = types.SimpleNamespace(events=steps, spec=SPEC)
    # 96 rows over 24 expert-layer calls of 8 held experts
    assert _read("moe.rows_per_held_expert", ctx) == pytest.approx(0.5)
    dense = types.SimpleNamespace(
        events=[{"name": "STEP", "args": {"cow": 0}}],
        spec={"name": "gpt2-large-muxq"})
    assert _read("moe.rows_per_held_expert", dense) is None


TRACE = json.loads((L.DATA / "trace_v5e_host.json").read_text())


def _trace_ctx(monkeypatch, trace):
    monkeypatch.setattr(hosttrace, "load", lambda path: trace)
    ends = [r["start_ns"] + r["dur_ns"] for r in trace["records"]]
    window = (max(ends) - min(r["start_ns"] for r in trace["records"])) * 1e-9
    return types.SimpleNamespace(
        spec=SPEC, trace_dir="cache/trace/chat-full-moonlight-muxq",
        device=tracefile.reduce(trace["records"], window))


def test_route_reader_reads_the_moe_scope_less_the_kernel(monkeypatch):
    """Two ops of a recorded v5e window moved under ``moe``, one of them
    renamed ``muxq_gemm``: only the other's self time counts, per step
    call."""
    trace = copy.deepcopy({"spans": TRACE["spans"],
                           "records": TRACE["records"]})
    ops = [r for r in trace["records"] if r["line"] == tracefile.OPS
           and r["scope"].startswith("jit(decode)/") and "/pool/" not in
           r["scope"]]
    ops.sort(key=lambda r: -r["dur_ns"])
    route, kernel = ops[0], ops[1]
    route["scope"] = "jit(decode)/while/body/moe/sort"
    kernel["scope"] = "jit(decode)/while/body/moe/muxq_gemm"
    kernel["name"] = "%muxq_gemm.3 = f32[448,2816] custom-call()"
    ctx = _trace_ctx(monkeypatch, trace)
    calls = sum(1 for r in trace["records"] if r["line"] == tracefile.MODULES
                and tracefile._module(r["name"]) in hosttrace.STEP_PROGRAMS)
    inner = [r for r in trace["records"] if r["line"] == tracefile.OPS
             and r["chip"] == route["chip"]
             and r["start_ns"] >= route["start_ns"]
             and r["start_ns"] + r["dur_ns"] <= route["start_ns"] + route["dur_ns"]
             and r is not route]
    own = route["dur_ns"] - sum(r["dur_ns"] for r in inner)
    assert _read("moe.route_ms_per_step", ctx) == pytest.approx(
        own * 1e-6 / calls)


def test_route_reader_finds_nothing_without_the_scope(monkeypatch):
    """GPT-2's window has no ``moe`` scope (nor has a program before the
    expert layer): nothing to read, nothing raised."""
    ctx = _trace_ctx(monkeypatch, copy.deepcopy(
        {"spans": TRACE["spans"], "records": TRACE["records"]}))
    assert _read("moe.route_ms_per_step", ctx) is None


# ---------------------------------------------------------------------------
# the test-size cell through the harness
# ---------------------------------------------------------------------------

def test_tiny_cell_is_counted_from_its_own_description(tmp_path, monkeypatch):
    """The test-size Moonlight (latent 32 + 16, a dense layer, 4 of 16
    experts held, top 3) served, traced and checked on the CPU: ``mfu``
    and both rooflines read the work its reference describes, and the
    expert-row count returns from the step programs.  The CPU's profile
    has no TPU plane, so the v5e's peaks and kernel times stand in."""
    root = L.root_of(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-moonlight-muxq", "source": "test",
                             "file": "configs/tiny-moonlight-muxq.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-moon-chat",
                               "config": "tiny-moonlight-muxq",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    names = [n for n, _, _ in run.per_layer_readers(root, bench)]
    assert {"moe.rows_per_held_expert", "moe.route_ms_per_step"} <= set(names)
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
    res = L.run_cell(tmp_path, "tiny-moon-chat", trace=True)
    assert res["correct"], res["check"]
    (model, kw, w), = seen
    assert model == REF.work_model(TINY)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["mfu"] == pytest.approx(
        100 * w["model_ops"] / res["device"]["window_s"]
        / PEAKS["int8_ops_per_s"])
    assert got["muxq_gemm_roofline"] == pytest.approx(100 * max(
        w["quantized_ops"] / PEAKS["int8_ops_per_s"],
        w["quantized_bytes"] / PEAKS["hbm_bytes_per_s"]) / 0.5)
    assert got["paged_attention_roofline"] == pytest.approx(100 * max(
        w["attention_ops"] / PEAKS["bf16_flops_per_s"],
        w["attention_bytes"] / PEAKS["hbm_bytes_per_s"]) / 0.25)
    # a held expert's rows per call: at most the 3 pairs of each of a
    # prefill step's 2 x 32 tokens over the 4 experts held
    assert 0 < got["moe.rows_per_held_expert"] <= 2 * 32 * 3 / 4
    # the CPU's profile has no device ops, so no ``moe`` scope to read
    assert "moe.route_ms_per_step" not in got
