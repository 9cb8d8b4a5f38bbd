"""Tensor-parallel paged-serving tests: the serve stack on a ("model",)
device mesh with KV pages sharded by KV-head (``parallel/serve_sharding.py``
+ ``ServeEngine(tp=N)``).

Everything meshy runs in a subprocess with
``--xla_force_host_platform_device_count`` (the flag must never leak into
the main test process — same contract as tests/test_distributed.py).  The
load-bearing claim in every parity test is BIT-identical token streams:
per-shard attention uses the zero-pad+psum head merge, so fp pages at any
mesh size reproduce the single-device streams exactly, and the int8/int4
page quantizers are head-local (per-(pos, head) scales / per-head redist
rows) so quantized pages are exact too.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


def run_with_devices(code: str, n: int = 4) -> str:
    import os
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={n}",
           "PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": os.environ.get("PATH", "/usr/bin:/bin")}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# shared subprocess preamble: tiny model + a runner that returns the token
# streams plus the compile-count invariant every mesh size must hold.
# Indented to match the per-test snippets so the dedent in
# ``run_with_devices`` strips both uniformly.
_PRELUDE = """
    import jax, json
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config("gpt2-small", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))

    def run(tp, prompts, max_new=8, arrivals=None, cfg=cfg, params=params,
            **kw):
        kw.setdefault("max_batch", 2)
        kw.setdefault("s_max", 64)
        kw.setdefault("page_size", 16)
        kw.setdefault("prefill_chunk", 8)
        kw.setdefault("kv_mode", "fp")
        eng = ServeEngine(cfg, params, tp=tp, **kw)
        reqs = [Request(p, max_new_tokens=max_new) for p in prompts]
        eng.generate(reqs, arrivals=arrivals)
        # compile-count invariant: one trace per decode/prefill/verify
        # bucket, at EVERY mesh size (shard_map must not retrace per device)
        assert eng.decode_traces == len(eng.decode_buckets), \\
            (tp, eng.decode_traces, eng.decode_buckets)
        assert eng.prefill_traces == len(eng.prefill_buckets), \\
            (tp, eng.prefill_traces, eng.prefill_buckets)
        assert eng.verify_traces == len(eng.verify_buckets), \\
            (tp, eng.verify_traces, eng.verify_buckets)
        return [r.out_tokens for r in reqs], eng
"""


def test_tp_fp_parity_and_shard_bytes():
    """fp pages: tp=2 and tp=4 streams are bit-identical to tp=1, and each
    shard holds exactly global/tp of the pool bytes."""
    out = run_with_devices(_PRELUDE + """
    prompts = ["the model computes", "a kernel shards"]
    base, eb = run(1, prompts)
    assert eb.pool.kv_shards == 1
    g = eb.pool.cache_bytes()
    assert eb.pool.cache_bytes_per_shard() == g
    for tp in (2, 4):
        toks, e = run(tp, prompts)
        assert toks == base, (tp, toks, base)
        assert e.pool.heads_sharded and e.pool.kv_shards == tp
        assert e.pool.cache_bytes() == g            # global bytes unchanged
        assert e.pool.cache_bytes_per_shard() == g // tp, tp
        st = e.pool.stats()
        assert st["kv_shards"] == tp
        assert st["cache_bytes_per_shard"] == g // tp
    print("ok")
    """)
    assert out.strip() == "ok"


def test_tp_quantized_pages_exact():
    """int8 and int4 pages: the page quantizers are head-local, so sharded
    quantize/dequantize reproduces the single-device streams exactly."""
    out = run_with_devices(_PRELUDE + """
    prompts = ["the model computes", "a kernel shards"]
    for kv_mode in ("int8", "int4"):
        base, _ = run(1, prompts, kv_mode=kv_mode)
        for tp in (2, 4):
            toks, e = run(tp, prompts, kv_mode=kv_mode)
            assert toks == base, (kv_mode, tp)
            assert e.pool.kv_shards == tp
    print("ok")
    """)
    assert out.strip() == "ok"


def test_tp_spec_decode_and_prefix_sharing_parity():
    """Speculative (ngram) decoding + prefix-shared duplicate prompts +
    staggered arrivals under the mesh: streams, prefix hits and verify
    trace counts all match single-device."""
    out = run_with_devices(_PRELUDE + """
    prompts = ["the model computes", "the model computes", "a kernel shards"]
    base, eb = run(1, prompts, max_new=10, arrivals=[0, 1, 3],
                   spec_mode="ngram", spec_k=3)
    assert eb.metrics.prefix_hits > 0
    assert eb.metrics.spec_verify_steps > 0
    for tp in (2, 4):
        toks, e = run(tp, prompts, max_new=10, arrivals=[0, 1, 3],
                      spec_mode="ngram", spec_k=3)
        assert toks == base, tp
        assert e.metrics.prefix_hits == eb.metrics.prefix_hits
        assert e.metrics.spec_accepted == eb.metrics.spec_accepted
    print("ok")
    """)
    assert out.strip() == "ok"


def test_tp_preemption_replay_parity():
    """A pool too small for the working set forces preemption + replay
    (re-prefill of prompt + generated tokens); the replayed streams must
    still be bit-identical at every mesh size."""
    out = run_with_devices(_PRELUDE + """
    prompts = ["the model", "a kernel", "the model"]
    kw = dict(page_size=4, s_max=32, prefill_chunk=8)
    base, eb = run(1, prompts, max_new=14, arrivals=[0, 0, 1],
                   n_pages=8, **kw)
    assert eb.metrics.preemptions > 0, "pool not tight enough to preempt"
    for tp in (2, 4):
        toks, e = run(tp, prompts, max_new=14, arrivals=[0, 0, 1],
                      n_pages=8, **kw)
        assert toks == base, tp
        assert e.metrics.preemptions == eb.metrics.preemptions
    print("preempt", eb.metrics.preemptions)
    """)
    assert out.startswith("preempt")


def test_tp_gqa_fallback_replicated():
    """kv-head counts that don't divide the mesh fall back to replicated
    pool placement (no shard_map, no capacity win) with identical outputs;
    a dividing mesh on the same GQA config shards normally."""
    out = run_with_devices(_PRELUDE + """
    gcfg = cfg.replace(n_kv_heads=2)        # GQA: h=4 query heads, kvh=2
    gparams = T.init_params(gcfg, jax.random.PRNGKey(1))
    prompts = ["the model computes", "a kernel shards"]
    base, eb = run(1, prompts, cfg=gcfg, params=gparams)
    g = eb.pool.cache_bytes()
    # kvh=2 % tp=4 != 0 -> replicated fallback
    toks4, e4 = run(4, prompts, cfg=gcfg, params=gparams)
    assert toks4 == base
    assert not e4.pool.heads_sharded and e4.pool.kv_shards == 1
    assert e4.pool.cache_bytes_per_shard() == g
    # kvh=2 % tp=2 == 0 -> sharded
    toks2, e2 = run(2, prompts, cfg=gcfg, params=gparams)
    assert toks2 == base
    assert e2.pool.heads_sharded and e2.pool.kv_shards == 2
    assert e2.pool.cache_bytes_per_shard() == g // 2
    print("ok")
    """)
    assert out.strip() == "ok"


def test_tp_quantized_artifact_parity():
    """A fused MUXQ artifact (packed weights + kv_calib) serves identically
    under the mesh: weights are replicated inside shard_map, int8 pages
    shard by head."""
    out = run_with_devices(_PRELUDE + """
    from repro.core.muxq import QuantConfig
    from repro.core.policy import SitePolicy
    from repro.data.pipeline import PipelineConfig, TokenPipeline
    from repro.quantize import quantize_model
    spec = QuantConfig(method="muxq", act_granularity="per_token",
                       outlier_mode="static", backend="fused",
                       weight_granularity="per_channel")
    pipe = TokenPipeline(PipelineConfig(seq_len=64, global_batch=2))
    art = quantize_model(cfg, params, [next(pipe) for _ in range(2)],
                         SitePolicy.uniform(spec), pack_target="both")
    prompts = ["the model computes", "a kernel shards"]
    base, _ = run(1, prompts, params=art, kv_mode="int8")
    toks, e = run(2, prompts, params=art, kv_mode="int8")
    assert toks == base
    assert e.pool.kv_shards == 2
    print("ok")
    """)
    assert out.strip() == "ok"


def test_tp_mesh_obs_surface():
    """Mesh shape reaches the metrics registry gauges, the report, and the
    Chrome-trace process metadata."""
    out = run_with_devices(_PRELUDE + """
    from repro.obs.trace import TraceRecorder
    rec = TraceRecorder()
    toks, e = run(2, ["the model computes"], recorder=rec)
    assert e.metrics.registry.value("serve/mesh_devices") == 2.0
    assert e.metrics.registry.value("serve/kv_shards") == 2.0
    rep = e.metrics.report()
    assert rep["kv_shards"] == 2.0
    assert rep["cache_bytes_per_shard"] * 2 == rep["cache_bytes"]
    assert rec.metadata["mesh_devices"] == 2
    import json, tempfile, os
    p = os.path.join(tempfile.mkdtemp(), "t.json")
    rec.export_chrome(p)
    doc = json.load(open(p))
    assert doc["otherData"]["mesh_devices"] == 2
    labels = [ev for ev in doc["traceEvents"]
              if ev.get("name") == "process_labels"]
    assert labels and all("mesh_devices=2" in ev["args"]["labels"]
                          for ev in labels)
    print("ok")
    """)
    assert out.strip() == "ok"


def test_tp_mesh_larger_than_devices_raises():
    out = run_with_devices("""
    from repro.parallel import serve_sharding as SS
    try:
        SS.serve_mesh(64)
    except ValueError as e:
        assert "xla_force_host_platform_device_count" in str(e)
        print("ok")
    """, n=2)
    assert out.strip() == "ok"


# -- head-slice algebra (no mesh needed: pure shape/grid property) ------------

def test_kernel_head_slice_parity():
    """The paged kernels derive kvh (and the GQA group) from array shapes,
    and a stored page keeps each head's lanes in one contiguous run, so
    running the reference on each KV-head shard's contiguous cut of the
    lane axis and concatenating equals the full-width call — the property
    the mesh'd attention path relies on."""
    import jax.numpy as jnp
    import paged_testlib as PT
    from repro.kernels.paged_attention import paged_attention_ref

    b, h, kvh, dh, ps, npages = 2, 8, 4, 16, 8, 6
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, h, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((npages, kvh, ps, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((npages, kvh, ps, dh)), jnp.float32)
    table = jnp.asarray([[0, 2, 4], [1, 3, 5]], jnp.int32)
    pos = jnp.asarray([13, 9], jnp.int32)
    k, v, kw = PT.stored(k, v, {}, table)

    full = paged_attention_ref(q, k, v, table, pos, **kw)
    g = h // kvh
    for shards in (2, 4):
        hl, ll = (kvh // shards) * g, k.shape[-1] // shards
        parts = [paged_attention_ref(
            q[:, i * hl:(i + 1) * hl],
            k[..., i * ll:(i + 1) * ll], v[..., i * ll:(i + 1) * ll],
            table, pos, **kw) for i in range(shards)]
        np.testing.assert_array_equal(np.concatenate(parts, axis=1),
                                      np.asarray(full))


# -- production configs lower through the mesh'd serve path -------------------

@pytest.mark.slow
def test_tp_dryrun_production_archs():
    """qwen1.5-110b / dbrx-132b (kvh=8) lower through the shard_map'd
    pooled decode on a 4-device mesh with per-shard KV bytes == global/4."""
    out = run_with_devices("""
    import json
    from repro.launch.dryrun import lower_paged_cell
    for arch in ("qwen1.5-110b", "dbrx-132b"):
        cell = lower_paged_cell(arch, 4, kv_mode="int8")
        assert cell["lowered"], arch
        assert cell["heads_sharded"] and cell["kv_shards"] == 4, arch
        assert cell["cache_bytes_per_shard"] == cell["cache_bytes"] // 4
        print(json.dumps({k: cell[k] for k in
                          ("arch", "n_kv_heads", "cache_bytes_per_shard")}))
    """)
    assert len(out.strip().splitlines()) == 2
