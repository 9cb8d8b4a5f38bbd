"""Paged KV pool + continuous-batching scheduler subsystem.

Acceptance criteria covered here:
  * scheduler parity — mixed-length request sets produce identical
    ``out_tokens`` under the pooled per-slot-position decode vs
    single-request generation, for fused / fake / fp backends; fp pages are
    additionally bit-exact against the dense-cache decode step and INT8
    pages stay within a stated logits tolerance of fp pages;
  * no-alignment-fallback — with misaligned slot positions the engine
    issues exactly ONE jit'd decode call per step for the whole pool
    (call-count + trace-count test);
plus pool alloc/free/occupancy, preemption-and-resume exactness, streaming
callbacks, capacity truncation, arrival gating and the serve_bench smoke.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.muxq import QuantConfig
from repro.core.policy import SitePolicy
from repro.models import transformer as T
from repro.models.attention import init_cache
from repro.quantize import quantize_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.pool import PagePool

BASE = QuantConfig(method="muxq", outlier_mode="static",
                   act_granularity="per_token",
                   weight_granularity="per_channel", real_int8=True,
                   muxq_form="fused")
FUSED = BASE.replace(backend="fused")

PROMPTS = ["abc", "defg hi", "x"]     # deliberately mixed prompt lengths


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("gpt2-small", reduced=True).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=256,
        vocab_size=120)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batches = [{"tokens": rng.integers(0, cfg.vocab_size, (2, 16))}
               for _ in range(2)]
    return cfg, params, batches


@pytest.fixture(scope="module")
def engines_src(small_model):
    """Per-backend engine constructor args: (params-or-artifact, {})."""
    cfg, params, batches = small_model
    return {
        "fp": params,
        "fake": quantize_model(cfg, params, batches, SitePolicy.uniform(BASE)),
        "fused": quantize_model(cfg, params, batches,
                                SitePolicy.uniform(FUSED)),
    }


# ---------------------------------------------------------------------------
# PagePool
# ---------------------------------------------------------------------------

def test_pool_alloc_free_and_occupancy(small_model):
    cfg, _, _ = small_model
    pool = PagePool(cfg, n_slots=2, s_max=32, page_size=8, mode="int8")
    assert pool.pages_per_slot == 4 and pool.capacity == 32
    assert pool.n_pages == 2 * 4 + 1          # + reserved scratch page
    assert pool.pages_free == 8
    assert pool.admit(0, 9)                   # 2 pages
    assert pool.admit(1, 1)                   # 1 page
    assert pool.pages_in_use == 3
    assert np.all(pool.page_table[0, :2] > 0)  # scratch page 0 never handed out
    assert pool.page_table[1, 0] > 0
    assert pool.ensure(0, 2) and pool.pages_in_use == 4
    assert pool.ensure(0, 2)                  # idempotent, no extra page
    assert pool.pages_in_use == 4
    st = pool.stats({0: 17, 1: 1})
    assert st["pages_in_use"] == 4 and 0 < st["occupancy"] < 1
    assert st["internal_fragmentation"] == pytest.approx(1 - 18 / 32)
    assert pool.release(0) == 3 and pool.pages_in_use == 1
    pool.release(1)
    assert pool.pages_free == 8 and not pool.page_table.any()


def test_pool_exhaustion_and_failure_counters(small_model):
    cfg, _, _ = small_model
    pool = PagePool(cfg, n_slots=2, s_max=32, page_size=8, n_pages=3,
                    mode="fp", dtype=jnp.float32)
    assert pool.admit(0, 16)                  # both usable pages
    assert not pool.admit(1, 8)               # exhausted: nothing allocated
    assert not pool.page_table[1].any()
    assert not pool.ensure(0, 2)
    assert pool.alloc_failures == 2
    with pytest.raises(ValueError, match="pages_per_slot"):
        pool.admit(1, 33)


def test_pool_cache_bytes_int8_vs_fp(small_model):
    cfg, _, _ = small_model
    kw = dict(n_slots=2, s_max=32, page_size=8)
    p8 = PagePool(cfg, mode="int8", **kw)
    p32 = PagePool(cfg, mode="fp", dtype=jnp.float32, **kw)
    dh = cfg.head_dim
    # int8 + f32 per-(pos, head) scales vs 4-byte fp: ~(1 + 4/dh)/4
    assert p8.cache_bytes() == pytest.approx(
        p32.cache_bytes() * (1 + 4 / dh) / 4)


# ---------------------------------------------------------------------------
# Scheduler parity (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["fp", "fake", "fused"])
@pytest.mark.parametrize("kv_mode", ["int8", "fp"])
def test_scheduler_parity_pooled_vs_single(engines_src, small_model,
                                           backend, kv_mode):
    """Mixed-length requests generated together (pooled, misaligned
    positions) produce the same tokens as one-at-a-time generation."""
    cfg, _, _ = small_model
    src = engines_src[backend]
    kw = dict(max_batch=3, s_max=48, kv_mode=kv_mode,
              cache_dtype=jnp.float32)
    eng = ServeEngine(cfg, src, **kw)
    mixed = [Request(p, max_new_tokens=6) for p in PROMPTS]
    eng.generate(mixed)
    assert all(r.done for r in mixed)
    for p, m in zip(PROMPTS, mixed):
        r = Request(p, max_new_tokens=6)
        ServeEngine(cfg, src, **kw).generate([r])
        assert m.out_tokens == r.out_tokens, (backend, kv_mode, p)


def test_fp_pages_bit_exact_vs_dense_decode(small_model):
    """fp pages + fp32 cache dtype: the pooled per-slot-position decode step
    reproduces the dense-cache decode step bit for bit; int8 pages stay
    within 5% relative logits error of it."""
    cfg, params, _ = small_model
    from repro.data import tokenizer as tok
    ids = tok.encode("abcdefghijk")
    s = len(ids)

    # dense reference: prefill then one decode step, at the pool's 2-row
    # width (the row count picks XLA's matmul kernel, and a 1-row program
    # rounds differently in the last ulp); row 0 is the one compared
    cache = init_cache(cfg, 2, 64, dtype=jnp.float32)
    out = T.forward(cfg, params, jnp.tile(jnp.asarray(ids)[None], (2, 1)),
                    cache=cache)
    nxt = int(jnp.argmax(out["logits"][0, -1, : cfg.vocab_size]))
    lg_ref, _ = T.decode_step(cfg, params, jnp.full((2, 1), nxt),
                              out["cache"])
    lg_ref = lg_ref[:1]

    def paged_logits(kv_mode):
        eng = ServeEngine(cfg, params, max_batch=2, s_max=64, page_size=16,
                          kv_mode=kv_mode, cache_dtype=jnp.float32)
        # dense full-prompt prefill (the parity oracle), scattered into
        # pages through the pool's host-side write path
        k, v = out["cache"]["k"][:, 0, :s], out["cache"]["v"][:, 0, :s]
        assert eng.pool.admit(0, s)
        eng.pool.write_prefill(0, k, v)
        assert eng.pool.ensure(0, s // eng.pool.page_size)
        pos = np.zeros(2, np.int32)
        pos[0] = s
        last = np.zeros(2, np.int32)
        last[0] = nxt
        lg, _ = T.decode_step_paged(
            cfg, eng.params, jnp.asarray(last)[:, None], eng.pool.state(),
            eng.pool.table(), jnp.asarray(pos), eng.ctx,
            qparams=eng.qparams)
        return lg[:1]

    lg_fp = paged_logits("fp")
    assert bool(jnp.array_equal(lg_fp, lg_ref)), \
        "fp pages must be bit-exact vs the dense cache path"
    lg_8 = paged_logits("int8")
    rel = float(jnp.linalg.norm(lg_8 - lg_ref) / jnp.linalg.norm(lg_ref))
    assert rel < 0.05, rel


@pytest.mark.parametrize("heads", [4, 8],
                         ids=["two-positions-a-row", "one-position-a-row"])
@pytest.mark.parametrize("step", ["decode", "prefill", "verify"])
def test_fp_page_steps_bit_exact_vs_dense_cache(heads, step):
    """fp pages at both stored-row layouts (``kvq.page_fold``: 4 heads of
    16 make 64-lane rows, two positions to a stored row; 8 heads, one):
    each paged step's logits equal the dense-cache path's bit for bit.
    Decode against ``decode_step`` after a dense prefill written into the
    pages; a prefill chunk and a verify block of 16 rows from position 0,
    over a page budget wider than the rows, against ``forward`` over the
    same rows."""
    cfg = get_config("gpt2-small", reduced=True).replace(
        n_layers=2, d_model=16 * heads, n_heads=heads, n_kv_heads=heads,
        d_ff=256, vocab_size=120)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    seq = np.random.default_rng(0).integers(0, 120, 16).astype(np.int32)
    pool = PagePool(cfg, n_slots=2, s_max=32, page_size=8, mode="fp",
                    dtype=jnp.float32)
    assert pool.admit(0, 16) and pool.admit(1, 16)
    if step == "decode":
        s = 12
        out = T.forward(cfg, params, jnp.tile(jnp.asarray(seq[:s])[None],
                                              (2, 1)),
                        cache=init_cache(cfg, 2, 32, dtype=jnp.float32))
        want, _ = T.decode_step(cfg, params, jnp.full((2, 1), seq[s]),
                                out["cache"])
        pool.write_prefill(0, out["cache"]["k"][:, 0, :s],
                           out["cache"]["v"][:, 0, :s])
        pool.write_prefill(1, out["cache"]["k"][:, 1, :s],
                           out["cache"]["v"][:, 1, :s])
        got, _ = T.decode_step_paged(cfg, params, jnp.full((2, 1), seq[s]),
                                     pool.state(), pool.table(),
                                     jnp.full(2, s, jnp.int32))
    else:
        tokens = jnp.tile(jnp.asarray(seq)[None], (2, 1))
        want = T.forward(cfg, params, tokens)["logits"]
        zero, rows = jnp.zeros(2, jnp.int32), jnp.full(2, 16, jnp.int32)
        if step == "prefill":
            got, _ = T.prefill_chunk_paged(cfg, params, tokens, pool.state(),
                                           pool.table(), zero, zero, rows)
        else:
            got, _ = T.decode_verify_paged(cfg, params, tokens, pool.state(),
                                           pool.table(), zero, rows)
    assert pool.kv["k"].shape[2:] == ((4, 128) if heads == 4 else (8, 128))
    assert bool(jnp.array_equal(got, want))


# ---------------------------------------------------------------------------
# No-alignment-fallback guarantee (acceptance criterion)
# ---------------------------------------------------------------------------

def test_single_jit_decode_call_per_step_misaligned(small_model):
    """Misaligned slot positions: exactly ONE jit'd decode invocation per
    step for the whole pool, with a single trace — no per-slot fallback."""
    cfg, params, _ = small_model
    eng = ServeEngine(cfg, params, max_batch=3, s_max=48)
    calls = []
    real = eng._decode

    def counting(params, tokens, kv, table, pos):
        calls.append(np.asarray(pos).copy())
        return real(params, tokens, kv, table, pos)

    eng._decode = counting
    reqs = [Request(p, max_new_tokens=6) for p in PROMPTS]
    eng.generate(reqs)
    assert all(r.done for r in reqs)
    # one jit'd call per pooled step, total == step count — no extras
    assert len(calls) == eng.metrics.decode_steps
    # the pool really was misaligned while batched: some step carries >= 2
    # distinct live positions (live slots have pos >= 1; parked slots are 0)
    assert any(len({int(p) for p in pos_vec if p > 0}) >= 2
               for pos_vec in calls), "expected misaligned live slots"
    # and the whole run compiled the pooled step exactly once
    assert eng.decode_traces == 1


def test_no_retrace_across_generate_calls(small_model):
    cfg, params, _ = small_model
    eng = ServeEngine(cfg, params, max_batch=2, s_max=48)
    eng.generate([Request("abc", max_new_tokens=3)])
    eng.generate([Request("wxyz", max_new_tokens=4),
                  Request("q", max_new_tokens=2)])
    assert eng.decode_traces == 1
    # pool fully drains between runs
    assert eng.pool.pages_in_use == 0


# ---------------------------------------------------------------------------
# Preemption, streaming, capacity, arrivals
# ---------------------------------------------------------------------------

def test_preemption_evicts_longest_and_resumes_exactly(small_model):
    """With too few pages, the longest sequence is evicted and later
    resumed by replaying prompt + generated tokens — final outputs match
    the uncontended pool bit for bit (K/V replay is per-position exact)."""
    cfg, params, _ = small_model

    def run(n_pages):
        eng = ServeEngine(cfg, params, max_batch=3, s_max=64, page_size=8,
                          n_pages=n_pages, kv_mode="fp",
                          cache_dtype=jnp.float32)
        reqs = [Request("abcdefgh", max_new_tokens=20),
                Request("ij klmno", max_new_tokens=20),
                Request("pq", max_new_tokens=20)]
        eng.generate(reqs)
        return [r.out_tokens for r in reqs], eng.metrics

    toks_big, m_big = run(None)       # ample pool: no preemption
    toks_small, m_small = run(8)      # 7 usable pages across 3 slots
    assert m_big.preemptions == 0
    assert m_small.preemptions >= 1
    assert toks_small == toks_big
    assert m_small.completed == 3


def test_streaming_callback_and_ttft(small_model):
    cfg, params, _ = small_model
    seen = {}
    reqs = [Request(p, max_new_tokens=4,
                    stream=lambda t, p=p: seen.setdefault(p, []).append(t))
            for p in PROMPTS]
    eng = ServeEngine(cfg, params, max_batch=2, s_max=48)
    eng.generate(reqs)
    for r in reqs:
        assert seen[r.prompt] == r.out_tokens
    rep = eng.metrics.report()
    assert len(eng.metrics.ttft_s) == len(reqs)
    assert rep["ttft_ms_mean"] > 0 and rep["tokens_per_sec"] > 0
    assert 0 < rep["pool_occupancy_peak"] <= 1


def test_capacity_truncates_and_finishes(small_model):
    cfg, params, _ = small_model
    eng = ServeEngine(cfg, params, max_batch=1, s_max=16, page_size=8)
    req = Request("abcdefgh", max_new_tokens=1000)   # prompt: 9 ids w/ BOS
    eng.generate([req])
    assert req.done
    # positions 9..15 decoded: 1 prefill token + 7 decode tokens
    assert len(req.out_tokens) == eng.pool.capacity - 9 + 1
    assert eng.pool.pages_in_use == 0


def test_prompt_exceeding_capacity_raises(small_model):
    cfg, params, _ = small_model
    eng = ServeEngine(cfg, params, max_batch=1, s_max=8, page_size=8)
    with pytest.raises(ValueError, match="capacity"):
        eng.generate([Request("a" * 20, max_new_tokens=2)])


def test_oversized_prompt_mid_batch_keeps_engine_usable(small_model):
    """An oversized prompt is rejected pre-flight — before any pool
    allocation — so the (engine-persistent) pool stays clean and the
    engine keeps serving afterwards."""
    cfg, params, _ = small_model
    eng = ServeEngine(cfg, params, max_batch=2, s_max=16, page_size=8)
    ok, bad = Request("abc", max_new_tokens=3), Request("a" * 40)
    with pytest.raises(ValueError, match="capacity"):
        eng.generate([ok, bad])
    assert not ok.out_tokens            # rejected before any work started
    assert eng.pool.pages_in_use == 0
    retry = Request("abc", max_new_tokens=3)
    eng.generate([retry])
    assert retry.done and len(retry.out_tokens) == 3


def test_default_kv_mode_follows_weight_path(engines_src, small_model):
    """kv_mode=None: plain fp params keep a lossless fp cache; quantized
    serving defaults to int8 pages."""
    cfg, _, _ = small_model
    assert ServeEngine(cfg, engines_src["fp"], max_batch=1,
                       s_max=32).pool.mode == "fp"
    assert ServeEngine(cfg, engines_src["fake"], max_batch=1,
                       s_max=32).pool.mode == "int8"


def test_arrivals_length_mismatch_raises(small_model):
    cfg, params, _ = small_model
    eng = ServeEngine(cfg, params, max_batch=2, s_max=32)
    reqs = [Request("ab", max_new_tokens=2) for _ in range(3)]
    with pytest.raises(ValueError, match="arrival"):
        eng.generate(reqs, arrivals=[0])
    assert eng.pool.pages_in_use == 0


def test_arrivals_gate_admission(small_model):
    cfg, params, _ = small_model
    eng = ServeEngine(cfg, params, max_batch=2, s_max=48)
    reqs = [Request("abc", max_new_tokens=3), Request("de", max_new_tokens=3)]
    eng.generate(reqs, arrivals=[0, 6])
    assert all(r.done for r in reqs)
    assert eng.metrics.prefills == 2
    # request 1 finishes (step 2) before request 2 arrives (step 6): the two
    # are never co-resident, so every pooled step carries exactly one slot
    assert eng.metrics.report()["decode_batch_mean"] == 1.0


# ---------------------------------------------------------------------------
# serve_bench smoke (CI fast-gate hook)
# ---------------------------------------------------------------------------

def test_serve_bench_run_mesh_refuses_accelerator(monkeypatch):
    """run_mesh's child runs on forced CPU host devices: from a process on
    an accelerator it refuses and points at the chip smoke instead of
    recording a CPU number under a serve-bench name."""
    from benchmarks.serve_bench import run_mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit, match="chip_smoke.py --four-chips"):
        run_mesh(tp=2)


def test_serve_bench_smoke_case():
    from benchmarks.serve_bench import run_case
    rep = run_case("fp", "int8", smoke=True, n_requests=3, rate=1.0,
                   max_batch=2, s_max=32, page_size=8)
    assert rep["completed"] == 3 and rep["tokens_per_sec"] > 0
    # one compiled executable per page-budget bucket, never per length
    # (the engine is warmed + run over lengths spanning several buckets)
    assert rep["decode_traces"] == len(rep["decode_buckets_seen"])
    # block-sparse decode reads strictly less than the capacity gather
    assert 0 < rep["kv_bytes_read"] < rep["kv_bytes_read_dense"]
    for key in ("ttft_ms_mean", "pool_occupancy_mean", "fragmentation_mean",
                "cache_bytes", "kv_read_savings", "prefix_hits"):
        assert key in rep
