"""Self-speculative decoding: n-gram drafts + the batched paged verify.

Acceptance criteria covered here:
  * the proposer is pure prompt-lookup — longest recent suffix first, most
    recent earlier occurrence wins, clamped draft length, [] on no match;
  * greedy acceptance (``accept_length``) keeps exactly the longest
    agreeing draft prefix;
  * spec decoding on fp pages at fp32 is BIT-EXACT against both the
    step-by-step dense greedy oracle and the same engine with
    ``spec_mode='off'``, for every request in a mixed workload — including
    under preemption/replay (page-starved pool) and for prefix-shared
    slots (the k-token write COWs every touched shared page first);
  * int8/int4 pages: spec on/off still agree (the verify block writes and
    reads the same per-position-quantized pages a sequential decode
    would), and the run completes with consistent counters;
  * the k-token verify compiles once per (k bucket, page bucket) pair at
    most — never per draft length;
  * repetitive text finishes in strictly fewer pooled decode steps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import tokenizer as tok
from repro.models import transformer as T
from repro.models.attention import init_cache
from repro.serve import spec
from repro.serve.engine import Request, ServeEngine


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("gpt2-small", reduced=True).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=256,
        vocab_size=120)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _dense_reference(cfg, params, prompt, n_new):
    """The pre-paging engine path: full dense prefill + one-token greedy
    decode steps — the bit-exactness oracle spec decoding must reproduce."""
    ids = tok.encode(prompt)
    cache = init_cache(cfg, 1, len(ids) + n_new, dtype=jnp.float32)
    out = T.forward(cfg, params, jnp.asarray(ids)[None], cache=cache)
    toks = [int(jnp.argmax(out["logits"][0, -1, : cfg.vocab_size]))]
    cache = out["cache"]
    for _ in range(n_new - 1):
        lg, cache = T.decode_step(cfg, params, jnp.asarray([[toks[-1]]]),
                                  cache)
        toks.append(int(jnp.argmax(lg[0, -1, : cfg.vocab_size])))
    return toks


def _spec_engine(cfg, params, *, spec_mode="ngram", spec_k=4, **kw):
    base = dict(max_batch=3, s_max=64, page_size=8, kv_mode="fp",
                cache_dtype=jnp.float32)
    base.update(kw)
    return ServeEngine(cfg, params, spec_mode=spec_mode, spec_k=spec_k,
                       **base)


# ---------------------------------------------------------------------------
# Proposer / acceptance units (host-side, no model)
# ---------------------------------------------------------------------------

def test_propose_ngram_prompt_lookup():
    # suffix [7, 8] occurred earlier; the continuation follows it
    assert spec.propose_ngram([7, 8, 9, 1, 7, 8], 3) == [9, 1, 7]
    # draft clamp
    assert spec.propose_ngram([7, 8, 9, 1, 7, 8], 1) == [9]
    # no earlier occurrence of any suffix n-gram -> no draft
    assert spec.propose_ngram([1, 2, 3, 4], 3) == []
    assert spec.propose_ngram([5], 3) == []
    assert spec.propose_ngram([], 3) == []
    assert spec.propose_ngram([1, 2, 1], 0) == []


def test_propose_ngram_most_recent_occurrence_wins():
    # [2] occurs at index 1 (-> 9) and index 3 (-> 4): recency wins
    assert spec.propose_ngram([1, 2, 9, 2, 4, 2], 2) == [4, 2]


def test_propose_ngram_longest_suffix_first():
    # trigram [1, 2, 3] matches (-> 7) even though the unigram [3]
    # also occurs later with a different continuation
    h = [1, 2, 3, 7, 5, 3, 6, 1, 2, 3]
    assert spec.propose_ngram(h, 2, max_ngram=3) == [7, 5]
    # with max_ngram=1 only the unigram is tried: most recent [3] -> 6
    assert spec.propose_ngram(h, 2, max_ngram=1) == [6, 1]


def test_accept_length_longest_agreeing_prefix():
    assert spec.accept_length([], [5]) == 0
    assert spec.accept_length([3, 4], [3, 4, 9]) == 2
    assert spec.accept_length([3, 4], [3, 7, 9]) == 1
    assert spec.accept_length([3, 4], [8, 4, 9]) == 0
    assert spec.accept_length([3, 4, 5], [3, 4]) == 2   # outs exhausted


# ---------------------------------------------------------------------------
# Bit-exactness vs the dense oracle and the spec-off engine (acceptance)
# ---------------------------------------------------------------------------

MIXED = ["abcabcabcabcabc", "the pool maps the pool maps", "xy",
         "one two one two one two"]


@pytest.mark.parametrize("spec_k", [2, 4])
def test_spec_bit_exact_vs_dense_and_off(small_model, spec_k):
    cfg, params = small_model
    n_new = 12
    eng_on = _spec_engine(cfg, params, spec_k=spec_k)
    eng_off = _spec_engine(cfg, params, spec_mode="off")
    on = [Request(p, max_new_tokens=n_new) for p in MIXED]
    off = [Request(p, max_new_tokens=n_new) for p in MIXED]
    eng_on.generate(on)
    eng_off.generate(off)
    for p, a, b in zip(MIXED, on, off):
        ref = _dense_reference(cfg, params, p, n_new)
        assert a.out_tokens == ref, (spec_k, p)
        assert a.out_tokens == b.out_tokens, (spec_k, p)
    # speculation engaged on the repetitive prompts and only ever SAVED
    # steps (never added any: a drafted step replaces a decode step)
    m = eng_on.metrics
    assert m.spec_proposed > 0 and m.spec_accepted > 0
    assert m.decode_steps <= eng_off.metrics.decode_steps
    assert m.decode_steps_saved == m.spec_accepted


def test_spec_bit_exact_under_preemption(small_model):
    """A page-starved pool preempts and replays mid-run; spec decoding on
    fp pages still reproduces the uncontended spec-off outputs exactly
    (draft clamps respect the replayed slot's capacity headroom)."""
    cfg, params = small_model
    prompts = ["abcabcabcabc", "xyzxyzxyzxyz", "mn mn mn"]

    def run(spec_mode, n_pages):
        eng = _spec_engine(cfg, params, spec_mode=spec_mode, n_pages=n_pages)
        reqs = [Request(p, max_new_tokens=16) for p in prompts]
        eng.generate(reqs)
        return [r.out_tokens for r in reqs], eng

    toks_ref, _ = run("off", None)
    toks_spec, eng = run("ngram", 9)          # 8 usable pages: contended
    assert eng.metrics.preemptions >= 1
    assert eng.metrics.spec_accepted > 0
    assert toks_spec == toks_ref
    assert eng.metrics.completed == len(prompts)
    assert eng.pool.pages_in_use == 0


def test_spec_bit_exact_with_prefix_sharing(small_model):
    """Identical prompts share pages; the k-token verify write COWs every
    touched shared page first, so siblings never corrupt each other and
    outputs match the unshared spec-off run bit for bit."""
    cfg, params = small_model
    prompts = ["abcabcabcabcab", "abcabcabcabcab", "abcabcabcabcab"]

    def run(spec_mode, prefix_sharing):
        eng = _spec_engine(cfg, params, spec_mode=spec_mode,
                           prefix_sharing=prefix_sharing)
        reqs = [Request(p, max_new_tokens=14) for p in prompts]
        eng.generate(reqs, arrivals=[0, 1, 2])
        return [r.out_tokens for r in reqs], eng

    toks_ref, _ = run("off", False)
    toks_spec, eng = run("ngram", True)
    assert eng.metrics.prefix_hits >= 1
    assert eng.metrics.spec_accepted > 0
    assert eng.pool.cow_count >= 1            # shared pages split pre-write
    assert toks_spec == toks_ref


@pytest.mark.parametrize("kv_mode", ["int8", "int4"])
def test_spec_quantized_pages_match_spec_off(small_model, kv_mode):
    """Quantized pages: the verify block writes the same per-position
    quantized K/V a sequential decode would and reads the same pages, so
    spec on/off still emit identical streams — and the run completes with
    consistent counters."""
    cfg, params = small_model

    def run(spec_mode):
        eng = ServeEngine(cfg, params, max_batch=2, s_max=64, page_size=8,
                          kv_mode=kv_mode, cache_dtype=jnp.float32,
                          spec_mode=spec_mode, spec_k=4)
        reqs = [Request("abcabcabcabc", max_new_tokens=10),
                Request("zy zy zy zy", max_new_tokens=10)]
        eng.generate(reqs)
        return [r.out_tokens for r in reqs], eng.metrics

    toks_on, m = run("ngram")
    toks_off, _ = run("off")
    assert toks_on == toks_off, kv_mode
    assert all(len(t) == 10 for t in toks_on)
    assert m.completed == 2
    assert 0 <= m.spec_accepted <= m.spec_proposed


# ---------------------------------------------------------------------------
# Bucketed verify compiles (acceptance criterion)
# ---------------------------------------------------------------------------

def test_verify_compiles_per_bucket_pair_not_per_draft_len(small_model):
    cfg, params = small_model
    eng = _spec_engine(cfg, params, spec_k=8, s_max=128)
    # varied prompt lengths/periods -> many distinct draft lengths
    for p in ("ab" * 9, "cde" * 7, "f g " * 6, "hi" * 3, "jklm " * 5):
        eng.generate([Request(p, max_new_tokens=12)])
    assert eng.verify_traces >= 1
    assert eng.verify_traces == len(eng.verify_buckets)
    k_buckets = {k for k, _ in eng.verify_buckets}
    page_buckets = {p for _, p in eng.verify_buckets}
    assert eng.verify_traces <= len(k_buckets) * len(page_buckets)
    assert k_buckets <= {2, 4, 8}            # pow2, clamped to spec_k
    # a second pass over the same workload adds NO traces
    before = eng.verify_traces
    for p in ("ab" * 9, "cde" * 7, "f g " * 6, "hi" * 3, "jklm " * 5):
        eng.generate([Request(p, max_new_tokens=12)])
    assert eng.verify_traces == before


# ---------------------------------------------------------------------------
# Step savings on repetitive text (the point of the whole thing)
# ---------------------------------------------------------------------------

def test_spec_saves_decode_steps_on_repetitive_text(small_model):
    cfg, params = small_model
    prompt = "tick tock tick tock tick tock"
    n_new = 24

    def steps(spec_mode):
        eng = _spec_engine(cfg, params, spec_mode=spec_mode, spec_k=6,
                           s_max=128)
        req = Request(prompt, max_new_tokens=n_new)
        eng.generate([req])
        return req.out_tokens, eng.metrics

    toks_on, m_on = steps("ngram")
    toks_off, m_off = steps("off")
    assert toks_on == toks_off
    assert m_on.spec_accepted > 0
    assert m_on.decode_steps < m_off.decode_steps
    # conservation: past the prefill-sampled first token, every emitted
    # token is either a decode/verify argmax or an accepted draft
    assert m_on.decode_steps + m_on.spec_accepted >= len(toks_on) - 1


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------

def test_spec_config_validation(small_model):
    cfg, params = small_model
    with pytest.raises(ValueError, match="spec_mode"):
        ServeEngine(cfg, params, max_batch=2, s_max=32,
                    spec_mode="medusa")
    with pytest.raises(ValueError, match="spec_k"):
        _spec_engine(cfg, params, spec_k=1).scheduler()


def test_spec_traced_verify_spans(small_model):
    """With a recorder, each speculative step's host work is split like
    decode's: propose, pages, then verify prepare / dispatch / wait / emit
    (the dispatch span carrying slots and buckets); the streams match an
    untraced run."""
    from repro.obs.trace import TraceRecorder
    cfg, params = small_model
    rec = TraceRecorder()
    traced = [Request(p, max_new_tokens=12) for p in MIXED]
    plain = [Request(p, max_new_tokens=12) for p in MIXED]
    _spec_engine(cfg, params, recorder=rec).generate(traced)
    _spec_engine(cfg, params).generate(plain)
    assert [r.out_tokens for r in traced] == [r.out_tokens for r in plain]
    names = [e["name"] for e in rec.events
             if e["phase"] == "HOST" and e["kind"] == "B"]
    verify = ["sched.verify.prepare", "sched.verify.dispatch",
              "sched.verify.wait", "sched.verify.emit"]
    starts = [i for i, n in enumerate(names) if n == "sched.verify.prepare"]
    assert starts
    for i in starts:
        assert names[i:i + 4] == verify
        assert names[i - 2:i] == ["sched.verify.propose", "sched.pages"]
    args = [e["args"] for e in rec.events if e["kind"] == "B"
            and e["name"] == "sched.verify.dispatch"]
    assert all(a["k_bucket"] >= 2 and a["slots"] >= 1 for a in args)
