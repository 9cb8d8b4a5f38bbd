"""Block-sparse paged decode + prefix-sharing/copy-on-write pages.

Acceptance criteria covered here:
  * block-sparse parity — the bucketed page-budget gather is BIT-EXACT
    against the old full-capacity gather on fp pages, for the fused / fake
    / fp execution backends (and a sequence of length t gathers only
    ``bucket(ceil(t/ps))`` pages, priced by the bytes-read metric);
  * bucketing never retraces within a bucket — the pooled step compiles
    once per distinct page budget, and a second run over the same length
    range adds no traces;
  * prefix sharing — two requests with a common prompt prefix map the same
    physical pages (fewer pages allocated than two independent requests),
    stay output-identical to unshared runs on fp pages, and copy-on-write
    splits a shared tail page before either sibling writes into it;
    preemption/free with refcounted pages never corrupts the sibling;
  * the Pallas paged-attention decode kernel (interpret mode) matches the
    jnp gather reference on fp, int8 and int4 (nibble-packed + redistributed)
    pages, with sliding windows and logit softcap, under GQA (h > kvh);
    over ragged and idle slots it walks only each slot's live pages (pages
    past them, poisoned with NaN, never reach the output), and the
    scheduler's ``attn_pages_*`` counters price that walk.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.muxq import QuantConfig
from repro.core.policy import SitePolicy
from repro.kernels import paged_attention as PA
from repro.models import transformer as T
from repro.quantize import quantize_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.pool import PagePool

BASE = QuantConfig(method="muxq", outlier_mode="static",
                   act_granularity="per_token",
                   weight_granularity="per_channel", real_int8=True,
                   muxq_form="fused")
FUSED = BASE.replace(backend="fused")


def _dense_prefill(eng, ids):
    """Full-prompt dense prefill (the engine's OLD prefill path, kept here
    as the parity oracle): (next_token, k [L, s, kvh, dh], v)."""
    from repro.models.attention import init_cache
    tokens = jnp.asarray(ids)[None]
    cache = init_cache(eng.cfg, 1, tokens.shape[1], dtype=eng.cache_dtype)
    out = T.forward(eng.cfg, eng.params, tokens, eng.ctx, cache=cache,
                    qparams=eng.qparams)
    nxt = int(jnp.argmax(out["logits"][0, -1, : eng.cfg.vocab_size]))
    return nxt, out["cache"]["k"][:, 0], out["cache"]["v"][:, 0]


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
    cfg, params, batches = small_model
    return {
        "fp": params,
        "fake": quantize_model(cfg, params, batches, SitePolicy.uniform(BASE)),
        "fused": quantize_model(cfg, params, batches,
                                SitePolicy.uniform(FUSED)),
    }


# ---------------------------------------------------------------------------
# Block-sparse gather parity (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["fp", "fake", "fused"])
def test_sparse_gather_bit_exact_vs_full_table(engines_src, small_model,
                                               backend):
    """decode_step_paged over the budget-sliced page table == the same step
    over the full capacity table, bit for bit (fp pages: positions beyond
    the mask underflow to exactly 0 probability either way)."""
    cfg, _, _ = small_model
    from repro.data import tokenizer as tok
    eng = ServeEngine(cfg, engines_src[backend], max_batch=2, s_max=64,
                      page_size=8, kv_mode="fp", cache_dtype=jnp.float32)
    ids = tok.encode("abcdefghijk")          # 12 ids -> 2 pages of 8
    s = len(ids)
    nxt, k, v = _dense_prefill(eng, ids)
    assert eng.pool.admit(0, s)
    eng.pool.write_prefill(0, k, v)
    assert eng.pool.ensure(0, s // eng.pool.page_size)
    pos = np.zeros(2, np.int32)
    pos[0] = s
    last = np.zeros(2, np.int32)
    last[0] = nxt

    def step(table):
        lg, _ = T.decode_step_paged(
            cfg, eng.params, jnp.asarray(last)[:, None], eng.pool.state(),
            table, jnp.asarray(pos), eng.ctx, qparams=eng.qparams)
        return lg

    full = eng.pool.table()                          # [2, 8] capacity table
    budget = eng.pool.bucket_pages(s // eng.pool.page_size + 1)
    assert budget == 2 < eng.pool.pages_per_slot     # genuinely sparse
    lg_full = step(full)
    lg_sparse = step(full[:, :budget])
    assert bool(jnp.array_equal(lg_sparse[:1], lg_full[:1])), backend


def test_decode_reads_only_bucketed_pages(small_model):
    """A short sequence's pooled decode gathers ceil(t/ps) pages (bucketed),
    not pages_per_slot — verified by the bytes-read metric."""
    cfg, params, _ = small_model
    eng = ServeEngine(cfg, params, max_batch=2, s_max=128, page_size=8,
                      kv_mode="fp", cache_dtype=jnp.float32)
    assert eng.pool.pages_per_slot == 16
    req = Request("abcdef", max_new_tokens=4)        # 7 ids + 4 < one page*2
    eng.generate([req])
    m = eng.metrics
    # every step's budget was the 2-page bucket (pos 7..10 -> 1-2 pages)
    assert set(m.decode_buckets) <= {1, 2}
    assert m.kv_bytes_read == sum(
        b * n * eng.pool.n_slots * eng.pool.page_read_bytes()
        for b, n in m.decode_buckets.items())
    # 16-page capacity gather would have read 8x+ more
    assert m.kv_bytes_read * 8 <= m.kv_bytes_read_dense


def test_bucketing_never_retraces_within_bucket(small_model):
    """One compiled executable per page-budget bucket: a run spanning
    several buckets traces once per bucket, and a second run over the same
    lengths adds zero traces."""
    cfg, params, _ = small_model
    eng = ServeEngine(cfg, params, max_batch=2, s_max=64, page_size=8,
                      kv_mode="fp", cache_dtype=jnp.float32)
    # 26 ids + up to 14 new tokens: buckets 4 (pages 4) after pos 24 etc.
    eng.generate([Request("a" * 25, max_new_tokens=14),
                  Request("bc", max_new_tokens=6)])
    buckets_first = set(eng.decode_buckets)
    assert len(buckets_first) >= 2                  # spanned several buckets
    assert eng.decode_traces == len(buckets_first)  # one trace per bucket
    eng.generate([Request("d" * 25, max_new_tokens=14)])
    assert set(eng.decode_buckets) == buckets_first
    assert eng.decode_traces == len(buckets_first)  # no retrace in-bucket


@pytest.mark.parametrize("spec_mode", ["off", "ngram"])
def test_attn_page_counters_match_the_dispatched_steps(small_model,
                                                       spec_mode):
    """``attn_pages_bucket`` / ``attn_pages_live`` equal sums over the
    arrays each prefill, decode and verify call was handed: slots x the
    page table's width, and per slot the pages up to its last query row
    ``(pos + sq - 1) // ps``, capped at the width."""
    cfg, params, _ = small_model
    eng = ServeEngine(cfg, params, max_batch=3, s_max=64, page_size=8,
                      kv_mode="fp", cache_dtype=jnp.float32,
                      prefill_chunk=8, spec_mode=spec_mode, spec_k=4)
    sched = eng.scheduler()
    calls = []                              # (kind, sq, table, first pos)

    def spy(kind, fn):
        def call(toks, state, table, pos, *rest):
            sq = 1 if kind == "decode" else toks.shape[1]
            # copies: on the CPU the uploaded arrays may alias the
            # scheduler's own, which it updates after the step
            calls.append((kind, sq, np.array(table), np.array(pos)))
            return fn(toks, state, table, pos, *rest)
        return call

    sched.prefill = spy("prefill", sched.prefill)
    sched.decode = spy("decode", sched.decode)
    sched.verify = spy("verify", sched.verify)
    sched.run([Request(p, max_new_tokens=12) for p in
               ("abcabcabcabcabcabc", "one two one two", "xy")])
    kinds = {c[0] for c in calls}
    assert {"prefill", "decode"} <= kinds
    assert ("verify" in kinds) == (spec_mode == "ngram")
    ps = eng.pool.page_size
    bucket = live = 0
    for _, sq, table, pos in calls:
        width = table.shape[1]
        bucket += table.shape[0] * width
        live += sum(min(width, (int(p) + sq - 1) // ps + 1) for p in pos)
    m = sched.metrics
    assert m.attn_pages_bucket == bucket
    assert m.attn_pages_live == live
    assert 0 < live < bucket                # idle slots read one page
    rep = m.report()
    assert (rep["attn_pages_bucket"], rep["attn_pages_live"]) == (bucket,
                                                                 live)


# ---------------------------------------------------------------------------
# Prefix sharing + copy-on-write (acceptance criterion)
# ---------------------------------------------------------------------------

def test_pool_share_refcounts_and_release(small_model):
    cfg, _, _ = small_model
    pool = PagePool(cfg, n_slots=3, s_max=32, page_size=8, mode="fp",
                    dtype=jnp.float32)
    assert pool.admit(0, 20)                 # 3 pages
    assert pool.admit(1, 20, share_from=0, shared_pages=2)  # 2 shared + 1
    assert pool.pages_in_use == 4            # 3 + 1 fresh, 2 deduplicated
    assert np.array_equal(pool.page_table[1, :2], pool.page_table[0, :2])
    shared = pool.page_table[0, :2]
    assert np.all(pool.refcount[shared] == 2)
    assert pool.stats()["pages_shared"] == 2
    # releasing the sharer only frees its private page
    assert pool.release(1) == 1
    assert np.all(pool.refcount[shared] == 1)
    assert pool.pages_in_use == 3
    # releasing the original frees the rest
    assert pool.release(0) == 3
    assert pool.pages_free == pool.n_pages - 1


def test_pool_cow_splits_shared_page(small_model):
    cfg, _, _ = small_model
    pool = PagePool(cfg, n_slots=2, s_max=32, page_size=8, mode="fp",
                    dtype=jnp.float32)
    L = pool.kv["k"].shape[0]
    assert pool.admit(0, 8)
    k = jnp.arange(L * 8 * cfg.n_kv_heads * cfg.head_dim, dtype=jnp.float32
                   ).reshape(L, 8, cfg.n_kv_heads, cfg.head_dim)
    pool.write_prefill(0, k, k * 2)
    assert pool.admit(1, 8, share_from=0, shared_pages=1)
    p0 = int(pool.page_table[0, 0])
    assert int(pool.page_table[1, 0]) == p0
    # writable without sharing: no copy
    assert pool.ensure_writable(0, 0) and int(pool.page_table[0, 0]) == p0 \
        if pool.refcount[p0] == 1 else True
    # slot 1 wants to write into the shared page -> copy-on-write
    assert pool.ensure_writable(1, 0)
    p1 = int(pool.page_table[1, 0])
    assert p1 != p0 and pool.cow_count == 1
    assert pool.refcount[p0] == 1 and pool.refcount[p1] == 1
    # the copy carries the page content, and writing it leaves p0 untouched
    np.testing.assert_array_equal(np.asarray(pool.kv["k"][:, p1]),
                                  np.asarray(pool.kv["k"][:, p0]))
    before = np.asarray(pool.kv["k"][:, p0]).copy()
    pool.kv["k"] = pool.kv["k"].at[:, p1].set(-1.0)
    np.testing.assert_array_equal(np.asarray(pool.kv["k"][:, p0]), before)


@pytest.mark.parametrize("kv_mode", ["fp", "int8"])
def test_prefix_share_outputs_identical_and_fewer_pages(small_model, kv_mode):
    """Two requests sharing a prompt prefix: identical outputs to unshared
    serving, fewer pages allocated, COW fires when the shared tail page is
    written."""
    cfg, params, _ = small_model
    prompts = ["abcdefghij", "abcdefghij", "abcdefghij klm"]  # 11/11/15 ids

    def run(prefix_sharing):
        eng = ServeEngine(cfg, params, max_batch=3, s_max=64, page_size=8,
                          kv_mode=kv_mode, cache_dtype=jnp.float32,
                          prefix_sharing=prefix_sharing)
        reqs = [Request(p, max_new_tokens=8) for p in prompts]
        eng.generate(reqs)
        return [r.out_tokens for r in reqs], eng

    toks_shared, eng_s = run(True)
    toks_plain, eng_p = run(False)
    assert toks_shared == toks_plain, kv_mode
    m = eng_s.metrics
    assert m.prefix_hits >= 2 and m.shared_pages_mapped >= 2
    assert m.pages_shared_peak >= 1
    # identical prompts end on a partial page -> the first decode write into
    # the shared tail page must copy-on-write (sibling stays intact, proven
    # by output equality above)
    assert eng_s.pool.cow_count >= 1
    assert eng_p.pool.cow_count == 0
    # sharing allocated strictly fewer fresh pages for the same work
    assert eng_s.pool.alloc_count < eng_p.pool.alloc_count


def test_prefix_share_preemption_keeps_sibling_intact(small_model):
    """Preempting/freeing a slot that shares refcounted pages never corrupts
    the sibling: a page-starved pool (preemptions > 0) still reproduces the
    uncontended pool's outputs bit for bit on fp pages."""
    cfg, params, _ = small_model
    prompts = ["abcdefghijklmnop", "abcdefghijklmnop", "abcdefgh"]

    def run(n_pages):
        eng = ServeEngine(cfg, params, max_batch=3, s_max=64, page_size=8,
                          n_pages=n_pages, kv_mode="fp",
                          cache_dtype=jnp.float32)
        reqs = [Request(p, max_new_tokens=16) for p in prompts]
        eng.generate(reqs)
        return [r.out_tokens for r in reqs], eng

    toks_big, eng_big = run(None)
    assert eng_big.metrics.prefix_hits >= 1      # sharing actually engaged
    toks_small, eng_small = run(8)               # 7 usable pages: contended
    assert eng_small.metrics.preemptions >= 1
    assert toks_small == toks_big
    assert eng_small.metrics.completed == 3
    assert eng_small.pool.pages_in_use == 0      # fully drained, refcounts 0
    assert not eng_small.pool.refcount.any()


def test_share_detection_prefers_longest_prefix(small_model):
    cfg, params, _ = small_model
    eng = ServeEngine(cfg, params, max_batch=3, s_max=64, page_size=4,
                      kv_mode="fp", cache_dtype=jnp.float32)
    sched = eng.scheduler()
    # manufacture two live slots with different stored ids
    from repro.serve.scheduler import _Slot
    ids_a = np.arange(1, 13, dtype=np.int32)         # 12 ids -> 3 pages
    ids_b = np.arange(1, 5, dtype=np.int32)
    assert eng.pool.admit(0, len(ids_a))
    assert eng.pool.admit(1, len(ids_b))
    sched.slots[0] = _Slot(object(), 0.0, ids_a, 0, 0, prefilling=False)
    sched.slots[1] = _Slot(object(), 0.0, ids_b, 0, 1, prefilling=False)
    src, n_share, write_from, pending = sched._shared_prefix(
        np.concatenate([np.arange(1, 11, dtype=np.int32), [99]]))
    assert src == 0 and not pending                   # 10-id prefix beats 4
    assert n_share == 2 and write_from == 8           # whole pages only
    # prompt fully inside the prefix: partial tail page shares too
    src, n_share, write_from, pending = sched._shared_prefix(
        np.arange(1, 11, dtype=np.int32))             # 10 ids, c == len
    assert src == 0 and n_share == 3 and not pending
    assert write_from == 10                           # nothing to prefill
    # a mid-prefill source that has not written the prefix yet is PENDING:
    # admission waits a step instead of recomputing what is being written
    sched.slots[0].prefilling, sched.slots[0].pre_pos = True, 4
    src, n_share, write_from, pending = sched._shared_prefix(
        np.arange(1, 11, dtype=np.int32))
    assert pending and src is None
    eng.pool.release(0)
    eng.pool.release(1)


# ---------------------------------------------------------------------------
# Pallas paged-attention kernel parity (interpret vs ref)
# ---------------------------------------------------------------------------

def _random_paged_case(seed, *, b=3, h=8, kvh=4, dh=16, ps=8, pages=4,
                       mode="fp"):
    """Random paged-attention operands for one page mode.  Returns
    ``(q, k_pages, v_pages, kw, table, pos)`` where ``kw`` carries the
    mode's scale/redistribution operands (h > kvh exercises GQA)."""
    from repro.serve import kvq

    rng = np.random.default_rng(seed)
    n_pages = 1 + b * pages                           # + scratch page 0
    q = jnp.asarray(rng.normal(size=(b, h, dh)).astype(np.float32))
    kw = {}
    if mode == "int8":
        kp = jnp.asarray(rng.integers(-127, 128, (n_pages, kvh, ps, dh)),
                         dtype=jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (n_pages, kvh, ps, dh)),
                         dtype=jnp.int8)
        kw["k_scale"] = jnp.asarray(rng.uniform(1e-3, 2e-2,
                                                (n_pages, kvh, ps, 1))
                                    .astype(np.float32))
        kw["v_scale"] = jnp.asarray(rng.uniform(1e-3, 2e-2,
                                                (n_pages, kvh, ps, 1))
                                    .astype(np.float32))
    elif mode == "int4":
        ki = rng.integers(-7, 8, (n_pages, kvh, ps, dh)).astype(np.int8)
        vi = rng.integers(-7, 8, (n_pages, kvh, ps, dh)).astype(np.int8)
        kp = kvq.pack_int4(jnp.asarray(ki))          # [..., dh//2] nibbles
        vp = kvq.pack_int4(jnp.asarray(vi))
        kw["k_scale"] = jnp.asarray(rng.uniform(1e-3, 2e-2,
                                                (n_pages, kvh, ps, 1))
                                    .astype(np.float32)).astype(jnp.bfloat16)
        kw["v_scale"] = jnp.asarray(rng.uniform(1e-3, 2e-2,
                                                (n_pages, kvh, ps, 1))
                                    .astype(np.float32)).astype(jnp.bfloat16)
        # per-head inverse redistribution rows: a few 2^e channels per head
        mask = rng.random((kvh, dh)) < 0.2
        kw["k_redist"] = jnp.asarray(kvq.redist_from_mask(mask))
        kw["v_redist"] = jnp.asarray(kvq.redist_from_mask(~mask & (
            rng.random((kvh, dh)) < 0.2)))
    else:
        kp = jnp.asarray(rng.normal(size=(n_pages, kvh, ps, dh))
                         .astype(np.float32))
        vp = jnp.asarray(rng.normal(size=(n_pages, kvh, ps, dh))
                         .astype(np.float32))
    # distinct physical pages per slot, scrambled
    table = np.zeros((b, pages), np.int32)
    perm = rng.permutation(np.arange(1, n_pages))
    for i in range(b):
        table[i] = perm[i * pages:(i + 1) * pages]
    pos = jnp.asarray(rng.integers(0, pages * ps, b), dtype=jnp.int32)
    return q, kp, vp, kw, jnp.asarray(table), pos


@pytest.mark.parametrize("mode", ["fp", "int8", "int4"])
@pytest.mark.parametrize("window,softcap", [(None, None), (5, None),
                                            (None, 30.0), (7, 50.0)])
def test_paged_kernel_interpret_matches_ref(mode, window, softcap):
    seed = {"fp": 0, "int8": 1, "int4": 3}[mode]
    q, kp, vp, kw, table, pos = _random_paged_case(seed, mode=mode)
    kw = dict(kw, window=window, softcap=softcap)
    ref = PA.paged_attention_ref(q, kp, vp, table, pos, **kw)
    out = PA.paged_attention_pallas(q, kp, vp, table, pos, interpret=True,
                                    **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _ragged_case(seed, *, sq, h, kvh, mode, pos, ps=8, pages=6, dh=16):
    """Operands of one pooled step whose slots sit at ragged ``pos``: a
    slot at position 0 is idle (zeroed table row: every entry the scratch
    page 0), the others own distinct scrambled pages.  The ``pages``
    bucket is wider than every slot's live range, so each slot has pages
    past its last live one.  Returns ``(q [b, sq, h, dh], k_pages,
    v_pages, kw, table, pos, live slots)``."""
    q, kp, vp, kw, table, _ = _random_paged_case(
        seed, b=len(pos), h=h, kvh=kvh, dh=dh, ps=ps, pages=pages, mode=mode)
    rng = np.random.default_rng(seed + 1)
    q = jnp.asarray(rng.normal(size=(len(pos), sq, h, dh)).astype(np.float32))
    table = np.asarray(table).copy()
    live = [i for i, p in enumerate(pos) if p > 0]
    table[[i for i in range(len(pos)) if i not in live]] = 0
    assert max(p + sq - 1 for p in pos) // ps < pages - 1
    return (q, kp, vp, kw, jnp.asarray(table),
            jnp.asarray(pos, jnp.int32), live)


@pytest.mark.parametrize("mode", ["fp", "int8", "int4"])
@pytest.mark.parametrize("sq", [1, 4])
@pytest.mark.parametrize("h,kvh", [(4, 4), (8, 4)], ids=["mha", "gqa"])
@pytest.mark.parametrize("window,softcap", [(None, None), (6, 30.0)])
def test_paged_kernel_walks_only_live_pages(mode, sq, h, kvh, window,
                                            softcap):
    """Decode (sq=1) and verify (sq=4) blocks over ragged slots: an idle
    slot, one inside its first page, one whose rows cross a page boundary,
    one deep in the bucket — every live slot's rows match the reference,
    which reads the whole bucket."""
    pos = [0, 1, 7, 3 * 8 + 2]
    q, kp, vp, kw, table, pos, live = _ragged_case(
        {"fp": 20, "int8": 21, "int4": 23}[mode] + sq, sq=sq, h=h, kvh=kvh,
        mode=mode, pos=pos)
    kw = dict(kw, window=window, softcap=softcap)
    ref = PA.paged_attention_ref(q, kp, vp, table, pos, **kw)
    out = PA.paged_attention_pallas(q, kp, vp, table, pos, interpret=True,
                                    **kw)
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("mode", ["fp", "int8", "int4"])
def test_pages_past_the_live_range_are_never_read(mode):
    """Every page past a slot's last live page is poisoned with NaN (the
    K/V of fp pages, the scales of int8/int4 codes): the output stays
    finite and equal to the reference on the same pages with those NaNs
    zeroed.  Read, such a page's V would poison the accumulator (a masked
    key's 0 probability times NaN)."""
    ps, sq = 8, 1
    q, kp, vp, kw, table, pos, live = _ragged_case(
        30, sq=sq, h=8, kvh=4, mode=mode, pos=[0, 1, 7, 26], ps=ps)
    last = (np.asarray(pos) + sq - 1) // ps
    dead = np.asarray([int(table[i, j]) for i in live
                       for j in range(last[i] + 1, table.shape[1])])
    names = ("k", "v") if mode == "fp" else ("k_scale", "v_scale")
    arrays = dict(kw, k=kp, v=vp)
    poisoned = {n: arrays[n].at[dead].set(jnp.nan) for n in names}
    zeroed = {n: arrays[n].at[dead].set(0) for n in names}

    def call(fn, a, **extra):
        a = dict(arrays, **a)
        ops = {n: a[n] for n in kw}
        return np.asarray(fn(q, a["k"], a["v"], table, pos, **ops, **extra))

    out = call(PA.paged_attention_pallas, poisoned, interpret=True)
    ref = call(PA.paged_attention_ref, zeroed)
    assert np.isfinite(out[live]).all()
    np.testing.assert_allclose(out[live], ref[live], atol=2e-5, rtol=2e-5)


def test_paged_kernel_respects_page_table_indirection():
    """Swapping two physical pages while swapping the table entries leaves
    the output invariant — the kernel really reads through the table."""
    q, kp, vp, _, table, pos = _random_paged_case(2)
    ref = PA.paged_attention_ref(q, kp, vp, table, pos)
    a, b_ = int(table[0, 0]), int(table[0, 1])
    swap = jnp.asarray([a, b_])
    swapped = jnp.asarray([b_, a])
    kp2 = kp.at[swap].set(kp[swapped])
    vp2 = vp.at[swap].set(vp[swapped])
    table2 = table.at[0, 0].set(b_).at[0, 1].set(a)
    out = PA.paged_attention_pallas(q, kp2, vp2, table2, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_attention_decode_paged_interpret_impl(small_model):
    """The model-level paged decode step under set_paged_impl('interpret')
    (Pallas in-kernel gather + dequant) matches the ref gather within
    float tolerance, fp / int8 / int4 pages (int4 exercises the in-kernel
    nibble unpack + inverse redistribution)."""
    cfg, params, _ = small_model
    from repro.data import tokenizer as tok
    for kv_mode in ("fp", "int8", "int4"):
        eng = ServeEngine(cfg, params, max_batch=2, s_max=32, page_size=8,
                          kv_mode=kv_mode, cache_dtype=jnp.float32)
        ids = tok.encode("abcdefghij")
        nxt, k, v = _dense_prefill(eng, ids)
        assert eng.pool.admit(0, len(ids))
        eng.pool.write_prefill(0, k, v)
        assert eng.pool.ensure(0, len(ids) // eng.pool.page_size)
        pos = np.zeros(2, np.int32)
        pos[0] = len(ids)
        last = np.zeros(2, np.int32)
        last[0] = nxt

        def step():
            lg, _ = T.decode_step_paged(
                cfg, eng.params, jnp.asarray(last)[:, None],
                eng.pool.state(), eng.pool.table(), jnp.asarray(pos),
                eng.ctx, qparams=eng.qparams)
            return np.asarray(lg[:1])

        ref = step()
        prev = PA.set_paged_impl("interpret")
        try:
            out = step()
        finally:
            PA.set_paged_impl(prev)
        np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)
