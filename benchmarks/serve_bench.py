"""Serving load generator: continuous-batching engine under Poisson traffic.

Drives ``ServeEngine`` (paged KV pool + pooled per-slot-position decode)
with Poisson request arrivals and mixed prompt/output lengths, across
execution backends (``fused`` packed-kernel / ``fake`` quantize-dequantize /
``fp``) and page modes (``int8`` pages + per-(pos, head) scales, ``int4``
MUXQ'd nibble-packed pages, ``fp`` pages), and emits a machine-readable
``results/BENCH_serve.json``
({case: {tokens_per_sec, ttft_ms_mean, pool occupancy/fragmentation,
preemptions, kv_bytes_read / kv_bytes_read_dense / kv_read_savings,
decode_buckets, prefix sharing stats, ...}}) so serving-throughput AND
decode read-traffic trajectory across PRs can be tracked by CI next to
``BENCH_kernels.json``.  A **long-prompt flood** case compares chunked
prefill (``prefill_chunk``) against the un-chunked whole-prompt baseline
on the same scheduler and workload.  In ``--smoke`` mode the run asserts
the block-sparse page-budget gather read strictly fewer KV bytes than the
old full-capacity gather would have, that no live decode slot stalled
while the flood prefilled (and that chunks really interleaved with
decode), that the short request queued behind the long prompt waited out
at most one chunk per prefill slot of foreign prefill per step — strictly
less than the baseline's whole-prompt wait — that chunked prefill
compiled at most once per (chunk, page) bucket pair (the CI regression
gates for the paged decode + chunked prefill paths), that multi-slot
batching engaged (>= one STEP record shows >= 2 slots' chunks advancing
in ONE traced call), and that the aging picker bounded every prefilling
request's queue age.  A **resume case** preempts a mid-prefill slot
under pool pressure and gates that the replay re-ran ZERO written
chunks (``rerun_chunk_tokens == 0``) with bit-identical fp streams.  The int4 page-mode gates assert
that nibble-packed pages halve both the bytes-per-token and the decode KV
read traffic vs int8 pages (``read_ratio <= 0.55`` over identical decode
trajectories), that a fixed pool byte budget holds ~2x the concurrent
prompts (``live_slots_peak`` ratio >= 1.8), and that one paged decode
step's logits on int4 pages stay within ``INT4_QUALITY_RTOL`` of fp pages.
A **repetitive-text spec case** compares ``spec_mode='ngram'`` against
plain decode on the same workload and gates the deterministic counters:
output token streams bit-identical, acceptance > 0, >= 25% fewer pooled
decode steps, and verify traces bounded by the (k bucket, page bucket)
grid — wall clock is reported for trajectory, never gated.
An **observability case** runs one queued workload with and without a
``repro.obs.trace.TraceRecorder`` and gates that tracing perturbs nothing
(identical token streams and decode-step counts), that the recorded
request lifecycles satisfy the span-ordering invariants, and that the
exported Chrome-trace JSON (``results/TRACE_serve.json``) is well-formed;
the full metrics-registry snapshot rides the bench artifact so
``tools/bench_diff.py`` can gate any of it against the committed baseline.
A **tensor-parallel mesh case** (subprocess, forced host devices) serves
the same fp-page workload at ``tp=1`` and ``tp=2`` and gates the
deterministic counters: streams bit-identical, per-shard pool bytes
exactly half the global bytes, compile counts within the bucket bounds
(decode == page buckets; prefill <= chunk x page bucket grid) at every
mesh size.

CLI:  PYTHONPATH=src python -m benchmarks.serve_bench [--smoke]
"""
from __future__ import annotations

import argparse
import json
import string
import time
from pathlib import Path
from typing import Optional

import numpy as np

RESULTS = Path(__file__).resolve().parent / "results"
JSON_OUT = RESULTS / "BENCH_serve.json"
TRACE_OUT = RESULTS / "TRACE_serve.json"

BACKENDS = ("fused", "fake", "fp")
KV_MODES = ("int8", "int4", "fp")

# smoke gate: one paged decode step's logits on int4 pages vs fp pages
# (identical dense-oracle prefill, same quantized weights) — max abs logit
# error relative to the fp logit magnitude.  Int4 KV is lossy by design;
# this bounds the loss so a packing/redistribution regression can't hide
INT4_QUALITY_RTOL = 0.10


def _model(smoke: bool):
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T

    cfg = get_config("gpt2-small", reduced=True).replace(
        n_layers=2 if smoke else 4, d_model=64 if smoke else 128,
        n_heads=4, n_kv_heads=4, d_ff=256 if smoke else 512, vocab_size=300)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, backend: str, kv_mode: str, *, max_batch: int,
            s_max: int, page_size: int):
    from repro.core.muxq import QuantConfig
    from repro.core.policy import SitePolicy
    from repro.quantize import quantize_model
    from repro.serve.engine import ServeEngine

    kw = dict(max_batch=max_batch, s_max=s_max, page_size=page_size,
              kv_mode=kv_mode)
    if backend == "fp":
        return ServeEngine(cfg, params, **kw)
    base = QuantConfig(method="muxq", outlier_mode="static",
                       act_granularity="per_token",
                       weight_granularity="per_channel", real_int8=True,
                       muxq_form="fused")
    if backend == "fused":
        base = base.replace(backend="fused")
    rng = np.random.default_rng(0)
    batches = [{"tokens": rng.integers(0, cfg.vocab_size, (2, 32))}
               for _ in range(2)]
    art = quantize_model(cfg, params, batches, SitePolicy.uniform(base))
    return ServeEngine(cfg, art, **kw)


def _workload(seed: int, n_requests: int, rate: float,
              prompt_lens=(4, 24), out_lens=(4, 24)):
    """Poisson arrivals (decode-step clock) + mixed prompt/output lengths."""
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    letters = np.asarray(list(string.ascii_lowercase + " "))
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests)).astype(int)
    reqs = []
    for _ in range(n_requests):
        # byte tokenizer: an n-char prompt is n tokens (+BOS)
        n = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
        prompt = "".join(rng.choice(letters, n))
        reqs.append(Request(prompt, max_new_tokens=int(
            rng.integers(out_lens[0], out_lens[1] + 1))))
    return reqs, [int(a) for a in arrivals]


def run_case(backend: str, kv_mode: str, *, smoke: bool = True,
             n_requests: int = 8, rate: float = 0.5, max_batch: int = 4,
             s_max: int = 64, page_size: int = 8, seed: int = 0) -> dict:
    cfg, params = _model(smoke)
    eng = _engine(cfg, params, backend, kv_mode,
                  max_batch=max_batch, s_max=s_max, page_size=page_size)
    return _drive(eng, n_requests, rate, seed)


def _drive(eng, n_requests: int, rate: float, seed: int) -> dict:
    # warm up compiles (prefill chunk/page buckets + decode buckets)
    # outside the timed run, with the same length distribution
    warm, warm_arr = _workload(seed + 1, max(2, n_requests // 4), rate)
    eng.generate(warm, warm_arr)
    reqs, arrivals = _workload(seed, n_requests, rate)
    eng.generate(reqs, arrivals)
    assert all(r.done for r in reqs)
    rep = eng.metrics.report()
    rep["decode_traces"] = eng.decode_traces
    rep["decode_buckets_seen"] = sorted(eng.decode_buckets)  # engine lifetime
    rep["prefill_traces"] = eng.prefill_traces
    rep["prefill_buckets_seen"] = sorted(eng.prefill_buckets)
    return rep


# ---------------------------------------------------------------------------
# Long-prompt flood: chunked prefill vs the un-chunked baseline
# ---------------------------------------------------------------------------

def _flood_workload(s_max: int, gaps: Optional[list] = None):
    """A deterministic long-prompt flood.  Two 'decoder' requests occupy
    two of the three slots decoding; a LONG prompt arrives and takes the
    last free slot, with two shorts queued right behind it (FIFO).  The
    shorts' TTFT clock starts the step the long's prefill starts, so their
    first-token window contains that prefill: the WHOLE prompt at once in
    the un-chunked baseline, but only a couple of chunks when prefill is
    chunked — a decoder slot frees while the long is still mid-prefill and
    shortest-remaining-first lets the first short overtake it at a chunk
    boundary.  ``gaps`` (optional) collects the second decoder's
    inter-token wall-clock gaps — its peak is the decode stall a
    whole-prompt prefill injects between two consecutive tokens."""
    from repro.serve.engine import Request

    long_len = min(s_max - 16, 240)
    stream = None
    if gaps is not None:
        last = []

        def stream(_tok):
            now = time.perf_counter()
            if last:
                gaps.append(now - last[0])
            last[:] = [now]

    reqs = [
        Request("warm a", max_new_tokens=5),            # decoders: arrive 0
        Request("warm bbb", max_new_tokens=9, stream=stream),
        Request("L" * long_len, max_new_tokens=4),      # the flood: arrive 1
        Request("s one", max_new_tokens=5),             # shorts right behind
        Request("s two", max_new_tokens=5),
    ]
    arrivals = [0, 0, 1, 1, 1]
    short_ix = [3, 4]
    return reqs, arrivals, short_ix


def run_flood(*, smoke: bool = True, prefill_chunk: int = 16,
              max_batch: int = 3, s_max: int = 256,
              page_size: int = 8, prefill_slots: int = 2,
              repeats: int = 1) -> dict:
    """Flood runs at a given chunk size; returns the best-of-``repeats``
    metrics report (same warm engine, compiles amortized; best-of damps CI
    scheduling noise) plus per-class TTFT splits — the chunked-vs-unchunked
    comparison the CI smoke asserts on.  Always uses the full-size bench
    model: on the tiny smoke model a whole-prompt prefill is
    call-overhead-dominated and costs about the same as one chunk, which
    would invert the comparison the gate exists to protect.

    The run is traced so the multi-slot gate can read the STEP records
    directly: ``multi_prefill_step_records`` counts steps whose ONE
    batched prefill call advanced >= 2 slots' chunks."""
    del smoke
    from repro.obs.trace import TraceRecorder
    from repro.serve.engine import ServeEngine

    cfg, params = _model(False)
    eng = ServeEngine(cfg, params, max_batch=max_batch, s_max=s_max,
                      page_size=page_size, prefill_chunk=prefill_chunk,
                      prefill_slots=prefill_slots)
    warm, warm_arr, _ = _flood_workload(s_max)          # compile warmup
    eng.generate(warm, warm_arr)
    best = None
    for _ in range(max(1, repeats)):
        gaps: list = []
        reqs, arrivals, short_ix = _flood_workload(s_max, gaps)
        rec = eng.recorder = TraceRecorder()
        eng.generate(reqs, arrivals)
        assert all(r.done for r in reqs)
        rep = eng.metrics.report()
        shorts = [reqs[i] for i in short_ix]
        assert all(r.ttft_s is not None for r in shorts)
        # the headline gate number: the short queued immediately behind
        # the long prompt — the request class chunking exists to protect
        rep["ttft_short_ms"] = 1e3 * shorts[0].ttft_s
        rep["ttft_short_steps"] = shorts[0].ttft_steps
        # deterministic TTFT face: other requests' prompt tokens prefilled
        # between the short's arrival and its first token (chunking bounds
        # this by one chunk per step; the un-chunked baseline pays the
        # whole long prompt)
        rep["ttft_short_wait_tokens"] = shorts[0].ttft_prefill_tokens
        rep["ttft_short_mean_ms"] = (1e3 * sum(r.ttft_s for r in shorts)
                                     / len(shorts))
        rep["ttft_long_ms"] = 1e3 * reqs[2].ttft_s
        rep["decode_gap_ms_max"] = 1e3 * max(gaps) if gaps else 0.0
        rep["prefill_chunk"] = prefill_chunk
        rep["prefill_slots_cfg"] = prefill_slots
        rep["prefill_traces"] = eng.prefill_traces
        rep["prefill_buckets_seen"] = sorted(eng.prefill_buckets)
        rep["multi_prefill_step_records"] = sum(
            1 for e in rec.events if e.get("name") == "STEP"
            and len(e["args"].get("prefill_slots") or ()) >= 2)
        if best is None or rep["ttft_short_ms"] < best["ttft_short_ms"]:
            best = rep
    return best


def run_resume() -> dict:
    """True chunk-boundary resume under pool pressure, on the tiny smoke
    model (the quantities are structural counters, not throughput).  A
    long prompt admits first into a pool one page short of both requests'
    needs; the decoder behind it grows and preempts the long MID-PREFILL.
    The written chunks' pages detach with the queue entry and the replay
    resumes at the chunk boundary, so total ``prefill_chunk_tokens``
    equal the prompts' ids exactly — the same number the uncontended run
    pays — and the fp-page streams stay bit-identical.  Returns both
    reports plus the gate numbers (``rerun_chunk_tokens`` == tokens
    re-prefilled beyond the prompts' ids, ``outputs_equal``)."""
    import jax.numpy as jnp
    from repro.data import tokenizer as tok
    from repro.serve.engine import Request, ServeEngine

    cfg, params = _model(True)

    def drive(n_pages):
        eng = ServeEngine(cfg, params, max_batch=2, s_max=32, page_size=4,
                          n_pages=n_pages, kv_mode="fp",
                          cache_dtype=jnp.float32, prefill_chunk=4,
                          prefix_sharing=False)
        long = Request("z" * 20, max_new_tokens=4)
        dec = Request("abc", max_new_tokens=10)
        eng.generate([long, dec], arrivals=[0, 1])
        return [r.out_tokens for r in (long, dec)], eng.metrics.report()

    base_toks, base = drive(None)
    toks, rep = drive(8)                      # 7 usable pages: one short
    prompt_ids = len(tok.encode("z" * 20)) + len(tok.encode("abc"))
    return {
        "resume/tight": rep,
        "resume/uncontended": base,
        "prompt_ids": prompt_ids,
        "preemptions": rep["preemptions"],
        "prefill_resumes": rep["prefill_resumes"],
        "rerun_chunk_tokens": rep["prefill_chunk_tokens"] - prompt_ids,
        "outputs_equal": toks == base_toks,
    }


# ---------------------------------------------------------------------------
# Self-speculative decoding: n-gram drafts + batched paged verify
# ---------------------------------------------------------------------------

def _spec_workload(max_new: int):
    """Repetitive-text prompts — the workload prompt-lookup drafting
    exists for.  A greedy LM falls into short argmax cycles on text like
    this, so the n-gram proposer's continuations keep agreeing with the
    verify argmax and the acceptance rate stays high."""
    from repro.serve.engine import Request

    prompts = [
        "the pool maps pages the pool maps pages the pool maps pages",
        "a b a b a b a b a b a b a b a b",
        "tick tock tick tock tick tock tick tock tick tock",
        "one two one two one two one two one two one two",
    ]
    return [Request(p, max_new_tokens=max_new) for p in prompts]


def run_spec(*, spec_k: int = 8, max_new: int = 96) -> dict:
    """The spec-decoding comparison: the SAME repetitive workload through
    ``spec_mode='off'`` and ``'ngram'`` engines (fp pages, fp32 cache —
    greedy argmax is bit-deterministic, so acceptance is exact bookkeeping,
    not luck).  Returns both reports plus the gate numbers:

      * ``outputs_equal`` — every request's token stream identical on/off
        (greedy longest-agreeing-prefix acceptance is lossless);
      * ``step_ratio``    — pooled decode steps ngram / off (accepted
        drafts retire several slot tokens per verify step);
      * ``verify_traces`` / ``verify_buckets_seen`` — the k-token verify
        compiles once per (k bucket, page bucket) pair at most.

    Wall clock rides along in each report (``elapsed_s``) for trajectory;
    on this CPU container the step-count ratio is the tracked signal.
    """
    import jax.numpy as jnp
    from repro.serve.engine import ServeEngine

    cfg, params = _model(True)
    out, reps, streams = {}, {}, {}
    for mode in ("off", "ngram"):
        eng = ServeEngine(cfg, params, max_batch=4, s_max=128, page_size=8,
                          kv_mode="fp", cache_dtype=jnp.float32,
                          spec_mode=mode, spec_k=spec_k)
        reqs = _spec_workload(max_new)
        eng.generate(reqs)
        assert all(r.done for r in reqs)
        rep = eng.metrics.report()
        streams[mode] = [list(r.out_tokens) for r in reqs]
        if mode == "ngram":
            rep["verify_traces"] = eng.verify_traces
            rep["verify_buckets_seen"] = sorted(eng.verify_buckets)
        reps[mode] = rep
        out[f"spec/{mode}"] = rep
    out["outputs_equal"] = streams["ngram"] == streams["off"]
    out["step_ratio"] = (reps["ngram"]["decode_steps"]
                         / reps["off"]["decode_steps"])
    out["spec_k"] = spec_k
    return out


# ---------------------------------------------------------------------------
# Int4 KV pages: byte halving, concurrency at fixed pool bytes, quality
# ---------------------------------------------------------------------------

def _muxq_artifact(cfg, params):
    """One calibrated muxq artifact (its ``kv_calib`` section feeds the int4
    pools' outlier redistribution) shared by every kvq-comparison case."""
    from repro.core.muxq import QuantConfig
    from repro.core.policy import SitePolicy
    from repro.quantize import quantize_model

    base = QuantConfig(method="muxq", outlier_mode="static",
                       act_granularity="per_token",
                       weight_granularity="per_channel", real_int8=True,
                       muxq_form="fused")
    rng = np.random.default_rng(0)
    batches = [{"tokens": rng.integers(0, cfg.vocab_size, (2, 32))}
               for _ in range(2)]
    return quantize_model(cfg, params, batches, SitePolicy.uniform(base))


def _drive_no_eos(eng, reqs, arrivals=None) -> dict:
    """Run requests with EOS stopping disabled: every request decodes
    exactly ``max_new_tokens`` steps, so runs that differ ONLY in page mode
    see identical admission/decode trajectories and their byte counters
    compare 1:1.  (Greedy argmax on lossy pages can hit EOS at a different
    step than on fp pages, which would silently change the number of decode
    steps being priced and wash out the per-step byte ratio.)"""
    sched = eng.scheduler()
    sched.eos = -1          # no token id is ever -1
    sched.run(reqs, arrivals)
    assert all(r.done for r in reqs)
    return sched.metrics.report()


def run_kvq(*, seed: int = 0) -> dict:
    """The int4 page-mode comparison: the SAME workload and weights (one
    muxq artifact, ``kv_calib`` attached) through int8 / int4 / fp pools.
    Returns the gate numbers the smoke run asserts on:

      * ``bytes_ratio`` — pool bytes per token position, int4 / int8
        (structural: nibble packing + bf16 scales make it exactly 0.5);
      * ``read_ratio``  — decode ``kv_bytes_read`` int4 / int8 over
        identical trajectories (EOS disabled; the per-step page buckets are
        asserted identical first, so the ratio isolates page bytes);
      * ``conc_ratio``  — ``live_slots_peak`` int4 / int8 at the SAME pool
        page-byte budget: half-size pages mean twice the pages, so twice
        the prompts resident at once;
      * ``quality_rel_int4`` / ``quality_rel_int8`` — one paged decode
        step's logits vs fp pages after an identical dense-oracle prefill
        (max abs error / max abs fp logit).
    """
    import jax.numpy as jnp
    from repro.data import tokenizer as tok
    from repro.models import transformer as T
    from repro.models.attention import init_cache
    from repro.serve.engine import Request, ServeEngine

    cfg, params = _model(True)
    art = _muxq_artifact(cfg, params)
    out = {}

    # -- decode read traffic at equal page COUNTS ---------------------------
    reps = {}
    for mode in KV_MODES:
        eng = ServeEngine(cfg, art, max_batch=4, s_max=64, page_size=8,
                          kv_mode=mode)
        reqs, arrivals = _workload(seed, 8, 0.5)
        reps[mode] = _drive_no_eos(eng, reqs, arrivals)
        out[f"traffic/{mode}"] = reps[mode]
    r8, r4 = reps["int8"], reps["int4"]
    assert r4["decode_buckets"] == r8["decode_buckets"], (
        "int4 vs int8 decode trajectories diverged", r4, r8)
    out["bytes_ratio"] = r4["bytes_per_token"] / r8["bytes_per_token"]
    out["read_ratio"] = r4["kv_bytes_read"] / r8["kv_bytes_read"]

    # -- concurrency at a fixed pool page-byte budget -----------------------
    # prompts sized so each slot lives in exactly 3 pages, admit to release
    # (20 ids + 4 decode tokens = 24 = 3 pages of 8; admission allocates 3,
    # decode never grows): int8 gets 6 usable pages -> 2 resident prompts,
    # int4 the same BYTES as 13 usable pages -> 4 resident.  Distinct
    # prompts + prefix_sharing off keep page accounting exact.
    peaks, budgets = {}, {}
    for mode, n_pages in (("int8", 7), ("int4", 14)):
        eng = ServeEngine(cfg, art, max_batch=8, s_max=32, page_size=8,
                          n_pages=n_pages, kv_mode=mode, prefix_sharing=False)
        budgets[mode] = eng.pool.page_read_bytes() * eng.pool.n_pages
        reqs = [Request(c * 19, max_new_tokens=4) for c in "abcdefgh"]
        rep = _drive_no_eos(eng, reqs, [0] * len(reqs))
        peaks[mode] = rep["live_slots_peak"]
        out[f"concurrency/{mode}"] = rep
    assert budgets["int4"] == budgets["int8"], budgets   # same byte budget
    out["conc_pool_bytes"] = budgets["int8"]
    out["conc_ratio"] = peaks["int4"] / peaks["int8"]

    # -- decode quality vs fp pages -----------------------------------------
    ids = tok.encode("the pool quantizes kv pages")

    def one_step_logits(mode):
        eng = ServeEngine(cfg, art, max_batch=2, s_max=64, page_size=8,
                          kv_mode=mode)
        tokens = jnp.asarray(ids)[None]
        cache = init_cache(cfg, 1, tokens.shape[1], dtype=eng.cache_dtype)
        o = T.forward(cfg, eng.params, tokens, eng.ctx, cache=cache,
                      qparams=eng.qparams)
        nxt = int(jnp.argmax(o["logits"][0, -1, : cfg.vocab_size]))
        assert eng.pool.admit(0, len(ids))
        eng.pool.write_prefill(0, o["cache"]["k"][:, 0], o["cache"]["v"][:, 0])
        assert eng.pool.ensure(0, len(ids) // eng.pool.page_size)
        pos = np.zeros(2, np.int32)
        pos[0] = len(ids)
        last = np.zeros(2, np.int32)
        last[0] = nxt
        lg, _ = T.decode_step_paged(cfg, eng.params,
                                    jnp.asarray(last)[:, None],
                                    eng.pool.state(), eng.pool.table(),
                                    jnp.asarray(pos), eng.ctx,
                                    qparams=eng.qparams)
        return np.asarray(lg[0, -1, : cfg.vocab_size], np.float32)

    lgf = one_step_logits("fp")
    scale = float(np.max(np.abs(lgf))) or 1.0
    for mode in ("int8", "int4"):
        err = float(np.max(np.abs(one_step_logits(mode) - lgf)))
        out[f"quality_rel_{mode}"] = err / scale
    return out


# ---------------------------------------------------------------------------
# Observability: tracing parity + lifecycle invariants
# ---------------------------------------------------------------------------

def run_traced(*, seed: int = 0, trace_out: Optional[Path] = None) -> dict:
    """The observability case: the SAME queued workload (8 requests into 3
    slots, so the run genuinely queues) through a plain engine and one
    carrying a :class:`repro.obs.trace.TraceRecorder`.  Gate numbers:

      * ``outputs_equal`` / ``decode_steps_on == _off`` — recording is
        host-side bookkeeping between traced steps, so turning it on must
        not perturb scheduling by a single step or output token;
      * ``lifecycle_errors`` — every finished request's recorded span
        sequence is well-formed (SUBMITTED <= ADMITTED <= first CHUNK <=
        FIRST_TOKEN <= FINISHED on the step clock, B/E pairing, STEP
        records summing to ``decode_steps``);
      * ``chrome_errors`` — the exported Chrome-trace JSON parses and only
        references declared pids/tids (drop it on ui.perfetto.dev);
      * ``phase_spans`` — at least one complete span per lifecycle phase
        the workload exercised.

    Also returns the full registry snapshot under ``"registry"`` so the
    bench artifact carries the whole metric surface, histograms included.
    """
    from repro.obs.trace import TraceRecorder, chrome_errors, lifecycle_errors
    from repro.serve.engine import ServeEngine

    cfg, params = _model(True)
    streams, steps = {}, {}
    rec = registry = None
    for name in ("off", "on"):
        recorder = TraceRecorder() if name == "on" else None
        eng = ServeEngine(cfg, params, max_batch=3, s_max=64, page_size=8,
                          recorder=recorder)
        reqs, arrivals = _workload(seed, 8, 0.5)
        eng.generate(reqs, arrivals)
        assert all(r.done for r in reqs)
        streams[name] = [list(r.out_tokens) for r in reqs]
        steps[name] = eng.metrics.decode_steps
        if name == "on":
            rec = recorder
            registry = eng.metrics.registry.snapshot()
    phase_spans: dict = {}
    for spans in rec.spans().values():
        for s in spans:
            phase_spans[s["phase"]] = phase_spans.get(s["phase"], 0) + 1
    path = Path(trace_out) if trace_out else TRACE_OUT
    rec.export_chrome(path)
    return {
        "outputs_equal": streams["on"] == streams["off"],
        "decode_steps_off": steps["off"],
        "decode_steps_on": steps["on"],
        "events": len(rec.events),
        "dropped": rec.dropped,
        "phase_spans": phase_spans,
        "lifecycle_errors": lifecycle_errors(rec.events,
                                             decode_steps=steps["on"]),
        "chrome_errors": chrome_errors(path),
        "trace_path": str(path),
        "registry": registry,
    }


# the tp subprocess: same tiny model at tp=1 and tp=N, fixed workload —
# prints one JSON doc.  Runs OUTSIDE this process because the forced
# host-device flag must never leak into the single-device bench runs.
_MESH_CODE = """
import json, time
import jax
from repro.configs import get_config
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine

TP = %d
cfg = get_config("gpt2-small", reduced=True).replace(n_layers=2)
params = T.init_params(cfg, jax.random.PRNGKey(0))
PROMPTS = ["the model computes", "a kernel shards", "the model computes"]

def drive(tp):
    eng = ServeEngine(cfg, params, max_batch=2, s_max=64, page_size=8,
                      prefill_chunk=16, kv_mode="fp", tp=tp)
    reqs = [Request(p, max_new_tokens=12) for p in PROMPTS]
    t0 = time.perf_counter()
    eng.generate(reqs, arrivals=[0, 0, 2])
    dt = time.perf_counter() - t0
    rep = eng.metrics.report()
    return [r.out_tokens for r in reqs], eng, rep, dt

base, _, _, _ = drive(1)
toks, eng, rep, dt = drive(TP)
doc = {
    "streams_match": toks == base,
    "mesh_devices": eng.metrics.registry.value("serve/mesh_devices"),
    "kv_shards": rep["kv_shards"],
    "cache_bytes": rep["cache_bytes"],
    "cache_bytes_per_shard": rep["cache_bytes_per_shard"],
    "tokens_per_sec": rep["tokens_per_sec"],
    "decode_steps": rep["decode_steps"],
    "decode_trace_count": eng.decode_traces,
    "decode_bucket_count": len(eng.decode_buckets),
    "prefill_trace_count": eng.prefill_traces,
    "prefill_chunk_buckets": len({c for c, _ in eng.prefill_buckets}),
    "prefill_page_buckets": len({p for _, p in eng.prefill_buckets}),
    "elapsed_s": dt,
}
print(json.dumps(doc))
"""


def run_mesh(*, tp: int = 2) -> dict:
    """Tensor-parallel smoke: fp-page workload at tp=1 vs tp=N on a forced
    host-device CPU mesh, in a subprocess.  Gates determinism (streams
    bit-identical), the per-shard capacity split (bytes == global/tp) and
    the compile-count invariant; wall clock rides for trajectory only."""
    import os
    import subprocess
    import sys

    import jax
    if jax.default_backend() != "cpu":
        # the child below runs on forced CPU host devices while this
        # process holds the accelerator: its numbers would be CPU numbers
        # under a serve-bench name
        raise SystemExit(
            f"run_mesh measures a forced CPU host-device mesh, but this "
            f"process runs on {jax.default_backend()!r}; serve the "
            f"tensor-parallel path on chips with `python chip_smoke.py "
            f"--four-chips`")

    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={tp}",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    out = subprocess.run([sys.executable, "-c", _MESH_CODE % tp],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    # CI gates — all deterministic counters, never wall clock
    assert rep["streams_match"], "tp streams diverged from single-device"
    assert rep["kv_shards"] == tp and rep["mesh_devices"] == tp, rep
    assert rep["cache_bytes_per_shard"] * tp == rep["cache_bytes"], rep
    assert rep["decode_trace_count"] == rep["decode_bucket_count"], rep
    # the multi-slot prefill trace bound holds at every mesh size: the
    # batched call always runs at the full pool width, so slots never
    # become a compile axis
    assert rep["prefill_trace_count"] <= (
        rep["prefill_chunk_buckets"] * rep["prefill_page_buckets"]), rep
    return rep


def run(emit: bool = True, smoke: bool = True, **kw):
    """benchmarks.run suite hook: (name, us_per_decoded_token, derived)."""
    from benchmarks import common

    rows = []
    for backend in BACKENDS:
        for kv_mode in KV_MODES:
            rep = run_case(backend, kv_mode, smoke=smoke, **kw)
            tps = rep["tokens_per_sec"]
            us = 1e6 / tps if tps else 0.0
            rows.append((f"serve/decode_{backend}_{kv_mode}", us,
                         f"tokens_per_sec={tps:.1f}"
                         f"_occ={rep['pool_occupancy_mean']:.2f}"
                         f"_frag={rep['fragmentation_mean']:.2f}"
                         f"_read_savings={rep['kv_read_savings']:.2f}"))
    if emit:
        common.emit(rows)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config: 2-layer model, 8 requests/case")
    ap.add_argument("--backends", nargs="*", default=list(BACKENDS),
                    choices=list(BACKENDS))
    ap.add_argument("--kv-modes", nargs="*", default=list(KV_MODES),
                    choices=list(KV_MODES))
    ap.add_argument("--n-requests", type=int, default=None)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="Poisson arrival rate (requests per decode step)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=None)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="chunked-prefill token budget for the flood case "
                         "(the baseline run uses one whole-prompt chunk)")
    ap.add_argument("--prefill-slots", type=int, default=2,
                    help="prefilling slots advanced per step in the flood "
                         "case, batched into ONE traced call (the "
                         "multi-slot and anti-starvation gates need >= 2)")
    ap.add_argument("--spec-k", type=int, default=8,
                    help="speculative block width for the repetitive-text "
                         "spec case (1 committed + spec-k - 1 drafted)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=str(JSON_OUT))
    ap.add_argument("--trace-out", default=str(TRACE_OUT),
                    help="where the traced case writes its Chrome-trace/"
                         "Perfetto JSON (uploaded as a CI artifact)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    n_requests = args.n_requests or (8 if args.smoke else 24)
    s_max = args.s_max or (64 if args.smoke else 128)
    print("name,us_per_call,derived")
    from benchmarks import common
    results = {}
    # long-prompt flood first (before the backend sweep fills the process
    # with live engines): chunked prefill vs the un-chunked baseline (one
    # whole-prompt chunk), same scheduler, same workload
    flood_c = run_flood(smoke=args.smoke, page_size=args.page_size,
                        prefill_chunk=args.prefill_chunk,
                        prefill_slots=args.prefill_slots)
    flood_u = run_flood(smoke=args.smoke, page_size=args.page_size,
                        prefill_chunk=256,
                        prefill_slots=args.prefill_slots)
    results["flood/chunked"] = flood_c
    results["flood/unchunked"] = flood_u
    for name, rep in (("chunked", flood_c), ("unchunked", flood_u)):
        common.emit([(f"serve/flood_{name}", rep["ttft_short_ms"] * 1e3,
                      f"ttft_short_ms={rep['ttft_short_ms']:.1f}"
                      f"_chunks={rep['prefill_chunks']}"
                      f"_interleaved={rep['interleaved_steps']}")])
    # with a chunk >= the flood's long prompt the "chunked" run IS the
    # whole-prompt baseline — the comparison gates below would be
    # vacuously equal, so they only engage for a genuinely chunked config
    degenerate = args.prefill_chunk >= 240
    if degenerate:
        print(f"# note: --prefill-chunk {args.prefill_chunk} >= the flood's "
              "240-token prompt; chunked-vs-baseline gates skipped")
    if args.smoke:
        # CI gates for the chunked-prefill tentpole:
        # 1. a live decode slot never stalls longer than one chunk step —
        #    every step with live decode slots ran the pooled decode
        assert flood_c["decode_stall_steps"] == 0, flood_c
        # 2. prefill chunks genuinely interleaved with pooled decode steps
        assert flood_c["interleaved_steps"] > 0, flood_c
        # 3. the short request queued behind the long prompt sees a better
        #    TTFT than under the un-chunked baseline: its first token no
        #    longer waits out the whole long prefill.  Gated on the
        #    deterministic step-clock quantity (prompt tokens prefilled
        #    ahead of it) — wall-clock TTFT is reported for trajectory but
        #    too noisy on shared CI runners to gate a build on
        if not degenerate:
            assert (flood_c["ttft_short_wait_tokens"]
                    < flood_u["ttft_short_wait_tokens"]), (
                flood_c["ttft_short_wait_tokens"],
                flood_u["ttft_short_wait_tokens"])
            #    ... and chunking's per-step budget bounds the wait: at
            #    most one chunk per prefill SLOT of foreign prefill per
            #    step of its window
            assert (flood_c["ttft_short_wait_tokens"]
                    <= args.prefill_chunk * args.prefill_slots
                    * flood_c["ttft_short_steps"]), flood_c
        # 4. chunked prefill compiles per (chunk, page) bucket pair at most
        assert flood_c["prefill_traces"] <= (
            len({c for c, _ in flood_c["prefill_buckets_seen"]})
            * len({p for _, p in flood_c["prefill_buckets_seen"]})), flood_c
        # 5. multi-slot batching engaged: >= one step advanced >= 2 slots'
        #    chunks in ONE traced call — visible both in the metrics
        #    counter and directly in the recorded STEP records
        if args.prefill_slots >= 2:
            assert flood_c["prefill_multi_steps"] >= 1, flood_c
            assert flood_c["multi_prefill_step_records"] >= 1, flood_c
        # 6. aging bound: no prefilling request (the flood prompt included)
        #    waits more than its own chunk count plus a constant past its
        #    arrival — the anti-starvation guarantee, on the step clock
        if not degenerate:
            assert flood_c["prefill_wait_steps_max"] <= (
                -(-240 // args.prefill_chunk) + 12), flood_c
    # true chunk-boundary resume under pool pressure (tiny smoke model;
    # every gated quantity is a deterministic counter)
    resume = run_resume()
    results["resume/compare"] = resume
    common.emit([("serve/resume", 0.0,
                  f"resumes={resume['prefill_resumes']}"
                  f"_preemptions={resume['preemptions']}"
                  f"_rerun_tokens={resume['rerun_chunk_tokens']}"
                  f"_outputs_equal={int(resume['outputs_equal'])}")])
    if args.smoke:
        # CI gates for the true-resume tentpole:
        # 1. the tight pool really preempted a mid-prefill slot and the
        #    replay resumed it instead of restarting it
        assert resume["preemptions"] >= 1, resume
        assert resume["prefill_resumes"] >= 1, resume
        # 2. ZERO written chunks re-ran: total chunk tokens == prompt ids,
        #    exactly what the uncontended run pays
        assert resume["rerun_chunk_tokens"] == 0, resume
        assert (resume["resume/uncontended"]["prefill_chunk_tokens"]
                == resume["prompt_ids"]), resume
        # 3. fp-page streams bit-identical through preempt + resume
        assert resume["outputs_equal"], "resume changed output tokens"
    # self-speculative decoding on repetitive text: n-gram drafts + the
    # batched k-token verify step vs plain one-token decode (always on the
    # tiny smoke model; the step-count ratio is deterministic)
    spec = run_spec(spec_k=args.spec_k)
    results["spec/compare"] = spec
    ng = spec["spec/ngram"]
    common.emit([("serve/spec_ngram", 0.0,
                  f"step_ratio={spec['step_ratio']:.3f}"
                  f"_acceptance={ng['spec_acceptance']:.2f}"
                  f"_saved={ng['decode_steps_saved']}"
                  f"_wall_s={ng['elapsed_s']:.2f}")])
    if args.smoke:
        # CI gates for the self-speculative decoding tentpole:
        # 1. lossless: greedy acceptance reproduces the exact spec-off
        #    token streams (fp pages + fp32 cache → bit-determinism)
        assert spec["outputs_equal"], "spec decoding changed output tokens"
        # 2. drafting engaged and paid off on repetitive text: the
        #    workload finishes in >= 25% fewer pooled decode steps
        assert ng["spec_proposed"] > 0 and ng["spec_accepted"] > 0, ng
        assert ng["spec_acceptance"] > 0, ng
        assert spec["step_ratio"] <= 0.75, spec["step_ratio"]
        # 3. the k-token verify compiles once per (k, page) bucket pair
        #    at most — pow2 bucketing bounds trace count, not workload size
        assert ng["verify_traces"] <= (
            len({k for k, _ in ng["verify_buckets_seen"]})
            * len({p for _, p in ng["verify_buckets_seen"]})), ng
    # int4 page-mode comparison: byte halving, concurrency at fixed pool
    # bytes, decode quality vs fp pages (always on the tiny smoke model —
    # the ratios are structural, not throughput)
    kvq = run_kvq(seed=args.seed)
    results["kvq/compare"] = kvq
    common.emit([("serve/kvq_int4", 0.0,
                  f"read_ratio={kvq['read_ratio']:.3f}"
                  f"_conc_ratio={kvq['conc_ratio']:.2f}"
                  f"_quality_rel={kvq['quality_rel_int4']:.4f}")])
    if args.smoke:
        # CI gates for the int4 KV-page tentpole:
        # 1. nibble packing + bf16 scales halve the page bytes exactly,
        #    and the decode read traffic follows (identical trajectories)
        assert kvq["bytes_ratio"] == 0.5, kvq["bytes_ratio"]
        assert 0 < kvq["read_ratio"] <= 0.55, kvq["read_ratio"]
        # 2. at a fixed pool byte budget, half-size pages hold ~2x the
        #    concurrent prompts
        assert kvq["conc_ratio"] >= 1.8, (kvq["conc_ratio"],
                                          kvq["concurrency/int4"])
        # 3. int4 decode quality stays bounded vs fp pages (int8 must not
        #    be worse than the int4 bound either — it has more bits)
        assert kvq["quality_rel_int4"] <= INT4_QUALITY_RTOL, kvq
        assert kvq["quality_rel_int8"] <= INT4_QUALITY_RTOL, kvq
    # observability: tracing must not perturb the run, and the recorded
    # lifecycle must satisfy the span/ordering invariants (PR 8 gates);
    # the Chrome-trace JSON lands next to the bench artifact for CI upload
    traced = run_traced(seed=args.seed, trace_out=args.trace_out)
    results["obs/registry"] = traced.pop("registry")
    results["obs/trace"] = traced
    common.emit([("serve/traced", 0.0,
                  f"events={traced['events']}"
                  f"_phases={len(traced['phase_spans'])}"
                  f"_outputs_equal={int(traced['outputs_equal'])}")])
    if args.smoke:
        # CI gates for the observability tentpole:
        # 1. tracing on vs off: bit-identical token streams, identical
        #    pooled decode step count (zero perturbation)
        assert traced["outputs_equal"], "tracing changed output tokens"
        assert traced["decode_steps_on"] == traced["decode_steps_off"], traced
        # 2. recorded lifecycles are well-formed on the step clock and the
        #    export parses as a valid Chrome trace
        assert traced["lifecycle_errors"] == [], traced["lifecycle_errors"]
        assert traced["chrome_errors"] == [], traced["chrome_errors"]
        assert traced["dropped"] == 0, traced
        # 3. every phase this queued workload exercises shows >= 1 span
        for phase in ("QUEUED", "PREFILLING", "DECODING"):
            assert traced["phase_spans"].get(phase, 0) > 0, \
                traced["phase_spans"]
    for backend in args.backends:
        for kv_mode in args.kv_modes:
            rep = run_case(backend, kv_mode, smoke=args.smoke,
                           n_requests=n_requests, rate=args.rate,
                           max_batch=args.max_batch, s_max=s_max,
                           page_size=args.page_size, seed=args.seed)
            results[f"serve/{backend}_{kv_mode}"] = rep
            tps = rep["tokens_per_sec"]
            common.emit([(f"serve/decode_{backend}_{kv_mode}",
                          1e6 / tps if tps else 0.0,
                          f"tokens_per_sec={tps:.1f}")])
            if args.smoke:
                # CI gate: short sequences must not pay the slot-capacity
                # read tax — the bucketed gather reads strictly fewer bytes
                assert 0 < rep["kv_bytes_read"] < rep["kv_bytes_read_dense"], (
                    backend, kv_mode, rep["kv_bytes_read"],
                    rep["kv_bytes_read_dense"])
    # tensor-parallel mesh smoke (subprocess: forced host devices must not
    # leak into this process) — deterministic gates live in run_mesh
    mesh = run_mesh(tp=2)
    results["mesh/tp2"] = mesh
    common.emit([("serve/mesh_tp2",
                  1e6 / mesh["tokens_per_sec"]
                  if mesh["tokens_per_sec"] else 0.0,
                  f"kv_shards={mesh['kv_shards']}"
                  f"_per_shard={mesh['cache_bytes_per_shard']}")])
    results["_config"] = {
        "smoke": args.smoke, "n_requests": n_requests, "rate": args.rate,
        "max_batch": args.max_batch, "s_max": s_max,
        "page_size": args.page_size, "prefill_chunk": args.prefill_chunk,
        "prefill_slots": args.prefill_slots,
        "spec_k": args.spec_k, "seed": args.seed,
    }
    out = Path(args.json_out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(results, indent=2, sort_keys=True) + "\n")
    print(f"# wrote {out} ({len(results) - 1} cases)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
