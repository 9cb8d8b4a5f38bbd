"""Observability subsystem (PR 8): registry, trace recorder, quality
observers, the ServeMetrics facade, and one end-to-end traced serve run.

The invariants pinned here are the PR's contract:

  * ``ServeMetrics.report()`` keeps every pre-PR8 key (the serve_bench
    JSON schema and CI gates are pinned on them; new keys additive only);
  * the trace recorder's event model satisfies the lifecycle checkers it
    ships (``lifecycle_errors`` / ``chrome_errors``) on both synthetic
    sequences and a real queued engine run, including preemption;
  * tracing off is the shared ``NULL_RECORDER`` no-op, and tracing on
    does not perturb scheduling (same streams, same decode_steps);
  * the quality observer counts saturation/hot-channels the way its
    docstrings promise, on both the activation and KV-pool seams.
"""
import json

import numpy as np
import pytest

from repro.obs.registry import (COUNT_BUCKETS, STEP_BUCKETS, Counter, Gauge,
                                Histogram, MetricsRegistry)
from repro.obs.trace import (NULL_RECORDER, PHASES, SCHED_RID, TraceRecorder,
                             chrome_errors, lifecycle_errors)
from repro.serve.metrics import ServeMetrics

# every report() key that existed before PR 8 — the schema CI and the
# bench artifacts are pinned on; removing any of these is a regression
GOLDEN_PRE_PR8_KEYS = {
    "tokens_out", "tokens_per_sec", "decode_steps", "decode_batch_mean",
    "prefills", "prefill_chunks", "prefill_chunk_tokens",
    "prefill_chunks_per_prompt", "interleaved_steps", "decode_stall_steps",
    "spec_verify_steps", "spec_proposed", "spec_accepted", "spec_acceptance",
    "decode_steps_saved", "preemptions", "submitted", "completed",
    "ttft_ms_mean", "ttft_ms_max", "ttft_steps_mean", "ttft_steps_max",
    "pool_occupancy_mean", "pool_occupancy_peak", "fragmentation_mean",
    "cache_bytes", "live_slots_peak", "kv_mode", "bytes_per_token",
    "kv_bytes_read", "kv_bytes_read_dense", "kv_read_savings",
    "decode_buckets", "prefix_hits", "shared_pages_mapped",
    "pages_shared_peak", "cow_copies", "elapsed_s",
}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("steps")
    c.inc()
    c.inc(4)
    assert reg.value("steps") == 5
    reg.set_value("steps", 7)
    assert reg.counter("steps") is c and c.value == 7
    g = reg.gauge("ratio")
    g.set(0.5)
    assert reg.value("ratio") == 0.5


def test_registry_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    reg.histogram("h")
    with pytest.raises(KeyError):
        reg.value("h")              # histograms have no scalar value
    with pytest.raises(KeyError):
        reg.set_value("h", 1)


def test_histogram_buckets_and_percentiles():
    h = Histogram("h", (1, 2, 4, 8))
    for x in (1, 1, 2, 3, 5):
        h.observe(x)
    assert h.count == 5 and h.min == 1 and h.max == 5
    assert h.counts == [2, 1, 1, 1] and h.overflow == 0
    # p50 resolves to the smallest edge covering half the mass
    assert h.percentile(0.5) == 2
    assert h.percentile(1.0) == 8
    h.observe(100)                  # beyond the last edge
    assert h.overflow == 1
    assert h.percentile(1.0) == 100  # overflow resolves to the exact max
    snap = h.snapshot()
    assert snap["count"] == 6 and snap["overflow"] == 1
    assert snap["buckets"]["4"] == 1


def test_histogram_rejects_bad_edges():
    with pytest.raises(ValueError):
        Histogram("h", ())
    with pytest.raises(ValueError):
        Histogram("h", (1, 1, 2))
    with pytest.raises(ValueError):
        Histogram("h", (4, 2))


def test_default_bucket_tables_are_increasing():
    assert all(b < a for b, a in zip(STEP_BUCKETS, STEP_BUCKETS[1:]))
    assert all(b < a for b, a in zip(COUNT_BUCKETS, COUNT_BUCKETS[1:]))


def test_registry_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("c").inc(3)
    reg.histogram("hist/x", (1, 2)).observe(1)
    snap = reg.snapshot()
    assert snap["c"] == 3
    assert snap["hist/x"]["count"] == 1 and "p95" in snap["hist/x"]
    assert json.dumps(snap)         # JSON-able as-is


# ---------------------------------------------------------------------------
# ServeMetrics facade
# ---------------------------------------------------------------------------

def test_report_keeps_every_pre_pr8_key():
    rep = ServeMetrics().report()
    missing = GOLDEN_PRE_PR8_KEYS - set(rep)
    assert not missing, f"report() lost pre-PR8 keys: {sorted(missing)}"


def test_facade_routes_counters_to_registry():
    m = ServeMetrics()
    m.decode_steps += 3             # the unchanged call-site idiom
    m.tokens_out = 7
    assert m.decode_steps == 3
    assert m.registry.value("decode_steps") == 3
    assert m.registry.snapshot()["tokens_out"] == 7
    m.observe("ttft_steps", 4)
    assert m.percentile("ttft_steps", 1.0) == 4
    assert m.registry.snapshot()["hist/ttft_steps"]["count"] == 1


def test_facade_plain_attrs_stay_plain():
    m = ServeMetrics()
    m.ttft_s.append(0.5)
    m.kv_mode = "int8"
    assert "kv_mode" in m.__dict__ and m.kv_mode == "int8"
    with pytest.raises(AttributeError):
        m.not_a_metric


# ---------------------------------------------------------------------------
# trace recorder
# ---------------------------------------------------------------------------

def test_null_recorder_is_inert():
    assert NULL_RECORDER.enabled is False
    NULL_RECORDER.begin(0, "QUEUED", 0)
    NULL_RECORDER.step_record(0, decode_ran=True)
    assert NULL_RECORDER.events == [] and NULL_RECORDER.dropped == 0


def test_recorder_spans_pair_up():
    rec = TraceRecorder()
    rec.begin(0, "QUEUED", 1)
    rec.end(0, "QUEUED", 3)
    rec.begin(0, "DECODING", 3)
    rec.end(0, "DECODING", 9, tokens=6)
    spans = rec.spans()[0]
    assert [(s["phase"], s["t0"], s["t1"]) for s in spans] == [
        ("QUEUED", 1, 3), ("DECODING", 3, 9)]
    assert spans[1]["args"]["tokens"] == 6


def test_null_recorder_span_is_one_shared_noop():
    first = NULL_RECORDER.span("sched.admit")
    assert NULL_RECORDER.span("sched.iteration", step=3) is first
    assert NULL_RECORDER.span("sched.decode.dispatch", slots=2) is first
    with first:
        with NULL_RECORDER.span("sched.decode.wait"):
            pass
    assert NULL_RECORDER.events == []


def test_recorder_span_pushes_host_begin_end():
    rec = TraceRecorder()
    with rec.span("sched.iteration", step=7):
        with rec.span("sched.decode.dispatch", slots=2, page_bucket=4):
            pass
    evs = [(e["kind"], e["name"], e["step"], e["phase"]) for e in rec.events]
    assert evs == [("B", "sched.iteration", 7, "HOST"),
                   ("B", "sched.decode.dispatch", None, "HOST"),
                   ("E", "sched.decode.dispatch", None, "HOST"),
                   ("E", "sched.iteration", 7, "HOST")]
    assert rec.events[1]["args"] == {"slots": 2, "page_bucket": 4}
    assert rec.spans() == {}            # host spans are not request spans


def test_recorder_span_closes_on_exception():
    rec = TraceRecorder()
    with pytest.raises(KeyError):
        with rec.span("sched.decode.emit"):
            raise KeyError("stream closed")
    assert [e["kind"] for e in rec.events] == ["B", "E"]


def test_recorder_span_lands_in_the_profile(tmp_path):
    """A span opens a TraceAnnotation: a profiler session records it in
    the host plane with its args as stats (the iteration's step as
    ``step_num``)."""
    import jax
    rec = TraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("sched.iteration", step=5):
            with rec.span("sched.prefill.dispatch", slots=2, chunk_bucket=32,
                          page_bucket=8):
                pass
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    found = {ev.name: dict(ev.stats) for plane in data.planes
             if plane.name.startswith("/host") for line in plane.lines
             for ev in line.events if ev.name.startswith("sched.")}
    assert found["sched.iteration"]["step_num"] == 5
    assert found["sched.prefill.dispatch"] == {
        "slots": 2, "chunk_bucket": 32, "page_bucket": 8}


def test_recorder_ring_drops_oldest():
    rec = TraceRecorder(capacity=3)
    for i in range(5):
        rec.instant(0, "SCHED", "STEP", i)
    assert rec.dropped == 2
    assert [e["step"] for e in rec.events] == [2, 3, 4]


def test_recorder_rejects_unknown_phase():
    rec = TraceRecorder()
    with pytest.raises(ValueError):
        rec.begin(0, "TEARDOWN", 0)


def test_export_chrome_well_formed(tmp_path):
    rec = TraceRecorder()
    rec.begin(0, "QUEUED", 0)
    rec.end(0, "QUEUED", 1)
    rec.instant(0, "DECODING", "FIRST_TOKEN", 2, ttft_steps=2)
    rec.step_record(2, decode_ran=True, slots=1)
    rec.compile_event("decode", bucket=4, traces=1)
    path = rec.export_chrome(tmp_path / "t.json")
    assert chrome_errors(path) == []
    doc = json.loads(path.read_text())
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    # pid 0 is the scheduler pseudo-request, requests start at pid 1
    assert {e["pid"] for e in evs} == {0, 1}
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
    assert {"process_name", "thread_name"} <= names
    first = next(e for e in evs if e["name"] == "FIRST_TOKEN")
    assert first["ph"] == "i" and first["s"] == "t"
    assert first["args"]["step"] == 2   # step clock rides args


def test_chrome_errors_flags_unknown_pid(tmp_path):
    bad = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 1, "args": {"name": "r"}},
        {"name": "X", "ph": "i", "pid": 2, "tid": 1, "ts": 0, "args": {}},
    ]}
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(bad))
    assert chrome_errors(p)


# ---------------------------------------------------------------------------
# lifecycle invariants (synthetic sequences)
# ---------------------------------------------------------------------------

def _ev(kind, rid, phase, name, step, **args):
    return {"kind": kind, "rid": rid, "phase": phase, "name": name,
            "step": step, "wall": 0.0, "args": args}


def _well_formed(rid=0):
    return [
        _ev("I", rid, "QUEUED", "SUBMITTED", 0),
        _ev("B", rid, "QUEUED", "QUEUED", 0),
        _ev("E", rid, "QUEUED", "QUEUED", 1),
        _ev("I", rid, "QUEUED", "ADMITTED", 1),
        _ev("B", rid, "PREFILLING", "PREFILLING", 1),
        _ev("I", rid, "PREFILLING", "CHUNK", 1, tokens=8),
        _ev("E", rid, "PREFILLING", "PREFILLING", 2),
        _ev("B", rid, "DECODING", "DECODING", 2),
        _ev("I", rid, "DECODING", "FIRST_TOKEN", 2),
        _ev("I", rid, "DECODING", "FINISHED", 5),
        _ev("E", rid, "DECODING", "DECODING", 5),
    ]


def test_lifecycle_well_formed_passes():
    assert lifecycle_errors(_well_formed()) == []


def test_lifecycle_incomplete_request_skipped():
    # no FINISHED -> no invariants enforced (mid-run snapshot)
    assert lifecycle_errors(_well_formed()[:5]) == []


def test_lifecycle_flags_step_disorder():
    evs = _well_formed()
    evs[3]["step"] = 9              # ADMITTED after FIRST_TOKEN
    assert any("ADMITTED" in e for e in lifecycle_errors(evs))


def test_lifecycle_flags_open_span():
    evs = [e for e in _well_formed() if not
           (e["kind"] == "E" and e["phase"] == "DECODING")]
    assert any("open spans" in e for e in lifecycle_errors(evs))


def test_lifecycle_flags_preempt_without_replay():
    evs = _well_formed()
    evs.insert(9, _ev("I", 0, "DECODING", "PREEMPTED", 4))
    errs = lifecycle_errors(evs)
    assert any("PREEMPTED" in e for e in errs)
    # ... but a replay re-entering PREFILLING satisfies the invariant
    evs_ok = evs[:10] + [
        _ev("E", 0, "DECODING", "DECODING", 4),
        _ev("B", 0, "PREFILLING", "PREFILLING", 6),
        _ev("E", 0, "PREFILLING", "PREFILLING", 7),
        _ev("B", 0, "DECODING", "DECODING", 7),
    ] + evs[10:]
    assert lifecycle_errors(evs_ok) == []


def test_lifecycle_step_record_sum():
    evs = _well_formed()
    evs += [_ev("I", SCHED_RID, "SCHED", "STEP", s, decode_ran=True)
            for s in (2, 3, 4, 5)]
    evs += [_ev("I", SCHED_RID, "SCHED", "STEP", 1, decode_ran=False)]
    assert lifecycle_errors(evs, decode_steps=4) == []
    assert lifecycle_errors(evs, decode_steps=5)


# ---------------------------------------------------------------------------
# quality observer
# ---------------------------------------------------------------------------

def test_observe_activation_counts_saturation():
    from repro.obs.quality import QualityObserver
    obs = QualityObserver(ratio=4.0)
    # per-token abs-max scaling: exactly the row-max elements saturate
    x = np.array([[1.0, 1.0, 1.0, 2.0, 100.0],
                  [1.0, 1.0, 1.0, 50.0, 0.5]], np.float32)
    obs.observe_activation("site", x, qmax=127)
    st = obs.sites["site"]
    assert st.calls == 1 and st.elements == 10
    assert st.amax == 100.0
    assert st.saturated == 2        # one row-max per token row
    # channel amax = [1, 1, 1, 50, 100], median 1: channels 3 and 4 are
    # hot at ratio 4
    assert st.hot_channels == 2
    assert st.outlier_hit_rate == 1.0       # no mask: vacuous hits
    obs.observe_activation("site", x, qmax=127,
                           mask=np.array([False] * 4 + [True]))
    assert obs.sites["site"].hot_hits == 2 + 1   # mask covers only ch 4
    assert json.dumps(obs.snapshot())


def test_quality_observer_hooks_eager_quantctx():
    import jax.numpy as jnp
    from repro.core.context import QuantCtx
    from repro.core.muxq import QuantConfig
    from repro.kernels import dispatch
    from repro.obs.quality import QualityObserver

    ctx = QuantCtx(QuantConfig(method="naive"))
    x = jnp.ones((2, 8), jnp.float32)
    w = jnp.ones((8, 4), jnp.float32)
    obs = QualityObserver()
    prev = dispatch.set_quality_observer(obs)
    try:
        ctx("site", x, w)
        assert obs.sites["site"].calls == 1
        # traced calls must NOT observe (tracers carry no data)
        import jax
        jax.jit(lambda a: ctx("site", a, w))(x)
        assert obs.sites["site"].calls == 1
    finally:
        dispatch.set_quality_observer(prev)
    # uninstalled again: no further accumulation
    ctx("site", x, w)
    assert obs.sites["site"].calls == 1


def test_quality_observer_samples_int8_pool():
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.obs.quality import QualityObserver
    from repro.serve.pool import PagePool

    cfg = get_config("gpt2-small", reduced=True).replace(
        n_layers=2, n_heads=2, n_kv_heads=2, d_model=32)
    pool = PagePool(cfg, n_slots=2, s_max=16, page_size=4, mode="int8")
    kvh, dh = cfg.n_kv_heads, cfg.d_model // cfg.n_heads
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(cfg.n_layers, 8, kvh, dh)), jnp.float32)
    assert pool.admit(0, 8)
    pool.write_prefill(0, k, k)
    obs = QualityObserver(sample_every=4)
    obs.maybe_sample_pool(pool, step=1)      # off-cycle: skipped
    assert obs.pool_samples == 0
    obs.maybe_sample_pool(pool, step=4)
    assert obs.pool_samples == 1
    st = obs.sites["kv/k"]
    assert st.elements > 0 and st.saturated > 0   # abs-max rows pin to 127
    assert st.amax > 0


def test_quality_observer_ignores_fp_pool():
    from repro.configs import get_config
    from repro.obs.quality import QualityObserver
    from repro.serve.pool import PagePool

    cfg = get_config("gpt2-small", reduced=True).replace(
        n_layers=1, n_heads=2, n_kv_heads=2, d_model=32)
    pool = PagePool(cfg, n_slots=1, s_max=8, page_size=4, mode="fp")
    obs = QualityObserver()
    obs.sample_pool(pool)
    assert obs.pool_samples == 0 and obs.sites == {}


# ---------------------------------------------------------------------------
# end-to-end: a queued engine run with the recorder on
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run():
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config("gpt2-small", reduced=True).replace(
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
        vocab_size=300)
    params = T.init_params(cfg, jax.random.PRNGKey(0))

    def drive(recorder):
        eng = ServeEngine(cfg, params, max_batch=2, s_max=32, page_size=4,
                          recorder=recorder)
        # 4 requests into 2 slots: the run genuinely queues
        reqs = [Request(p, max_new_tokens=4)
                for p in ("a b", "c d e", "f", "g h i j")]
        eng.generate(reqs, [0, 0, 1, 2])
        assert all(r.done for r in reqs)
        return eng, reqs

    rec = TraceRecorder()
    eng_on, reqs_on = drive(rec)
    eng_off, reqs_off = drive(None)
    return rec, eng_on, reqs_on, eng_off, reqs_off


def test_traced_run_zero_perturbation(traced_run):
    rec, eng_on, reqs_on, eng_off, reqs_off = traced_run
    assert [r.out_tokens for r in reqs_on] == [r.out_tokens for r in reqs_off]
    for name in ("decode_steps", "prefill_steps", "prefill_chunks",
                 "tokens_out"):
        assert getattr(eng_on.metrics, name) == getattr(eng_off.metrics, name)


def _iterations(events):
    """The host spans of each scheduler iteration: [(step, [child names in
    order])], checking that iterations never nest."""
    out, depth = [], 0
    for e in events:
        if e["phase"] != "HOST":
            continue
        if e["name"] == "sched.iteration":
            depth += 1 if e["kind"] == "B" else -1
            assert depth in (0, 1), "nested iterations"
            if e["kind"] == "B":
                out.append((e["step"], []))
        else:
            assert depth == 1, f"{e['name']} outside an iteration"
            if e["kind"] == "B":
                out[-1][1].append(e["name"])
    assert depth == 0
    return out


PREFILL_SPANS = ["sched.prefill.prepare", "sched.prefill.dispatch",
                 "sched.prefill.wait", "sched.prefill.emit"]
DECODE_SPANS = ["sched.decode.prepare", "sched.decode.dispatch",
                "sched.decode.wait", "sched.decode.emit"]


def test_traced_run_iteration_spans(traced_run):
    """One ``sched.iteration`` per pass of the loop, on the step clock,
    holding admission, then the prefill and decode calls split into
    prepare / dispatch / wait / emit, then the step's accounting."""
    rec, eng_on = traced_run[0], traced_run[1]
    its = _iterations(rec.events)
    assert [step for step, _ in its] == list(range(len(its)))
    for _, names in its:
        assert names[0] == "sched.admit"
        if len(names) == 1:
            continue                    # nothing live: a future arrival
        rest = names[1:]
        if rest[:4] == PREFILL_SPANS:
            rest = rest[4:]
        assert rest[0] == "sched.pages" and rest[-1] == "sched.account"
        assert rest[1:-1] in ([], DECODE_SPANS)
    m = eng_on.metrics
    assert sum(n[1:5] == PREFILL_SPANS for _, n in its) == m.prefill_steps
    assert sum("sched.decode.dispatch" in n for _, n in its) == m.decode_steps
    args = [e["args"] for e in rec.events if e["kind"] == "B"
            and e["name"] == "sched.decode.dispatch"]
    assert all(a["slots"] >= 1 and a["page_bucket"] >= 1 for a in args)


def test_traced_run_lifecycle_invariants(traced_run):
    rec, eng_on, reqs_on, _, _ = traced_run
    errs = lifecycle_errors(rec.events,
                            decode_steps=eng_on.metrics.decode_steps)
    assert errs == [], errs
    phases = {s["phase"] for spans in rec.spans().values() for s in spans}
    assert {"QUEUED", "PREFILLING", "DECODING"} <= phases
    # one FINISHED per request
    fins = [e for e in rec.events if e["name"] == "FINISHED"]
    assert len(fins) == len(reqs_on)


def test_traced_run_stamps_latency_fields(traced_run):
    rec, eng_on, reqs_on, _, _ = traced_run
    for r in reqs_on:
        assert r.queue_wait_steps is not None and r.queue_wait_steps >= 0
        assert r.e2e_steps is not None and r.e2e_steps > 0
        assert r.e2e_steps >= r.queue_wait_steps
    rep = eng_on.metrics.report()
    assert rep["e2e_steps_p95"] >= rep["queue_wait_steps_p50"]
    snap = eng_on.metrics.registry.snapshot()
    assert snap["hist/e2e_steps"]["count"] == len(reqs_on)
    assert snap["hist/queue_wait_steps"]["count"] == len(reqs_on)


def test_traced_run_chrome_export(traced_run, tmp_path):
    rec = traced_run[0]
    path = rec.export_chrome(tmp_path / "serve.json")
    assert chrome_errors(path) == []


def test_traced_run_compile_events(traced_run):
    rec, eng_on = traced_run[0], traced_run[1]
    compiles = [e for e in rec.events if e["name"] == "COMPILE"]
    kinds = {e["args"]["kind"] for e in compiles}
    assert "decode" in kinds and "prefill" in kinds
    n_decode = sum(1 for e in compiles if e["args"]["kind"] == "decode")
    assert n_decode == eng_on.decode_traces


def test_engine_default_recorder_is_null(traced_run):
    eng_off = traced_run[3]
    assert eng_off.recorder is NULL_RECORDER
    assert eng_off.recorder.events == []
