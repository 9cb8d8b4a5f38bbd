"""The join of the scheduler's host spans to the device's step programs
(``bench/hosttrace.py``), on a small recorded trace cut from a traced
chat-bf16 window on a TPU v5e: host spans, the step programs, and the ops
under the ``pool`` scope with their neighbours."""
from __future__ import annotations

import copy
import json
import re
import types

import pytest

import bench_testlib as L
import hosttrace
import run
import tracefile

TRACE = json.loads((L.DATA / "trace_v5e_host.json").read_text())
NEW = ("sched.host_ms_per_call", "device.idle_named_share",
       "pool.copy_ms_per_step", "device.wait_idle_ms_per_call")


def _trace():
    return copy.deepcopy({"spans": TRACE["spans"], "records": TRACE["records"]})


def _window_s(trace):
    ends = [r["start_ns"] + r["dur_ns"] for r in trace["records"]]
    return (max(ends) - min(r["start_ns"] for r in trace["records"])) * 1e-9


# where the harness traced the window (the tests stand a recorded trace in
# for what ``hosttrace.load`` finds there)
TRACE_DIR = "cache/trace/chat-bf16"


def _ctx(trace):
    return types.SimpleNamespace(
        spec={"name": "gpt2-large-bf16"}, trace_dir=TRACE_DIR,
        device=tracefile.reduce(trace["records"], _window_s(trace)))


@pytest.fixture
def loaded(monkeypatch):
    """Readers see the recorded trace as the window's profile."""
    trace = _trace()
    monkeypatch.setattr(hosttrace, "load", lambda path: trace)
    return trace


def _read(name, ctx):
    return run.load_module(L.BENCH / "metrics" / f"{name}.py").read(ctx)


def test_spans_and_programs_line_up_after_the_clock_shift():
    """Each step program pairs with the dispatch and wait spans of its
    kind.  On the profile's raw clocks the program starts before its
    dispatch span opens (the profiler's host and device clocks disagree by
    about 1-2 ms); shifted by ``clock_shift`` every program starts after
    its dispatch opens and ends before its wait closes."""
    trace = _trace()
    calls = hosttrace.step_calls(trace)
    assert sorted({c["program"] for c in calls}) == ["jit_decode",
                                                     "jit_prefill"]
    assert len(calls) == 4
    assert all(c["start"] < c["dispatch"][0] for c in calls)
    shift, lo, hi = hosttrace.clock_shift(trace)
    assert -5_000_000 < lo <= shift <= hi < 0
    for c in calls:
        assert c["dispatch"][0] + shift <= c["start"]
        assert c["wait"][1] + shift >= c["end"]
        assert c["dispatch"][2]["slots"] >= 1
        assert c["dispatch"][2]["page_bucket"] >= 1


def _covered_by_sweep(trace, keep):
    """Idle ns between programs under a span whose name ``keep`` accepts,
    by an edge sweep independent of the module's helpers (spans shifted
    onto the device's clock)."""
    shift = hosttrace.clock_shift(trace)[0]
    named = [(s["start_ns"] + shift, s["start_ns"] + s["dur_ns"] + shift)
             for s in trace["spans"] if keep(s["name"])]
    mods = sorted((r["start_ns"], r["start_ns"] + r["dur_ns"])
                  for r in trace["records"] if r["line"] == tracefile.MODULES)
    total = covered = 0
    for (_, lo), (hi, _) in zip(mods, mods[1:]):
        if hi <= lo:
            continue
        total += hi - lo
        edges = sorted([(max(s, lo), 1) for s, e in named if s < hi and e > lo]
                       + [(min(e, hi), -1) for s, e in named
                          if s < hi and e > lo], key=lambda x: (x[0], -x[1]))
        depth, last = 0, None
        for t, d in edges:
            if depth > 0:
                covered += t - last
            depth += d
            last = t
    return covered, total


def _work(name):
    return name != "sched.iteration" and not name.endswith(".wait")


def _wait(name):
    return name.endswith(".wait")


def test_idle_between_programs_is_named():
    """The named share counts host work only: the waits on a program's
    tokens name the rest of the idle, and no span is counted twice."""
    trace = _trace()
    work, total = _covered_by_sweep(trace, _work)
    waits, _ = _covered_by_sweep(trace, _wait)
    either, _ = _covered_by_sweep(
        trace, lambda n: _work(n) or _wait(n))
    assert total > 0 and work > 0 and waits > 0
    assert either == work + waits <= total
    share = hosttrace.idle_named_share(trace)
    assert share == pytest.approx(100.0 * work / total)
    assert share < 100.0 * either / total


def test_wait_idle_per_call():
    trace = _trace()
    waits, _ = _covered_by_sweep(trace, _wait)
    calls = sum(1 for r in trace["records"] if r["line"] == tracefile.MODULES
                and tracefile._module(r["name"]) in hosttrace.STEP_PROGRAMS)
    assert calls == 4
    assert hosttrace.wait_idle_ms_per_call(trace) == pytest.approx(
        waits * 1e-6 / calls)


def test_a_wait_that_outlasts_an_iteration_keeps_its_program():
    """In a stall the host stands in a wait for seconds: the wait still
    pairs with the program dispatched before it, not with the wait that
    closes nearest the program's end."""
    trace = _trace()
    before = [(c["program"], c["dispatch"][0], c["wait"][0])
              for c in hosttrace.step_calls(trace)]
    wait = min((s for s in trace["spans"] if s["name"] == "sched.decode.wait"),
               key=lambda s: s["start_ns"])
    wait["dur_ns"] += 2_000_000_000
    after = [(c["program"], c["dispatch"][0], c["wait"][0])
             for c in hosttrace.step_calls(trace)]
    assert after == before
    assert hosttrace.clock_shift(trace) is not None


def test_crossed_clock_bounds_read_nothing(monkeypatch):
    """A dispatch span that opens after its program has ended is paired
    with a program no single clock shift can join: the bounds cross, and
    the readers give nothing rather than a number on an impossible
    shift."""
    trace = _trace()
    calls = hosttrace.step_calls(trace)
    first = min(calls, key=lambda c: c["start"])
    for s in trace["spans"]:
        if s["start_ns"] == first["dispatch"][0] and \
                s["name"].endswith(".dispatch"):
            s["start_ns"] = first["end"] + 1_000_000
    assert hosttrace.clock_shift(trace) is None
    assert hosttrace.idle_named_share(trace) is None
    assert hosttrace.wait_idle_ms_per_call(trace) is None
    monkeypatch.setattr(hosttrace, "load", lambda path: trace)
    ctx = _ctx(trace)
    labels = [g[0] for g in ctx.device["idle_gaps"]]
    assert _read("device.idle_named_share", ctx) is None
    assert _read("device.wait_idle_ms_per_call", ctx) is None
    assert [g[0] for g in run.breakdown(ctx)["idle_gaps"]] == labels


def test_gap_labels_name_a_span_after_the_program():
    trace = _trace()
    gaps = tracefile.reduce(trace["records"], _window_s(trace))["idle_gaps"]
    before = [list(g) for g in gaps]
    hosttrace.label_gaps(gaps, trace)
    assert len(gaps) == len(before) > 0
    for (label, sec), (old, old_sec) in zip(gaps, before):
        assert sec == old_sec
        assert re.fullmatch(re.escape(old) + r": sched\.[a-z_.]+", label)
    # the longest: the host still waiting on the decode's tokens, 96 ms
    # after the program ended
    assert max(gaps, key=lambda g: g[1])[0] == \
        "after jit_decode: sched.decode.wait"


def test_host_ms_per_call_is_iterations_less_waits():
    trace = _trace()
    its = [s for s in trace["spans"] if s["name"] == "sched.iteration"]
    assert its
    lo = min(s["start_ns"] for s in its)
    hi = max(s["start_ns"] + s["dur_ns"] for s in its)
    inside = [s for s in trace["spans"]
              if lo <= s["start_ns"] and s["start_ns"] + s["dur_ns"] <= hi]
    waits = sum(s["dur_ns"] for s in inside if s["name"].endswith(".wait"))
    calls = sum(1 for s in inside if s["name"].endswith(".dispatch"))
    want = (sum(s["dur_ns"] for s in its) - waits) * 1e-6 / calls
    assert hosttrace.host_ms_per_call(trace) == pytest.approx(want)


def test_pool_scope_self_time_per_call():
    trace = _trace()
    pool = [r for r in trace["records"] if r["line"] == tracefile.OPS
            and "/pool/" in r["scope"]]
    assert pool and any(tracefile.op_name(r["name"]) == "copy" for r in pool)
    # the pool's ops nest in the layer scan's while, never in each other;
    # the token upload's jit_broadcast_in_dim is not a step call
    calls = sum(1 for r in trace["records"] if r["line"] == tracefile.MODULES
                and not r["name"].startswith("jit_broadcast_in_dim"))
    assert calls == 4
    want = sum(r["dur_ns"] for r in pool) * 1e-6 / calls
    assert hosttrace.pool_ms_per_step(trace["records"]) == pytest.approx(want)


@pytest.mark.parametrize("scope,hit", [
    ("jit(decode)/while/body/closed_call/pool/scatter", True),
    ("jit(prefill)/jit(main)/while/body/pool/select_n", True),
    ("jit(decode)/while/body/dynamic_slice", False),
    ("jit(decode)/while/body/pool", False),      # an op named pool
    ("", False),
])
def test_in_scope(scope, hit):
    assert hosttrace.in_scope(scope) is hit


def test_readers(loaded):
    ctx = _ctx(loaded)
    assert _read("sched.host_ms_per_call", ctx) == pytest.approx(
        hosttrace.host_ms_per_call(loaded))
    assert _read("pool.copy_ms_per_step", ctx) == pytest.approx(
        hosttrace.pool_ms_per_step(loaded["records"]))
    assert _read("device.idle_named_share", ctx) == pytest.approx(
        hosttrace.idle_named_share(loaded))
    assert _read("device.wait_idle_ms_per_call", ctx) == pytest.approx(
        hosttrace.wait_idle_ms_per_call(loaded))
    # the readers only read; the harness names the breakdown's gaps, once
    assert not any(": sched." in g[0] for g in ctx.device["idle_gaps"])
    gaps = run.breakdown(ctx)["idle_gaps"]
    assert all(": sched." in g[0] for g in gaps)
    assert [g[1] for g in gaps] == [g[1] for g in ctx.device["idle_gaps"]]


def test_readers_on_a_program_without_spans_or_scope(monkeypatch):
    """An older program has neither: its readers give nothing, raise
    nothing, and leave the gap labels as they were."""
    trace = _trace()
    trace["spans"] = []
    for r in trace["records"]:
        r.pop("scope", None)
        if r["line"] == tracefile.OPS:
            r["scope"] = ""
    monkeypatch.setattr(hosttrace, "load", lambda path: trace)
    ctx = _ctx(trace)
    labels = [g[0] for g in ctx.device["idle_gaps"]]
    assert all(_read(name, ctx) is None for name in NEW)
    assert [g[0] for g in run.breakdown(ctx)["idle_gaps"]] == labels


def test_a_profile_of_other_programs_is_not_read(loaded):
    """The newest profile counts only if its programs are the window's."""
    ctx = _ctx(loaded)
    ctx.device["modules"]["jit_decode"][1] += 1
    assert all(_read(name, ctx) is None for name in NEW)


def test_only_the_cells_own_profile_is_read(monkeypatch):
    """The profile is looked for only in the directory the harness traced
    the window into (the context's ``trace_dir``): without one nothing is
    read, whatever other cells left."""
    trace = _trace()
    asked = []

    def load(path):
        asked.append(path)
        return trace
    monkeypatch.setattr(hosttrace, "load", load)
    ctx = _ctx(trace)
    assert hosttrace.window(ctx) is trace
    assert asked == [TRACE_DIR]
    ctx.trace_dir = None
    assert hosttrace.window(ctx) is None
    assert all(_read(name, ctx) is None for name in NEW)
    assert len(asked) == 1


def test_no_profile_reads_nothing(tmp_path):
    ctx = _ctx(_trace())
    ctx.trace_dir = tmp_path
    assert hosttrace.window(ctx) is None
    ctx.device["modules"] = {}
    assert hosttrace.window(ctx) is None


def test_load_reads_spans_from_a_profile(tmp_path):
    """A CPU profile of recorder spans: the loader finds them with their
    args (a CPU has no TPU plane, so no device records)."""
    import jax
    from repro.obs.trace import TraceRecorder
    rec = TraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for step in range(2):
            with rec.span("sched.iteration", step=step):
                with rec.span("sched.decode.dispatch", slots=3,
                              page_bucket=4):
                    pass
                with rec.span("sched.decode.wait"):
                    pass
    finally:
        jax.profiler.stop_trace()
    got = hosttrace.load(str(tmp_path))
    names = sorted(s["name"] for s in got["spans"])
    assert names == ["sched.decode.dispatch"] * 2 + ["sched.decode.wait"] * 2 \
        + ["sched.iteration"] * 2
    args = [s["args"] for s in got["spans"] if s["name"].endswith("dispatch")]
    assert args == [{"slots": 3, "page_bucket": 4}] * 2
    assert got["records"] == []
    assert hosttrace.host_ms_per_call(got) is not None


def test_the_new_metrics_are_in_the_benchmark():
    bench = json.loads((L.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert name in entries
        assert (L.BENCH / "metrics" / f"{name}.py").exists()
