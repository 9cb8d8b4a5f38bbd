"""Serving benchmark: one cell of BENCHMARK.json on the chip this process holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``BENCHMARK.json`` ``configs[].file``, sizes
plus serving settings) and a traffic mix (``bench/traffic/<name>.json``,
read by the one generator in ``gen.py``).  The run:

  1. set-up: seeded weights on the device (MUXQ: quantized once per
     checkout and cached under ``bench/.cache``), the serving engine, and
     a warm-up that runs every step program the mix can reach
     (``warm.py``);
  2. the window: the mix's whole backlog goes to ``Scheduler.run`` with
     EOS off and every streamed token is stamped.  The window opens once
     the mix's ``open_after_finished`` requests have finished (the slots
     have filled and turn over; serving up to then is set-up).  It runs
     from the end of that step call to the end of the first call whose
     tokens come ``--seconds`` or more later: every token of the calls
     between counts, over all of their time.  The next call's first token
     raises out of the run, which releases every slot and page.  A program
     compiled inside the window is an error: the warm-up missed a step
     program;
  3. with ``--trace 1``, a profiler trace of the window and the scheduler's
     own step records, reduced by ``tracefile.py`` and read by one file per
     per-layer metric in ``bench/metrics/``, beside the window's operations
     and bytes, which ``work.py`` counts from the reference's description
     of the model (``work_model``);
  4. the check: once the window is closed, memory read and the program's
     state freed, a seeded sample of the served requests, with the
     longest, is run through the plain reference (``bench/reference/``) in
     float32, and the gaps by which a served token's reference logit lies
     below the reference's best (the widest and the mean) are held to the
     configuration's limits.  ``--control 1`` judges, in place of the
     served tokens, the ones the reference one precision lower puts first:
     that run has to come out not correct.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``check``).  Without a TPU, or with fewer chips
than the cell asks for, it prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

import gen  # noqa: E402
import hosttrace  # noqa: E402
import tracefile  # noqa: E402
import warm  # noqa: E402
import work  # noqa: E402


class WindowClosed(Exception):
    """Raised from a request's stream at the first token past the window."""


class BenchError(Exception):
    """The run cannot produce a result (no chip, bad cell, short backlog,
    a compile inside the window)."""


# served requests the check judges, and sequences per reference call
SAMPLE_REQUESTS = 32
REFERENCE_ROWS = 4

# ServeMetrics counters the window's per-layer metrics read
COUNTERS = ("decode_steps", "decode_slot_steps", "prefill_steps",
            "prefill_chunk_tokens", "prefills", "completed", "kv_bytes_read",
            "prefix_hits", "cow_copies", "attn_pages_bucket",
            "attn_pages_live")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------

def load_cell(root: Path, name: str):
    """(benchmark, cell, configuration spec, traffic) for workload ``name``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    spec = json.loads((root / conf["file"]).read_text())
    traffic = gen.load(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, spec, traffic


def per_layer_readers(root: Path, bench):
    """(name, unit, reader module) of every per-layer metric; a reader
    that finds nothing to read in a cell returns None and is left out."""
    return [(m["name"], m["unit"],
             load_module(root / "bench" / "metrics" / f"{m['name']}.py"))
            for m in bench["per_layer"]]


def device_info(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    kind = devs[0].device_kind
    if require_tpu and kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs)}, \
        peaks.get(kind)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def check_layout(cfg, params) -> None:
    """The seeded tree must have the program's parameter layout."""
    import jax
    from repro.models import transformer as T
    want = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    shapes = lambda t: jax.tree.map(lambda a: tuple(a.shape), t)  # noqa: E731
    if shapes(want) != shapes(params):
        raise BenchError("seeded weights do not match the program's layout")


def artifact(spec, cfg, ref, cache: Path):
    """The MUXQ artifact of the configuration's seeded weights: quantized
    on the first run in a checkout and saved, loaded on later runs."""
    import jax
    import jax.numpy as jnp
    from repro.core.muxq import QuantConfig
    from repro.core.policy import SitePolicy
    from repro.quantize import QuantArtifact, quantize_model
    seed = spec["weight_seed"]
    path = cache / "artifacts" / f"{spec['name']}-w{seed}"
    t0 = time.perf_counter()
    if (path / "meta.json").exists():
        art = QuantArtifact.load(str(path))
        log(f"artifact loaded from {path} in "
            f"{time.perf_counter() - t0:.3f} s")
    else:
        params = ref.init_weights(spec, seed, jnp.float32)
        check_layout(cfg, params)
        rng = np.random.default_rng(seed)
        calib = [{"tokens": rng.integers(0, 256, (2, 64)).astype(np.int32)}
                 for _ in range(2)]
        policy = SitePolicy.uniform(QuantConfig(**spec["quant"]))
        art = quantize_model(cfg, params, calib, policy, pack_target="fused")
        del params
        t1 = time.perf_counter()
        art.save(str(path))
        log(f"calibrate + quantize_model {t1 - t0:.3f} s, saved in "
            f"{time.perf_counter() - t1:.3f} s")
    return dataclasses.replace(
        art, params=jax.device_put(art.params),
        scan_qparams=jax.device_put(art.scan_qparams))


def build_engine(spec, cfg, ref, recorder, cache: Path):
    import jax.numpy as jnp
    from repro.serve.engine import ServeEngine
    if spec.get("quant"):
        src = artifact(spec, cfg, ref, cache)
    else:
        src = ref.init_weights(spec, spec["weight_seed"], jnp.dtype(spec["dtype"]))
        check_layout(cfg, src)
    return ServeEngine(cfg, src, recorder=recorder, **spec["serve"])


def warm_up(eng, traffic, serve) -> int:
    """Run each warm-up request alone; returns how many ran."""
    from repro.serve.engine import Request
    plan = warm.runs(gen.bounds(traffic), serve)
    rng = np.random.default_rng(0)
    for length, new in plan:
        sched = eng.scheduler()
        sched.eos = -1
        sched.run([Request(gen.text(rng, length - 1), max_new_tokens=new)])
    return len(plan)


class CompileCounter:
    """Programs lowered (a compile or a compile-cache load) and XLA
    compiles, counted from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.lowered = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


def set_up(spec, traffic, trace: bool, cache: Path, root: Path = ROOT):
    """The warmed engine of a configuration, with what its windows need;
    the configuration's reference is ``bench/reference/<name>.py`` under
    ``root``."""
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs.trace import TraceRecorder
    log(f"compile cache: {enable_compile_cache()}")
    compiles = CompileCounter()
    ref = load_module(root / "bench" / "reference" / f"{spec['reference']}.py")
    recorder = TraceRecorder(capacity=1 << 20) if trace else None
    eng = build_engine(spec, ref.model_config(spec), ref, recorder, cache)
    t0 = time.perf_counter()
    n_warm = warm_up(eng, traffic, spec["serve"])
    log(f"warm-up: {n_warm} lone requests in {time.perf_counter() - t0:.3f} s; "
        f"decode buckets {sorted(eng.decode_buckets)}, prefill buckets "
        f"{sorted(eng.prefill_buckets)}")
    return types.SimpleNamespace(eng=eng, ref=ref, recorder=recorder,
                                 compiles=compiles)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def serve_window(s, reqs_spec, seconds: float, open_after: int,
                 trace_dir=None):
    """Serve the backlog; the window opens at the token that finishes the
    ``open_after``-th request and closes with the step call that streams
    the first token ``seconds`` later.  Tokens are labelled by the step
    call that produced them (every call that yields tokens bumps the
    scheduler's prefill or decode step count before streaming them), so
    the window holds whole calls: those after the opening token's, up to
    the closing one.  Returns per-request [(time, token, call)] streams
    (every token served, before the window too), the window's opening
    and closing calls and times, the traced seconds (None untraced), the
    scheduler's counters and step records over the window, the programs
    lowered in it, and the requests finished in all."""
    import jax
    from repro.serve.engine import Request
    sched = s.eng.scheduler()
    sched.eos = -1
    rec = s.recorder
    w = types.SimpleNamespace(t_open=None, call_open=None, deadline=math.inf,
                              t_close=None, call_close=None, finished=0,
                              base=None, n_events=0, lowered=None)

    def call():
        return sched.metrics.prefill_steps + sched.metrics.decode_steps

    gc_s = []

    def on_gc(phase, info):
        """Seconds of each garbage collection inside the window."""
        if phase == "start":
            on_gc.t = time.perf_counter()
        elif w.t_open is not None and w.t_close is None:
            gc_s.append(time.perf_counter() - on_gc.t)

    def open_window():
        if trace_dir is not None:
            jax.profiler.start_trace(str(trace_dir))
        w.base = {k: getattr(sched.metrics, k) for k in COUNTERS}
        w.n_events = len(rec.events) if rec is not None else 0
        w.lowered = s.compiles.lowered
        w.call_open = call()
        w.t_open = time.perf_counter()
        w.deadline = w.t_open + seconds

    def stream_to(out, n_new):
        def stream(token):
            t, c = time.perf_counter(), call()
            if w.call_close is None and t >= w.deadline:
                w.call_close, w.t_close = c, t
            if w.call_close is not None and c > w.call_close:
                raise WindowClosed
            out.append((t, int(token), c))
            if len(out) == n_new:
                w.finished += 1
            if w.t_open is None and w.finished >= open_after:
                open_window()
        return stream

    streams = [[] for _ in reqs_spec]
    reqs = [Request(r["prompt"], max_new_tokens=r["max_new"],
                    stream=stream_to(streams[i], r["max_new"]))
            for i, r in enumerate(reqs_spec)]
    gc.collect()
    if open_after <= 0:
        open_window()
    traced = None
    gc.callbacks.append(on_gc)
    try:
        sched.run(reqs)
    except WindowClosed:
        pass
    else:
        raise BenchError("the backlog ran out before the window closed")
    finally:
        gc.callbacks.remove(on_gc)
        if trace_dir is not None and w.t_open is not None:
            traced = time.perf_counter() - w.t_open
            jax.profiler.stop_trace()
    counters = {k: getattr(sched.metrics, k) - w.base[k] for k in COUNTERS}
    events = rec.events[w.n_events:] if rec is not None else []
    if rec is not None and rec.dropped:
        raise BenchError("the scheduler's step records overflowed")
    return types.SimpleNamespace(
        streams=streams, t_open=w.t_open, call_open=w.call_open,
        call_close=w.call_close, window_s=w.t_close - w.t_open,
        traced_s=traced, counters=counters, gc_s=gc_s,
        events=events, lowered=s.compiles.lowered - w.lowered,
        finished=w.finished)


def in_window(w):
    """Each request's tokens streamed inside window ``w`` (a
    :func:`serve_window` result), with their index in the request's
    output."""
    return [[(k, t, tok) for k, (t, tok, c) in enumerate(st)
             if w.call_open < c <= w.call_close]
            for st in w.streams]


def call_gaps(w):
    """Seconds between the first tokens of consecutive step calls, from
    the opening one to the closing one: a long gap is a stall."""
    first = {}
    for st in w.streams:
        for t, _, c in st:
            if w.call_open <= c <= w.call_close:
                first[c] = min(t, first.get(c, t))
    return np.diff(sorted(first.values()))


def itl_p95_ms(window) -> float:
    gaps = [b[1] - a[1] for s in window for a, b in zip(s, s[1:])]
    return float(np.percentile(gaps, 95)) * 1e3


# ---------------------------------------------------------------------------
# the check against the plain reference
# ---------------------------------------------------------------------------

def sample(streams, reqs_spec, n: int, seed: int):
    """Indices of a seeded sample of served requests, finished ones first,
    always with the one holding the longest served context."""
    served = [i for i, s in enumerate(streams) if s]
    if not served:
        return []
    key = lambda i: reqs_spec[i]["prompt_tokens"] + len(streams[i])  # noqa
    longest = max(served, key=key)
    done = [i for i in served if len(streams[i]) == reqs_spec[i]["max_new"]]
    pool = [i for i in (done if len(done) >= n else served) if i != longest]
    rng = np.random.default_rng([seed, 1])
    k = min(n - 1, len(pool))
    return [longest] + sorted(int(i) for i in rng.choice(pool, k, replace=False))


def sequences(spec, streams, reqs_spec, picked):
    """(token ids, prompt length) per picked request: BOS, the prompt's
    bytes, then the tokens the program streamed."""
    out = []
    for i in picked:
        ids = [spec["bos_token_id"]] + list(reqs_spec[i]["prompt"].encode())
        out.append((np.asarray(ids + [tok for _, tok, _ in streams[i]], np.int32),
                    len(ids)))
    return out


class Reference:
    """The plain reference over the configuration's seeded float32 weights,
    judging served sequences in fixed ``[REFERENCE_ROWS, t_pad]`` blocks
    (one compiled program for every call)."""

    def __init__(self, spec, ref, traffic):
        import jax.numpy as jnp
        self.spec, self.ref = spec, ref
        b = gen.bounds(traffic)
        self.t_pad = -(-(b["prompt_max"] + b["output_max"]) // 128) * 128
        self.params = ref.init_weights(spec, spec["weight_seed"], jnp.float32)
        self._fns = {}

    def _fn(self, bits):
        """jit: (params, tokens [b, T]) -> gaps [b, T - 1]: how far the
        reference logit of the token at p + 1 lies below the reference's
        best at p.  With ``bits``, the token judged at each position is the
        one the control (the reference at that precision) puts first."""
        import jax
        import jax.numpy as jnp
        if bits not in self._fns:
            spec, ref = self.spec, self.ref

            def f(params, tokens):
                logits = ref.forward(spec, params, tokens)[:, :-1]
                if bits is None:
                    pick = tokens[:, 1:]
                else:
                    low = ref.forward(spec, params, tokens, bits)[:, :-1]
                    pick = jnp.argmax(low, -1)
                got = jnp.take_along_axis(logits, pick[..., None], -1)[..., 0]
                return jnp.max(logits, -1) - got
            self._fns[bits] = jax.jit(f)
        return self._fns[bits]

    def gaps(self, seqs, bits=None):
        """One array per sequence: the gap at each served token."""
        import jax.numpy as jnp
        f, rows, out = self._fn(bits), REFERENCE_ROWS, []
        for g in range(0, len(seqs), rows):
            block = seqs[g:g + rows]
            toks = np.zeros((rows, self.t_pad), np.int32)
            for r, (ids, _) in enumerate(block):
                toks[r, :len(ids)] = ids
            gaps = np.asarray(f(self.params, jnp.asarray(toks)))
            for r, (ids, n_prompt) in enumerate(block):
                # position p judges token p + 1: the served ones start at
                # the prompt's last position
                out.append(gaps[r, n_prompt - 1:len(ids) - 1])
        return out


def gap_numbers(gaps):
    """The widest and the mean gap over every served token judged."""
    n = int(sum(len(g) for g in gaps))
    if not n:
        return math.inf, math.inf, 0
    return (float(max(g.max() for g in gaps if len(g))),
            float(sum(g.sum() for g in gaps) / n), n)


def check(spec, judge, streams, reqs_spec, seed: int, control=False):
    """Whether all limits hold, the numbers compared (each with its
    limit), and the readings (requests judged, both gaps, tokens judged).
    ``judge`` is a :class:`Reference`; with ``control``, the tokens judged
    are the control's, not the served ones.

    A limit of null in the configuration leaves that number uncompared
    (PERF.md says why, with its readings)."""
    lim = spec["check"]
    picked = sample(streams, reqs_spec, SAMPLE_REQUESTS, seed)
    seqs = sequences(spec, streams, reqs_spec, picked)
    in_vocab = all(((ids >= 0) & (ids < spec["vocab_size"])).all()
                   for ids, _ in seqs)
    bits = lim["control_bits"] if control else None
    gaps = judge.gaps(seqs, bits) if in_vocab else []
    widest, mean, n = gap_numbers(gaps)
    numbers, ok = {}, True
    for name, value in (("served_logit_gap_max", widest),
                        ("served_logit_gap_mean", mean)):
        limit = lim["limits"][name]
        if limit is None:
            log(f"check {name}: {value} (not compared)")
            continue
        numbers[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    numbers["tokens_compared"] = {"value": n, "limit": lim["min_tokens"]}
    ok = ok and n >= lim["min_tokens"]
    return ok, numbers, {"requests": len(picked), "gap_max": widest,
                         "gap_mean": mean, "tokens": n}


# ---------------------------------------------------------------------------
# per-layer metrics of a traced window
# ---------------------------------------------------------------------------

def window_work(spec, model, w, window, reqs_spec):
    """Operations and bytes of the window's served work, counted by
    ``work.py`` from the reference's description ``model``: a token after
    a request's first was decoded over its context."""
    import jax.numpy as jnp
    decode_keys = [reqs_spec[i]["prompt_tokens"] + k
                   for i, st in enumerate(window) for k, _, _ in st if k >= 1]
    chunks = [(e["args"]["tokens"], e["args"]["done"]) for e in w.events
              if e["name"] == "CHUNK"]
    c = w.counters
    return work.window(
        model, kv_mode=spec["serve"]["kv_mode"],
        quantized=bool(spec.get("quant")),
        dtype_bytes=jnp.dtype(spec["dtype"]).itemsize,
        decode_steps=c["decode_steps"], decode_slot_steps=c["decode_slot_steps"],
        prefill_steps=c["prefill_steps"], prefill_tokens=c["prefill_chunk_tokens"],
        prefills=c["prefills"], decode_keys=decode_keys, chunks=chunks)


def breakdown(ctx):
    """The device ops with the most self time, and the longest idle gaps
    between programs, each named by the program before it and by the host
    span that covers most of it (``after jit_decode: sched.decode.wait``)
    where the profile has the spans."""
    gaps = [list(g) for g in ctx.device["idle_gaps"]]
    trace = hosttrace.window(ctx)
    if trace is not None:
        hosttrace.label_gaps(gaps, trace)
    return {"device_ops": tracefile.top_ops(ctx.device), "idle_gaps": gaps}


def per_layer(spec, ref, readers, peaks, w, window, reqs_spec, trace_dir):
    """(metrics, busy and window seconds, breakdown) of a traced window.
    Each reader gets one context: the window's counters and step records,
    the reduced trace and its directory, the peaks, the configuration, and
    the work counted from the reference's description."""
    reduced = tracefile.reduce(tracefile.extract(str(trace_dir)), w.traced_s)
    ctx = types.SimpleNamespace(
        counters=w.counters, events=w.events, device=reduced, peaks=peaks,
        spec=spec, n_slots=spec["serve"]["max_batch"], trace_dir=trace_dir,
        work=window_work(spec, ref.work_model(spec), w, window, reqs_spec))
    metrics = {}
    for name, unit, mod in readers:
        value = mod.read(ctx)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": unit}
    return metrics, reduced, breakdown(ctx)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(workload: str, seed: int, seconds: float, trace: bool, *,
        control: bool = False, root: Path = ROOT, cache: Path = CACHE,
        require_tpu: bool = True) -> dict:
    """One run of cell ``workload``; returns the result line's object.
    ``root`` holds BENCHMARK.json and bench/, ``cache`` the MUXQ artifacts
    and traces; ``require_tpu=False`` lets a test drive it on the CPU."""
    bench, cell, spec, traffic = load_cell(root, workload)
    readers = per_layer_readers(root, bench) if trace else []
    dev, peaks = device_info(cell["chips"], require_tpu)
    import jax
    s = set_up(spec, traffic, trace, cache, root)
    reqs_spec = gen.requests(traffic, seed)
    trace_dir = None
    if trace:
        trace_dir = cache / "trace" / workload
        shutil.rmtree(trace_dir, ignore_errors=True)
    w = serve_window(s, reqs_spec, seconds,
                     int(traffic.get("open_after_finished", 0)), trace_dir)
    setup_s = w.t_open - T_START
    print(f"window_compiles: {w.lowered} programs lowered inside the window",
          flush=True)
    if w.lowered:
        raise BenchError(f"{w.lowered} programs compiled inside the window: "
                         "the warm-up does not reach every step program")
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    window = in_window(w)
    tokens = sum(len(st) for st in window)
    attempted = sum(1 for st in window if st)
    breakdown = None
    if trace:
        metrics, reduced, breakdown = per_layer(
            spec, s.ref, readers, peaks, w, window, reqs_spec, trace_dir)
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
    else:
        metrics = {
            "output_tok_s": {"value": tokens / w.window_s, "unit": "tokens/s"},
            "itl_p95_ms": {"value": itl_p95_ms(window), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    c = w.counters
    gaps = call_gaps(w)
    log(f"window: {w.window_s:.3f} s, step calls {w.call_open + 1}-"
        f"{w.call_close}, between their first tokens median "
        f"{np.median(gaps) if gaps.size else 0:.4f} s, longest "
        f"{gaps.max() if gaps.size else 0:.4f} s; {len(w.gc_s)} garbage "
        f"collections, {sum(w.gc_s):.4f} s, longest "
        f"{max(w.gc_s, default=0):.4f} s")
    log(f"window: {tokens} tokens streamed, {attempted} requests served, "
        f"{c['completed']} finished, {c['decode_steps']} decode steps, "
        f"{c['prefill_steps']} prefill steps; before it {w.finished - c['completed']} "
        f"finished; setup {setup_s:.3f} s; peak device memory "
        f"{dev['memory_peak_bytes']}")

    # the program's state goes before the reference takes the chip
    ref = s.ref
    del s
    gc.collect()
    t0 = time.perf_counter()
    ok, numbers, read = check(spec, Reference(spec, ref, traffic),
                              w.streams, reqs_spec, seed, control)
    log(f"check{' (control)' if control else ''}: {read['requests']} requests "
        f"through the reference in {time.perf_counter() - t0:.3f} s")
    # a request still running at the deadline has not failed; none is refused
    result = {"correct": bool(ok), "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    for name, v in numbers.items():
        log(f"check {name}: {v['value']} limit {v['limit']}")
    return result


def cache_env() -> None:
    """JAX's persistent compile cache for a benchmark process, set before
    JAX is imported so the program takes it.  The path is part of the
    cache's key: one fixed directory inside the checkout.  Every program
    is cached however fast it compiled, and nothing is evicted: eviction,
    where the environment turns it on, fails on entries without an
    access-time file and then caches nothing."""
    (CACHE / "jax").mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the control's tokens in place of the served "
                         "ones (never part of a benchmark run)")
    args = ap.parse_args(argv)
    cache_env()
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     control=bool(args.control))
    except BenchError as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
