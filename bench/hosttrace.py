"""The scheduler's host spans beside the device's step programs.

The scheduler wraps each piece of its host work in a recorder span
(``sched.iteration``, ``sched.admit``, ``sched.decode.dispatch``, ...; see
docs/OBSERVABILITY.md), and a span opens a ``jax.profiler.TraceAnnotation``:
in a traced window it lands in the profile's host plane, whose clock the
device's events share to within a few milliseconds (:func:`clock_shift`
closes the rest).  The step programs put the pool's page writes under the
``pool`` name scope, which the profile keeps in each device op's metadata.

:func:`load` reads both from the window's ``.xplane.pb``: the ``sched.*``
spans, and records in :mod:`tracefile`'s form (so ``tracefile.reduce``
reads them too) whose ops also carry their scope.  The functions below
join the spans to the programs' intervals; they are pure, so they are
checked on a small recorded trace kept with the tests.  A program without
the spans or the scope (an older checkout) gives no numbers: each
function returns None.
"""
from __future__ import annotations

import glob
import os
from pathlib import Path
from typing import Dict, List, Optional

import tracefile

HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "sched."
ITERATION = "sched.iteration"
WAIT_SUFFIX = ".wait"
POOL_SCOPE = "pool"
# the serving engine's step programs (its other programs, such as a
# token upload's ``jit_broadcast_in_dim``, are not step calls)
STEP_PROGRAMS = ("jit_decode", "jit_prefill", "jit_verify")
# the op metadata stat that holds the op's name scope (XLA's op_name)
SCOPE_STAT = "tf_op"

_loaded: Dict[tuple, Dict] = {}


def load(path: str) -> Dict:
    """``{"spans": [...], "records": [...]}`` of the newest ``.xplane.pb``
    under ``path`` (or of that file).  A span is ``{"name", "start_ns",
    "dur_ns", "args"}``; records are :func:`tracefile.extract`'s, and an
    op's record adds ``"scope"``, the name scope it was traced under."""
    import jax
    if os.path.isdir(path):
        paths = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = max(paths, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key in _loaded:
        return _loaded[key]
    data = jax.profiler.ProfileData.from_file(path)
    scopes = op_scopes(Path(path).read_bytes())
    spans, records = [], []
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append({"name": ev.name,
                                      "start_ns": int(ev.start_ns),
                                      "dur_ns": int(ev.duration_ns),
                                      "args": dict(ev.stats)})
        elif plane.name.startswith(tracefile.DEVICE_PLANE):
            chip = int(plane.name[len(tracefile.DEVICE_PLANE):].split()[0])
            for line in plane.lines:
                if line.name not in (tracefile.OPS, tracefile.MODULES):
                    continue
                for ev in line.events:
                    r = {"chip": chip, "line": line.name, "name": ev.name,
                         "start_ns": int(ev.start_ns),
                         "dur_ns": int(ev.duration_ns)}
                    if line.name == tracefile.OPS:
                        r["scope"] = scopes.get(ev.name, "")
                    records.append(r)
    _loaded[key] = out = {"spans": spans, "records": records}
    return out


def _varint(b: bytes, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes, lo: int = 0, hi: Optional[int] = None):
    """(field number, value) of a protobuf message in ``b[lo:hi]``: an int
    for a varint, (start, end) for a length-delimited field."""
    i, hi = lo, len(b) if hi is None else hi
    while i < hi:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def op_scopes(xspace: bytes) -> Dict[str, str]:
    """{op event name: name scope} of the device planes' ops.  The scope
    is the ``tf_op`` stat of the op's event metadata, which
    ``jax.profiler.ProfileData`` does not expose, so this walks the XSpace
    message itself (tsl/profiler/protobuf/xplane.proto: XSpace.planes 1;
    XPlane.name 2, .event_metadata 4, .stat_metadata 5, both maps of
    key 1 to value 2; XEventMetadata.name 2, .stats 5; XStatMetadata.name
    2; XStat.metadata_id 1, .str_value 5, .bytes_value 6, .ref_value 7).
    An op name that two programs give different scopes maps to ""."""
    text = lambda span: xspace[span[0]:span[1]].decode(errors="replace")  # noqa: E731
    found: Dict[str, set] = {}
    for field, plane in _fields(xspace):
        if field != 1:
            continue
        parts = {2: [], 4: [], 5: []}
        for f, v in _fields(xspace, *plane):
            if f in parts:
                parts[f].append(v)
        if not parts[2] or not text(parts[2][0]).startswith(
                tracefile.DEVICE_PLANE):
            continue
        names = {}
        for entry in parts[5]:
            for f, meta in _fields(xspace, *entry):
                if f == 2:
                    m = dict(_fields(xspace, *meta))
                    names[m.get(1, 0)] = text(m[2]) if 2 in m else ""
        for entry in parts[4]:
            for f, meta in _fields(xspace, *entry):
                if f != 2:
                    continue
                name, scope = None, ""
                for g, v in _fields(xspace, *meta):
                    if g == 2:
                        name = text(v)
                    elif g == 5:
                        stat = dict(_fields(xspace, *v))
                        if names.get(stat.get(1)) != SCOPE_STAT:
                            continue
                        if 7 in stat:
                            scope = names.get(stat[7], "")
                        elif 5 in stat or 6 in stat:
                            scope = text(stat.get(5) or stat[6])
                if name is not None:
                    found.setdefault(name, set()).add(scope)
    return {name: scope.pop() if len(scope) == 1 else ""
            for name, scope in found.items()}


def window(run) -> Optional[Dict]:
    """The traced window's spans and records, or None.  The profile is the
    newest under ``run.trace_dir``, the directory the harness traced the
    window into, and it counts only if its step programs are the ones the
    harness reduced (``run.device["modules"]``)."""
    want = run.device["modules"]
    if not want or not run.trace_dir:
        return None
    try:
        trace = load(str(run.trace_dir))
    except FileNotFoundError:
        return None
    got: Dict[str, List] = {}
    for r in trace["records"]:
        if r["line"] == tracefile.MODULES:
            t = got.setdefault(tracefile._module(r["name"]), [0.0, 0])
            t[0] += r["dur_ns"] * 1e-9
            t[1] += 1
    if got.keys() == want.keys() and all(
            got[k][1] == n and abs(got[k][0] - t) <= 1e-9 * t
            for k, (t, n) in want.items()):
        return trace
    return None


# ---------------------------------------------------------------------------
# the join (pure)
# ---------------------------------------------------------------------------

def programs(records) -> List[tuple]:
    """(start, end, program) of each program on the first chip, in order."""
    chips = sorted({r["chip"] for r in records})
    if not chips:
        return []
    return sorted((r["start_ns"], r["start_ns"] + r["dur_ns"],
                   tracefile._module(r["name"])) for r in records
                  if r["chip"] == chips[0] and r["line"] == tracefile.MODULES)


def idle_gaps(records) -> List[tuple]:
    """(start, end, label) of each gap between two programs on the first
    chip, labelled ``after <program>`` as ``tracefile.reduce`` labels it."""
    mods = programs(records)
    return [(e0, s1, f"after {n0}")
            for (_, e0, n0), (s1, _, _) in zip(mods, mods[1:]) if s1 > e0]


def _nearest(spans, t: int):
    return min(spans, key=lambda s: abs(s[0] - t), default=None)


def step_calls(trace) -> List[Dict]:
    """Each step program on the first chip whose kind the scheduler spans
    (``jit_decode``: ``sched.decode.*``), with the dispatch span of its
    kind that opens nearest the program's start and the first wait span of
    its kind after that dispatch (a wait can outlast an iteration in a
    stall, so it is not the one that closes nearest the program's end),
    both on the host's clock: ``{"program", "start", "end", "dispatch",
    "wait"}``, a span being (start, end, args)."""
    by_name: Dict[str, List[tuple]] = {}
    for sp in trace["spans"]:
        by_name.setdefault(sp["name"], []).append(
            (sp["start_ns"], sp["start_ns"] + sp["dur_ns"], sp["args"]))
    for spans in by_name.values():
        spans.sort(key=lambda sp: sp[0])
    out = []
    for s, e, prog in programs(trace["records"]):
        if prog not in STEP_PROGRAMS:
            continue
        kind = f"{SPAN_PREFIX}{prog[len('jit_'):]}"
        dispatch = _nearest(by_name.get(f"{kind}.dispatch", []), s)
        if dispatch is None:
            continue
        wait = next((w for w in by_name.get(f"{kind}{WAIT_SUFFIX}", [])
                     if w[0] >= dispatch[1]), None)
        if wait is not None:
            out.append({"program": prog, "start": s, "end": e,
                        "dispatch": dispatch, "wait": wait})
    return out


def clock_shift(trace) -> Optional[tuple]:
    """(shift, lo, hi) in ns: what to add to the host spans' times to put
    them on the device's.  The profiler puts the device's events on the
    host's clock, but not exactly: on a v5e a program started about 1 ms
    before the span that dispatched it opened.  Causality bounds the
    shift: a program starts after its dispatch span opens and ends before
    its wait span closes, so over the step calls it is at least lo =
    max(program end - wait end) and at most hi = min(program start -
    dispatch start).  The midpoint is used.  None without step calls, or
    when the bounds cross (lo > hi: a span paired with the wrong program,
    or clocks that no single shift joins), so nothing is read on a shift
    that cannot be."""
    calls = step_calls(trace)
    if not calls:
        return None
    lo = max(c["end"] - c["wait"][1] for c in calls)
    hi = min(c["start"] - c["dispatch"][0] for c in calls)
    if lo > hi:
        return None
    return (lo + hi) // 2, lo, hi


def on_device_clock(trace, waits: bool = True) -> Optional[List[tuple]]:
    """(start, end, name) of the spans of host work between programs,
    moved onto the device's clock: every ``sched.*`` span but the
    iteration that holds them, and but the ``*.wait`` spans unless
    ``waits`` (a wait is the host standing on a program's tokens, not
    doing work).  None when :func:`clock_shift` finds no shift."""
    shift = clock_shift(trace)
    if shift is None:
        return None
    return sorted((s["start_ns"] + shift[0],
                   s["start_ns"] + s["dur_ns"] + shift[0], s["name"])
                  for s in trace["spans"] if s["name"] != ITERATION
                  and (waits or not s["name"].endswith(WAIT_SUFFIX)))


def _overlaps(lo: int, hi: int, spans) -> Dict[str, int]:
    """Nanoseconds of [lo, hi) under each span name (spans sorted)."""
    out: Dict[str, int] = {}
    for s, e, name in spans:
        if s >= hi:
            break
        if e > lo:
            out[name] = out.get(name, 0) + min(e, hi) - max(s, lo)
    return out


def _covered(lo: int, hi: int, spans) -> int:
    """Nanoseconds of [lo, hi) under the union of the spans."""
    return tracefile._union_ns((max(s, lo), min(e, hi)) for s, e, _ in spans
                               if s < hi and e > lo)


def gap_spans(trace) -> List[tuple]:
    """(label, seconds, span) of each gap between programs: the span that
    covers most of it (a wait among them), or None where none does; no
    gaps without a clock shift."""
    named = on_device_clock(trace)
    if named is None:
        return []
    out = []
    for lo, hi, label in idle_gaps(trace["records"]):
        over = _overlaps(lo, hi, named)
        out.append((label, (hi - lo) * 1e-9,
                    max(over, key=over.get) if over else None))
    return out


def label_gaps(gaps: List[List], trace) -> None:
    """Add to each ``[label, seconds]`` of ``tracefile.reduce``'s
    ``idle_gaps`` the span that covers most of that gap:
    ``after jit_decode: sched.prefill.prepare``.  A gap no span covers keeps
    its label."""
    names: Dict[tuple, List[str]] = {}
    for label, sec, span in gap_spans(trace):
        if span is not None:
            names.setdefault((label, sec), []).append(span)
    for g in gaps:
        found = names.get((g[0], g[1]))
        if found:
            g[0] = f"{g[0]}: {found.pop(0)}"


def idle_named_share(trace) -> Optional[float]:
    """Percent of the idle time between programs on the first chip that a
    span of host work covers (:func:`on_device_clock` without the waits);
    None without spans, gaps or a clock shift."""
    named = on_device_clock(trace, waits=False)
    gaps = idle_gaps(trace["records"])
    total = sum(hi - lo for lo, hi, _ in gaps)
    if not named or not total:
        return None
    return 100.0 * sum(_covered(lo, hi, named) for lo, hi, _ in gaps) / total


def wait_idle_ms_per_call(trace) -> Optional[float]:
    """Milliseconds per step call of the idle time between programs on the
    first chip under a ``sched.*.wait`` span: the chip idle while the host
    stands on a finished program's tokens.  None without waits, step calls
    or a clock shift."""
    spans = on_device_clock(trace)
    if spans is None:
        return None
    waits = [sp for sp in spans if sp[2].endswith(WAIT_SUFFIX)]
    calls = sum(1 for *_, prog in programs(trace["records"])
                if prog in STEP_PROGRAMS)
    if not waits or not calls:
        return None
    return sum(_covered(lo, hi, waits) for lo, hi, _ in
               idle_gaps(trace["records"])) * 1e-6 / calls


def host_ms_per_call(trace) -> Optional[float]:
    """Host milliseconds per step call: each traced ``sched.iteration``
    less the waits on program tokens inside it, over the step calls those
    iterations dispatched; None without iterations."""
    spans = trace["spans"]
    its = [(s["start_ns"], s["start_ns"] + s["dur_ns"]) for s in spans
           if s["name"] == ITERATION]
    host_ns, calls = 0, 0
    for lo, hi in its:
        inside = [s for s in spans if lo <= s["start_ns"]
                  and s["start_ns"] + s["dur_ns"] <= hi]
        host_ns += hi - lo - sum(s["dur_ns"] for s in inside
                                 if s["name"].endswith(WAIT_SUFFIX))
        calls += sum(1 for s in inside if s["name"].endswith(".dispatch"))
    return host_ns * 1e-6 / calls if calls else None


def pool_ms_per_step(records) -> Optional[float]:
    """Device milliseconds per step call in ops under the ``pool`` scope
    (self time: the ops nested in an op are not its time); None when no
    op carries the scope."""
    evs = {}
    for r in records:
        if r["line"] == tracefile.OPS:
            evs.setdefault(r["chip"], []).append(
                (r["start_ns"], r["start_ns"] + r["dur_ns"],
                 in_scope(r["scope"])))
    calls = sum(1 for r in records if r["line"] == tracefile.MODULES
                and tracefile._module(r["name"]) in STEP_PROGRAMS)
    if not calls or not any(hit for ops in evs.values() for *_, hit in ops):
        return None
    pool = sum(t for ops in evs.values()
               for hit, t in tracefile._self_times(ops) if hit)
    return pool * 1e-6 / calls


def in_scope(scope: str) -> bool:
    """Whether an op's name scope path (``jit(decode)/while/body/pool/
    scatter``) passes through the ``pool`` scope."""
    return POOL_SCOPE in scope.split("/")[:-1]
