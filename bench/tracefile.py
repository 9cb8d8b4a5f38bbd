"""From a profiler trace to device time: the reduction every PR shares.

:func:`extract` reads the newest ``*.xplane.pb`` under a trace directory
into plain records, one per device event:
``{"chip": i, "line": "XLA Ops" | "XLA Modules", "name": str,
"start_ns": int, "dur_ns": int}``.  :func:`reduce` turns records into
the numbers the per-layer metrics read.  ``reduce`` is pure, so it is
checked on a small recorded trace kept with the tests.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List

DEVICE_PLANE = "/device:TPU:"
OPS, MODULES = "XLA Ops", "XLA Modules"


def extract(trace_dir: str) -> List[Dict]:
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        chip = int(plane.name[len(DEVICE_PLANE):].split()[0])
        for line in plane.lines:
            if line.name not in (OPS, MODULES):
                continue
            for ev in line.events:
                out.append({"chip": chip, "line": line.name, "name": ev.name,
                            "start_ns": int(ev.start_ns),
                            "dur_ns": int(ev.duration_ns)})
    return out


def _union_ns(intervals) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _module(name: str) -> str:
    """``jit_decode(1234)`` -> ``jit_decode``."""
    return name.split("(")[0]


def op_name(name: str) -> str:
    """An op event's name is its HLO text; keep the instruction's name
    without its number: ``%paged_attention.9 = f32[...] custom-call(...)``
    -> ``paged_attention``, ``%copy.192 = ...`` -> ``copy``."""
    head = name.split(" = ")[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def _self_times(events):
    """(name, self ns) of each (start, end, name) event: its length less
    that of the events directly nested in it (a scan's ``while`` holds its
    body's ops)."""
    evs = sorted(events, key=lambda x: (x[0], -x[1]))
    self_ns = [e - s for s, e, _ in evs]
    stack = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    return [(n, t) for (_, _, n), t in zip(evs, self_ns)]


def reduce(records: List[Dict], window_s: float) -> Dict:
    """Device time over a traced window of ``window_s`` host seconds.

    busy_s: union of op intervals per chip, averaged over chips.
    ops: {op name: [self seconds, count]} summed over chips, where an op's
    self time leaves out the ops nested in it.
    modules: {program name: [seconds, count]}, the compiled step programs.
    idle_gaps: the longest gaps between programs on the first chip, each
    named by the program that ran before it (the host works between
    programs)."""
    chips = sorted({r["chip"] for r in records})
    busy = 0
    ops: Dict[str, List[float]] = {}
    modules: Dict[str, List[float]] = {}
    for r in records:
        if r["line"] == MODULES:
            t = modules.setdefault(_module(r["name"]), [0.0, 0])
            t[0] += r["dur_ns"] * 1e-9
            t[1] += 1
    for c in chips:
        evs = [(r["start_ns"], r["start_ns"] + r["dur_ns"], op_name(r["name"]))
               for r in records if r["chip"] == c and r["line"] == OPS]
        busy += _union_ns((s, e) for s, e, _ in evs)
        for name, self_ns in _self_times(evs):
            t = ops.setdefault(name, [0.0, 0])
            t[0] += self_ns * 1e-9
            t[1] += 1
    busy_s = busy * 1e-9 / max(len(chips), 1)
    gaps = []
    if chips:
        mods = sorted((r["start_ns"], r["start_ns"] + r["dur_ns"],
                       _module(r["name"])) for r in records
                      if r["chip"] == chips[0] and r["line"] == MODULES)
        for (s0, e0, n0), (s1, _, _) in zip(mods, mods[1:]):
            if s1 > e0:
                gaps.append([f"after {n0}", (s1 - e0) * 1e-9])
        gaps.sort(key=lambda g: -g[1])
    return {"window_s": window_s, "busy_s": busy_s, "chips": len(chips),
            "ops": ops, "modules": modules, "idle_gaps": gaps[:10]}


def kernel_s(reduced: Dict, kernel: str) -> float:
    """Seconds in the ops of one kernel (a Pallas call's name)."""
    return reduced["ops"].get(kernel, [0.0, 0])[0]


def top_ops(reduced: Dict, n: int = 10) -> List[List]:
    """The ``n`` ops with the most self time: [[name, seconds], ...]."""
    return [[k, v[0]] for k, v in
            sorted(reduced["ops"].items(), key=lambda kv: -kv[1][0])[:n]]
