"""Post-compile HLO analysis: collective-traffic accounting.

``compiled.cost_analysis()`` has no collective-bytes entry, so we parse the
optimized HLO text (the per-device SPMD program) and sum wire bytes for
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, using ring-algorithm wire factors:

    all-reduce       2 (g-1)/g * bytes      (reduce-scatter + all-gather)
    all-gather         (g-1)/g * bytes      (bytes = gathered result)
    reduce-scatter     (g-1)   * bytes      (bytes = scattered result)
    all-to-all         (g-1)/g * bytes
    collective-permute         1 * bytes    (point-to-point)

g = replica-group size parsed from the op; bytes = per-device result size.
"""
from __future__ import annotations

import re
from typing import Dict, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,.\s]*?)[\}\]]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def shape_bytes(shape_str: str) -> int:
    """Total bytes of 'bf16[16,128]' or a '(t1, t2)' tuple string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # iota format [num_groups,group_size]
        return max(int(m.group(2)), 1)
    m = _GROUPS_RE.search(line)
    if m:
        members = [x for x in re.split(r"[,\s]+", m.group(1)) if x]
        return max(len(members), 1)
    return 2  # conservative default when groups elided


_WIRE_FACTOR = {
    "all-reduce": lambda g: 2.0 * (g - 1) / g,
    "all-gather": lambda g: (g - 1) / g,
    "reduce-scatter": lambda g: float(g - 1),
    "all-to-all": lambda g: (g - 1) / g,
    "collective-permute": lambda g: 1.0,
}


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-op-kind wire bytes (per device) + op counts from HLO text."""
    out = {k: 0.0 for k in _WIRE_FACTOR}
    counts = {k: 0 for k in _WIRE_FACTOR}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        if "-done" in line:
            continue  # async pair: count only the -start
        g = _group_size(line)
        b = shape_bytes(shape_str)
        out[kind] += _WIRE_FACTOR[kind](g) * b
        counts[kind] += 1
    out["total"] = sum(out[k] for k in _WIRE_FACTOR)
    out["counts"] = counts  # type: ignore[assignment]
    return out


def op_histogram(hlo_text: str, top: int = 15) -> Dict[str, int]:
    """Crude opcode histogram — duplicate-op detection for remat waste."""
    hist: Dict[str, int] = {}
    for m in re.finditer(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z][a-z0-9-]*)\(", hlo_text):
        op = m.group(1)
        hist[op] = hist.get(op, 0) + 1
    return dict(sorted(hist.items(), key=lambda kv: -kv[1])[:top])


_TPU_KERNEL_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%([\w-]+?)(?:\.\d+)?\s*=.*'
    r'custom_call_target="tpu_custom_call"', re.M)


def tpu_kernels(hlo_text: str) -> Dict[str, int]:
    """Compiled Pallas kernels in a TPU program: instruction name (the
    ``name=`` each ``pallas_call`` carries) -> number of call sites.  Empty
    for a CPU program, where kernels run as interpreted XLA ops."""
    out: Dict[str, int] = {}
    for name in _TPU_KERNEL_RE.findall(hlo_text):
        out[name] = out.get(name, 0) + 1
    return out
