"""The reduction from a profiler trace to device time, on a small recorded
trace: records cut from a traced chat-muxq window on a TPU v5e (one decode
and one prefill program, part of each layer scan's ops)."""
from __future__ import annotations

import json

import pytest

import bench_testlib as L
import tracefile

REC = json.loads((L.DATA / "trace_v5e_steps.json").read_text())["records"]


def _sweep_busy_ns(records):
    """Covered nanoseconds by an edge sweep (independent of the reduction)."""
    edges = []
    for r in records:
        if r["line"] == tracefile.OPS:
            edges += [(r["start_ns"], 1), (r["start_ns"] + r["dur_ns"], -1)]
    edges.sort(key=lambda e: (e[0], -e[1]))
    depth, last, busy = 0, None, 0
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


@pytest.fixture(scope="module")
def reduced():
    return tracefile.reduce(REC, 0.6)


def test_busy_is_the_union_of_op_intervals(reduced):
    assert reduced["busy_s"] == pytest.approx(_sweep_busy_ns(REC) * 1e-9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_self_times_add_up_to_busy(reduced):
    # ops nest (a layer scan's while holds its body), never overlap otherwise
    total = sum(t for t, _ in reduced["ops"].values())
    assert total == pytest.approx(reduced["busy_s"])
    assert reduced["ops"]["while"][1] == 2


def test_kernels_are_found_by_name(reduced):
    pa = [r for r in REC if r["name"].startswith("%paged_attention.")]
    assert pa and tracefile.kernel_s(reduced, "paged_attention") == \
        pytest.approx(sum(r["dur_ns"] for r in pa) * 1e-9)
    assert tracefile.kernel_s(reduced, "muxq_gemm") > 0
    assert tracefile.kernel_s(reduced, "no_such_kernel") == 0


@pytest.mark.parametrize("text,name", [
    ("%paged_attention.9 = f32[32,20,1,64]{3,2,1,0} custom-call(s32[32,8])",
     "paged_attention"),
    ("%copy.192 = s8[1,2049,20,16,64] copy(s8[1,2049,20,16,64] %x)", "copy"),
    ("%while.5 = (s32[], f32[32,1,1280]) while(%tuple)", "while"),
    ("%constant_dynamic-slice_fusion.28 = s8[1,2049]", "constant_dynamic-slice_fusion"),
    ("no-equals-sign", "no-equals-sign"),
])
def test_op_name(text, name):
    assert tracefile.op_name(text) == name


def test_programs_and_idle_gaps(reduced):
    mods = sorted((r for r in REC if r["line"] == tracefile.MODULES),
                  key=lambda r: r["start_ns"])
    assert reduced["modules"]["jit_decode"] == [mods[0]["dur_ns"] * 1e-9, 1]
    assert reduced["modules"]["jit_prefill"][1] == 1
    gap = mods[1]["start_ns"] - mods[0]["start_ns"] - mods[0]["dur_ns"]
    assert reduced["idle_gaps"] == [["after jit_decode", gap * 1e-9]]


def test_top_ops_by_self_time(reduced):
    top = tracefile.top_ops(reduced, 3)
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    assert top[0][0] == "while"
