"""Shared set-up for the benchmark's own tests: a benchmark root in a
temporary directory that holds test-size configurations, mixes and
references beside the real ones, so the harness runs end to end on the
CPU."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
DATA = Path(__file__).resolve().parent / "data"
for p in (str(REPO / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

# test-size cells: (name, configuration, traffic)
CELLS = (("tiny-chat-muxq", "tiny-muxq", "tiny-chat"),
         ("tiny-chat-bf16", "tiny-bf16", "tiny-chat"),
         ("tiny-docqa-muxq", "tiny-muxq", "tiny-docqa"))


def make_root(tmp: Path, cells=CELLS) -> Path:
    """A benchmark root with BENCHMARK.json's metrics and the test cells."""
    shutil.copytree(BENCH / "traffic", tmp / "bench" / "traffic")
    shutil.copytree(BENCH / "metrics", tmp / "bench" / "metrics")
    shutil.copytree(BENCH / "reference", tmp / "bench" / "reference",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for f in DATA.glob("*.py"):     # the test-size references
        shutil.copy(f, tmp / "bench" / "reference" / f.name)
    (tmp / "configs").mkdir()
    for f in DATA.glob("tiny-*.json"):
        dest = "traffic" if "chat" in f.name or "docqa" in f.name else None
        if dest:
            shutil.copy(f, tmp / "bench" / "traffic" / f.name)
        else:
            shutil.copy(f, tmp / "configs" / f.name)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    confs = sorted({c for _, c, _ in cells})
    bench["configs"] = [{"name": c, "source": "test", "file": f"configs/{c}.json",
                         "reduced": [], "why": "test size"} for c in confs]
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                           "why": "test size"} for n, c, t in cells]
    muxq = [n for n, c, _ in cells if "muxq" in c]
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = muxq
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp


def root_of(tmp: Path) -> Path:
    """The benchmark root under ``tmp``, made on first use."""
    root = tmp / "root"
    if not (root / "BENCHMARK.json").exists():
        root.mkdir(exist_ok=True)
        make_root(root)
    return root


def run_cell(tmp: Path, name: str, seed: int = 2 ** 31 + 7, seconds: float = 1.0,
             trace: bool = False, control: bool = False):
    import run
    return run.run(name, seed, seconds, trace, control=control,
                   root=root_of(tmp), cache=tmp / "cache", require_tpu=False)
