"""Readings that set a configuration's check limits; not run by the benchmark.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 51

One process, one set-up: the cell's window is served once per seed on the
same warmed engine (each with its tokens per second and token-gap p95, as
a run reports them), then the engine is freed and each window goes through
``run.check`` twice: once judging the tokens the program served (what a
benchmark run compares), once with ``control`` (the tokens the reference
one precision lower puts first at the same positions, which has to come
out not correct).  Prints one JSON line per seed and a summary line.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run


def readings(workload: str, seeds, seconds: float, *, root=run.ROOT,
             cache=run.CACHE, require_tpu: bool = True):
    _, cell, spec, traffic = run.load_cell(root, workload)
    dev, _ = run.device_info(cell["chips"], require_tpu)
    s = run.set_up(spec, traffic, False, cache, root)
    open_after = int(traffic.get("open_after_finished", 0))
    windows = []
    for seed in seeds:
        reqs_spec = run.gen.requests(traffic, seed)
        w = run.serve_window(s, reqs_spec, seconds, open_after)
        window = run.in_window(w)
        windows.append((seed, reqs_spec, w.streams, {
            "output_tok_s": sum(map(len, window)) / w.window_s,
            "itl_p95_ms": run.itl_p95_ms(window), "lowered": w.lowered}))
    ref = s.ref
    del s
    gc.collect()
    judge = run.Reference(spec, ref, traffic)
    out = []
    for seed, reqs_spec, streams, metrics in windows:
        row = {"seed": seed, **metrics}
        for key, control in (("program", False), ("control", True)):
            ok, _, read = run.check(spec, judge, streams, reqs_spec, seed,
                                    control)
            row[key] = {"correct": ok, **read}
        out.append(row)
    return dev, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    run.cache_env()
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        dev, rows = readings(args.workload, seeds, args.seconds)
    except run.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for r in rows:
        print(json.dumps(r), flush=True)
    summary = {"workload": args.workload, "device": dev,
               "program_correct": sum(r["program"]["correct"] for r in rows),
               "control_correct": sum(r["control"]["correct"] for r in rows)}
    for k in ("gap_max", "gap_mean"):
        summary[f"program_{k}"] = max(r["program"][k] for r in rows)
        summary[f"control_{k}"] = min(r["control"][k] for r in rows)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
