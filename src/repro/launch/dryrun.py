"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
record memory / cost / collective analysis for the roofline report.

``main`` asks for 512 placeholder CPU devices before JAX brings up its
backend (the device count locks at first use); importing this module
leaves the process's device setup alone.
"""
import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import hlo as hlo_mod
from repro.analysis import roofline as R
from repro.configs import get_config, list_archs
from repro.core.muxq import QuantConfig
from repro.core.prequant import prequantize_params
from repro.launch import specs as SP
from repro.launch import steps as ST
from repro.launch.mesh import make_production_mesh
from repro.models import moe as moe_mod
from repro.models import transformer as T
from repro.optim import adamw
from repro.parallel import sharding as SH
from repro.parallel.act_sharding import (set_activation_sharding,
                                          set_cache_update_mode)

OUT_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def _abstract_params(cfg, dtype=None):
    abs_p = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    if dtype is not None:
        cast = lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype)
        abs_p = jax.tree.map(cast, abs_p)
    return abs_p


def _opt_specs(pspecs, mesh):
    return {"mu": pspecs, "nu": pspecs, "step": SH.replicated(mesh)}


def _mem_dict(compiled):
    try:
        m = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(m, k, None)
        if v is not None:
            out[k] = int(v)
    out["total_nonarg_bytes"] = out.get("output_size_in_bytes", 0) + out.get("temp_size_in_bytes", 0)
    return out


def _cost_dict(compiled):
    try:
        c = compiled.cost_analysis() or {}
    except Exception:
        return {}
    return {k: float(v) for k, v in c.items() if isinstance(v, (int, float))}


def _lower_cell(cfg, shape, mesh, quant: str, *, fsdp: bool, seq_shard: bool,
                scan: bool):
    """Build + lower one step program.  Returns (lowered, tokens)."""
    set_activation_sharding(SH.activation_spec(mesh, seq_shard=seq_shard)
                            if shape.mode != "decode" else None)
    set_cache_update_mode(
        "select" if cfg.n_kv_heads % mesh.shape["model"] else "dus")
    if cfg.n_experts:
        dp = SH.dp_axes(mesh)
        moe_mod.set_expert_sharding(lambda shp: NamedSharding(
            mesh, SH.fit_spec(mesh, shp, (dp, "model", None, None))))
    else:
        moe_mod.set_expert_sharding(None)

    # quant modes: fp | muxq (quantize-at-use, paper protocol) | muxq_pq
    # (offline int8 weights — §Perf hillclimb lever)
    qcfg = ST.MUXQ_SERVE if quant.startswith("muxq") else None
    qparams = SP.synthetic_qparams(cfg) if quant.startswith("muxq") else None

    if shape.mode == "train":
        abs_p = _abstract_params(cfg)            # fp32 master
        pspecs = SH.param_specs(cfg, abs_p, mesh, fsdp=fsdp)
        abs_o = jax.eval_shape(adamw.init_state, abs_p)
        ospecs = _opt_specs(pspecs, mesh)
        abs_b = SP.batch_specs_abstract(cfg, shape)
        bspecs = SH.batch_specs(mesh, abs_b)
        step = ST.make_train_step(cfg, quant=qcfg, qparams=qparams, scan=scan,
                                  cast_bf16=(quant == "bf16cast"))
        jf = jax.jit(step, in_shardings=(pspecs, ospecs, bspecs),
                     out_shardings=(pspecs, ospecs, None))
        lowered = jf.lower(abs_p, abs_o, abs_b)
        tokens = shape.global_batch * shape.seq_len
    elif shape.mode == "prefill":
        abs_p = _abstract_params(cfg, jnp.bfloat16)
        if quant == "muxq_pq":
            abs_p = jax.eval_shape(lambda t: prequantize_params(cfg, t), abs_p)
        pspecs = SH.param_specs(cfg, abs_p, mesh, fsdp=fsdp)
        abs_b = SP.prefill_specs_abstract(cfg, shape)
        bspecs = SH.batch_specs(mesh, abs_b)
        step = ST.make_prefill_step(cfg, shape.seq_len, quant=qcfg,
                                    qparams=qparams, scan=scan)
        out_abs = jax.eval_shape(step, abs_p, abs_b)
        cspecs = SH.cache_specs(cfg, mesh, out_abs[1])
        jf = jax.jit(step, in_shardings=(pspecs, bspecs),
                     out_shardings=(None, cspecs))
        lowered = jf.lower(abs_p, abs_b)
        tokens = shape.global_batch * shape.seq_len
    else:  # decode
        abs_p = _abstract_params(cfg, jnp.bfloat16)
        if quant.startswith("muxq_pq"):
            abs_p = jax.eval_shape(lambda t: prequantize_params(cfg, t), abs_p)
        pspecs = SH.param_specs(cfg, abs_p, mesh, fsdp=fsdp)
        abs_b = SP.decode_specs_abstract(cfg, shape,
                                         int8_kv=quant.endswith("kv8"))
        cspecs = SH.cache_specs(cfg, mesh, abs_b["cache"])
        bspecs = {"tokens": SH.batch_specs(mesh, {"t": abs_b["tokens"]})["t"],
                  "cache": cspecs}
        step = ST.make_serve_step(cfg, quant=qcfg, qparams=qparams, scan=scan)
        out_abs = jax.eval_shape(step, abs_p, abs_b)
        jf = jax.jit(step, in_shardings=(pspecs, bspecs),
                     out_shardings=(None, SH.cache_specs(cfg, mesh, out_abs[1])))
        lowered = jf.lower(abs_p, abs_b)
        tokens = shape.global_batch  # one new token per sequence
    return lowered, tokens


def lower_paged_cell(arch: str, tp: int, *, kv_mode: str = "int8",
                     max_batch: int = 2, s_max: int = 128,
                     page_size: int = 16) -> dict:
    """Prove a production config lowers through the TENSOR-PARALLEL paged
    serving path: build a real (small) PagePool sharded over a ``tp``-device
    ("model",) serve mesh, lower the engine's shard_map'd pooled decode with
    abstract bf16 params (no 110B materialization, no compile), and report
    global vs per-shard pool bytes — the capacity-scaling figure the
    KV-head sharding exists to deliver (per-shard ≈ global / tp when the
    config's kvh divides).

    Unlike the roofline cells above this exercises the actual serve stack
    (``repro.serve.engine`` + pool + paged kernels), not the dense
    ``make_serve_step`` program."""
    from repro.serve.engine import ServeEngine

    cfg = get_config(arch).replace(dtype="bfloat16")
    abs_p = _abstract_params(cfg, jnp.bfloat16)
    eng = ServeEngine(cfg, abs_p, max_batch=max_batch, s_max=s_max,
                      kv_mode=kv_mode, page_size=page_size, tp=tp)
    pool = eng.pool
    bucket = pool.bucket_pages(pool.pages_per_slot)
    tokens = jax.ShapeDtypeStruct((max_batch, 1), jnp.int32)
    table = jax.ShapeDtypeStruct((max_batch, bucket), jnp.int32)
    pos = jax.ShapeDtypeStruct((max_batch,), jnp.int32)
    lowered = eng._decode.lower(eng.weights, tokens, pool.state(), table,
                                pos)
    return {"arch": arch, "tp": tp, "kv_mode": kv_mode,
            "n_kv_heads": cfg.n_kv_heads,
            "heads_sharded": pool.heads_sharded,
            "kv_shards": pool.kv_shards,
            "cache_bytes": pool.cache_bytes(),
            "cache_bytes_per_shard": pool.cache_bytes_per_shard(),
            "lowered": lowered.as_text() is not None}


def _compile_costs(cfg, shape, mesh, quant, *, fsdp, seq_shard, scan):
    lowered, tokens = _lower_cell(cfg, shape, mesh, quant, fsdp=fsdp,
                                  seq_shard=seq_shard, scan=scan)
    t0 = time.time()
    compiled = lowered.compile()
    t_c = time.time() - t0
    cost = _cost_dict(compiled)
    mem = _mem_dict(compiled)
    coll = hlo_mod.collective_bytes(compiled.as_text())
    return {"cost": cost, "mem": mem, "coll": coll, "tokens": tokens,
            "compile_s": t_c}


def _combine(c1: dict, c2: dict, k1: int, k2: int, L: int) -> tuple:
    """Two-point marginal-layer correction (XLA cost analysis counts a
    while/scan body once — see EXPERIMENTS.md §Dry-run methodology).
    cost(L) = fixed + (L/k_unit) * marginal, from unrolled k1/k2 variants."""
    def fix(d1, d2):
        keys = set(d1) | set(d2)
        out = {}
        for k in keys:
            if not isinstance(d1.get(k, 0.0), (int, float)):
                continue
            per = (d2.get(k, 0.0) - d1.get(k, 0.0)) / (k2 - k1)
            val = d1.get(k, 0.0) + per * (L - k1)
            if val <= 0 and d2.get(k, 0.0) > 0:
                # compile noise gave a negative marginal; fall back to a
                # through-origin linear estimate (slight overcount of fixed)
                val = d2[k] * L / k2
            out[k] = max(val, 0.0)
        return out
    return fix(c1["cost"], c2["cost"]), fix(c1["coll"], c2["coll"])


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, quant: str,
             seq_shard: bool = None, fsdp: bool = True,
             save: bool = True, tag: str = "", correct: bool = None) -> dict:
    t0 = time.time()
    cfg = get_config(arch).replace(dtype="bfloat16", remat=True)
    shape = SP.SHAPES[shape_name]
    chips = 512 if multi_pod else 256
    rec = {"arch": arch, "shape": shape_name, "mode": shape.mode,
           "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
           "quant": quant, "fsdp": fsdp, "status": "?", "tag": tag}

    ok, why = SP.cell_supported(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        _save(rec, save)
        return rec

    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        # sequence parallelism: default ON for training (remat saves must be
        # seq-sharded to fit 110B-class models), OFF for decode (seq dim = 1)
        if seq_shard is None:
            seq_shard = shape.mode == "train" and shape.seq_len % mesh.shape["model"] == 0

        uses_scan = cfg.family != "hybrid"
        full = _compile_costs(cfg, shape, mesh, quant, fsdp=fsdp,
                              seq_shard=seq_shard, scan=uses_scan)
        cost, coll = full["cost"], full["coll"]
        corrected = False
        # roofline-table cells (single pod) get the trip-count correction;
        # the multi-pod pass only proves compile + records raw numbers
        if correct is None:
            correct = not multi_pod
        if correct and uses_scan:
            pat = len(cfg.block_pattern)
            k1, k2 = pat, 2 * pat
            sub = {"n_layers": k1}
            sub2 = {"n_layers": k2}
            if cfg.is_enc_dec:
                sub["n_enc_layers"] = k1
                sub2["n_enc_layers"] = k2
            c1 = _compile_costs(cfg.replace(**sub), shape, mesh, quant,
                                fsdp=fsdp, seq_shard=seq_shard, scan=False)
            c2 = _compile_costs(cfg.replace(**sub2), shape, mesh, quant,
                                fsdp=fsdp, seq_shard=seq_shard, scan=False)
            cost, coll = _combine(c1, c2, k1, k2, cfg.n_layers)
            corrected = True

        int8_frac = 0.9 if quant == "muxq" and shape.mode != "train" else 0.0
        roof = R.make_roofline(cost, coll, cfg, full["tokens"], shape.mode,
                               chips, int8_fraction=int8_frac)
        rec.update(status="ok", seq_shard=bool(seq_shard), corrected=corrected,
                   compile_s=round(full["compile_s"], 1),
                   total_s=round(time.time() - t0, 1),
                   cost=cost, memory=full["mem"],
                   collectives={k: v for k, v in coll.items() if k != "counts"},
                   coll_counts=full["coll"].get("counts", {}),
                   roofline=roof.as_dict())
    except Exception as e:  # record the failure — dry-run bugs are OUR bugs
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    _save(rec, save)
    return rec


def _save(rec: dict, save: bool):
    if not save:
        return
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    tag = f"_{rec['tag']}" if rec.get("tag") else ""
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh'].replace('x','-')}_{rec['quant']}{tag}.json"
    (OUT_DIR / name).write_text(json.dumps(rec, indent=1, default=str))


def main(argv=None):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                               + os.environ.get("XLA_FLAGS_EXTRA", ""))
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all", choices=["all", *SP.SHAPES])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--quant", default="auto",
                    help="auto(=muxq for serve, fp for train)|fp|muxq")
    ap.add_argument("--tag", default="")
    ap.add_argument("--no-save", action="store_true")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SP.SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    n_bad = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                quant = args.quant
                if quant == "auto":
                    quant = "fp" if SP.SHAPES[shape].mode == "train" else "muxq"
                if args.resume:
                    mesh_s = "2-16-16" if mp else "16-16"
                    tag = f"_{args.tag}" if args.tag else ""
                    f = OUT_DIR / f"{arch}_{shape}_{mesh_s}_{quant}{tag}.json"
                    if f.exists() and json.loads(f.read_text()).get("status") in ("ok", "skipped"):
                        print(f"[cached ] {arch:24s} {shape:12s}", flush=True)
                        continue
                rec = run_cell(arch, shape, multi_pod=mp, quant=quant,
                               save=not args.no_save, tag=args.tag)
                status = rec["status"]
                n_bad += status == "error"
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f"dom={r['dominant']} step={r['step_s']:.2e}s "
                             f"mfu_bound={r['mfu_bound']:.3f} "
                             f"compile={rec['compile_s']:.0f}s")
                elif status == "error":
                    extra = rec["error"][:160]
                print(f"[{status:7s}] {arch:24s} {shape:12s} {rec['mesh']:8s} "
                      f"{quant:5s} {extra}", flush=True)
    return 1 if n_bad else 0


if __name__ == "__main__":
    sys.exit(main())
