"""Dry-run machinery smoke tests on an 8-device (2x4) virtual mesh via
subprocess (the production 512-device sweep runs out-of-band; these tests
validate the same code path end-to-end at CPU-test scale)."""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def run_with_devices(code: str, n: int = 8, timeout: int = 900) -> str:
    import os
    env = dict(os.environ)
    env.update({"XLA_FLAGS": f"--xla_force_host_platform_device_count={n}",
                "PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu"})
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("arch,mode", [
    ("qwen2-0.5b", "train"), ("gemma2-9b", "train"), ("dbrx-132b", "train"),
    ("mamba2-370m", "train"), ("zamba2-1.2b", "train"), ("whisper-tiny", "train"),
    ("internvl2-2b", "prefill"), ("qwen2-0.5b", "decode"),
    ("mamba2-370m", "decode"), ("llama4-scout-17b-a16e", "prefill"),
])
def test_cell_lowers_and_compiles_small_mesh(arch, mode):
    """Reduced-config version of the dry-run cell on a 2x4 mesh, including
    cost/memory/collective extraction."""
    code = f"""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch import specs as SP, steps as ST
    from repro.analysis import hlo as H
    from repro.parallel import sharding as SH
    from repro.optim import adamw
    from repro.models import transformer as T

    arch, mode = {arch!r}, {mode!r}
    cfg = get_config(arch, reduced=True).replace(dtype="bfloat16")
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    shape = SP.ShapeSpec("t", 32, 8, mode)

    def abs_params(dtype=None):
        p = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
        if dtype is not None:
            p = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, dtype if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype), p)
        return p

    if mode == "train":
        abs_p = abs_params()
        pspecs = SH.param_specs(cfg, abs_p, mesh, fsdp=True)
        abs_o = jax.eval_shape(adamw.init_state, abs_p)
        abs_b = SP.batch_specs_abstract(cfg, shape)
        step = ST.make_train_step(cfg, scan=cfg.family != "hybrid")
        jf = jax.jit(step, in_shardings=(pspecs, {{"mu": pspecs, "nu": pspecs,
                                                  "step": SH.replicated(mesh)}},
                                         SH.batch_specs(mesh, abs_b)))
        lowered = jf.lower(abs_p, abs_o, abs_b)
    elif mode == "prefill":
        abs_p = abs_params(jnp.bfloat16)
        pspecs = SH.param_specs(cfg, abs_p, mesh, fsdp=True)
        abs_b = SP.prefill_specs_abstract(cfg, shape)
        step = ST.make_prefill_step(cfg, shape.seq_len, quant=ST.MUXQ_SERVE,
                                    qparams=SP.synthetic_qparams(cfg))
        jf = jax.jit(step, in_shardings=(pspecs, SH.batch_specs(mesh, abs_b)))
        lowered = jf.lower(abs_p, abs_b)
    else:
        abs_p = abs_params(jnp.bfloat16)
        pspecs = SH.param_specs(cfg, abs_p, mesh, fsdp=True)
        abs_b = SP.decode_specs_abstract(cfg, shape)
        bspecs = {{"tokens": SH.batch_specs(mesh, {{"t": abs_b["tokens"]}})["t"],
                  "cache": SH.cache_specs(cfg, mesh, abs_b["cache"])}}
        step = ST.make_serve_step(cfg, quant=ST.MUXQ_SERVE,
                                  qparams=SP.synthetic_qparams(cfg))
        jf = jax.jit(step, in_shardings=(pspecs, bspecs))
        lowered = jf.lower(abs_p, abs_b)

    compiled = lowered.compile()
    from repro.launch.dryrun import _cost_dict
    cost = _cost_dict(compiled)
    coll = H.collective_bytes(compiled.as_text())
    assert cost.get("flops", 0) > 0 or mode == "decode"
    print("ok", cost.get("flops", 0), coll["total"])
    """
    run_with_devices(code)


def test_collective_bytes_parser():
    from repro.analysis.hlo import collective_bytes, shape_bytes
    assert shape_bytes("bf16[16,128]") == 16 * 128 * 2
    assert shape_bytes("(f32[8,8], s8[4])") == 8 * 8 * 4 + 4
    hlo = """
      %ag = f32[64,128]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}, dimensions={0}
      %ar = bf16[32]{0} all-reduce(%y), replica_groups=[8,4]<=[32]
      %cp = s8[16]{0} collective-permute(%z), source_target_pairs={{0,1}}
    """
    out = collective_bytes(hlo)
    assert out["all-gather"] == pytest.approx(64 * 128 * 4 * 3 / 4)
    assert out["all-reduce"] == pytest.approx(32 * 2 * 2 * 3 / 4)
    assert out["collective-permute"] == 16
    assert out["total"] == sum(out[k] for k in
                               ("all-gather", "all-reduce", "reduce-scatter",
                                "all-to-all", "collective-permute"))


def test_roofline_model():
    from repro.analysis.roofline import make_roofline, param_count
    from repro.configs import get_config
    cfg = get_config("qwen2-0.5b")
    n = param_count(cfg)
    assert 0.2e9 < n < 0.6e9, n      # ~0.35B non-embedding params
    r = make_roofline({"flops": 1e15, "bytes accessed": 1e12},
                      {"total": 1e11}, cfg, tokens=4096 * 256, mode="train",
                      chips=256)
    assert r.compute_s == pytest.approx(1e15 / 197e12)
    assert r.memory_s == pytest.approx(1e12 / 819e9)
    assert r.collective_s == pytest.approx(1e11 / 50e9)
    assert r.dominant == "compute"
    assert 0 < r.mfu_bound < 1


def test_moe_param_count_active_vs_total():
    from repro.analysis.roofline import param_count
    from repro.configs import get_config
    cfg = get_config("dbrx-132b")
    assert param_count(cfg, active_only=True) < param_count(cfg)
