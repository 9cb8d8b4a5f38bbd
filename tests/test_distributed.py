"""Distribution-layer tests: sharding rules, compressed collectives,
hierarchical psum, ring collective-matmul — on 8 virtual CPU devices via a
subprocess (the 512-device flag must never leak into the main test process).
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


def run_with_devices(code: str, n: int = 8) -> str:
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={n}",
           "PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin"}
    import os
    env["PATH"] = os.environ.get("PATH", env["PATH"])
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_fit_spec_drops_nondivisible():
    from jax.sharding import PartitionSpec as P
    code = """
    import jax
    from repro.parallel.sharding import fit_spec
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    print(fit_spec(mesh, (16, 64), ("data", "model")))
    print(fit_spec(mesh, (3, 64), ("data", "model")))     # 3 % 2 != 0 -> drop
    print(fit_spec(mesh, (8, 6), (("data",), "model")))   # 6 % 4 != 0 -> drop
    """
    out = run_with_devices(code).strip().splitlines()
    assert out[0] == "PartitionSpec('data', 'model')"
    assert out[1] == "PartitionSpec(None, 'model')"
    assert out[2] in ("PartitionSpec('data',)", "PartitionSpec('data', None)")


def test_param_specs_cover_all_leaves():
    code = """
    import jax, json
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.parallel.sharding import param_specs
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    for arch in ["qwen2-0.5b", "dbrx-132b", "mamba2-370m", "whisper-tiny", "zamba2-1.2b"]:
        cfg = get_config(arch)
        abs_p = jax.eval_shape(lambda c=cfg: T.init_params(c, jax.random.PRNGKey(0)))
        specs = param_specs(cfg, abs_p, mesh, fsdp=True)
        n = len(jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "spec")))
        n_p = len(jax.tree.leaves(abs_p))
        assert n == n_p, (arch, n, n_p)
        # the big matmul weights must actually be model-sharded
        sharded = sum("model" in str(s.spec) for s in jax.tree.leaves(
            specs, is_leaf=lambda x: hasattr(x, "spec")))
        print(arch, n, sharded)
        assert sharded >= 3, arch
    """
    run_with_devices(code)


def test_compressed_psum_and_error_feedback():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.launch.mesh import make_host_mesh
    from repro.optim.compress import ef_compressed_psum
    mesh = make_host_mesh(data=8)

    @partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
             out_specs=(P("data"), P("data")), check_vma=False)
    def run(g, err):
        tot, new_err = ef_compressed_psum(g[0], err[0], "data")
        return tot[None], new_err[None]

    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
    err = jnp.zeros((8, 64), jnp.float32)
    total, err1 = run(g, err)
    exact = np.asarray(g).sum(axis=0)
    got = np.asarray(total[0])
    rel = np.abs(got - exact).max() / (np.abs(exact).max() + 1e-9)
    assert rel < 0.05, rel                      # int8-accurate single shot
    # error feedback: residual + quantized == original (per shard, exact)
    # and accumulating over steps keeps the bias bounded
    errs = []
    e = err
    for step in range(20):
        total, e = run(g, e)
        errs.append(float(jnp.abs(e).max()))
    assert max(errs) < float(jnp.abs(g).max()), "EF residual must stay bounded"
    print("ok", rel)
    """
    run_with_devices(code)


def test_hierarchical_psum_matches_flat():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.parallel.collectives import hierarchical_psum
    mesh = jax.make_mesh((2, 4), ("pod", "data"))

    @partial(shard_map, mesh=mesh, in_specs=P(("pod", "data")),
             out_specs=P(("pod", "data")), check_vma=False)
    def run(x):
        return hierarchical_psum(x, "data", "pod")

    x = jnp.arange(8 * 6 * 5, dtype=jnp.float32).reshape(8, 6, 5)
    out = run(x)
    exact = np.asarray(x).sum(axis=0, keepdims=True).repeat(8, 0).reshape(8, 6, 5)
    np.testing.assert_allclose(np.asarray(out), exact, rtol=1e-6)
    print("ok")
    """
    run_with_devices(code)


def test_ring_allgather_matmul_exact():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.parallel.collectives import allgather_matmul, ring_allreduce_reference
    mesh = jax.make_mesh((4,), ("tp",))
    m, k, n = 16, 32, 24
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))

    @partial(shard_map, mesh=mesh, in_specs=(P("tp"), P(None, "tp")),
             out_specs=P(None, "tp"), check_vma=False)
    def run(xs, ws):
        return allgather_matmul(xs, ws, "tp")

    out = run(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w), atol=1e-4)

    @partial(shard_map, mesh=mesh, in_specs=P("tp"), out_specs=P("tp"),
             check_vma=False)
    def rr(xs):
        return ring_allreduce_reference(xs, "tp")
    v = jax.random.normal(jax.random.PRNGKey(2), (4, 7))
    np.testing.assert_allclose(np.asarray(rr(v)),
                               np.asarray(v).sum(0, keepdims=True).repeat(4, 0),
                               rtol=1e-5)
    print("ok")
    """
    run_with_devices(code)


def test_elastic_checkpoint_resharding():
    """Save on a (4, 2) mesh, restore onto (2, 4) — leaves land with the new
    shardings (elastic rescale)."""
    code = """
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from repro.checkpoint import ckpt
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.parallel.sharding import param_specs
    cfg = get_config("qwen2-0.5b", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    d = tempfile.mkdtemp()
    mesh_a = jax.make_mesh((4, 2), ("data", "model"))
    pa = jax.device_put(params, param_specs(cfg, params, mesh_a, fsdp=True))
    ckpt.save(d, 1, pa)
    mesh_b = jax.make_mesh((2, 4), ("data", "model"))
    specs_b = param_specs(cfg, params, mesh_b, fsdp=True)
    pb, _, _ = ckpt.restore(d, 1, params, shardings=specs_b)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("ok")
    """
    run_with_devices(code)


def test_small_mesh_train_step_runs():
    """Actually EXECUTE a sharded train step on 8 devices (2x4) — the same
    step function the dry-run lowers at 256/512."""
    code = """
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.steps import make_train_step
    from repro.models import transformer as T
    from repro.optim import adamw
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.sharding import param_specs, batch_specs
    cfg = get_config("qwen2-0.5b", reduced=True)
    mesh = make_host_mesh(data=2, model=4)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    pspecs = param_specs(cfg, params, mesh, fsdp=True)
    params = jax.device_put(params, pspecs)
    opt = adamw.init_state(params)
    ospecs = {"mu": pspecs, "nu": pspecs, "step": None}
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab_size)}
    step = jax.jit(make_train_step(cfg), in_shardings=(pspecs, ospecs, batch_specs(mesh, batch)))
    p2, o2, m = step(params, opt, batch)
    assert jnp.isfinite(m["loss"]), m
    print("loss", float(m["loss"]))
    """
    run_with_devices(code)


def test_pipeline_parallel_exact():
    """GPipe schedule over 4 stages == unpipelined layer stack, exactly."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.parallel.pipeline import pipeline_apply, split_stages, microbatch

    n_stages, L, n_micro, mb, d = 4, 8, 4, 2, 16
    mesh = jax.make_mesh((n_stages,), ("stage",))
    ws = jax.random.normal(jax.random.PRNGKey(0), (L, d, d)) * 0.3

    def block_fn(stage_ws, x):   # stage_ws [L/S, d, d]
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, stage_ws)
        return x

    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro * mb, d))

    # reference: plain stack
    ref = block_fn(ws, x)

    staged = split_stages(ws, n_stages)
    xm = microbatch(x, n_micro)

    @partial(shard_map, mesh=mesh,
             in_specs=(P("stage"), P(None)), out_specs=P(None),
             check_vma=False)
    def run(stage_ws, xm):
        out = pipeline_apply(block_fn, jax.tree.map(lambda w: w[0], stage_ws), xm, "stage")
        return out

    out = run(staged, xm).reshape(n_micro * mb, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    print("pipeline exact")
    """
    run_with_devices(code, n=4)


def test_pipeline_bubble_schedule_shapes():
    from repro.parallel.pipeline import microbatch, split_stages
    import jax.numpy as jnp
    x = jnp.zeros((8, 3))
    assert microbatch(x, 4).shape == (4, 2, 3)
    ws = {"w": jnp.zeros((8, 5))}
    st = split_stages(ws, 2)
    assert st["w"].shape == (2, 4, 5)


# -- fit_spec / cache_specs edge cases (PR 9) --------------------------------


def test_fit_spec_single_device_degeneracy():
    """A 1x1 mesh divides everything: axis names survive in the spec but
    every shard is the full array (replicated in effect)."""
    code = """
    import jax, numpy as np
    from jax.sharding import NamedSharding
    from repro.parallel.sharding import fit_spec
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    spec = fit_spec(mesh, (3, 7), ("data", "model"))     # odd dims still fit
    print(spec)
    sh = NamedSharding(mesh, spec)
    print(sh.shard_shape((3, 7)))
    """
    out = run_with_devices(code, n=1).strip().splitlines()
    assert out[0] == "PartitionSpec('data', 'model')"
    assert out[1] == "(3, 7)"


def test_fit_spec_multipod_partial_divide():
    """("pod","data","model") mesh: a compound dp request keeps the greedy
    prefix of axes that divide and drops the rest — and an axis consumed by
    one dim is not reused by a later dim."""
    code = """
    import jax
    from repro.parallel.sharding import fit_spec
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    # full divide: batch over (pod, data), heads over model
    print(fit_spec(mesh, (8, 4, 64), (("pod", "data"), None, "model")))
    # 6 % (pod*data)=4 fails after pod: keep the dividing prefix only
    print(fit_spec(mesh, (6, 64), (("pod", "data"), "model")))
    # axis reuse: "model" consumed by dim 0 is unavailable to dim 1
    print(fit_spec(mesh, (8, 8), ("model", "model")))
    """
    out = run_with_devices(code, n=8).strip().splitlines()
    assert out[0] == "PartitionSpec(('pod', 'data'), None, 'model')"
    assert out[1] == "PartitionSpec('pod', 'model')"
    assert out[2] == "PartitionSpec('model', None)"


def test_cache_specs_nondividing_heads_fall_back_to_seq():
    """kv-head counts that don't divide the model axis shard the cache on
    the SEQUENCE dim instead (flash-decoding style), never silently
    replicate; dividing counts shard the head dim."""
    code = """
    import jax, json
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.parallel.sharding import cache_specs
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    base = get_config("gpt2-small", reduced=True)
    L, b, s, dh = base.n_layers, 2, 8, base.d_model // base.n_heads
    for kvh in (4, 3):
        cfg = base.replace(n_kv_heads=kvh)
        tree = {"k": jax.ShapeDtypeStruct((L, b, s, kvh, dh), jnp.bfloat16),
                "v": jax.ShapeDtypeStruct((L, b, s, kvh, dh), jnp.bfloat16),
                "pos": jax.ShapeDtypeStruct((b,), jnp.int32)}
        specs = cache_specs(cfg, mesh, tree)
        print(kvh, specs["k"].spec, specs["pos"].spec)
    """
    out = run_with_devices(code, n=4).strip().splitlines()
    # kvh=4 divides model=2: head-sharded
    assert out[0] == "4 PartitionSpec(None, 'data', None, 'model', None) " \
                     "PartitionSpec(None,)"
    # kvh=3 doesn't: sequence-sharded fallback
    assert out[1] == "3 PartitionSpec(None, 'data', 'model', None, None) " \
                     "PartitionSpec(None,)"
