"""Kernel micro-benchmarks (paper §4.5: INT8 GEMM vs FP16 GEMM).

Wall times on this container are CPU-reference numbers (TPU is the target —
interpret-mode Pallas is NOT timed; we time the jnp int8/fp32 paths and
derive the analytic TPU speedup from the roofline constants)."""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.roofline import PEAK_BF16, PEAK_INT8
from repro.core import quantizers as Q
from repro.kernels import ops

from benchmarks import common


def _time(f, *args, n=5):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / n * 1e6


def run(emit=True):
    rows = []
    m, k, n = 256, 1024, 1024
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.05
    flops = 2 * m * k * n

    f_fp = jax.jit(lambda a, b: a @ b)
    us = _time(f_fp, x, w)
    rows.append((f"kernel/fp32_gemm_{m}x{k}x{n}", us,
                 f"gflops={flops / us / 1e3:.2f}"))

    xi, sx = Q.quantize(x, 8, "per_token")
    wi, sw = Q.quantize(w, 8, "per_channel")
    f_i8 = jax.jit(lambda a, b: Q.int_matmul(a, b))
    us = _time(f_i8, xi, wi)
    rows.append((f"kernel/int8_gemm_{m}x{k}x{n}", us,
                 f"gflops={flops / us / 1e3:.2f}"))

    mask = np.zeros(k, bool)
    mask[:16] = True
    mw = ops.prepare_weights(w, mask, exp_factor=2, bk=128)
    f_muxq = jax.jit(lambda a: ops.muxq_linear_ref(a, mw, 2))
    us = _time(f_muxq, x)
    rows.append((f"kernel/muxq_gemm_jnp_{m}x{k}x{n}", us,
                 f"gflops={flops / us / 1e3:.2f}"))

    # artifact deployment path: QuantCtx over a pre-quantized {"q","s"} leaf
    # (per-site policy resolution + MUXQ int32 channel multiplier, the site
    # math ServeEngine runs per projection)
    from repro.core.context import QuantCtx
    from repro.core.muxq import QuantConfig
    from repro.core.policy import SitePolicy
    policy = SitePolicy.uniform(QuantConfig(
        method="muxq", real_int8=True, outlier_mode="static",
        act_granularity="per_token"))
    ctx = QuantCtx(policy, masks={"site": mask})
    wq = {"q": wi, "s": sw}
    f_site = jax.jit(lambda a: ctx("site", a, wq))
    us = _time(f_site, x)
    rows.append((f"kernel/muxq_prequant_site_{m}x{k}x{n}", us,
                 f"gflops={flops / us / 1e3:.2f}"))

    # unified dispatch entry point (what QuantCtx runs at a fused site):
    # gather/permute + per-token quantize + block-scaled int8 GEMM, oracle impl
    from repro.core.muxq import QuantConfig
    from repro.kernels import dispatch
    buf = dispatch.pack_site_buffer(
        w, mask, QuantConfig(method="muxq", outlier_mode="static",
                             backend="fused"))
    f_disp = jax.jit(lambda a: dispatch.fused_matmul(a, buf, impl="ref"))
    us = _time(f_disp, x)
    rows.append((f"kernel/muxq_dispatch_fused_{m}x{k}x{n}", us,
                 f"gflops={flops / us / 1e3:.2f}"))

    # paged-attention query blocks (the [slot, sq] kernel generalization):
    # timed on the jnp gather reference like everything above — interpret
    # Pallas is a parity tool, not a perf number.  The verify row prices a
    # k-token speculative verify block against the k sequential decode
    # steps it replaces; the prefill row prices one chunked-prefill read
    # through the page table.
    from repro.kernels import paged_attention as PA
    from repro.serve import kvq
    kvh, dh, ps, npg = 4, 64, 16, 16
    rng = jax.random.PRNGKey(2)
    shape = kvq.page_shape(1, npg, ps, kvh, dh)        # the pool's layout
    kp = jax.random.normal(rng, shape)
    vp = jax.random.normal(jax.random.PRNGKey(3), shape)
    bsl, pages = 4, 4                                  # 4 slots x 4 pages
    tab = jnp.arange(bsl * pages, dtype=jnp.int32).reshape(bsl, pages)
    pos = jnp.full((bsl,), pages * ps - 8, jnp.int32)
    f_pa = jax.jit(functools.partial(PA.paged_attention_ref, page_size=ps))
    q1 = jax.random.normal(jax.random.PRNGKey(4), (bsl, kvh, dh))
    us1 = _time(f_pa, q1, kp, vp, tab, pos)
    sk = 4
    qk = jax.random.normal(jax.random.PRNGKey(5), (bsl, sk, kvh, dh))
    usk = _time(f_pa, qk, kp, vp, tab, pos)
    rows.append((f"kernel/paged_verify_k{sk}_b{bsl}", usk,
                 f"vs_{sk}_decode_steps={sk * us1:.1f}us"
                 f"_block_speedup=x{sk * us1 / usk:.2f}"))
    chunk = 64
    qc = jax.random.normal(jax.random.PRNGKey(6), (1, chunk, kvh, dh))
    tab1 = jnp.arange(pages, dtype=jnp.int32)[None]
    usc = _time(f_pa, qc, kp, vp, tab1, jnp.zeros((1,), jnp.int32))
    rows.append((f"kernel/paged_prefill_chunk{chunk}", usc,
                 f"us_per_token={usc / chunk:.2f}"))

    # analytic TPU-target speedup of the MUXQ path (uniform int8 on MXU)
    rows.append(("kernel/tpu_int8_speedup_analytic", 0.0,
                 f"x{PEAK_INT8 / PEAK_BF16:.1f}_over_bf16"))
    # the fused form saves the aux GEMM entirely vs the paper's two-GEMM NPU
    # form: overhead = extra K blocks from padding only
    pad_frac = (mw.pad_out + mw.pad_tail) / k
    rows.append(("kernel/muxq_fused_aux_overhead", 0.0,
                 f"pad_fraction={pad_frac:.3f}_vs_paper_two_gemm=+n_out/K"))
    if emit:
        common.emit(rows)
    return rows


def run_engine(emit=True):
    """Engine-level decode throughput: ServeEngine tokens/sec, fused vs
    fake vs fp backends on one small dense LM (CPU numbers; the backend
    RATIO is the tracked signal, not the absolute wall time)."""
    from repro.configs import get_config
    from repro.core.muxq import QuantConfig
    from repro.core.policy import SitePolicy
    from repro.models import transformer as T
    from repro.quantize import quantize_model
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config("gpt2-small", reduced=True).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=256,
        vocab_size=300)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batches = [{"tokens": rng.integers(0, cfg.vocab_size, (2, 32))}
               for _ in range(2)]
    base = QuantConfig(method="muxq", outlier_mode="static",
                       act_granularity="per_token",
                       weight_granularity="per_channel", real_int8=True,
                       muxq_form="fused")
    engines = {
        "fp": ServeEngine(cfg, params, max_batch=1, s_max=96),
        "fake": ServeEngine(cfg, quantize_model(
            cfg, params, batches, SitePolicy.uniform(base)),
            max_batch=1, s_max=96),
        "fused": ServeEngine(cfg, quantize_model(
            cfg, params, batches,
            SitePolicy.uniform(base.replace(backend="fused"))),
            max_batch=1, s_max=96),
    }
    rows = []
    n_new = 32
    prompt = "the model computes"
    for name, eng in engines.items():
        # warm up with the SAME prompt: prefill compiles per token count,
        # so a different length would put XLA compile inside the timed region
        eng.generate([Request(prompt, max_new_tokens=2)])
        t0 = time.perf_counter()
        reqs = [Request(prompt, max_new_tokens=n_new)]
        eng.generate(reqs)
        dt = time.perf_counter() - t0
        n_tok = len(reqs[0].out_tokens)
        rows.append((f"engine/decode_{name}", dt / n_tok * 1e6,
                     f"tokens_per_sec={n_tok / dt:.1f}"))
    if emit:
        common.emit(rows)
    return rows


if __name__ == "__main__":
    run()
    run_engine()
