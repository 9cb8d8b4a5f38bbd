"""Chip smoke: the quantized serving path at GPT-2 small's published widths.

Drives calibrate -> ``quantize_model`` -> ``ServeEngine`` -> scheduler ->
fused MUXQ GEMM and paged attention on a TPU, with weights drawn from a
seed (no trained GPT-2 weights ship with the repository), in four phases:

  int8   fused MUXQ weights, int8 KV pages
  int4   fused MUXQ weights, int4 (MUXQ-redistributed) KV pages
  spec   fused MUXQ weights, int8 pages, n-gram speculation (verify step)
  bf16   bf16 weights and activations, bf16 KV pages

Ten prompts of 160-330 tokens share a 160-token system prefix (prefix
sharing), and the pool holds fewer pages than they grow to (preemption).
Each phase checks that every request produced its tokens, that each step
program compiled once per shape bucket, that the compiled steps hold the
Pallas kernels, and that the engine agrees with a reference on the same
chip, teacher-forced on the engine's own token streams:

  * quantized phases: the same model step with the jnp implementations
    (``ref``) in place of the Pallas kernels;
  * bf16 phase: ``T.forward`` in float32 under
    ``jax.default_matmul_precision("highest")``.

Tolerance: the relative L2 error of the logits over every compared
position stays under ``LOGIT_RTOL`` = 0.05, the bound the repo's own
int8-page parity test holds (``tests/test_serve_pool.py``).  The
quantized paths do the same integer products on both sides and differ only
in float order and bf16 rounding inside the kernels; the bf16 phase
rounds every activation to 8 significant bits across 12 layers, which
moves logits by a few tenths of a percent up to a few percent.  Each
generated token must equal the reference's argmax wherever the
reference's lead over its runner-up exceeds ``MARGIN_FRAC`` = 0.2 of the
position's logit RMS — four times the per-logit error the bound admits —
so a near-tie may break either way, and nothing else may.

``--four-chips`` runs only the tensor-parallel path: the int8 phase's
requests at tp=4 and at tp=1, whose token streams must be identical.

Usage (from the repository root, one process for the chip):

    python chip_smoke.py               # one chip, the four phases
    python chip_smoke.py --four-chips  # four chips, tp=4 vs tp=1

Any failure exits non-zero.  On success the last line of standard output
is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import string
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "gpt2-small"
SEED = 0
MAX_BATCH, S_MAX, PAGE_SIZE, CHUNK = 8, 1024, 16, 32
N_REQUESTS, MAX_NEW = 10, 24
# the 10 sequences grow to 11-23 pages each, 10 of them the shared system
# prefix; a 40-page pool runs out while they decode (preemption)
N_PAGES = 40
LOGIT_RTOL = 0.05
MARGIN_FRAC = 0.2


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------

def make_prompts(seed=SEED, n=N_REQUESTS):
    """A 159-char system prefix (160 tokens with BOS: 10 whole pages) plus a
    per-request tail of 0-188 chars; some tails repeat a phrase, so the
    n-gram proposer finds drafts."""
    rng = np.random.default_rng(seed)

    def words(k):
        return " ".join("".join(rng.choice(list(string.ascii_lowercase),
                                           rng.integers(2, 8)))
                        for _ in range(k))

    system = (words(40) + " " * 200)[:159]
    lens = [0] + [int(x) for x in rng.integers(20, 189, n - 1)]
    prompts = []
    for i, m in enumerate(lens):
        tail = words(40)
        if i % 2:
            tail = " ".join([words(3)] * 12)
        prompts.append(system + tail[:m])
    return prompts


def requests(prompts):
    from repro.serve.engine import Request
    return [Request(p, max_new_tokens=MAX_NEW) for p in prompts]


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

_COMPILE_S = [0.0]


def _on_event(event, duration, **kw):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


def memory_line():
    stats = jax.devices()[0].memory_stats() or {}
    gib = 1 << 30
    return (f"device memory: {stats.get('bytes_in_use', 0) / gib:.3f} GiB in "
            f"use, peak {stats.get('peak_bytes_in_use', 0) / gib:.3f} GiB of "
            f"{stats.get('bytes_limit', 0) / gib:.3f} GiB")


# ---------------------------------------------------------------------------
# one phase
# ---------------------------------------------------------------------------

def serve(cfg, src, prompts, **kw):
    """Run the requests through a fresh engine; returns (engine, requests,
    host seconds, compile seconds)."""
    from repro.serve.engine import ServeEngine
    eng = ServeEngine(cfg, src, max_batch=MAX_BATCH, s_max=S_MAX,
                      page_size=PAGE_SIZE, n_pages=N_PAGES,
                      prefill_chunk=CHUNK, **kw)
    reqs = requests(prompts)
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    eng.generate(reqs)
    return eng, reqs, time.perf_counter() - t0, _COMPILE_S[0] - c0


def check_served(name, eng, reqs, *, spec=False):
    from repro.data import tokenizer as tok
    for i, r in enumerate(reqs):
        n = len(r.out_tokens)
        check(r.done and (n == MAX_NEW or (n and r.out_tokens[-1] == tok.EOS)),
              f"{name}: request {i} finished with {n}/{MAX_NEW} tokens")
        check(all(0 <= t < eng.cfg.vocab_size for t in r.out_tokens),
              f"{name}: request {i} produced an id outside the vocabulary")
    check(eng.decode_traces == len(eng.decode_buckets),
          f"{name}: decode traced {eng.decode_traces}x for "
          f"{len(eng.decode_buckets)} buckets")
    check(eng.prefill_traces == len(eng.prefill_buckets),
          f"{name}: prefill traced {eng.prefill_traces}x for "
          f"{len(eng.prefill_buckets)} buckets")
    check(eng.verify_traces == len(eng.verify_buckets),
          f"{name}: verify traced {eng.verify_traces}x for "
          f"{len(eng.verify_buckets)} buckets")
    rep = eng.metrics.report()
    check(rep["prefix_hits"] >= 1, f"{name}: no prefix was shared")
    check(rep["preemptions"] >= 1, f"{name}: nothing was preempted")
    if spec:
        check(rep["spec_verify_steps"] >= 1, f"{name}: verify never ran")
    check(eng.pool.pages_in_use == 0, f"{name}: pages leaked after the run")
    return rep


def _abstract(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        tree)


def step_kernels(eng):
    """Pallas kernels in each compiled step program, lowered at a bucket
    the run used (the persistent compile cache serves the executable)."""
    from repro.analysis.hlo import tpu_kernels
    n = eng.pool.n_slots
    i32 = jnp.int32
    kv = _abstract(eng.pool.state())
    vec = jax.ShapeDtypeStruct((n,), i32)
    out = {}
    pb = max(eng.decode_buckets)
    out["decode"] = eng._decode.lower(
        eng.weights, jax.ShapeDtypeStruct((n, 1), i32), kv,
        jax.ShapeDtypeStruct((n, pb), i32), vec)
    cb, pb = max(eng.prefill_buckets)
    out["prefill"] = eng._prefill_step.lower(
        eng.weights, jax.ShapeDtypeStruct((n, cb), i32), kv,
        jax.ShapeDtypeStruct((n, pb), i32), vec, vec, vec)
    if eng.verify_buckets:
        kb, pb = max(eng.verify_buckets)
        out["verify"] = eng._verify_step.lower(
            eng.weights, jax.ShapeDtypeStruct((n, kb), i32), kv,
            jax.ShapeDtypeStruct((n, pb), i32), vec, vec)
    return {k: tpu_kernels(v.compile().as_text()) for k, v in out.items()}


# ---------------------------------------------------------------------------
# teacher-forced reference comparison
# ---------------------------------------------------------------------------

def _sequences(reqs):
    from repro.data import tokenizer as tok
    seqs, n_prompt = [], []
    for r in reqs:
        ids = tok.encode(r.prompt)
        seqs.append(np.concatenate([ids, np.asarray(r.out_tokens, np.int32)]))
        n_prompt.append(len(ids))
    return seqs, n_prompt


def paged_logits(eng, seqs, kv_calib, impl="auto"):
    """Logits at every position of ``seqs`` through the engine's model step
    and page layout (chunked paged prefill into a fresh pool), traced with
    the given kernel implementation (``auto``: the one the engine ran).
    Yields one [MAX_BATCH, CHUNK, V] float32 block per (group, chunk)."""
    from repro.kernels import dispatch
    from repro.kernels import paged_attention as PA
    from repro.models import transformer as T
    from repro.serve.pool import PagePool
    cfg = eng.cfg

    def step(weights, tokens, kv, table, start, lo, hi):
        # the impl switches are read while tracing: set them only here
        prev = dispatch.set_fused_impl(impl), PA.set_paged_impl(impl)
        try:
            logits, kv = T.prefill_chunk_paged(
                cfg, weights[0], tokens, kv, table, start, lo, hi, eng.ctx,
                qparams=weights[1])
        finally:
            dispatch.set_fused_impl(prev[0])
            PA.set_paged_impl(prev[1])
        return logits[..., :cfg.vocab_size].astype(jnp.float32), kv

    step = jax.jit(step, donate_argnums=(2,))
    for g0 in range(0, len(seqs), MAX_BATCH):
        group = seqs[g0:g0 + MAX_BATCH]
        pool = PagePool(cfg, MAX_BATCH, S_MAX, page_size=PAGE_SIZE,
                        mode=eng.pool.mode, dtype=eng.cache_dtype,
                        kv_calib=kv_calib)
        lens = np.zeros(MAX_BATCH, np.int32)
        for i, s in enumerate(group):
            check(pool.admit(i, len(s)), "reference pool too small")
            lens[i] = len(s)
        table = jnp.asarray(pool.page_table)
        t_max = int(lens.max())
        for c0 in range(0, t_max, CHUNK):
            toks = np.zeros((MAX_BATCH, CHUNK), np.int32)
            for i, s in enumerate(group):
                part = s[c0:c0 + CHUNK]
                toks[i, :len(part)] = part
            start = np.full(MAX_BATCH, c0, np.int32)
            hi = np.clip(lens, c0, c0 + CHUNK).astype(np.int32)
            logits, kv = step(eng.weights, jnp.asarray(toks), pool.state(),
                              table, jnp.asarray(start),
                              jnp.asarray(start), jnp.asarray(hi))
            pool.adopt(kv)
            yield g0, c0, logits


def forward_logits(cfg, params, seqs):
    """float32 ``T.forward`` logits at "highest" matmul precision, in the
    same (group, chunk) blocks as :func:`paged_logits`."""
    from repro.data import tokenizer as tok
    from repro.models import transformer as T
    cfg32 = cfg.replace(dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    @jax.jit
    def fwd(params, tokens):
        with jax.default_matmul_precision("highest"):
            out = T.forward(cfg32, params, tokens)
        return out["logits"][..., :cfg.vocab_size].astype(jnp.float32)

    t_max = max(len(s) for s in seqs)
    t_pad = -(-t_max // CHUNK) * CHUNK
    for g0 in range(0, len(seqs), MAX_BATCH):
        toks = np.full((MAX_BATCH, t_pad), tok.PAD, np.int32)
        for i, s in enumerate(seqs[g0:g0 + MAX_BATCH]):
            toks[i, :len(s)] = s
        logits = fwd(p32, jnp.asarray(toks))
        group_max = max(len(s) for s in seqs[g0:g0 + MAX_BATCH])
        for c0 in range(0, group_max, CHUNK):
            yield g0, c0, logits[:, c0:c0 + CHUNK]


@jax.jit
def _compare(test, ref):
    """Per-position reductions of two [b, C, V] logit blocks."""
    top2 = jax.lax.top_k(ref, 2)[0]
    return {"finite": jnp.isfinite(test).all(axis=-1),
            "err2": jnp.sum(jnp.square(test - ref), axis=-1),
            "ref2": jnp.sum(jnp.square(ref), axis=-1),
            "rms": jnp.sqrt(jnp.mean(jnp.square(ref), axis=-1)),
            "margin": top2[..., 0] - top2[..., 1],
            "ref_argmax": jnp.argmax(ref, axis=-1)}


def compare(name, test_blocks, ref_blocks, reqs):
    """Teacher-forced comparison of the engine with its reference over
    every prompt and generated position of every request."""
    seqs, n_prompt = _sequences(reqs)
    err2 = ref2 = 0.0
    checked = agreed = generated = 0
    for (g0, c0, lt), (g1, c1, lr) in zip(test_blocks, ref_blocks):
        check((g0, c0) == (g1, c1), f"{name}: reference blocks misaligned")
        r = jax.device_get(_compare(lt, lr))
        for i, s in enumerate(seqs[g0:g0 + MAX_BATCH]):
            n = min(CHUNK, len(s) - c0)
            if n <= 0:
                continue
            check(r["finite"][i, :n].all(),
                  f"{name}: non-finite logits in request {g0 + i}")
            err2 += float(r["err2"][i, :n].sum())
            ref2 += float(r["ref2"][i, :n].sum())
            # the token generated after position p is out_tokens[p - P + 1]
            for j in range(n):
                p = c0 + j
                k = p - n_prompt[g0 + i] + 1
                if k < 0 or p + 1 >= len(s):
                    continue
                generated += 1
                if r["margin"][i, j] > MARGIN_FRAC * r["rms"][i, j]:
                    checked += 1
                    agreed += int(s[p + 1] == r["ref_argmax"][i, j])
    rel = float(np.sqrt(err2 / ref2))
    log(f"  {name}: logits rel L2 err {rel:.6f} (limit {LOGIT_RTOL}); "
        f"{agreed}/{checked} clear-margin tokens agree, "
        f"{generated - checked} near-ties skipped, of {generated} generated")
    check(rel < LOGIT_RTOL, f"{name}: logits rel err {rel} >= {LOGIT_RTOL}")
    check(checked > 0 and agreed == checked,
          f"{name}: {checked - agreed} of {checked} clear-margin tokens "
          "differ from the reference")


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def check_platform(n_chips):
    from repro.kernels import dispatch, ops
    from repro.kernels import paged_attention as PA
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX runs on {devs[0].platform!r}")
    check(len(devs) >= n_chips, f"needs {n_chips} chips, JAX sees {len(devs)}")
    impls = {"fused": dispatch.fused_impl(), "paged": PA.paged_impl()}
    log(f"resolved impls: {impls}; muxq_linear interpret="
        f"{not ops._on_tpu()}")
    check(impls == {"fused": "pallas", "paged": "pallas"},
          f"kernels would not run as compiled Pallas: {impls}")
    check(ops._on_tpu(), "muxq_linear would run in interpret mode")


def quantize(cfg, params):
    from repro.core.muxq import QuantConfig
    from repro.core.policy import SitePolicy
    from repro.data.pipeline import PipelineConfig, TokenPipeline
    from repro.quantize import quantize_model
    spec = QuantConfig(method="muxq", act_granularity="per_token",
                       outlier_mode="static", backend="fused",
                       weight_granularity="per_channel")
    pipe = TokenPipeline(PipelineConfig(seq_len=64, global_batch=2, seed=SEED))
    return quantize_model(cfg, params, [next(pipe) for _ in range(2)],
                          SitePolicy.uniform(spec))


def phase(name, cfg, src, prompts, ref_fn, kernels, **kw):
    eng, reqs, wall, comp = serve(cfg, src, prompts, **kw)
    rep = check_served(name, eng, reqs, spec=kw.get("spec_mode") == "ngram")
    found = step_kernels(eng)
    log(f"[{name}] {sum(len(r.out_tokens) for r in reqs)} tokens for "
        f"{len(reqs)} requests; host {wall:.1f} s incl. {comp:.1f} s "
        f"compiling; buckets decode {sorted(eng.decode_buckets)} prefill "
        f"{sorted(eng.prefill_buckets)} verify {sorted(eng.verify_buckets)}; "
        f"prefix hits {rep['prefix_hits']}, preemptions "
        f"{rep['preemptions']}, verify steps {rep['spec_verify_steps']}; "
        f"kv pages [{eng.pool.mode}] {eng.pool.cache_bytes()} bytes")
    log(f"  kernels per step: {found}")
    for step, ks in found.items():
        check(all(ks.get(k) for k in kernels),
              f"{name}: compiled {step} step lacks {kernels}: {ks}")
    ref_fn(name, eng, reqs)
    log("  " + memory_line())
    return eng, reqs


def model_config():
    from repro.configs import get_config
    return get_config(ARCH)


def one_chip():
    from repro.models import transformer as T
    cfg = model_config()
    prompts = make_prompts()
    params = T.init_params(cfg, jax.random.PRNGKey(SEED))
    t0 = time.perf_counter()
    art = quantize(cfg, params)
    log(f"calibrate + quantize_model: {time.perf_counter() - t0:.1f} s host")

    def quant_ref(name, eng, reqs):
        seqs, _ = _sequences(reqs)
        compare(name, paged_logits(eng, seqs, art.kv_calib),
                paged_logits(eng, seqs, art.kv_calib, impl="ref"), reqs)

    fused = ("muxq_gemm", "rowwise_quantize", "paged_attention")
    phase("int8", cfg, art, prompts, quant_ref, fused, kv_mode="int8")
    phase("int4", cfg, art, prompts, quant_ref, fused, kv_mode="int4")
    phase("spec", cfg, art, prompts, quant_ref, fused, kv_mode="int8",
          spec_mode="ngram")

    cfg16 = cfg.replace(dtype="bfloat16")
    p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)

    def fp_ref(name, eng, reqs):
        seqs, _ = _sequences(reqs)
        compare(name, paged_logits(eng, seqs, None),
                forward_logits(cfg, params, seqs), reqs)

    phase("bf16", cfg16, p16, prompts, fp_ref, ("paged_attention",),
          kv_mode="fp")


def four_chips():
    from repro.models import transformer as T
    cfg = model_config()
    prompts = make_prompts()
    params = T.init_params(cfg, jax.random.PRNGKey(SEED))
    art = quantize(cfg, params)
    runs = {}
    for tp in (4, 1):
        eng, reqs, wall, comp = serve(cfg, art, prompts, kv_mode="int8",
                                      tp=tp)
        check_served(f"tp={tp}", eng, reqs)
        pool = eng.pool
        found = step_kernels(eng)
        log(f"[tp={tp}] host {wall:.1f} s incl. {comp:.1f} s compiling; "
            f"kv shards {pool.kv_shards}; cache bytes global "
            f"{pool.cache_bytes()} per shard {pool.cache_bytes_per_shard()}; "
            f"kernels per step {found}")
        for step, ks in found.items():
            check(ks.get("paged_attention") and ks.get("muxq_gemm"),
                  f"tp={tp}: compiled {step} step lacks the kernels: {ks}")
        if tp == 4:
            check(pool.heads_sharded and pool.kv_shards == 4,
                  "tp=4 pool is not head-sharded")
            check(pool.cache_bytes_per_shard() * 4 == pool.cache_bytes(),
                  "per-shard cache bytes are not global / 4")
        runs[tp] = [list(r.out_tokens) for r in reqs]
        log("  " + memory_line())
    same = sum(a == b for a, b in zip(runs[4], runs[1]))
    log(f"streams identical at tp=4 and tp=1: {same}/{len(runs[1])}")
    check(same == len(runs[1]), "tp=4 streams differ from tp=1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel path: tp=4 vs tp=1")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    try:
        check_platform(n_chips)
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        (four_chips if args.four_chips else one_chip)()
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
