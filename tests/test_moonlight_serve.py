"""Moonlight's block served through the paged engine, against the plain
reference (``bench/reference/moonlight.py``) at a test size with every
mechanism present: width 64, 4 heads, a 32-wide latent and a 16-wide
rotary key (nope 16, v 16), a leading dense layer, then expert layers of
16 experts, 4 held (experts 4-7), 3 per token, one shared expert;
vocabulary 512.

Logits are compared per position of a 40-token prompt prefilled in chunks
of 8 and then 8 tokens decoded through the pages (teacher-forced), as the
largest logit error at each position.  A quantized router input sits near
a routing tie at a few positions, where a different expert is chosen and
that position's output moves wholesale (as it does for the reference's
own lower-precision control); so the lossy cases compare the median
position, and the exact case every position."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paged_testlib as PT
from repro.core.context import FpCtx
from repro.core.muxq import QuantConfig
from repro.core.policy import SitePolicy
from repro.kernels import dispatch
from repro.kernels import paged_attention as PA
from repro.models import attention as A
from repro.models import moe as E
from repro.models import transformer as T
from repro.quantize import quantize_model
from repro.serve.engine import Request, ServeEngine

REPO = Path(__file__).resolve().parents[1]


def _ref():
    m = importlib.util.spec_from_file_location(
        "moonlight_ref_serve", REPO / "bench" / "reference" / "moonlight.py")
    mod = importlib.util.module_from_spec(m)
    m.loader.exec_module(mod)
    return mod


REF = _ref()
SPEC = json.loads((REPO / "tests" / "bench" / "data"
                   / "tiny-moonlight-muxq.json").read_text())
CFG = REF.model_config(SPEC)
PROMPT, DECODED, CHUNK = 40, 8, 8
IDS = np.random.default_rng(1).integers(0, 256, PROMPT + DECODED).astype(
    np.int32)

# Each tolerance bounds the error at the median position (the exact case:
# at every position) and says why it is that size.
TOL = {
    # float32 weights and pages: only the summation order differs (the
    # absorbed latent attention against the reference's expanded one)
    ("f32", "fp"): 1e-4,
    # int8 latent rows: every cached value rounded to 1/254 of its row's
    # absmax (measured median 0.027)
    ("f32", "int8"): 0.06,
    # bfloat16 weights, activations and pages: 8 mantissa bits on every
    # product (measured medians 0.037 fp, 0.043 int8 pages); the reference
    # one precision lower (int8 everything) reads 0.105
    ("bf16", "fp"): 0.07,
    ("bf16", "int8"): 0.07,
    # MUXQ W8A8 on every projection (measured median 0.11, the reference's
    # int8 control 0.105); one precision lower (int4) reads 1.67
    ("muxq", "fp"): 0.25,
    ("muxq", "int8"): 0.25,
}


@pytest.fixture(scope="module")
def params():
    return REF.init_weights(SPEC, 0, jnp.float32)


@pytest.fixture(scope="module")
def want(params):
    return np.asarray(REF.forward(SPEC, params, jnp.asarray(IDS[None])))[0]


@pytest.fixture(scope="module")
def artifact(params):
    rng = np.random.default_rng(0)
    calib = [{"tokens": rng.integers(0, 256, (2, 64)).astype(np.int32)}
             for _ in range(2)]
    return quantize_model(CFG, params, calib,
                          SitePolicy.uniform(QuantConfig(**SPEC["quant"])),
                          pack_target="fused")


@pytest.fixture
def interpret():
    """The Pallas kernels (grouped muxq_gemm, latent paged attention) in
    interpret mode, as the chip runs them."""
    prev = dispatch.set_fused_impl("interpret"), PA.set_paged_impl(
        "interpret")
    yield
    dispatch.set_fused_impl(prev[0])
    PA.set_paged_impl(prev[1])


def paged_logits(eng, ids, *, verify_at=None):
    """Logits at every position of ``ids``: the prompt prefilled through the
    pages in chunks of CHUNK, then one token per decode step (or, from
    ``verify_at`` on, one verify block over the rest)."""
    cfg, pool, ctx = eng.cfg, eng.pool, eng.ctx
    n, slot = len(ids), 0
    assert pool.admit(slot, n)
    tab = np.zeros_like(pool.page_table)
    tab[slot] = pool.page_table[slot]
    tab = jnp.asarray(tab)
    kv, out = pool.state(), []
    rows = np.zeros(pool.n_slots, np.int32)

    def at(v):
        r = rows.copy()
        r[slot] = v
        return jnp.asarray(r)

    def step(fn):
        return jax.jit(lambda w, *a: fn(cfg, w[0], *a, ctx, qparams=w[1]))

    prefill, decode, verify = (step(T.prefill_chunk_paged),
                               step(T.decode_step_paged),
                               step(T.decode_verify_paged))
    prompt = n - DECODED
    for s0 in range(0, prompt, CHUNK):
        c = min(CHUNK, prompt - s0)
        toks = np.zeros((pool.n_slots, CHUNK), np.int32)
        toks[slot, :c] = ids[s0:s0 + c]
        lg, kv = prefill(eng.weights, jnp.asarray(toks), kv, tab, at(s0),
                         at(s0), at(s0 + c))
        out.append(np.asarray(lg[slot, :c]))
    end = n if verify_at is None else verify_at
    for p in range(prompt, end):
        toks = np.zeros((pool.n_slots, 1), np.int32)
        toks[slot, 0] = ids[p]
        lg, kv = decode(eng.weights, jnp.asarray(toks), kv, tab, at(p))
        out.append(np.asarray(lg[slot]))
    if verify_at is not None:
        k = n - verify_at
        toks = np.zeros((pool.n_slots, k), np.int32)
        toks[slot] = ids[verify_at:]
        lg, kv = verify(eng.weights, jnp.asarray(toks), kv, tab,
                        at(verify_at), at(k))
        out.append(np.asarray(lg[slot]))
    pool.adopt(kv)
    pool.release(slot)
    return np.concatenate(out)[:, :cfg.vocab_size]


def _engine(kind, mode, params, artifact):
    if kind == "bf16":
        cfg = CFG.replace(dtype="bfloat16")
        weights = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        return ServeEngine(cfg, weights, max_batch=2, s_max=64, kv_mode=mode,
                           cache_dtype=jnp.bfloat16)
    return ServeEngine(CFG, artifact if kind == "muxq" else params,
                       max_batch=2, s_max=64, kv_mode=mode,
                       cache_dtype=jnp.float32)


def _errors(got, want):
    return np.abs(got - want).max(-1)         # per position


@pytest.mark.parametrize("kind,mode", list(TOL))
def test_engine_logits_match_reference(kind, mode, params, artifact, want,
                                       interpret):
    err = _errors(paged_logits(_engine(kind, mode, params, artifact), IDS),
                  want)
    if (kind, mode) == ("f32", "fp"):
        assert err.max() <= TOL[kind, mode], err.max()
    else:
        assert np.median(err) <= TOL[kind, mode], np.median(err)


def test_tolerances_fail_one_precision_lower(params, want):
    """Each tolerance above fails for a run one precision lower than its
    case: the f32 case's for bfloat16, the bfloat16 cases' for the
    reference at int8, MUXQ's for the reference at int4."""
    bf16 = _errors(paged_logits(_engine("bf16", "fp", params, None), IDS),
                   want)
    assert bf16.max() > TOL["f32", "fp"]
    for bits, kind in ((8, "bf16"), (4, "muxq")):
        low = np.asarray(REF.forward(SPEC, params, jnp.asarray(IDS[None]),
                                     bits))[0]
        assert np.median(_errors(low, want)) > TOL[kind, "int8"], bits


def test_verify_block_matches_sequential_decode(params):
    """A verify block over the last 4 tokens scores them as one decode
    step each would."""
    eng = _engine("f32", "fp", params, None)
    seq = paged_logits(eng, IDS)
    blk = paged_logits(eng, IDS, verify_at=len(IDS) - 4)
    np.testing.assert_allclose(blk, seq, atol=1e-5)


def test_generate_matches_reference_greedy(params):
    """The scheduler's own loop (chunked multi-slot prefill, pooled decode)
    streams the reference's greedy tokens, and the steps return their
    held-expert rows with the tokens."""
    eng = ServeEngine(CFG, params, max_batch=3, s_max=96, kv_mode="fp",
                      cache_dtype=jnp.float32, prefill_chunk=8)
    prompts = ["latent pages hold one row", "experts " * 4, "zq"]
    reqs = [Request(p, max_new_tokens=6) for p in prompts]
    eng.generate(reqs)
    from repro.data import tokenizer as tok
    for r in reqs:
        ids = np.concatenate([tok.encode(r.prompt), r.out_tokens])
        lg = REF.forward(SPEC, params, jnp.asarray(ids[None], jnp.int32))[0]
        n = len(ids) - len(r.out_tokens)
        assert list(np.asarray(jnp.argmax(lg[n - 1:-1], -1))) == r.out_tokens
    m = eng.metrics
    # one count per expert layer (2 of the 3) in every step call
    assert m.moe_expert_calls == 2 * (m.decode_steps + m.prefill_steps)
    # live tokens only: at most top 3 of 4 held per prompt and decoded token
    tokens = sum(len(tok.encode(p)) for p in prompts) + 3 * 6
    assert 0 < m.moe_rows_held <= 2 * 3 * tokens


def test_absorbed_equals_expanded(params):
    """One latent attention layer, float32: the program's absorbed form
    (queries through W_UK, the latent as key and value, W_UV after) equals
    the expanded form (per-head keys and values from the latent)."""
    lp = T.layer_slice(params["layers"], 0)["attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, CFG.d_model))
    pos = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))
    absorbed, _ = A.attention(CFG, lp, FpCtx(), x, pos)
    h, r = CFG.n_heads, CFG.kv_lora_rank
    nope, rope, v = CFG.qk_nope_dim, CFG.qk_rope_dim, CFG.v_head_dim
    with jax.default_matmul_precision("highest"):
        qc = x @ lp["wq"]
        q = qc[..., :h * (nope + rope)].reshape(2, 24, h, nope + rope)
        c = qc[..., h * (nope + rope):]
        c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + 1e-6) * (
            1 + lp["kv_norm"]["gain"])
        from repro.models.common import apply_rope
        k_pe = apply_rope((x @ lp["wkpe"])[:, :, None], pos, CFG.rope_theta)
        kv = (c @ lp["wkv_b"]).reshape(2, 24, h, nope + v)
        q = jnp.concatenate([q[..., :nope],
                             apply_rope(q[..., nope:], pos, CFG.rope_theta)], -1)
        k = jnp.concatenate([kv[..., :nope],
                             jnp.broadcast_to(k_pe, (2, 24, h, rope))], -1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (nope + rope) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((24, 24), bool)), s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., nope:])
        expanded = o.reshape(2, 24, h * v) @ lp["wo"]
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-5)


@pytest.mark.parametrize("mode", ["fp", "int8"])
def test_latent_kernel_matches_reference(mode):
    """The paged kernel on latent pages (values = the first v_dim columns
    of the key rows, one scale per position) against the gather
    reference, over ragged slots and a 3-row query block."""
    rng = np.random.default_rng(0)
    slots, pages, ps, h, width, vd = 3, 4, 8, 4, 48, 32
    n_pages = slots * pages + 1
    rows = rng.standard_normal((n_pages, 1, ps, width)).astype(np.float32)
    kw = {}
    if mode == "int8":
        scale = np.abs(rows).max(-1, keepdims=True) / 127
        kw["k_scale"] = jnp.asarray(scale)
        rows = np.round(rows / scale).astype(np.int8)
    table = jnp.asarray(1 + np.arange(slots * pages).reshape(slots, pages),
                        jnp.int32)
    pos = jnp.asarray([0, 9, 28], jnp.int32)
    q = jnp.asarray(rng.standard_normal((slots, 3, h, width)), jnp.float32)
    args = (q, jnp.asarray(rows), None, table, pos)
    ref = PT.run(PA.paged_attention_ref, *args, v_dim=vd, scale=0.3, **kw)
    got = PT.run(PA.paged_attention_pallas, *args, v_dim=vd, scale=0.3,
                 interpret=True, **kw)
    assert got.shape == (slots, 3, h, vd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_expert_shares_add_up(params):
    """``model-configs`` §4: four chips holding experts 0-3, 4-7, 8-11 and
    12-15 each compute their experts' part; with the shared expert counted
    once, the parts add up to the uncut reference layer, which holds all 16
    experts."""
    m = T.layer_slice(params["layers"], 0)["moe"]
    uncut = dict(m, wi=jnp.tile(m["wi"], (4, 1, 1)),
                 wo=jnp.tile(m["wo"], (4, 1, 1)) * jnp.repeat(
                     jnp.arange(1.0, 5.0), 4)[:, None, None])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 12, CFG.d_model))
    spec = dict(SPEC, n_routed_experts=16, experts_held_from=0)
    with jax.default_matmul_precision("highest"):
        whole = REF.expert_layer(spec, uncut, x)
        shared = REF._swiglu(x, m["shared"]["wi"], m["shared"]["wo"], None)
    parts = []
    for lo in range(0, 16, 4):
        cfg = CFG.replace(expert_lo=lo, n_held=4)
        share = dict(uncut, wi=uncut["wi"][lo:lo + 4],
                     wo=uncut["wo"][lo:lo + 4])
        y, held, _ = E.moe_held(cfg, share, FpCtx(), x)
        parts.append(np.asarray(y))
    total = sum(parts) - 3 * np.asarray(shared)
    np.testing.assert_allclose(total, np.asarray(whole), atol=2e-5)
    # each pair is held by exactly one share
    counts = [int(E.moe_held(CFG.replace(expert_lo=lo, n_held=4),
                             dict(uncut, wi=uncut["wi"][lo:lo + 4],
                                  wo=uncut["wo"][lo:lo + 4]),
                             FpCtx(), x)[1]) for lo in range(0, 16, 4)]
    assert sum(counts) == 2 * 12 * CFG.top_k
