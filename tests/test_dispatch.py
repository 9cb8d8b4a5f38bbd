"""Unified kernel dispatch: backend selection, fused/fake/fp parity across
sites, ragged shapes, scan-vs-eager, artifact persistence and the engine
running the fused path end-to-end (interpret mode: CPU validation protocol).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.muxq import QuantConfig
from repro.core.policy import SitePolicy
from repro.kernels import dispatch, ops, ref
from repro.kernels.muxq_gemm import muxq_gemm
from repro.kernels.quantize import rowwise_quantize
from repro.models import transformer as T
from repro.quantize import QuantArtifact, quantize_model

BASE = QuantConfig(method="muxq", outlier_mode="static",
                   act_granularity="per_token",
                   weight_granularity="per_channel", real_int8=True,
                   muxq_form="fused")
FUSED = BASE.replace(backend="fused")


@pytest.fixture(autouse=True)
def _interpret_fused():
    """Interpret-mode Pallas for every fused site (the CPU validation
    protocol); individual tests override via set_fused_impl."""
    prev = dispatch.set_fused_impl("interpret")
    yield
    dispatch.set_fused_impl(prev)


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("gpt2-small", reduced=True).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=256,
        vocab_size=120)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batches = [{"tokens": rng.integers(0, cfg.vocab_size, (2, 16))}
               for _ in range(2)]
    return cfg, params, batches


# ---------------------------------------------------------------------------
# Backend selection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,bn", [(200, 200), (256, 256), (384, 128),
                                  (768, 256), (2304, 256), (3072, 256)])
def test_pick_bn_is_whole_axis_or_lane_multiple(n, bn):
    from repro.kernels.muxq_gemm import pick_bn
    assert pick_bn(n) == bn


def test_pack_rejects_width_without_lane_tile():
    """An out width over one tile with no 128-multiple divisor fails when
    the weight is packed, not later inside the TPU compiler."""
    w = jnp.zeros((64, 300), jnp.float32)
    with pytest.raises(ValueError, match="no column tile"):
        dispatch.pack_site_buffer(w, None, FUSED)


def test_site_backend_resolution():
    assert dispatch.site_backend(QuantConfig(method="fp")) == "fp"
    assert dispatch.site_backend(BASE) == "fake"
    assert dispatch.site_backend(FUSED) == "fused"
    assert dispatch.site_backend(BASE.replace(backend="fp")) == "fp"
    with pytest.raises(ValueError, match="no fused kernel"):
        dispatch.site_backend(QuantConfig(method="llm_int8", backend="fused"))


def test_fused_dynamic_outliers_cannot_pack():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    with pytest.raises(ValueError, match="static"):
        dispatch.pack_site_buffer(
            w, None, QuantConfig(method="muxq", outlier_mode="dynamic",
                                 backend="fused"))


# ---------------------------------------------------------------------------
# Ragged shapes (satellite: arbitrary token counts)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [1, 5, 300])
def test_muxq_gemm_ragged_m(m):
    k, n = 512, 384
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.05
    xi, sx = ref.rowwise_quantize_ref(x)
    from repro.core import quantizers as Q
    wi, sw = Q.quantize(w, 8, "per_channel")
    bs = jnp.asarray(np.array([4, 1, 1, 1], np.int32))
    y_k = muxq_gemm(xi, wi, bs, sx, sw.reshape(1, -1), bk=128, interpret=True)
    y_r = ref.muxq_gemm_ref(xi, wi, bs, sx, sw.reshape(1, -1), 128)
    assert y_k.shape == (m, n)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m", [3, 130, 300])
def test_rowwise_quantize_ragged_m(m):
    x = jax.random.normal(jax.random.PRNGKey(0), (m, 96))
    qk, sk = rowwise_quantize(x, interpret=True)
    qr, sr = ref.rowwise_quantize_ref(x)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)


@pytest.mark.parametrize("m", [7, 300])
def test_muxq_linear_ragged_m_interpret_vs_ref(m):
    x = jax.random.normal(jax.random.PRNGKey(0), (m, 512))
    mask = np.zeros(512, bool)
    mask[[3, 99, 200]] = True
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 192)) * 0.05
    mw = ops.prepare_weights(w, mask, 2, bk=128)
    y_i = ops.muxq_linear(x, mw, interpret=True)
    y_r = ops.muxq_linear_ref(x, mw)
    np.testing.assert_allclose(np.asarray(y_i), np.asarray(y_r),
                               rtol=1e-4, atol=1e-3)


def test_pad_buffer_to_is_inert():
    """Stacking helper: extending a buffer with zero K-blocks must not
    change the fused result (the scan path relies on this)."""
    k = 256
    w = jax.random.normal(jax.random.PRNGKey(1), (k, 64)) * 0.05
    mask = np.zeros(k, bool)
    mask[:3] = True
    buf = dispatch.pack_site_buffer(w, mask, FUSED, bk=128)
    padded = dispatch.pad_buffer_to(buf, dispatch.buffer_k_pad(buf) + 256)
    x = jax.random.normal(jax.random.PRNGKey(0), (9, k))
    y0 = dispatch.fused_matmul(x, buf, impl="ref")
    y1 = dispatch.fused_matmul(x, padded, impl="ref")
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Backend parity across sites (fused interpret vs oracle vs fake vs fp)
# ---------------------------------------------------------------------------

def _logits(cfg, art, toks, scan=False, qparams=None, ctx=None):
    ctx = ctx or art.ctx()
    return T.forward(cfg, art.params, toks, ctx, scan=scan,
                     qparams=qparams)["logits"], ctx


def test_backend_parity_across_sites(small_model):
    """Same policy, four execution forms: fused(interpret) == fused(oracle)
    bit-for-bit-ish, both == fake real-int8 exactly (identical math), and
    all within quantization distance of fp."""
    cfg, params, batches = small_model
    toks = jnp.asarray(batches[0]["tokens"])
    art_fused = quantize_model(cfg, params, batches, SitePolicy.uniform(FUSED))
    art_fake = quantize_model(cfg, params, batches, SitePolicy.uniform(BASE))

    lg_int, _ = _logits(cfg, art_fused, toks)            # interpret Pallas
    dispatch.set_fused_impl("ref")
    lg_ref, _ = _logits(cfg, art_fused, toks)            # jnp oracle
    lg_fake, _ = _logits(cfg, art_fake, toks)
    lg_fp = T.forward(cfg, params, toks, None, scan=False)["logits"]

    np.testing.assert_allclose(np.asarray(lg_int), np.asarray(lg_ref),
                               rtol=1e-4, atol=1e-4)
    rel_fake = float(jnp.linalg.norm(lg_ref - lg_fake) /
                     jnp.linalg.norm(lg_fake))
    assert rel_fake < 1e-2, rel_fake
    rel_fp = float(jnp.linalg.norm(lg_ref - lg_fp) / jnp.linalg.norm(lg_fp))
    assert rel_fp < 0.3, rel_fp          # int8 noise, not garbage


def test_mixed_backend_policy_and_log(small_model):
    """fused / fake / fp can mix per site; the ctx records the routing."""
    cfg, params, batches = small_model
    pol = SitePolicy(default=FUSED,
                     rules=(("*attn_out", QuantConfig(method="fp")),
                            ("*mlp_down", BASE)))
    art = quantize_model(cfg, params, batches, pol)
    assert not any(s.endswith("attn_out") or s.endswith("mlp_down")
                   for s in art.kernel_buffers)
    toks = jnp.asarray(batches[0]["tokens"])
    lg, ctx = _logits(cfg, art, toks)
    assert bool(jnp.all(jnp.isfinite(lg)))
    assert ctx.backend_log["layer0/attn_qkv"] == "fused"
    assert ctx.backend_log["layer0/attn_out"] == "fp"
    assert ctx.backend_log["layer0/mlp_down"] == "fake"


def test_fused_eager_matches_scan(small_model):
    cfg, params, batches = small_model
    art = quantize_model(cfg, params, batches, SitePolicy.uniform(FUSED))
    toks = jnp.asarray(batches[0]["tokens"])
    lg_eager, _ = _logits(cfg, art, toks)
    lg_scan, _ = _logits(cfg, art, toks, scan=True, qparams=art.scan_qparams)
    np.testing.assert_allclose(np.asarray(lg_scan), np.asarray(lg_eager),
                               rtol=1e-4, atol=1e-4)


def test_fused_smooth_folds_factors(small_model):
    cfg, params, batches = small_model
    art = quantize_model(
        cfg, params, batches,
        SitePolicy.uniform(FUSED.replace(method="muxq_smooth")))
    assert art.smooth_factors
    toks = jnp.asarray(batches[0]["tokens"])
    dispatch.set_fused_impl("ref")
    lg, _ = _logits(cfg, art, toks)
    assert bool(jnp.all(jnp.isfinite(lg)))
    # and a ctx without factors refuses rather than serving unsmoothed
    from repro.core.context import QuantCtx
    ctx = QuantCtx(art.policy, kernel_buffers=art.kernel_buffers)
    with pytest.raises(RuntimeError, match="folded smooth factors"):
        T.forward(cfg, art.params, toks, ctx, scan=False)


def test_fused_naive_packs_without_calibration(small_model):
    """Maskless fused (plain int8): no calibration pass needed; parity with
    the fake real-int8 path is exact (same grids, same math)."""
    cfg, params, batches = small_model
    naive = QuantConfig(method="naive", act_granularity="per_token",
                        weight_granularity="per_channel", real_int8=True)
    art = quantize_model(cfg, params, None, naive.replace(backend="fused"))
    assert art.kernel_buffers and not art.masks
    toks = jnp.asarray(batches[0]["tokens"])
    dispatch.set_fused_impl("ref")
    lg_f, _ = _logits(cfg, art, toks)
    art_k = quantize_model(cfg, params, None, naive)
    lg_k, _ = _logits(cfg, art_k, toks)
    rel = float(jnp.linalg.norm(lg_f - lg_k) / jnp.linalg.norm(lg_k))
    assert rel < 1e-5, rel


def test_fused_moe_expert_sites():
    """Per-expert fused emm: shared outlier permutation, per-expert int8
    weights; parity against the fake per-expert path."""
    cfg = get_config("llama4-scout-17b-a16e", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batches = [{"tokens": rng.integers(0, cfg.vocab_size, (1, 8))}]
    pol = SitePolicy(default=BASE, rules=(("*moe_*", FUSED),))
    art = quantize_model(cfg, params, batches, pol)
    assert any("moe_up" in s for s in art.kernel_buffers)
    assert art.kernel_buffers["layer0/moe_up"]["w_int"].ndim == 3
    toks = jnp.asarray(batches[0]["tokens"])
    dispatch.set_fused_impl("ref")
    lg_f, ctx = _logits(cfg, art, toks)
    assert ctx.backend_log["layer0/moe_up"] == "fused"
    art_k = quantize_model(cfg, params, batches, SitePolicy.uniform(BASE))
    lg_k, _ = _logits(cfg, art_k, toks)
    rel = float(jnp.linalg.norm(lg_f - lg_k) / jnp.linalg.norm(lg_k))
    assert rel < 1e-2, rel


# ---------------------------------------------------------------------------
# Artifact persistence
# ---------------------------------------------------------------------------

def test_artifact_round_trip_kernel_buffers_bit_exact(tmp_path, small_model):
    cfg, params, batches = small_model
    art = quantize_model(cfg, params, batches, SitePolicy.uniform(FUSED))
    art.save(str(tmp_path / "a"))
    art2 = QuantArtifact.load(str(tmp_path / "a"))
    assert set(art2.kernel_buffers) == set(art.kernel_buffers)
    for site, buf in art.kernel_buffers.items():
        for field in dispatch.BUFFER_FIELDS:
            np.testing.assert_array_equal(np.asarray(buf[field]),
                                          art2.kernel_buffers[site][field])
    # scanned fused stacks survive too (dict-valued entries)
    assert set(art2.scan_qparams) == set(art.scan_qparams)
    for f in dispatch.BUFFER_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(art.scan_qparams["attn_qkv@fused"][f]),
            art2.scan_qparams["attn_qkv@fused"][f])
    toks = jnp.asarray(batches[0]["tokens"])
    dispatch.set_fused_impl("ref")
    lg1, _ = _logits(cfg, art, toks)
    lg2, _ = _logits(cfg, art2, toks)
    assert bool(jnp.array_equal(lg1, lg2)), "round-trip must be bit-exact"


def test_old_format_v1_bundle_still_loads(tmp_path, small_model):
    """A v1 bundle (no kernel_buffers group, policy configs without a
    backend field) must load as an all-fake-backend artifact."""
    cfg, params, batches = small_model
    art = quantize_model(cfg, params, batches, SitePolicy.uniform(BASE))
    path = tmp_path / "v1"
    art.save(str(path))
    # rewrite the bundle the way PR-1-era code laid it out
    meta = json.loads((path / "meta.json").read_text())
    meta["format_version"] = 1
    for cfg_json in [meta["policy"]["default"]] + \
            [c for _, c in meta["policy"]["rules"]]:
        cfg_json.pop("backend", None)
    (path / "meta.json").write_text(json.dumps(meta))
    if (path / "kernel_buffers.npz").exists():
        os.remove(path / "kernel_buffers.npz")
    art2 = QuantArtifact.load(str(path))
    assert art2.kernel_buffers == {}
    assert art2.policy.default.backend == "fake"
    toks = jnp.asarray(batches[0]["tokens"])
    lg1, _ = _logits(cfg, art, toks)
    lg2, _ = _logits(cfg, art2, toks)
    assert bool(jnp.array_equal(lg1, lg2))


def test_pack_target_fused_drops_tree_copy(tmp_path, small_model):
    """pack_target='fused': fused sites keep only the kernel buffers; their
    packed tree leaves shrink to inert stubs, output is unchanged, and the
    bundle round-trips through the normal load path."""
    cfg, params, batches = small_model
    art = quantize_model(cfg, params, batches, SitePolicy.uniform(FUSED))
    art_f = quantize_model(cfg, params, batches, SitePolicy.uniform(FUSED),
                           pack_target="fused")
    q = art_f.params["layers"]["attn"]["wqkv"]["q"]
    assert q.shape == (cfg.n_layers, 1, 1)       # stub, not a weight copy
    assert set(art_f.kernel_buffers) == set(art.kernel_buffers)
    toks = jnp.asarray(batches[0]["tokens"])
    dispatch.set_fused_impl("ref")
    lg, _ = _logits(cfg, art, toks)
    lg_f, _ = _logits(cfg, art_f, toks)
    assert bool(jnp.array_equal(lg, lg_f))
    # save-time variant: smaller bundle, same logits after load
    p_both, p_fused = tmp_path / "both", tmp_path / "fused"
    art.save(str(p_both))
    art.save(str(p_fused), pack_target="fused")
    size = lambda d: sum(f.stat().st_size for f in d.glob("*"))
    assert size(p_fused) < size(p_both)
    art2 = QuantArtifact.load(str(p_fused))
    assert art2.meta.get("pack_target") == "fused"
    lg2, _ = _logits(cfg, art2, toks)
    assert bool(jnp.array_equal(lg, lg2))


def test_pack_target_tree_drops_kernel_buffers(tmp_path, small_model):
    """pack_target='tree': kernel buffers and @fused scan stacks are
    dropped, fused routing rewrites to the fake backend, and the loaded
    bundle (missing kernel_buffers.npz entirely) matches the fake-backend
    artifact bit for bit."""
    cfg, params, batches = small_model
    art = quantize_model(cfg, params, batches, SitePolicy.uniform(FUSED))
    path = tmp_path / "tree"
    art.save(str(path), pack_target="tree")
    assert not (path / "kernel_buffers.npz").exists()
    art_t = QuantArtifact.load(str(path))
    assert art_t.kernel_buffers == {}
    assert not any(k.endswith("@fused") for k in art_t.scan_qparams)
    assert art_t.policy.default.backend == "fake"
    toks = jnp.asarray(batches[0]["tokens"])
    art_fake = quantize_model(cfg, params, batches, SitePolicy.uniform(BASE))
    lg_t, ctx = _logits(cfg, art_t, toks)
    lg_k, _ = _logits(cfg, art_fake, toks)
    assert bool(jnp.array_equal(lg_t, lg_k))
    assert set(ctx.backend_log.values()) == {"fake"}
    with pytest.raises(ValueError, match="pack_target"):
        art.save(str(tmp_path / "x"), pack_target="everything")


def test_pack_target_fused_keeps_partial_coverage(small_model):
    """A site fused in only SOME form (here: mixed policy keeps attn_out on
    the fake backend) must keep its real tree copy — only fully-fused
    stacked leaves stub out."""
    cfg, params, batches = small_model
    pol = SitePolicy(default=FUSED, rules=(("*attn_out", BASE),))
    art = quantize_model(cfg, params, batches, pol, pack_target="fused")
    assert art.params["layers"]["attn"]["wo"]["q"].shape[1] > 1  # real copy
    assert art.params["layers"]["attn"]["wqkv"]["q"].shape == (cfg.n_layers, 1, 1)
    toks = jnp.asarray(batches[0]["tokens"])
    dispatch.set_fused_impl("ref")
    lg, ctx = _logits(cfg, art, toks)
    assert bool(jnp.all(jnp.isfinite(lg)))
    assert ctx.backend_log["layer0/attn_out"] == "fake"


def test_future_format_version_refuses(tmp_path, small_model):
    cfg, params, batches = small_model
    art = quantize_model(cfg, params, batches, SitePolicy.uniform(BASE))
    path = tmp_path / "vX"
    art.save(str(path))
    meta = json.loads((path / "meta.json").read_text())
    meta["format_version"] = 99
    (path / "meta.json").write_text(json.dumps(meta))
    with pytest.raises(ValueError, match="unsupported artifact format"):
        QuantArtifact.load(str(path))


# ---------------------------------------------------------------------------
# ServeEngine end-to-end (acceptance criterion)
# ---------------------------------------------------------------------------

def test_engine_decode_runs_muxq_linear_interpret(small_model, monkeypatch):
    """ServeEngine(cfg, artifact) decode executes muxq_linear (interpret
    mode on CPU) for fused-policy sites: backend selection asserted via the
    ctx log and a trace-time call counter, output parity <= 1e-2 vs the
    fake-quant engine, and the traced step performs no per-step weight
    dequantization of fused sites (corrupting their packed int8 leaves does
    not change the output)."""
    from repro.serve.engine import Request, ServeEngine
    cfg, params, batches = small_model
    art_fused = quantize_model(cfg, params, batches, SitePolicy.uniform(FUSED))
    art_fake = quantize_model(cfg, params, batches, SitePolicy.uniform(BASE))

    calls = []
    real = ops.muxq_linear

    def counting(x, mw, *a, **kw):
        calls.append(kw.get("interpret"))
        return real(x, mw, *a, **kw)

    monkeypatch.setattr(dispatch.ops, "muxq_linear", counting)

    eng = ServeEngine(cfg, art_fused, max_batch=1, s_max=48)
    reqs = [Request("the model", max_new_tokens=4)]
    eng.generate(reqs)
    assert reqs[0].done and len(reqs[0].out_tokens) >= 4
    # every quantized site routed fused, through interpret-mode muxq_linear
    assert calls and all(i is True for i in calls)
    assert set(eng.ctx.backend_log.values()) == {"fused"}

    # decode-step logits parity vs the fake-quant engine (same cache state)
    from repro.models.attention import init_cache
    eng_fake = ServeEngine(cfg, art_fake, max_batch=1, s_max=48)
    toks = jnp.asarray(batches[0]["tokens"][:1, :8])
    cache_f = T.forward(cfg, art_fused.params, toks, eng.ctx, scan=True,
                        cache=init_cache(cfg, 1, 48, dtype=jnp.float32),
                        qparams=eng.qparams)["cache"]
    cache_k = T.forward(cfg, art_fake.params, toks, eng_fake.ctx, scan=True,
                        cache=init_cache(cfg, 1, 48, dtype=jnp.float32),
                        qparams=eng_fake.qparams)["cache"]
    step = jnp.asarray([[5]])
    lg_f, _ = T.decode_step(cfg, art_fused.params, step, cache_f, eng.ctx,
                            qparams=eng.qparams)
    lg_k, _ = T.decode_step(cfg, art_fake.params, step, cache_k, eng_fake.ctx,
                            qparams=eng_fake.qparams)
    rel = float(jnp.linalg.norm(lg_f - lg_k) / jnp.linalg.norm(lg_k))
    assert rel <= 1e-2, rel

    # no per-step dequantization of fused-site weights: the packed {"q","s"}
    # leaves are dead in the traced fn — garbage in, same logits out
    corrupted = jax.tree.map(lambda x: x, art_fused.params)  # shallow copy
    for leaf_path in (("attn", "wqkv"), ("attn", "wo"),
                      ("mlp", "wi"), ("mlp", "wo")):
        node = corrupted["layers"]
        for p in leaf_path:
            node = node[p]
        node["q"] = jnp.zeros_like(node["q"])
    lg_c, _ = T.decode_step(cfg, corrupted, step, cache_f, eng.ctx,
                            qparams=eng.qparams)
    assert bool(jnp.array_equal(lg_f, lg_c)), \
        "fused sites must not read the packed weight tree per step"


def test_engine_refuses_fused_without_buffers(small_model):
    from repro.serve.engine import ServeEngine
    cfg, params, _ = small_model
    with pytest.raises(ValueError, match="fused"):
        ServeEngine(cfg, params, quant=SitePolicy.uniform(FUSED),
                    max_batch=1, s_max=32)


def test_engine_ignores_inert_fused_rule(small_model):
    """A fused rule whose pattern matches no site in this model must not
    block construction (e.g. one shared policy across model families)."""
    from repro.serve.engine import Request, ServeEngine
    cfg, params, batches = small_model
    pol = SitePolicy(default=BASE, rules=(("*cross_*", FUSED),))
    art = quantize_model(cfg, params, batches, pol)
    assert not art.kernel_buffers           # no cross sites in a decoder LM
    eng = ServeEngine(cfg, art, max_batch=1, s_max=32)
    reqs = [Request("the", max_new_tokens=2)]
    eng.generate(reqs)
    assert reqs[0].done


def test_fused_hybrid_shared_block():
    """zamba2-style hybrid: the shared attn+MLP block packs one buffer per
    execution instance (shared weight, per-instance masks) and the eager
    forward runs fused end-to-end."""
    cfg = get_config("zamba2-1.2b", reduced=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batches = [{"tokens": rng.integers(0, cfg.vocab_size, (1, 8))}]
    art = quantize_model(cfg, params, batches, SitePolicy.uniform(FUSED))
    assert any(s.startswith("shared0/") for s in art.kernel_buffers)
    assert any(s.endswith("ssm_in_zx") for s in art.kernel_buffers)
    toks = jnp.asarray(batches[0]["tokens"])
    dispatch.set_fused_impl("ref")
    lg, ctx = _logits(cfg, art, toks)
    assert bool(jnp.all(jnp.isfinite(lg)))
    assert ctx.backend_log["shared0/attn_qkv"] == "fused"
    assert ctx.backend_log["layer0/ssm_in_zx"] == "fused"


# ---------------------------------------------------------------------------
# The grouped (expert-axis) muxq_gemm
# ---------------------------------------------------------------------------

def _grouped_case(experts, n_held=4, k=96, n=64, seed=0):
    """Rows of ``experts`` [t, top_k] (held-local ids; out of range: held
    elsewhere) laid out by ``moe.expert_rows``, and a packed [E, K, N]
    buffer with a calibrated outlier channel."""
    from repro.models.moe import expert_rows, row_tile
    rng = np.random.default_rng(seed)
    experts = jnp.asarray(experts, jnp.int32)
    t, top = experts.shape
    keep = (experts >= 0) & (experts < n_held)
    rows = expert_rows(experts, keep, n_held, row_tile(t * top))
    x = rng.standard_normal((t + 1, k)).astype(np.float32)
    x[:, 5] *= 20.0                                   # an outlier channel
    x[t] = 0.0                                        # the padding row
    w = rng.standard_normal((n_held, k, n)).astype(np.float32) / np.sqrt(k)
    mask = np.zeros(k, bool)
    mask[5] = True
    buf = dispatch.pack_site_buffer(jnp.asarray(w), mask, FUSED)
    return jnp.asarray(x)[rows["row_token"]], buf, rows, w, keep


@pytest.mark.parametrize("case", ["spread", "one_empty", "all_to_one",
                                  "ragged"])
def test_grouped_muxq_gemm_interpret_vs_oracle(case):
    """The kernel's expert axis against the jnp oracle (one integer
    ragged_dot) on every row a tile in use holds: rows spread over the
    experts, one held expert with no rows (its tile is streamed, its rows
    are padding), every row to one expert, and a row count that is no
    multiple of the 32-row tile."""
    rng = np.random.default_rng(1)
    t = {"spread": 16, "one_empty": 16, "all_to_one": 24, "ragged": 37}[case]
    experts = rng.integers(-2, 6, (t, 3))
    if case == "one_empty":
        experts = np.where(experts == 2, 3, experts)
    if case == "all_to_one":
        experts = np.stack([np.full(t, 1), np.full(t, 7), np.full(t, -1)], 1)
    x, buf, rows, w, keep = _grouped_case(experts)
    used = int(rows["n_used"][0]) * (x.shape[0] // rows["tile_expert"].shape[0])
    got = dispatch.fused_gmm(x, buf, rows, impl="interpret")
    ref = dispatch.fused_gmm(x, buf, rows, impl="ref")
    np.testing.assert_allclose(np.asarray(got)[:used], np.asarray(ref)[:used],
                               rtol=1e-6, atol=1e-6)
    # every kept pair's row holds its token through its own expert's weight
    pair = np.asarray(rows["pair_row"])
    own = np.repeat(np.asarray(rows["tile_expert"]),
                    x.shape[0] // rows["tile_expert"].shape[0])
    for ti, j in zip(*np.nonzero(np.asarray(keep))):
        r = pair[ti, j]
        assert own[r] == experts[ti, j]
        want = np.asarray(x)[r] @ w[experts[ti, j]]
        np.testing.assert_allclose(np.asarray(got)[r], want, rtol=0.05,
                                   atol=0.05 * np.abs(want).max())
    if case == "one_empty":
        assert int(np.asarray(rows["group_sizes"])[2]) == 32   # one tile
