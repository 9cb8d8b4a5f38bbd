"""Flash-style Pallas chunked-prefill kernel: interpret-vs-ref parity.

The chunked prefill's paged read is the [slot, sq] query-block kernel
with b=1, sq=C and ``pos=[start]`` (the chunk's first absolute
position).  Parity protocol follows ``test_paged_sparse``: interpret-mode
Pallas against the jnp gather reference, over GQA (h > kvh), sliding
windows, logit softcap, all three page modes (fp / int8 / int4
nibble-packed + redistributed), and chunk sizes below, at, and above the
page size, and a pooled step whose idle slots and wide page bucket leave
pages past each slot's live range — plus the model-level chunked prefill
under ``set_paged_impl('interpret')`` and a verify-block (sq=k, per-slot
pos) sweep, since both ride the same kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paged_testlib as PT
from repro.configs import get_config
from repro.kernels import paged_attention as PA
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine

PS = 8          # page size for every case here


def _block_case(seed, *, b, sq, h=8, kvh=4, dh=16, pages=4, mode="fp",
                start=None):
    """Random [b, sq] query-block operands over a scrambled page table,
    pages head-major (see ``paged_testlib``).  ``start``: each slot's
    first query-row position (random if None)."""
    rng = np.random.default_rng(seed)
    n_pages = 1 + b * pages                        # + scratch page 0
    q = jnp.asarray(rng.normal(size=(b, sq, h, dh)).astype(np.float32))
    kp, vp, kw = PT.random_pages(rng, n_pages, kvh, PS, dh, mode)
    table = PT.scrambled_table(rng, b, pages, n_pages)
    if start is None:
        pos = rng.integers(0, pages * PS - sq + 1, b)
    else:
        pos = np.full(b, start)
    return q, kp, vp, kw, table, jnp.asarray(pos, jnp.int32)


# ---------------------------------------------------------------------------
# Kernel-level parity: chunk sizes below / at / above the page size
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["fp", "int8", "int4"])
@pytest.mark.parametrize("sq", [4, 8, 16])       # < PS, == PS, > PS
@pytest.mark.parametrize("window,softcap", [(None, None), (5, None),
                                            (None, 30.0), (7, 50.0)])
def test_prefill_block_interpret_matches_ref(mode, sq, window, softcap):
    seed = {"fp": 0, "int8": 1, "int4": 3}[mode] + 10 * sq
    # b=1 + a mid-sequence start offset: exactly the chunked-prefill read
    q, kp, vp, kw, table, pos = _block_case(seed, b=1, sq=sq, mode=mode,
                                            start=PS + 3)
    kw = dict(kw, window=window, softcap=softcap)
    ref = PT.run(PA.paged_attention_ref, q, kp, vp, table, pos, **kw)
    out = PT.run(PA.paged_attention_pallas, q, kp, vp, table, pos,
                 interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("mode", ["fp", "int8", "int4"])
@pytest.mark.parametrize("starts", [(0, 0, 2 * PS), (0, PS + 3, 5)],
                         ids=["on-page", "off-page"])
@pytest.mark.parametrize("h,kvh", [(4, 4), (8, 4)], ids=["mha", "gqa"])
@pytest.mark.parametrize("window,softcap", [(None, None), (9, 30.0)])
def test_prefill_step_walks_only_live_pages(mode, starts, h, kvh, window,
                                            softcap):
    """The pooled prefill step's read, sq=32: slot 0 idle (start 0,
    zeroed table row), the chunking slots starting on or off a page
    boundary, and a page bucket wider than every slot's live range — each
    chunking slot's rows match the reference, which reads the whole
    bucket."""
    sq, pages = 32, 10
    q, kp, vp, kw, table, pos = _block_case(
        {"fp": 40, "int8": 41, "int4": 43}[mode] + starts[1], b=len(starts),
        sq=sq, h=h, kvh=kvh, pages=pages, mode=mode)
    pos = jnp.asarray(starts, jnp.int32)
    table = table.at[0].set(0)
    assert (max(starts) + sq - 1) // PS < pages - 1
    kw = dict(kw, window=window, softcap=softcap)
    ref = PT.run(PA.paged_attention_ref, q, kp, vp, table, pos, **kw)
    out = PT.run(PA.paged_attention_pallas, q, kp, vp, table, pos,
                 interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out)[1:], np.asarray(ref)[1:],
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("mode", ["fp", "int8", "int4"])
def test_verify_block_interpret_matches_ref(mode):
    """Multi-slot sq=k blocks with per-slot start positions — the
    speculative-verify face of the same kernel."""
    seed = {"fp": 4, "int8": 5, "int4": 6}[mode]
    q, kp, vp, kw, table, pos = _block_case(seed, b=3, sq=4, mode=mode)
    ref = PT.run(PA.paged_attention_ref, q, kp, vp, table, pos, **kw)
    out = PT.run(PA.paged_attention_pallas, q, kp, vp, table, pos,
                 interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_start_offset_causal_mask_rows():
    """Row i of a chunk starting at ``start`` sees exactly keys
    [0, start + i]: each block row reproduces the equivalent standalone
    single-query call at its absolute position."""
    q, kp, vp, _, table, _ = _block_case(7, b=1, sq=4, start=0)
    start = 9
    pos = jnp.asarray([start], jnp.int32)
    block = PT.run(PA.paged_attention_ref, q, kp, vp, table, pos)
    for i in range(q.shape[1]):
        row = PT.run(PA.paged_attention_ref, q[:, i], kp, vp, table,
                     jnp.asarray([start + i], jnp.int32))
        np.testing.assert_array_equal(np.asarray(block[:, i]),
                                      np.asarray(row))


def test_decode_row_unchanged_by_block_generalization():
    """sq=1 block == the 3-D decode call bit for bit (the PR's no-regression
    contract for the existing decode path)."""
    q, kp, vp, _, table, pos = _block_case(8, b=3, sq=1)
    out4 = PT.run(PA.paged_attention_ref, q, kp, vp, table, pos)
    out3 = PT.run(PA.paged_attention_ref, q[:, 0], kp, vp, table, pos)
    assert out4.shape == (3, 1, 8, 16) and out3.shape == (3, 8, 16)
    np.testing.assert_array_equal(np.asarray(out4[:, 0]), np.asarray(out3))
    outp = PT.run(PA.paged_attention_pallas, q[:, 0], kp, vp, table, pos,
                  interpret=True)
    np.testing.assert_allclose(np.asarray(outp), np.asarray(out3),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Model-level: chunked prefill through the interpret kernel
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("gpt2-small", reduced=True).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=256,
        vocab_size=120)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.mark.parametrize("kv_mode", ["fp", "int8", "int4"])
@pytest.mark.parametrize("prefill_chunk", [4, 8, 16])
def test_engine_prefill_interpret_matches_ref_impl(small_model, kv_mode,
                                                   prefill_chunk):
    """End-to-end: the engine's chunked prefill + decode under
    set_paged_impl('interpret') (in-kernel dequant, online softmax,
    start-offset mask) emits the same greedy tokens as the ref gather
    path, chunk sizes below / at / above the page size."""
    cfg, params = small_model
    prompt = "abcdefghijklmnopqr"

    def run():
        eng = ServeEngine(cfg, params, max_batch=2, s_max=64, page_size=PS,
                          kv_mode=kv_mode, cache_dtype=jnp.float32,
                          prefill_chunk=prefill_chunk)
        req = Request(prompt, max_new_tokens=6)
        eng.generate([req])
        return req.out_tokens

    prev = PA.set_paged_impl("ref")
    try:
        ref = run()
    finally:
        PA.set_paged_impl(prev)
    prev = PA.set_paged_impl("interpret")
    try:
        out = run()
    finally:
        PA.set_paged_impl(prev)
    # greedy argmax over logits agreeing to ~1e-5: token streams match
    assert out == ref, (kv_mode, prefill_chunk)
