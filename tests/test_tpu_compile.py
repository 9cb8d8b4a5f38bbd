"""The serve path's Pallas kernels, compiled for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  What it refuses here (tiles
not aligned to (8, 128), more VMEM than a kernel may use) is what the
chip would refuse.  Shapes are GPT-2 small's published widths
(d 768, 12 heads, head_dim 64, d_ff 3072) at the serve step's decode and
prefill-chunk sizes; the paged-attention kernel also at GPT-2 large's
(20 kv heads of 64) and the chat cells' pool; and the whole paged step
programs at GPT-2 large's and Moonlight's widths, checked for what the
pool costs inside them.

The topology is described only inside the fixture below: only one
process at a time may load the TPU library, so doing it at import (here,
in ``skipif``/``parametrize`` or in ``conftest.py``) would break the other
test workers.  Keep every such compile in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.analysis.hlo import tpu_kernels
from repro.configs import get_config
from repro.configs.gpt2 import GPT2_LARGE
from repro.configs.moonlight_16b_a3b import CHIP_SHARE
from repro.kernels import dispatch
from repro.kernels import paged_attention as PA
from repro.kernels.paged_attention import paged_attention_pallas
from repro.models import transformer as T
from repro.models.attention import cache_rows
from repro.models.common import group_name
from repro.serve import kvq

CFG = get_config("gpt2-small")
D, FF, H, DH = CFG.d_model, CFG.d_ff, CFG.n_heads, CFG.head_dim
KVH = CFG.n_kv_heads
SLOTS, CHUNK, PS, S_MAX = 8, 32, 16, 1024     # the chip smoke's pool
PAGES = S_MAX // PS
BK = 512
# fused sites (input width, K packed to whole bk blocks, N): qkv,
# attn_out, mlp_up, mlp_down — 768 pads to 1024; a calibrated outlier run
# can add up to one more block
SITES = [(D, 1024, 3 * D), (D, 1024, D), (D, 1024, FF), (FF, FF, D)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off here (JAX
    # memoizes whether the cache is in use, hence the resets)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("m", [SLOTS, SLOTS * CHUNK],
                         ids=["decode", "prefill"])
@pytest.mark.parametrize("k_in,k,n", SITES, ids=["qkv", "attn_out",
                                                 "mlp_up", "mlp_down"])
def test_fused_site_compiles(one_chip, m, k_in, k, n, precision):
    """Activation gather + rowwise_quantize + muxq_gemm, as a fused site
    runs them inside the serve step — also under a process-wide
    "highest" matmul precision, which must not reach the int8 MXU dot."""
    buf = {"w_int": _shape(one_chip, (k, n), jnp.int8),
           "sw": _shape(one_chip, (1, n), jnp.float32),
           "block_scale": _shape(one_chip, (k // BK,), jnp.int32),
           "gather_idx": _shape(one_chip, (k,), jnp.int32),
           "in_scale": _shape(one_chip, (k,), jnp.float32)}
    x = _shape(one_chip, (m, k_in), jnp.bfloat16)
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(
            lambda x, b: dispatch.fused_matmul(x, b, impl="pallas")
        ).lower(x, buf).compile()
    assert tpu_kernels(compiled.as_text()) == {"rowwise_quantize": 1,
                                               "muxq_gemm": 1}


# (kv heads, q heads, head_dim, slots, page bucket, q dtype of int pages):
# GPT-2 small at the chip smoke's pool, and GPT-2 large (20 kv heads of 64)
# at the chat cells' 32 slots and 32-page bucket, whose MUXQ cell feeds
# float32 queries to int8 pages
SMALL = (KVH, H, DH, SLOTS, PAGES, jnp.bfloat16)
LARGE = (20, 20, 64, 32, 32, jnp.float32)


@pytest.mark.parametrize("sq", [1, CHUNK], ids=["decode", "prefill"])
@pytest.mark.parametrize("mode,widths", [
    ("fp", SMALL), ("int8", SMALL), ("int4", SMALL),
    ("fp", LARGE), ("int8", LARGE)],
    ids=["fp", "int8", "int4", "gpt2-large-fp", "gpt2-large-int8"])
def test_paged_attention_compiles(one_chip, mode, widths, sq):
    """Stored pages [L, n_pages, R, lanes] (``kvq.page_shape``: a page's
    rows of every kv head side by side) read at a traced layer: one grid
    step's block is one stored page, each head a run of its lanes; int4
    pages unpack their nibbles in the kernel."""
    kvh, h, dh, slots, pages_per_slot, int_q_dtype = widths
    n_pages = slots * pages_per_slot + 1
    pk = dh // 2 if mode == "int4" else dh
    dtype = jnp.bfloat16 if mode == "fp" else jnp.int8
    q_dtype = jnp.bfloat16 if mode == "fp" else int_q_dtype
    shape = kvq.page_shape(2, n_pages, PS, kvh, pk)
    pages = _shape(one_chip, shape, dtype)
    q = _shape(one_chip, (slots, sq, h, dh), q_dtype)
    table = _shape(one_chip, (slots, pages_per_slot), jnp.int32)
    pos = _shape(one_chip, (slots,), jnp.int32)
    layer = _shape(one_chip, (), jnp.int32)
    kw = {}
    if mode != "fp":
        sdt = jnp.bfloat16 if mode == "int4" else jnp.float32
        rows = shape[2]
        kw["k_scale"] = kw["v_scale"] = _shape(
            one_chip, (slots, pages_per_slot, rows, kvh * PS // rows), sdt)
    if mode == "int4":
        kw["k_redist"] = kw["v_redist"] = _shape(one_chip, (kvh, dh),
                                                 jnp.float32)
    compiled = jax.jit(
        lambda q, k, v, t, p, l, kw: paged_attention_pallas(
            q, k, v, t, p, page_size=PS, layer=l, **kw)
    ).lower(q, pages, pages, table, pos, layer, kw).compile()
    assert tpu_kernels(compiled.as_text()) == {"paged_attention": 1}


# Moonlight-16B-A3B's share (bench/configs/moonlight-16b-a3b-muxq.json):
# the expert sites (8 experts held, width 1408: up K 2048 -> N 2816, down K
# 1408 packed to 1536 -> N 2048) as the grouped kernel's expert axis, at
# a decode batch of 32 slots (192 routed pairs, tiles of 32 rows) and a
# prefill chunk step of 32 x 32 rows (6144 pairs, tiles of 128)
MOON_EXPERTS, MOON_TOPK = 8, 6
MOON_SITES = [(2048, 2048, 2816), (1408, 1536, 2048)]


@pytest.mark.parametrize("tokens", [SLOTS * 4, SLOTS * 4 * CHUNK],
                         ids=["decode", "prefill"])
@pytest.mark.parametrize("k_in,k,n", MOON_SITES, ids=["moe_up", "moe_down"])
def test_grouped_expert_site_compiles(one_chip, tokens, k_in, k, n):
    """Rows sorted into expert tiles + rowwise_quantize + the muxq_gemm
    kernel's expert axis (tile experts and tiles in use by scalar
    prefetch), at the layout ``moe.expert_rows`` gives."""
    from repro.models.moe import row_tile
    pairs = tokens * MOON_TOPK
    bm = row_tile(pairs)
    tiles = -(-pairs // bm) + MOON_EXPERTS
    buf = {"w_int": _shape(one_chip, (MOON_EXPERTS, k, n), jnp.int8),
           "sw": _shape(one_chip, (MOON_EXPERTS, 1, n), jnp.float32),
           "block_scale": _shape(one_chip, (k // BK,), jnp.int32),
           "gather_idx": _shape(one_chip, (k,), jnp.int32),
           "in_scale": _shape(one_chip, (k,), jnp.float32)}
    rows = {"group_sizes": _shape(one_chip, (MOON_EXPERTS,), jnp.int32),
            "tile_expert": _shape(one_chip, (tiles,), jnp.int32),
            "n_used": _shape(one_chip, (1,), jnp.int32)}
    x = _shape(one_chip, (tiles * bm, k_in), jnp.float32)
    compiled = jax.jit(
        lambda x, b, r: dispatch.fused_gmm(x, b, r, impl="pallas")
    ).lower(x, buf, rows).compile()
    assert tpu_kernels(compiled.as_text()) == {"rowwise_quantize": 1,
                                               "muxq_gemm": 1}


@pytest.mark.parametrize("m", [SLOTS * 4, SLOTS * 4 * CHUNK],
                         ids=["decode", "prefill"])
def test_wide_dense_site_compiles(one_chip, m):
    """Moonlight's dense layer's down projection, K 11264 packed with an
    outlier block to 11776: its float32 rows halve rowwise_quantize's row
    block to stay inside the 16 MiB of scoped VMEM."""
    k, n = 11776, 2048
    buf = {"w_int": _shape(one_chip, (k, n), jnp.int8),
           "sw": _shape(one_chip, (1, n), jnp.float32),
           "block_scale": _shape(one_chip, (k // BK,), jnp.int32),
           "gather_idx": _shape(one_chip, (k,), jnp.int32),
           "in_scale": _shape(one_chip, (k,), jnp.float32)}
    x = _shape(one_chip, (m, 11264), jnp.float32)
    compiled = jax.jit(
        lambda x, b: dispatch.fused_matmul(x, b, impl="pallas")
    ).lower(x, buf).compile()
    assert tpu_kernels(compiled.as_text()) == {"rowwise_quantize": 1,
                                               "muxq_gemm": 1}


@pytest.mark.parametrize("sq", [1, CHUNK], ids=["decode", "prefill"])
@pytest.mark.parametrize("mode", ["fp", "int8"])
def test_latent_paged_attention_compiles(one_chip, mode, sq):
    """Moonlight's latent pages: one 576-wide row per position (512 latent
    + 64 rotary), two to a stored row of 1152, read as key, its first 512
    columns as value, 16 query heads, float32 queries, at 32 slots and a
    256-page bucket (4096 positions)."""
    slots, pages_per_slot, h, width, v_dim = 32, 256, 16, 576, 512
    n_pages = slots * pages_per_slot + 1
    dtype = jnp.bfloat16 if mode == "fp" else jnp.int8
    shape = kvq.page_shape(2, n_pages, PS, 1, width)
    assert shape[2:] == (PS // 2, 2 * width)
    pages = _shape(one_chip, shape, dtype)
    q = _shape(one_chip, (slots, sq, h, width), jnp.float32)
    table = _shape(one_chip, (slots, pages_per_slot), jnp.int32)
    pos = _shape(one_chip, (slots,), jnp.int32)
    layer = _shape(one_chip, (), jnp.int32)
    kw = {}
    if mode == "int8":
        kw["k_scale"] = _shape(one_chip, (slots, pages_per_slot, PS // 2, 2),
                               jnp.float32)
    compiled = jax.jit(
        lambda q, k, t, p, l, kw: paged_attention_pallas(
            q, k, None, t, p, page_size=PS, layer=l, v_dim=v_dim,
            scale=192 ** -0.5, **kw)
    ).lower(q, pages, table, pos, layer, kw).compile()
    assert tpu_kernels(compiled.as_text()) == {"paged_attention": 1}


# The whole paged step programs, compiled as the chat cells run them: the
# pool's arrays at 2049 pages of 16 (GPT-2 large, 2 layers, bf16 and int8
# pages) and 8193 (Moonlight's dense layer and 2 expert layers, int8 latent
# pages; one expert held: the pool does not depend on it), 32 slots.
# What the pool may cost in them: nothing moves a pool array or one layer of
# it (no copy, transpose, dynamic-slice or dynamic-update-slice of that
# shape: each layer writes its rows in place and the kernel reads the
# stacked pages), every pool array enters in row-major layout, stored
# unpadded, and (where the pool is the largest state: GPT-2) the program's
# temporaries stay under one layer's pages.
_MOVES = ("copy", "transpose", "dynamic-slice", "dynamic-update-slice")
_INSTR = re.compile(r"= (\w+)\[([\d,]*)\]\{([^}]*)\} ([\w-]+)\(")


def _pool_shapes(cfg, mode, n_pages, one_chip):
    kvh, dh = cache_rows(cfg)
    quant = kvq.make_quantizer(mode, kvh=kvh, dh=dh, latent=cfg.is_mla)
    out = {}
    for key, lo, hi in cfg.layer_groups:
        arrs = jax.eval_shape(
            lambda: quant.page_arrays(hi - lo, n_pages, PS, kvh, dh))
        out.update({group_name(key, n): _shape(one_chip, a.shape, a.dtype)
                    for n, a in arrs.items()})
    return out


@pytest.mark.parametrize("kind", ["decode", "prefill"])
@pytest.mark.parametrize("model,mode", [
    ("gpt2-large", "fp"), ("gpt2-large", "int8"), ("moonlight", "int8")])
def test_paged_step_keeps_the_pool_in_place(one_chip, model, mode, kind):
    if model == "gpt2-large":
        cfg, n_pages, pages_per_slot = GPT2_LARGE.replace(n_layers=2), 2049, 32
    else:
        cfg, n_pages, pages_per_slot = (CHIP_SHARE.replace(n_layers=3,
                                                           n_held=1),
                                        8193, 256)
    slots = 32
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda a: _shape(one_chip, a.shape, (
        jnp.bfloat16 if a.dtype == jnp.float32 else a.dtype)), params)
    kv = _pool_shapes(cfg, mode, n_pages, one_chip)
    vec = _shape(one_chip, (slots,), jnp.int32)
    table = _shape(one_chip, (slots, pages_per_slot), jnp.int32)
    tokens = _shape(one_chip, (slots, 1 if kind == "decode" else CHUNK),
                    jnp.int32)
    if kind == "decode":
        step = lambda p, t, kv, tab, pos: T.decode_step_paged(
            cfg, p, t, kv, tab, pos)
        args = (params, tokens, kv, table, vec)
    else:
        step = lambda p, t, kv, tab, s, lo, hi: T.prefill_chunk_paged(
            cfg, p, t, kv, tab, s, lo, hi)
        args = (params, tokens, kv, table, vec, vec, vec)
    prev = PA.set_paged_impl("pallas")
    try:
        compiled = jax.jit(step, donate_argnums=(2,)).lower(*args).compile()
    finally:
        PA.set_paged_impl(prev)
    text = compiled.as_text()
    assert tpu_kernels(text).get("paged_attention") == len(cfg.layer_groups)

    pool = {tuple(a.shape) for a in kv.values()}
    pool |= {s[1:] for s in pool} | {(1,) + s[1:] for s in pool}
    moved = [(op, dims) for _, dims, _, op in _INSTR.findall(text)
             if op in _MOVES
             and tuple(int(d) for d in dims.split(",") if d) in pool]
    assert not moved, moved

    entry = text.split("entry_computation_layout={(", 1)[1].split(")->", 1)[0]
    for a in kv.values():
        row_major = ",".join(map(str, range(len(a.shape) - 1, -1, -1)))
        dims = ",".join(map(str, a.shape))
        layouts = re.findall(r"\[" + dims + r"\]\{([\d,]*)", entry)
        assert layouts and set(layouts) == {row_major}, (a.shape, layouts)

    mem = compiled.memory_analysis()
    logical = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(args))
    assert mem.argument_size_in_bytes <= 1.07 * logical
    layer_pages = max(a.size // a.shape[0] * a.dtype.itemsize
                      for n, a in kv.items() if not n.endswith("_scale"))
    if model == "gpt2-large":
        assert mem.temp_size_in_bytes < layer_pages, (
            mem.temp_size_in_bytes, layer_pages)
