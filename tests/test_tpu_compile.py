"""The serve path's Pallas kernels, compiled for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  What it refuses here (tiles
not aligned to (8, 128), more VMEM than a kernel may use) is what the
chip would refuse.  Shapes are GPT-2 small's published widths
(d 768, 12 heads, head_dim 64, d_ff 3072) at the serve step's decode and
prefill-chunk sizes.

The topology is described only inside the fixture below: only one
process at a time may load the TPU library, so doing it at import (here,
in ``skipif``/``parametrize`` or in ``conftest.py``) would break the other
test workers.  Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.analysis.hlo import tpu_kernels
from repro.configs import get_config
from repro.kernels import dispatch
from repro.kernels.paged_attention import paged_attention_pallas

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


@pytest.mark.parametrize("sq", [1, CHUNK], ids=["decode", "prefill"])
@pytest.mark.parametrize("mode", ["fp", "int8", "int4"])
def test_paged_attention_compiles(one_chip, mode, sq):
    """Head-major pages [n_pages, kvh, ps, dh]: one (page, head) block is
    a (ps, dh) tile; int4 pages unpack their nibbles in the kernel."""
    n_pages = SLOTS * PAGES + 1
    pk = DH // 2 if mode == "int4" else DH
    dtype = jnp.bfloat16 if mode == "fp" else jnp.int8
    pages = _shape(one_chip, (n_pages, KVH, PS, pk), dtype)
    q = _shape(one_chip, (SLOTS, sq, H, DH), jnp.bfloat16)
    table = _shape(one_chip, (SLOTS, PAGES), jnp.int32)
    pos = _shape(one_chip, (SLOTS,), jnp.int32)
    kw = {}
    if mode != "fp":
        sdt = jnp.bfloat16 if mode == "int4" else jnp.float32
        kw["k_scale"] = kw["v_scale"] = _shape(one_chip,
                                               (n_pages, KVH, PS, 1), sdt)
    if mode == "int4":
        kw["k_redist"] = kw["v_redist"] = _shape(one_chip, (KVH, DH),
                                                 jnp.float32)
    compiled = jax.jit(
        lambda q, k, v, t, p, kw: paged_attention_pallas(q, k, v, t, p, **kw)
    ).lower(q, pages, pages, table, pos, kw).compile()
    assert tpu_kernels(compiled.as_text()) == {"paged_attention": 1}
