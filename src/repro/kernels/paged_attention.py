"""Pallas TPU kernel: paged attention over block-sparse KV (vLLM-style).

One traced step of the serving pool reads each slot's K/V *through its
page table*: the kernel never materializes the gathered ``[b, pages*ps,
...]`` key range that the jnp reference builds — page ids ride a
scalar-prefetch page table straight into the BlockSpec index maps, so the
grid's innermost dimension streams one physical page per step from HBM
and accumulates flash-attention-style (running max / denominator /
un-normalized accumulator in VMEM scratch).  INT8 pages are dequantized
in-kernel from their per-(position, head) scales — the int8 bytes are
what crosses HBM.  INT4 pages (MUXQ'd KV, ``repro.serve.kvq``) go
further: the kernel unpacks two nibbles per byte, applies the
per-(position, head) scale AND the per-head inverse
magnitude-redistribution rows (``k_redist``/``v_redist`` [kvh, dh]: 2^e
on calibrated outlier channels) — so the *packed* int4 bytes are what
crosses HBM, half the int8 traffic.

Pages are HEAD-MAJOR, ``[n_pages, kvh, ps, dh]``: one (page, head) block
is a ``(ps, dh)`` tile whose last two dims the TPU compiler accepts (ps a
multiple of 8, dh the whole axis).  A position-major page would put a
size-1 head block on the second-minor axis, which it refuses.

The query side is a ``[slot, sq]`` BLOCK, not a single token:

  * decode           — sq=1, ``pos[b]`` the slot's write position;
  * speculative verify — sq=k draft tokens per slot, query row ``i`` sits
    at absolute position ``pos[b] + i`` (the per-row causal mask admits
    exactly the keys a sequential decode at that position would see);
  * chunked prefill  — b=1, sq=C chunk queries with ``pos=[start]``, the
    flash-style replacement for the gather→dequantize→sdpa read.

The page table arrives pre-sliced to the scheduler's bucketed page budget
(``pages`` = table.shape[1]), so read traffic scales with the longest live
sequence, not the slot capacity.

**Tensor-parallel serving** (``parallel/serve_sharding.py``) needs no code
here: both the reference and the Pallas kernel derive ``kvh`` and the GQA
group ``g = h // kvh`` from the array shapes, so inside a ``shard_map``
body they see the per-shard head slice (``kvh / mesh``) and the grid's
KV-head dimension shrinks to match — same program, fewer heads per device.
The head merge (zero-pad + psum) happens in ``models/attention.py``, after
the kernel returns.

Execution selection mirrors ``repro.kernels.dispatch``:

  * ``auto``      — compiled Pallas on TPU, the jnp reference on CPU;
  * ``pallas``    — force compiled kernels;
  * ``interpret`` — interpret-mode Pallas (the CPU parity protocol);
  * ``ref``       — the jnp gather reference (bit-identical to the dense
                    full-range gather the serve tests pin against).

GQA rides in the grid: programs iterate (slot, kv_head, page) and each
program attends all ``sq * h // kvh`` query rows of its group at once, so
the broadcast KV never materializes (same trick as ``flash_attention``).
"""
from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.serve.kvq import unpack_int4

NEG_INF = -1e9          # matches models/attention.NEG_INF (parity)
NO_WINDOW = 1 << 30     # "sliding window off" sentinel (int32-safe)

PagedImpl = Literal["auto", "pallas", "interpret", "ref"]

_PAGED_IMPL: PagedImpl = "auto"


def set_paged_impl(impl: PagedImpl) -> PagedImpl:
    """Select how paged attention executes; returns the previous
    setting.  ``auto`` (default): compiled Pallas on TPU, the jnp gather
    reference on CPU.  ``interpret`` forces interpret-mode Pallas (CPU
    parity tests), ``ref`` forces the reference, ``pallas`` forces
    compiled kernels."""
    global _PAGED_IMPL
    if impl not in ("auto", "pallas", "interpret", "ref"):
        raise ValueError(f"unknown paged impl {impl!r}")
    prev, _PAGED_IMPL = _PAGED_IMPL, impl
    return prev


def paged_impl() -> str:
    """The resolved (non-auto) paged-attention execution mode."""
    if _PAGED_IMPL != "auto":
        return _PAGED_IMPL
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# ---------------------------------------------------------------------------
# jnp reference (the math the serve tests pin bit-exact on fp pages)
# ---------------------------------------------------------------------------

def paged_attention_ref(q, k_pages, v_pages, page_table, pos, *,
                        k_scale=None, v_scale=None, k_redist=None,
                        v_redist=None, window=None,
                        softcap: Optional[float] = None):
    """Gather-then-attend reference.  q [b, h, dh] (decode) or
    [b, sq, h, dh] (verify block / prefill chunk); k/v_pages
    [n_pages, kvh, ps, dh] (+ optional [n_pages, kvh, ps, 1] int8 scales;
    int4 pages store nibble-packed [n_pages, kvh, ps, dh//2] with bf16
    scales and per-head [kvh, dh] ``k_redist``/``v_redist`` inverse
    redistribution rows); page_table [b, pages] int32; pos [b] int32 —
    the absolute position of each slot's FIRST query row (query row i
    masks ``kpos <= pos[b] + i``); ``window`` a traced or static int32
    scalar (``NO_WINDOW`` disables).  Returns q's shape.

    The op sequence mirrors ``models.attention.sdpa`` exactly — including
    the query-sequence dim riding through the grouped einsums — so fp
    pages stay BIT-exact against the dense cache decode/prefill paths
    (the serve parity tests pin this)."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]                                # [b, 1, h, dh]
    b, sq, h, dh = q.shape
    kvh = k_pages.shape[1]
    g = h // kvh

    def gather(pages):
        gp = pages[page_table]                        # [b, P, kvh, ps, *]
        gp = gp.transpose(0, 1, 3, 2, 4)              # [b, P, ps, kvh, *]
        return gp.reshape(b, -1, *gp.shape[3:])

    kk, vv = gather(k_pages), gather(v_pages)
    if k_redist is not None:
        # int4: unpack nibbles, scale, undo the MUXQ magnitude shift
        kk = (unpack_int4(kk).astype(jnp.float32)
              * gather(k_scale).astype(jnp.float32) * k_redist).astype(q.dtype)
        vv = (unpack_int4(vv).astype(jnp.float32)
              * gather(v_scale).astype(jnp.float32) * v_redist).astype(q.dtype)
    elif k_scale is not None:
        kk = (kk.astype(jnp.float32) * gather(k_scale)).astype(q.dtype)
        vv = (vv.astype(jnp.float32) * gather(v_scale)).astype(q.dtype)
    else:
        kk = kk.astype(q.dtype)
        vv = vv.astype(q.dtype)

    window = NO_WINDOW if window is None else window
    kpos = jnp.arange(kk.shape[1])[None, None, :]     # [1, 1, P*ps]
    qpos = pos[:, None, None] + jnp.arange(sq)[None, :, None]   # [b, sq, 1]
    allow = (kpos <= qpos) & (kpos > qpos - window)
    bias = jnp.where(allow, 0.0, NEG_INF)[:, None, None].astype(jnp.float32)

    qg = q.reshape(b, sq, kvh, g, dh)                 # [b, sq, kv, g, dh]
    scale = dh ** -0.5
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, kk).astype(jnp.float32) * scale
    if softcap is not None:
        scores = (softcap * jnp.tanh(scores.astype(jnp.float32) / softcap)
                  ).astype(scores.dtype)
    scores = scores + bias                            # [b,1,1,sq,S] bcast
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, vv)
    out = out.reshape(b, sq, h, dh)
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _kernel(tab_ref, pos_ref, win_ref,              # scalar prefetch
            q_ref, k_ref, v_ref, ks_ref, vs_ref,    # blocks (scales opt.)
            kr_ref, vr_ref,                         # int4 redist rows (opt.)
            o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, nj: int, ps: int, g: int, mode: str,
            softcap: Optional[float]):
    bb, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # [sq*g, dh]
    k = k_ref[0, 0]                                   # [ps, dh | dh//2]
    v = v_ref[0, 0]
    if mode == "int4":
        # unpack two nibbles per byte, apply the per-(pos, head) scale and
        # the per-head inverse redistribution row ([1, dh] bcast): only
        # the packed int4 bytes ever crossed HBM
        k = (unpack_int4(k).astype(jnp.float32)
             * ks_ref[0, 0].astype(jnp.float32) * kr_ref[0])
        v = (unpack_int4(v).astype(jnp.float32)
             * vs_ref[0, 0].astype(jnp.float32) * vr_ref[0])
    elif mode == "int8":
        k = k.astype(jnp.float32) * ks_ref[0, 0].astype(jnp.float32)
        v = v.astype(jnp.float32) * vs_ref[0, 0].astype(jnp.float32)
    else:
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    # logical key positions of page j: [j*ps, (j+1)*ps).  Query row r of
    # the [sq*g] block sits at absolute position pos[bb] + r//g — the
    # per-row causal mask that makes one kernel serve decode (sq=1),
    # speculative verify (sq=k) and chunked prefill (sq=C, pos=start).
    pos = pos_ref[bb]
    win = win_ref[0]
    rows = q.shape[0]                                 # sq * g
    kpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
    qpos = pos + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 0) // g
    allow = (kpos <= qpos) & (kpos > qpos - win)
    s = jnp.where(allow, s, NEG_INF)

    m_prev = m_ref[...]                               # [sq*g, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                            # [sq*g, ps]
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, page_table, pos, *,
                           k_scale=None, v_scale=None, k_redist=None,
                           v_redist=None, window=None,
                           softcap: Optional[float] = None,
                           interpret: bool = False):
    """Pallas paged attention.  Same contract as
    :func:`paged_attention_ref`; the page table and per-slot start
    positions ride scalar prefetch so the K/V BlockSpec index maps load
    physical pages directly (no gathered intermediate).  The whole
    ``[sq, g]`` query block of a (slot, kv-head) program attends one page
    per grid step with online softmax, so the verify block (sq=k) and the
    chunked-prefill read (sq=C) cost ONE pass over the key pages — not sq
    passes.  Int4 pages arrive nibble-packed (last dim dh//2) with
    [kvh, dh] redistribution rows; the kernel block loads one
    (page, head) tile of *packed* bytes and dequantizes in VMEM."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, sq, h, dh = q.shape
    n_pages, kvh, ps, pk_dh = k_pages.shape
    assert h % kvh == 0
    g = h // kvh
    nj = page_table.shape[1]
    mode = ("int4" if k_redist is not None
            else "int8" if k_scale is not None else "fp")
    assert pk_dh == (dh // 2 if mode == "int4" else dh), (pk_dh, dh, mode)
    scale = dh ** -0.5

    table = page_table.astype(jnp.int32)
    pos32 = pos.astype(jnp.int32)
    win = jnp.full((1,), NO_WINDOW if window is None else window, jnp.int32)
    # [b, kvh, sq*g, dh]: all of a kv head's query rows in one block
    qg = q.reshape(b, sq, kvh, g, dh).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, kvh, sq * g, dh)

    # page blocks: physical page tab[b, j], kv head hh, all ps positions
    kv_spec = pl.BlockSpec(
        (1, 1, ps, pk_dh),
        lambda bb, hh, j, tab, pos_r, win_r: (tab[bb, j], hh, 0, 0))
    sc_spec = pl.BlockSpec(
        (1, 1, ps, 1),
        lambda bb, hh, j, tab, pos_r, win_r: (tab[bb, j], hh, 0, 0))
    q_spec = pl.BlockSpec(
        (1, 1, sq * g, dh),
        lambda bb, hh, j, tab, pos_r, win_r: (bb, hh, 0, 0))
    # inert placeholder for operands a mode doesn't use (uniform signature)
    def _inert_spec():
        return pl.BlockSpec((1, 1),
                            lambda bb, hh, j, tab, pos_r, win_r: (0, 0))
    _inert = jnp.zeros((1, 1), jnp.float32)

    in_specs = [q_spec, kv_spec, kv_spec]
    args = [qg, k_pages, v_pages]
    if mode in ("int8", "int4"):
        in_specs += [sc_spec, sc_spec]
        args += [k_scale, v_scale]
    else:
        in_specs += [_inert_spec(), _inert_spec()]
        args += [_inert, _inert]
    if mode == "int4":
        # per-head inverse redistribution rows as [kvh, 1, dh]: block
        # (1, 1, dh) at head hh keeps the tile's last two dims whole
        rd_spec = pl.BlockSpec(
            (1, 1, dh), lambda bb, hh, j, tab, pos_r, win_r: (hh, 0, 0))
        in_specs += [rd_spec, rd_spec]
        args += [jnp.reshape(k_redist, (kvh, 1, dh)).astype(jnp.float32),
                 jnp.reshape(v_redist, (kvh, 1, dh)).astype(jnp.float32)]
    else:
        in_specs += [_inert_spec(), _inert_spec()]
        args += [_inert, _inert]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, kvh, nj),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, sq * g, dh),
            lambda bb, hh, j, tab, pos_r, win_r: (bb, hh, 0, 0)),
        scratch_shapes=[pltpu.VMEM((sq * g, 1), jnp.float32),
                        pltpu.VMEM((sq * g, 1), jnp.float32),
                        pltpu.VMEM((sq * g, dh), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, nj=nj, ps=ps, g=g, mode=mode,
                          softcap=softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, sq * g, dh), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(table, pos32, win, *args)
    out = out.reshape(b, kvh, sq, g, dh).transpose(0, 2, 1, 3, 4)
    out = out.reshape(b, sq, h, dh)
    return out[:, 0] if squeeze else out


def paged_attention_decode(q, k_pages, v_pages, page_table, pos, *,
                           k_scale=None, v_scale=None, k_redist=None,
                           v_redist=None, window=None,
                           softcap: Optional[float] = None,
                           impl: Optional[str] = None):
    """Impl-dispatching entry point (see :func:`set_paged_impl`).  q may
    be [b, h, dh] (decode) or [b, sq, h, dh] (verify block / prefill
    chunk, with ``pos`` the first query row's absolute position)."""
    if impl in (None, "auto"):
        impl = paged_impl()
    if impl == "ref":
        return paged_attention_ref(
            q, k_pages, v_pages, page_table, pos, k_scale=k_scale,
            v_scale=v_scale, k_redist=k_redist, v_redist=v_redist,
            window=window, softcap=softcap)
    return paged_attention_pallas(
        q, k_pages, v_pages, page_table, pos, k_scale=k_scale,
        v_scale=v_scale, k_redist=k_redist, v_redist=v_redist,
        window=window, softcap=softcap, interpret=(impl == "interpret"))
