"""Pallas TPU kernel: paged attention over block-sparse KV (vLLM-style).

One traced step of the serving pool reads each slot's K/V *through its
page table*: the kernel never materializes the gathered ``[b, pages*ps,
...]`` key range that the jnp reference builds — page ids ride a
scalar-prefetch page table straight into the BlockSpec index maps, so the
grid's inner dimension streams one physical page per step from HBM and
accumulates flash-attention-style (running max / denominator /
un-normalized accumulator in VMEM scratch).  INT8 pages are dequantized
in-kernel from their per-(position, head) scales — the int8 bytes are
what crosses HBM.  INT4 pages (MUXQ'd KV, ``repro.serve.kvq``) go
further: the kernel unpacks two nibbles per byte, applies the
per-(position, head) scale AND the per-head inverse
magnitude-redistribution rows (``k_redist``/``v_redist`` [kvh, dh]: 2^e
on calibrated outlier channels) — so the *packed* int4 bytes are what
crosses HBM, half the int8 traffic.

Pages are HEAD-MAJOR, ``[n_pages, kvh, ps, dh]``: one page of every head
is one contiguous ``(kvh, ps, dh)`` block whose last two dims the TPU
compiler accepts (ps a multiple of 8, dh the whole axis).  A
position-major page would put a size-1 head block on the second-minor
axis, which it refuses.

The grid is ``(slot, page)``: one step loads a page of every kv head and
updates every head's running sums, with dots batched over the head axis.
A slot walks only its LIVE pages.  Its last live page is
``(pos[b] + sq - 1) // ps``, the page of its last query row; past it the
K/V index maps repeat that page, which Pallas does not copy again, and
``pl.when`` skips the body — every key there is masked for every row, so
the result is bit for bit what the full walk gives.  An idle slot (``pos`` 0, zeroed table row) thus costs
one page, not the longest live slot's bucket.  Pages below a sliding
window are still walked and masked.

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
body they see the per-shard head slice (``kvh / mesh``) and each grid
step's head block shrinks to match — same program, fewer heads per
device.  The head merge (zero-pad + psum) happens in
``models/attention.py``, after the kernel returns.

Execution selection mirrors ``repro.kernels.dispatch``:

  * ``auto``      — compiled Pallas on TPU, the jnp reference on CPU;
  * ``pallas``    — force compiled kernels;
  * ``interpret`` — interpret-mode Pallas (the CPU parity protocol);
  * ``ref``       — the jnp gather reference (bit-identical to the dense
                    full-range gather the serve tests pin against).

GQA rides in the block: each kv head attends all ``sq * h // kvh`` query
rows of its group at once, so the broadcast KV never materializes (same
trick as ``flash_attention``).

**Latent pages** (``v_pages=None``, ``v_dim`` set): absorbed latent
attention is attention with one KV head whose values are the first
``v_dim`` columns of the key rows — one page array (and one scale per
position) serves as both.  ``scale`` overrides the ``dh ** -0.5`` default
(a latent's scores equal the expanded per-head dot products, scaled by
the expanded width).
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
                        softcap: Optional[float] = None,
                        v_dim: Optional[int] = None,
                        scale: Optional[float] = None):
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

    if v_pages is None:
        # latent pages: the values are the keys' first v_dim columns
        kk = gather(k_pages)
        if k_scale is not None:
            kk = kk.astype(jnp.float32) * gather(k_scale)
        kk = kk.astype(q.dtype)
        vv = kk[..., :v_dim]
    elif k_redist is not None:
        # int4: unpack nibbles, scale, undo the MUXQ magnitude shift
        kk = (unpack_int4(gather(k_pages)).astype(jnp.float32)
              * gather(k_scale).astype(jnp.float32) * k_redist).astype(q.dtype)
        vv = (unpack_int4(gather(v_pages)).astype(jnp.float32)
              * gather(v_scale).astype(jnp.float32) * v_redist).astype(q.dtype)
    elif k_scale is not None:
        kk = (gather(k_pages).astype(jnp.float32)
              * gather(k_scale)).astype(q.dtype)
        vv = (gather(v_pages).astype(jnp.float32)
              * gather(v_scale)).astype(q.dtype)
    else:
        kk = gather(k_pages).astype(q.dtype)
        vv = gather(v_pages).astype(q.dtype)

    window = NO_WINDOW if window is None else window
    kpos = jnp.arange(kk.shape[1])[None, None, :]     # [1, 1, P*ps]
    qpos = pos[:, None, None] + jnp.arange(sq)[None, :, None]   # [b, sq, 1]
    allow = (kpos <= qpos) & (kpos > qpos - window)
    bias = jnp.where(allow, 0.0, NEG_INF)[:, None, None].astype(jnp.float32)

    qg = q.reshape(b, sq, kvh, g, dh)                 # [b, sq, kv, g, dh]
    scale = dh ** -0.5 if scale is None else scale
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, kk).astype(jnp.float32) * scale
    if softcap is not None:
        scores = (softcap * jnp.tanh(scores.astype(jnp.float32) / softcap)
                  ).astype(scores.dtype)
    scores = scores + bias                            # [b,1,1,sq,S] bcast
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, vv)
    out = out.reshape(b, sq, h, vv.shape[-1])
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _dequant(x, s_ref, r_ref, mode: str):
    """One page's block of every head, ``[kvh, ps, dh | dh//2]``, as
    float32 ``[kvh, ps, dh]``: int8 codes times their per-(position, head)
    scale; int4 nibbles unpacked, scaled, and times the head's inverse
    redistribution row (``[kvh, 1, dh]``, broadcast over positions)."""
    if mode == "int4":
        return (unpack_int4(x).astype(jnp.float32)
                * s_ref[0].astype(jnp.float32) * r_ref[...])
    if mode == "int8":
        return x.astype(jnp.float32) * s_ref[0].astype(jnp.float32)
    return x.astype(jnp.float32)


def _kernel(tab_ref, pos_ref, last_ref, win_ref,    # scalar prefetch
            q_ref, k_ref, v_ref, ks_ref, vs_ref,    # blocks (scales opt.)
            kr_ref, vr_ref,                         # int4 redist rows (opt.)
            o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, nj: int, ps: int, g: int, mode: str,
            softcap: Optional[float], v_dim: Optional[int]):
    bb, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a page past the slot's last live page holds only keys that every
    # query row masks (it would add exp(NEG_INF - m) = 0 to the sums):
    # skip it.  Its index map repeats the last live page, so no copy
    # is issued for it either.
    @pl.when(j <= last_ref[bb])
    def _attend():
        q = q_ref[0].astype(jnp.float32)              # [kvh, sq*g, dh]
        k = _dequant(k_ref[0], ks_ref, kr_ref, mode)  # [kvh, ps, dh]
        if v_dim is None:
            v = _dequant(v_ref[0], vs_ref, vr_ref, mode)
        else:                                         # latent: v in k's rows
            v = k[:, :, :v_dim]
        # every head of the page at once: dots batched over the head axis
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        # logical key positions of page j: [j*ps, (j+1)*ps).  Query row r
        # of the [sq*g] block sits at absolute position pos[bb] + r//g —
        # the per-row causal mask that makes one kernel serve decode
        # (sq=1), speculative verify (sq=k) and chunked prefill (sq=C,
        # pos=start).
        kpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        qpos = pos_ref[bb] + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1) // g
        allow = (kpos <= qpos) & (kpos > qpos - win_ref[0])
        s = jnp.where(allow, s, NEG_INF)              # [kvh, sq*g, ps]

        m_prev = m_ref[...]                           # [kvh, sq*g, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, page_table, pos, *,
                           k_scale=None, v_scale=None, k_redist=None,
                           v_redist=None, window=None,
                           softcap: Optional[float] = None,
                           v_dim: Optional[int] = None,
                           scale: Optional[float] = None,
                           interpret: bool = False):
    """Pallas paged attention.  Same contract as
    :func:`paged_attention_ref`; the page table and per-slot start
    positions ride scalar prefetch so the K/V BlockSpec index maps load
    physical pages directly (no gathered intermediate).

    The grid is ``(slot, page)``.  One grid step loads one physical page
    of EVERY kv head — head-major pages make that one contiguous
    ``[kvh, ps, dh]`` block — and attends the slot's whole ``[kvh, sq*g]``
    query block to it with online softmax, so the verify block (sq=k) and
    the chunked-prefill read (sq=C) cost ONE pass over the key pages, not
    sq passes.  A slot walks only its live pages: past its last live page
    ``(pos + sq - 1) // ps`` the index maps repeat that page (no copy) and
    the body is skipped, so an idle slot (``pos`` 0, zeroed table row)
    costs one page, not the whole bucket.  Int4 pages arrive
    nibble-packed (last dim dh//2) with [kvh, dh] redistribution rows; the
    block carries the *packed* bytes and is dequantized in VMEM."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, sq, h, dh = q.shape
    n_pages, kvh, ps, pk_dh = k_pages.shape
    assert h % kvh == 0
    g = h // kvh
    nj = page_table.shape[1]
    rows = sq * g
    mode = ("int4" if k_redist is not None
            else "int8" if k_scale is not None else "fp")
    assert pk_dh == (dh // 2 if mode == "int4" else dh), (pk_dh, dh, mode)
    scale = dh ** -0.5 if scale is None else scale
    latent = v_pages is None
    vd = v_dim if latent else dh

    table = page_table.astype(jnp.int32)
    pos32 = pos.astype(jnp.int32)
    # each slot's last live page: the page of its last query row's position
    last = jnp.minimum((pos32 + sq - 1) // ps, nj - 1)
    win = jnp.full((1,), NO_WINDOW if window is None else window, jnp.int32)
    # [b, kvh, sq*g, dh]: every head's query rows in one block
    qg = q.reshape(b, sq, kvh, g, dh).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, kvh, rows, dh)

    def page_index(bb, j, tab, pos_r, last_r, win_r):
        # physical page of logical page j, held at the last live page
        return (tab[bb, jnp.minimum(j, last_r[bb])], 0, 0, 0)

    def slot_index(bb, j, *_):
        return (bb, 0, 0, 0)

    def whole(*_):
        return (0, 0, 0)

    kv_spec = pl.BlockSpec((1, kvh, ps, pk_dh), page_index)
    sc_spec = pl.BlockSpec((1, kvh, ps, 1), page_index)
    q_spec = pl.BlockSpec((1, kvh, rows, dh), slot_index)
    # inert placeholder for operands a mode doesn't use (uniform signature)
    inert_spec = pl.BlockSpec((1, 1, 1), whole)
    inert = jnp.zeros((1, 1, 1), jnp.float32)

    in_specs = [q_spec, kv_spec]
    args = [qg, k_pages]
    if latent:                  # the values are read from the key block
        in_specs.append(inert_spec)
        args.append(inert)
    else:
        in_specs.append(kv_spec)
        args.append(v_pages)
    if latent and mode == "int8":
        in_specs += [sc_spec, inert_spec]
        args += [k_scale, inert]
    elif mode in ("int8", "int4"):
        in_specs += [sc_spec, sc_spec]
        args += [k_scale, v_scale]
    else:
        in_specs += [inert_spec, inert_spec]
        args += [inert, inert]
    if mode == "int4":
        # per-head inverse redistribution rows as [kvh, 1, dh], whole:
        # they broadcast over a page's positions in the kernel
        rd_spec = pl.BlockSpec((kvh, 1, dh), whole)
        in_specs += [rd_spec, rd_spec]
        args += [jnp.reshape(k_redist, (kvh, 1, dh)).astype(jnp.float32),
                 jnp.reshape(v_redist, (kvh, 1, dh)).astype(jnp.float32)]
    else:
        in_specs += [inert_spec, inert_spec]
        args += [inert, inert]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, nj),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, rows, vd), slot_index),
        scratch_shapes=[pltpu.VMEM((kvh, rows, 1), jnp.float32),
                        pltpu.VMEM((kvh, rows, 1), jnp.float32),
                        pltpu.VMEM((kvh, rows, vd), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, nj=nj, ps=ps, g=g, mode=mode,
                          softcap=softcap, v_dim=v_dim if latent else None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows, vd), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(table, pos32, last, win, *args)
    out = out.reshape(b, kvh, sq, g, vd).transpose(0, 2, 1, 3, 4)
    out = out.reshape(b, sq, h, vd)
    return out[:, 0] if squeeze else out


def paged_attention_decode(q, k_pages, v_pages, page_table, pos, *,
                           k_scale=None, v_scale=None, k_redist=None,
                           v_redist=None, window=None,
                           softcap: Optional[float] = None,
                           v_dim: Optional[int] = None,
                           scale: Optional[float] = None,
                           impl: Optional[str] = None):
    """Impl-dispatching entry point (see :func:`set_paged_impl`).  q may
    be [b, h, dh] (decode) or [b, sq, h, dh] (verify block / prefill
    chunk, with ``pos`` the first query row's absolute position).  Latent
    pages pass ``v_pages=None`` and ``v_dim`` (see the module docstring)."""
    if impl in (None, "auto"):
        impl = paged_impl()
    if impl == "ref":
        return paged_attention_ref(
            q, k_pages, v_pages, page_table, pos, k_scale=k_scale,
            v_scale=v_scale, k_redist=k_redist, v_redist=v_redist,
            window=window, softcap=softcap, v_dim=v_dim, scale=scale)
    return paged_attention_pallas(
        q, k_pages, v_pages, page_table, pos, k_scale=k_scale,
        v_scale=v_scale, k_redist=k_redist, v_redist=v_redist,
        window=window, softcap=softcap, v_dim=v_dim, scale=scale,
        interpret=(impl == "interpret"))
