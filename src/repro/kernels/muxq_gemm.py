"""Pallas TPU kernel: fused MUXQ INT8 GEMM with per-K-block exponent scaling.

The TPU-native realization of paper Eq. 7 (DESIGN.md §3.2): channels are
pre-permuted so the calibrated outlier set occupies contiguous, K-tile-
aligned blocks.  ONE int8 MXU GEMM runs; outlier K-tiles have their INT32
partial products multiplied by 2^exp (exact shift — |prod| <= 127*127*512
so *2^e stays far inside int32) before accumulation.  Aux GEMM cost: zero.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary") with a VMEM int32
accumulator; dequant (row scale x col scale) fused into the final K step.

Expert axis (:func:`muxq_gemm_grouped`): the rows are routed tokens sorted
by expert in whole row tiles, the weights ``[E, K, N]``; each row tile's
expert rides scalar prefetch into the weight index map, so one call runs
every expert's rows.  Tiles past the ones in use repeat the last block
index (no copy) and skip the body.

VMEM budget per step (defaults bm=bn=256, bk=512):
    x tile 256x512 int8 = 128 KiB, w tile 512x256 int8 = 128 KiB,
    acc 256x256 int32 = 256 KiB, out 256x256 bf16 = 128 KiB  << 16 MiB.
MXU alignment: tile dims are multiples of 128 (or span the whole axis);
``block_scale`` rides whole in SMEM — a rank-1 VMEM block of one element
is not a tile the TPU compiler accepts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, bs_ref, sx_ref, sw_ref, o_ref, acc_ref, *, nk: int):
    _accumulate(x_ref, w_ref, bs_ref, sx_ref, sw_ref, o_ref, acc_ref,
                pl.program_id(2), nk)


def _accumulate(x_ref, w_ref, bs_ref, sx_ref, sw_ref, o_ref, acc_ref, kk,
                nk: int):
    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # precision pinned: an int8 x int8 -> int32 product is exact, and a
    # process-wide jax_default_matmul_precision="highest" would otherwise
    # ask Mosaic for an fp32 contraction, which it refuses for int8
    prod = jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32,
    )
    # per-K-block exponent scaling: 2^exp on outlier blocks, 1 elsewhere
    # (the whole [K/bk] scale vector sits in SMEM; a scalar read per step)
    acc_ref[...] += prod * bs_ref[kk]

    @pl.when(kk == nk - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * sx_ref[...] * sw_ref[...]).astype(o_ref.dtype)


def _grouped_kernel(te_ref, nu_ref, bs_ref, x_ref, w_ref, sx_ref, sw_ref,
                    o_ref, acc_ref, *, nk: int):
    kk = pl.program_id(2)

    # tiles past the ones in use hold no rows: nothing to compute
    @pl.when(pl.program_id(0) < nu_ref[0])
    def _tile():
        _accumulate(x_ref, w_ref, bs_ref, sx_ref, sw_ref, o_ref, acc_ref, kk,
                    nk)


LANE = 128


def pick_bn(n: int, bn: int = 256) -> int:
    """Output-column tile for an N-wide weight: the whole axis when it fits
    in one tile, else the largest multiple of 128 (<= ``bn``) dividing N.
    Raises when no such tile exists — weights are packed offline at a known
    width, so this fails at pack time, not inside the chip's compiler."""
    if n <= bn:
        return n
    for t in range(bn - bn % LANE, 0, -LANE):
        if n % t == 0:
            return t
    raise ValueError(f"N={n} has no column tile: it exceeds bn={bn} and no "
                     f"multiple of {LANE} up to {bn} divides it")


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def muxq_gemm(x_int: jnp.ndarray, w_int: jnp.ndarray,
              block_scale: jnp.ndarray, sx: jnp.ndarray, sw: jnp.ndarray,
              *, bm: int = 256, bn: int = 256, bk: int = 512,
              out_dtype=jnp.float32, interpret: bool = False) -> jnp.ndarray:
    """Y = dequant(sum_kb block_scale[kb] * X[:,kb] @ W[kb,:]).

    x_int [M, K] int8, w_int [K, N] int8, block_scale [K/bk] int32,
    sx [M, 1] f32 row scales, sw [1, N] f32 column scales.
    """
    m, k = x_int.shape
    k2, n = w_int.shape
    assert k == k2 and k % bk == 0 and block_scale.shape == (k // bk,), (
        f"K={k} must tile by bk={bk} with one scale per block")
    # ragged M (arbitrary token counts, e.g. a 300-token prefill): zero-pad
    # rows up to a bm multiple and slice the output — padded rows carry
    # scale 0 so they cost one partial tile, never correctness
    bm = min(bm, m)
    pad_m = (-m) % bm
    if pad_m:
        x_int = jnp.pad(x_int, ((0, pad_m), (0, 0)))
        sx = jnp.pad(sx, ((0, pad_m), (0, 0)))
    # N stays un-padded (weights are packed offline at a known width)
    bn = pick_bn(n, bn)
    nk = k // bk

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=((m + pad_m) // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pad_m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="muxq_gemm",
    )(x_int, w_int, block_scale, sx, sw)
    return out[:m] if pad_m else out


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def muxq_gemm_grouped(x_int: jnp.ndarray, w_int: jnp.ndarray,
                      block_scale: jnp.ndarray, sx: jnp.ndarray,
                      sw: jnp.ndarray, tile_expert: jnp.ndarray,
                      n_used: jnp.ndarray, *, bm: int, bn: int = 256,
                      bk: int = 512, interpret: bool = False) -> jnp.ndarray:
    """The expert axis of :func:`muxq_gemm`: Y[r] = dequant(X[r] @ W[e(r)])
    where e(r) is the expert of row r's tile.

    x_int [M, K] int8 (M a multiple of ``bm``: rows sorted by expert, each
    expert's rows padded to whole tiles), w_int [E, K, N] int8,
    block_scale [K/bk] int32 (one outlier permutation shared by the
    experts), sx [M, 1] f32, sw [E, 1, N] f32, tile_expert [M/bm] int32,
    n_used [1] int32 (tiles in use; rows of the others are not written).
    Returns [M, N] float32.  Every tile in use reads its expert's weight
    tiles once."""
    m, k = x_int.shape
    e, k2, n = w_int.shape
    assert k == k2 and k % bk == 0 and m % bm == 0, (m, k, k2, bk, bm)
    bn = pick_bn(n, bn)
    nt, nj, nk = m // bm, n // bn, k // bk

    def live(i, nu):
        return i < nu[0]

    def row(i, nu):
        return jnp.where(live(i, nu), i, nu[0] - 1)

    def col(i, j, nu):
        return jnp.where(live(i, nu), j, nj - 1)

    def kblk(i, kk, nu):
        return jnp.where(live(i, nu), kk, nk - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nt, nj, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk, te, nu, bs: (
                row(i, nu), kblk(i, kk, nu))),
            pl.BlockSpec((None, bk, bn), lambda i, j, kk, te, nu, bs: (
                te[row(i, nu)], kblk(i, kk, nu), col(i, j, nu))),
            pl.BlockSpec((bm, 1), lambda i, j, kk, te, nu, bs: (
                row(i, nu), 0)),
            pl.BlockSpec((None, 1, bn), lambda i, j, kk, te, nu, bs: (
                te[row(i, nu)], 0, col(i, j, nu))),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, te, nu, bs: (
            row(i, nu), col(i, j, nu))),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_grouped_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="muxq_gemm",
    )(tile_expert.astype(jnp.int32), n_used.astype(jnp.int32),
      block_scale.astype(jnp.int32), x_int, w_int, sx, sw)
