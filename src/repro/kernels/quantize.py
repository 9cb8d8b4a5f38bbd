"""Pallas TPU kernel: fused row-wise (per-token) abs-max INT8 quantization.

One pass over x [M, K]: per-row abs-max -> scale -> round/clip -> int8 out +
f32 scales out.  Whole rows sit in VMEM, so no cross-block reduction is
needed — the right trade for activation quantization where K = d_model/d_ff.
A row block is halved until its double-buffered float32 input and int8
output fit :data:`BLOCK_BYTES` (a 16 MiB scoped VMEM limit on v5e; at
bm=128 a float32 block of K 11776 alone takes 6 MiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_EPS = 1e-9
BLOCK_BYTES = 12 << 20      # VMEM for one step's blocks, double-buffered


def _kernel(x_ref, q_ref, s_ref, *, bits: int):
    x = x_ref[...].astype(jnp.float32)
    qmax = float((1 << (bits - 1)) - 1)
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True), _EPS)
    scale = amax / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bits", "bm", "interpret"))
def rowwise_quantize(x: jnp.ndarray, *, bits: int = 8, bm: int = 128,
                     interpret: bool = False):
    """x [M, K] -> (int8 [M, K], scales f32 [M, 1]).  Ragged M is zero-padded
    to a bm multiple internally and sliced back off the outputs."""
    m, k = x.shape
    while bm > 32 and 2 * bm * k * (x.dtype.itemsize + 1) > BLOCK_BYTES:
        bm //= 2
    bm = min(bm, m)
    pad_m = (-m) % bm
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    q, s = pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        grid=((m + pad_m) // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((m + pad_m, k), jnp.int8),
                   jax.ShapeDtypeStruct((m + pad_m, 1), jnp.float32)],
        interpret=interpret,
        name="rowwise_quantize",
    )(x)
    return (q[:m], s[:m]) if pad_m else (q, s)
