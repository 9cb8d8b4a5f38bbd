"""Sharding specs + scoped shard context for tensor-parallel paged serving.

The serve stack shards by KV-head over a 1-D ``("model",)`` mesh: K/V
pages ``[L, n_pages, R, lanes]`` split on the lane axis, whose heads are
contiguous runs (``repro.serve.kvq``'s page layout), their int8/int4
scales ``[L, rows, X]`` on the lane axis too (one block per head shard),
the int4 outlier-redistribution rows ``[L, kvh, dh]`` on the head axis,
while page tables, positions and tokens stay replicated
and the scheduler stays host-side and mesh-oblivious.  This is the
MUXQ-native cut: per-(position, head) page scales and the per-head
redistribution rows are head-local, so int8/int4 page quantize/dequantize
never crosses a shard boundary and per-shard token streams stay
bit-identical to the single-device path.

Two layers of API:

  * **Spec building** (host side): :func:`serve_mesh` builds the 1-D mesh
    (with a clear error when the request exceeds visible devices);
    :func:`pool_specs` maps every pool array to a PartitionSpec through
    :func:`repro.parallel.sharding.fit_spec` — a GQA config whose
    ``kvh % tp != 0`` drops the "model" axis and the whole pool falls back
    to replicated placement (the engine then serves with plain jit'd
    steps, no collectives: GSPMD-replicated compute is bit-identical).
  * **Scoped shard context** (trace time): the engine wraps the model call
    inside its ``shard_map`` body in :func:`head_sharding`, and the paged
    attention / logits seams consult :func:`active` — the model files stay
    mesh-agnostic, exactly the :mod:`repro.parallel.act_sharding` pattern.

Bit-exactness of the collectives: attention outputs and logits are
combined with a **zero-pad psum** — each shard scatters its slice into a
full-width zero buffer at its own offset, then one ``psum`` adds M-1 exact
zeros to every element.  Addition order can't matter (zeros are exact in
floating point), so mesh=1 and mesh=N token streams match bit for bit on
fp pages, and int8/int4 pages match their single-device streams exactly.
"""
from __future__ import annotations

import dataclasses
from contextlib import contextmanager
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.parallel.sharding import fit_spec

SERVE_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class HeadShard:
    """The per-shard view of the head axis inside a shard_map body."""
    axis: str = SERVE_AXIS
    size: int = 1


_ACTIVE: Optional[HeadShard] = None


def active() -> Optional[HeadShard]:
    """The HeadShard installed by the engine's shard_map body (None when
    serving single-device / fallen back to replicated)."""
    return _ACTIVE


@contextmanager
def head_sharding(shard: Optional[HeadShard]):
    """Scoped install of the shard context — wrapped around the model call
    at trace time so tp=1 and tp>1 engines coexist (lazy bucket retraces
    see the right context because each engine re-enters it per trace)."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = shard
    try:
        yield
    finally:
        _ACTIVE = prev


# ---------------------------------------------------------------------------
# Mesh + pool specs (host side)
# ---------------------------------------------------------------------------

def serve_mesh(tp: int) -> Mesh:
    """A 1-D ``("model",)`` serving mesh over the first ``tp`` devices."""
    devs = jax.devices()
    if tp < 1:
        raise ValueError(f"mesh size must be >= 1, got {tp}")
    if tp > len(devs):
        raise ValueError(
            f"requested a {tp}-device serving mesh but only {len(devs)} "
            f"device(s) are visible — lower --tp or expose more devices "
            f"(CPU test meshes: XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={tp})")
    return Mesh(np.asarray(devs[:tp]), (SERVE_AXIS,))


def mesh_size(mesh: Mesh) -> int:
    return int(mesh.shape[SERVE_AXIS])


def pool_specs(mesh: Mesh, kv: Dict[str, jnp.ndarray], kvh: int
               ) -> Dict[str, P]:
    """PartitionSpec per pool array, sharding the KV-head axis on "model".

    Pages ``[L, n_pages, R, lanes]`` carry the heads as contiguous lane
    runs on axis 3, scales ``[L, rows, X]`` one block per head shard on
    axis 2, pool-state rows ``[L, kvh, dh]`` (int4 redistribution) the
    heads on axis 1.  A kvh the
    mesh doesn't divide drops the axis from every array — the whole-pool
    replicated fallback the engine detects via :func:`heads_sharded`
    (``fit_spec`` alone would still cut a lane or scale axis the mesh
    divides between heads)."""
    split = kvh % mesh_size(mesh) == 0
    specs: Dict[str, P] = {}
    for name, arr in kv.items():
        wanted = [None] * arr.ndim
        if split:               # lanes of pages and scales, else heads
            lanes = arr.ndim == 4 or name.endswith("_scale")
            wanted[-1 if lanes else 1] = SERVE_AXIS
        specs[name] = fit_spec(mesh, arr.shape, wanted)
    return specs


def heads_sharded(specs: Dict[str, P]) -> bool:
    """True when the K pages actually carry the "model" axis (fit_spec kept
    it) — the engine's sharded-vs-replicated-fallback discriminator."""
    spec = specs.get("k")
    return spec is not None and any(
        ax == SERVE_AXIS or (isinstance(ax, tuple) and SERVE_AXIS in ax)
        for ax in spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def local_bytes(arr) -> int:
    """Bytes of ONE shard of ``arr`` (== global bytes when unsharded).
    jax keeps ``arr.size`` global for sharded arrays, so per-shard
    accounting must go through the sharding's shard_shape."""
    shape = arr.sharding.shard_shape(arr.shape)
    return int(np.prod(shape, dtype=np.int64)) * arr.dtype.itemsize


# ---------------------------------------------------------------------------
# Trace-time helpers (inside the shard_map body)
# ---------------------------------------------------------------------------

def slice_heads(x: jnp.ndarray, shard: HeadShard) -> jnp.ndarray:
    """This shard's contiguous slice of the head axis of ``[b, s, H, dh]``.

    Works for q and k/v alike: GQA orders q heads as
    ``head = kvh_index * group + g`` (see :func:`repro.models.attention.
    sdpa`), so slicing ``h // size`` q heads at offset ``i * h_local``
    keeps exactly the q heads of this shard's kv heads."""
    hl = x.shape[2] // shard.size
    i = jax.lax.axis_index(shard.axis)
    return jax.lax.dynamic_slice_in_dim(x, i * hl, hl, axis=2)


def all_heads(o: jnp.ndarray, n_heads: int, shard: HeadShard) -> jnp.ndarray:
    """Gather per-shard attention outputs ``[..., h_local, dh]`` back to the
    full head axis, bit-exactly: scatter into a zero buffer at this shard's
    offset, then psum — every element is one shard's value plus exact
    zeros, so the sum is order-independent."""
    hl = o.shape[-2]
    i = jax.lax.axis_index(shard.axis)
    full = jnp.zeros(o.shape[:-2] + (n_heads, o.shape[-1]), o.dtype)
    full = jax.lax.dynamic_update_slice_in_dim(full, o, i * hl, o.ndim - 2)
    return jax.lax.psum(full, shard.axis)


def tp_logits(x: jnp.ndarray, head: jnp.ndarray) -> jnp.ndarray:
    """The lm-head matmul, vocab-split across the active shard context.

    Each shard computes its contiguous vocab-column slice (per-column
    contraction over d_model is unchanged by column slicing) and the
    zero-pad psum reassembles the full logits replicated — the shape every
    downstream argmax/softcap already expects.  A vocab the mesh doesn't
    divide, or no active shard, computes the full matmul replicated."""
    shard = active()
    V = head.shape[1]
    if shard is None or shard.size == 1 or V % shard.size:
        return x @ head
    vl = V // shard.size
    i = jax.lax.axis_index(shard.axis)
    part = x @ jax.lax.dynamic_slice_in_dim(head, i * vl, vl, axis=1)
    full = jnp.zeros(x.shape[:-1] + (V,), part.dtype)
    full = jax.lax.dynamic_update_slice_in_dim(full, part, i * vl,
                                               part.ndim - 1)
    return jax.lax.psum(full, shard.axis)
