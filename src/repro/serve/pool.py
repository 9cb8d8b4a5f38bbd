"""Paged KV-cache block pool (vLLM-style, CPU-scale reference).

The serving pool stores every slot's K/V in fixed-size *pages* instead of
one dense ``[b, s_max, ...]`` buffer: a sequence owns ``ceil(len/ps)``
pages, admitted/finished sequences allocate/free pages in O(1) from a free
list, and the decode step routes through a per-slot page table — so memory
scales with *live tokens*, not ``max_batch * s_max``.

Three page modes, each a :class:`repro.serve.kvq.KVQuantizer` (the single
quantize/dequantize seam shared with the attention write/read paths):

  * ``int8`` — pages hold K/V as int8 with per-(position, head) f32 scales
    (the paper's §1 KV-memory motivation: ~2x capacity per byte of HBM,
    Oaken-style);
  * ``int4`` — MUXQ'd nibble pages: calibrated outlier channels are
    magnitude-redistributed before a symmetric 4-bit quantization, K/V
    pack two values per byte and scales store as bf16 — exactly half the
    int8 page bytes, so the same pool byte budget holds 2x the live
    tokens.  Pass the artifact's ``kv_calib`` section for the calibrated
    redistribution (uncalibrated int4 degrades to plain symmetric int4);
  * ``fp``   — pages in ``dtype`` (default bf16), the parity-testing mode
    (bit-exact against the dense cache path).

Layout (``L`` = attention layers, leading so the pool rides ``lax.scan``;
head-major pages, so one (page, head) block is a ``(page_size, dh)`` tile
the paged-attention kernel loads whole):

  k/v        [L, n_pages, kvh, page_size, dh]     (int4: [..., dh//2] int8)
  k/v_scale  [L, n_pages, kvh, page_size, 1]      (int8: f32; int4: bf16)
  k/v_redist [L, kvh, dh] f32                     (int4 only; NOT pages —
                                                   per-head channel
                                                   redistribution rows)
  latent attention: k [L, n_pages, 1, page_size, latent + rope] (+ int8
                    k_scale), one shared row per position, no v
  page_table [n_slots, pages_per_slot] int32   host-side, 0 = unallocated
  refcount   [n_pages] int32                   host-side page sharing state

A model whose layers form several groups (``cfg.layer_groups``: an expert
model's leading dense layers) holds one set of these arrays per group, the
main group's under the bare names and the others' as ``<group>/<name>``
(``common.group_name``), so each group's layer scan takes its own.

Page 0 is a reserved scratch page: inactive slots' decode writes land
there and are never read back, which keeps the pooled step shape-stable
with no per-slot control flow.

**Tensor-parallel placement.**  With ``mesh=`` (a ``("model",)`` serving
mesh from :func:`repro.parallel.serve_sharding.serve_mesh`) the pages,
scales and int4 redistribution rows allocate with ``NamedSharding`` split
on the kvh axis — per-shard HBM is ~``1/mesh_size`` of the global figure
(:meth:`cache_bytes_per_shard` vs :meth:`cache_bytes`).  Everything
host-side (page tables, refcounts, free list) is mesh-oblivious numpy; a
GQA config the mesh doesn't divide falls back to replicated placement
(``heads_sharded`` False) and the engine serves without collectives.

**Prefix sharing / copy-on-write.**  Pages are refcounted so two slots
whose prompts share a prefix can map the *same* physical pages for the
shared positions (:meth:`admit` with ``share_from``/``shared_pages``).
K/V at position p depends only on tokens [0, p] under causal attention, so
identical prefixes produce identical pages — sharing is lossless.  A
shared page is read-only: before any slot writes into it (a decode token
landing in a shared tail page) the scheduler calls
:meth:`ensure_writable`, which copies the page to a private one
(copy-on-write) so the sibling slot's history is never corrupted.

**Block-sparse read budget.**  The decode step reads only the page-table
columns the *longest live* sequence needs (``ceil(pos/ps)`` pages,
bucketed to powers of two by :meth:`bucket_pages` so the pooled step
compiles once per bucket instead of once per length), not the full
``pages_per_slot`` capacity; :meth:`page_read_bytes` prices one page
across all layers for the bytes-read metrics.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import ModelConfig, group_name
from repro.models.attention import cache_rows
from repro.parallel import serve_sharding as SS
from repro.serve import kvq
from repro.serve.kvcache import cache_bytes


def bucket_pow2(n: int, cap: int) -> int:
    """Round ``n`` up to the next power of two, clamped to [1, cap] — the
    shared bucketing rule for decode page budgets AND prefill chunk sizes,
    so both compile one executable per bucket, never per length."""
    n = max(1, min(n, cap))
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


class PagePool:
    """Fixed-size page pool + per-slot page tables + free-list alloc/free."""

    def __init__(self, cfg: ModelConfig, n_slots: int, s_max: int, *,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 mode: str = "int8", dtype=jnp.bfloat16,
                 kv_calib: Optional[dict] = None, mesh=None):
        if mode not in kvq.KV_MODES:
            raise ValueError(f"unknown page mode {mode!r}")
        self.cfg, self.mode, self.dtype = cfg, mode, dtype
        self.n_slots, self.page_size = n_slots, page_size
        self.pages_per_slot = max(1, math.ceil(s_max / page_size))
        self.capacity = self.pages_per_slot * page_size  # tokens per slot
        # +1: page 0 is the reserved scratch page (never allocated)
        self.n_pages = (n_pages if n_pages is not None
                        else n_slots * self.pages_per_slot + 1)
        if self.n_pages < 2:
            raise ValueError("pool needs at least one allocatable page")

        kvh, dh = cache_rows(cfg)
        self.quantizer = kvq.make_quantizer(mode, kvh=kvh, dh=dh,
                                            dtype=dtype, calib=kv_calib,
                                            latent=cfg.is_mla)
        self.kv: Dict[str, jnp.ndarray] = {}
        state: Dict[str, jnp.ndarray] = {}
        for key, lo, hi in cfg.layer_groups:
            pages = self.quantizer.page_arrays(hi - lo, self.n_pages,
                                               page_size, kvh, dh)
            self.kv.update({group_name(key, n): a for n, a in pages.items()})
            state.update({group_name(key, n): a for n, a in
                          self.quantizer.pool_state(hi - lo, kvh, dh).items()})
        # keys whose second axis indexes pages (COW copies / prefill
        # scatters / read-bytes pricing touch these ONLY); the rest of
        # self.kv is per-pool state like the int4 redistribution rows,
        # stacked [L, ...] so it rides the same scan xs as the pages
        self._page_keys = tuple(self.kv)
        self.kv.update(state)
        # tensor-parallel placement: on a ("model",) mesh the pages, scales
        # and int4 redistribution rows shard on the kvh axis via the
        # parallel/serve_sharding spec builder (kvh % mesh -> replicated
        # fallback, fit_spec drops the axis); host-side free-list / admit /
        # COW / release logic below is numpy and never sees the mesh
        self.mesh = mesh
        if mesh is not None:
            self.kv_pspecs = SS.pool_specs(mesh, self.kv)
            self._shardings = {n: jax.sharding.NamedSharding(
                mesh, self.kv_pspecs[n]) for n in self.kv}
            self.kv = {n: jax.device_put(a, self._shardings[n])
                       for n, a in self.kv.items()}
            self.heads_sharded = SS.heads_sharded(self.kv_pspecs)
            self.kv_shards = (SS.mesh_size(mesh) if self.heads_sharded else 1)
        else:
            self.kv_pspecs = None
            self._shardings = None
            self.heads_sharded = False
            self.kv_shards = 1
        self.page_table = np.zeros((n_slots, self.pages_per_slot), np.int32)
        self.refcount = np.zeros(self.n_pages, np.int32)
        self._free = list(range(self.n_pages - 1, 0, -1))  # pop() -> page 1 first
        self._table_device: Optional[jnp.ndarray] = None
        # fragmentation/occupancy counters (lifetime, for metrics)
        self.alloc_count = 0
        self.free_count = 0
        self.alloc_failures = 0
        # prefix-sharing counters (lifetime)
        self.share_count = 0      # pages mapped into a second+ slot
        self.cow_count = 0        # copy-on-write page copies

    # -- alloc / free --------------------------------------------------------

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return (self.n_pages - 1) - len(self._free)

    def pages_needed(self, n_tokens: int) -> int:
        return max(1, math.ceil(n_tokens / self.page_size))

    def admit(self, slot: int, n_tokens: int, *,
              share_from: Optional[int] = None,
              shared_pages: int = 0) -> bool:
        """Allocate the pages covering positions [0, n_tokens) for ``slot``.
        Returns False (allocating/mapping nothing) when the pool lacks free
        pages.

        With ``share_from``/``shared_pages``, the first ``shared_pages``
        logical pages are MAPPED from ``share_from``'s page table instead of
        freshly allocated (prefix sharing): the physical pages' refcounts go
        up and both slots read the same K/V until a copy-on-write
        (:meth:`ensure_writable`) splits them."""
        assert not self.page_table[slot].any(), f"slot {slot} already has pages"
        need = self.pages_needed(n_tokens)
        if need > self.pages_per_slot:
            raise ValueError(
                f"{n_tokens} tokens need {need} pages > pages_per_slot="
                f"{self.pages_per_slot} (raise s_max or page_size)")
        assert 0 <= shared_pages <= need, (shared_pages, need)
        if shared_pages:
            assert share_from is not None and share_from != slot
            src = self.page_table[share_from, :shared_pages]
            assert np.all(src > 0), (
                share_from, "prefix share from unallocated source pages")
        if need - shared_pages > len(self._free):
            self.alloc_failures += 1
            return False
        for j in range(shared_pages):
            pid = int(self.page_table[share_from, j])
            self.page_table[slot, j] = pid
            self.refcount[pid] += 1
        self.share_count += shared_pages
        for j in range(shared_pages, need):
            pid = self._free.pop()
            self.page_table[slot, j] = pid
            self.refcount[pid] = 1
        self.alloc_count += need - shared_pages
        self._table_device = None
        return True

    def ensure(self, slot: int, page_idx: int) -> bool:
        """Make sure logical page ``page_idx`` of ``slot`` is backed; grows
        by one page from the free list.  False on exhaustion."""
        if self.page_table[slot, page_idx]:
            return True
        if not self._free:
            self.alloc_failures += 1
            return False
        pid = self._free.pop()
        self.page_table[slot, page_idx] = pid
        self.refcount[pid] = 1
        self.alloc_count += 1
        self._table_device = None
        return True

    def ensure_writable(self, slot: int, page_idx: int) -> bool:
        """Back logical page ``page_idx`` AND make it private to ``slot``.

        An unbacked page allocates (:meth:`ensure`); a page shared with a
        sibling slot (refcount > 1) is copied on write — the slot gets a
        fresh physical page holding the same K/V, the sibling keeps the
        original untouched.  False on pool exhaustion."""
        if not self.ensure(slot, page_idx):
            return False
        old = int(self.page_table[slot, page_idx])
        if self.refcount[old] <= 1:
            return True
        if not self._free:
            self.alloc_failures += 1
            return False
        new = self._free.pop()
        # device-side page copy across every page-indexed array (all layers
        # at once; pool state like the int4 redist rows has no page axis)
        for name in self._page_keys:
            upd = self.kv[name].at[:, new].set(self.kv[name][:, old])
            self.kv[name] = self._constrain(name, upd)
        self.refcount[old] -= 1
        self.refcount[new] = 1
        self.page_table[slot, page_idx] = new
        self.alloc_count += 1
        self.cow_count += 1
        self._table_device = None
        return True

    def detach_prefix(self, slot: int, n_tokens: int) -> list:
        """Transfer ownership of the pages covering positions [0, n_tokens)
        OUT of ``slot`` and release the rest of its pages.  The returned
        page ids (logical order) keep their refcounts — the caller now
        holds one reference per page and must hand them back via
        :meth:`readmit` or drop them via :meth:`drop_detached`.

        This is the true-chunk-boundary resume seam: a preempted
        mid-prefill slot's already-written prefill pages stay alive across
        requeue, so the eventual replay re-runs ZERO chunks.  Kept pages
        may include prefix-shared ones (refcount > 1) — the reference
        simply survives detached, exactly as it would have in the table."""
        keep = self.pages_needed(n_tokens) if n_tokens > 0 else 0
        kept = [int(p) for p in self.page_table[slot, :keep] if p]
        # zero the kept mappings WITHOUT decref (ownership moves to the
        # caller), then release whatever remains normally
        self.page_table[slot, :keep] = 0
        self.release(slot)
        return kept

    def readmit(self, slot: int, n_tokens: int, pages: list) -> bool:
        """Re-admit a slot whose first ``len(pages)`` logical pages are
        PREMAPPED (:meth:`detach_prefix`'s kept pages — the caller's
        references move back into the table, no refcount change),
        allocating fresh pages only for the remainder of [0, n_tokens).
        Returns False (installing nothing, references untouched) when the
        pool lacks free pages for the remainder."""
        assert not self.page_table[slot].any(), f"slot {slot} already has pages"
        need = self.pages_needed(n_tokens)
        if need > self.pages_per_slot:
            raise ValueError(
                f"{n_tokens} tokens need {need} pages > pages_per_slot="
                f"{self.pages_per_slot} (raise s_max or page_size)")
        k = len(pages)
        assert k <= need, (k, need, "detached pages exceed the prompt's span")
        if need - k > len(self._free):
            self.alloc_failures += 1
            return False
        for j, pid in enumerate(pages):
            assert self.refcount[pid] > 0, (pid, "readmit of a freed page")
            self.page_table[slot, j] = pid
        for j in range(k, need):
            pid = self._free.pop()
            self.page_table[slot, j] = pid
            self.refcount[pid] = 1
        self.alloc_count += need - k
        self._table_device = None
        return True

    def drop_detached(self, pages: list) -> int:
        """Drop the caller's references on :meth:`detach_prefix`'d pages (a
        resume that will never happen — run teardown, or kept pages
        reclaimed to un-wedge an exhausted pool).  Returns the number of
        pages actually freed (shared pages survive with their sibling)."""
        freed = []
        for p in pages:
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                freed.append(int(p))
        self._free.extend(reversed(freed))
        self.free_count += len(freed)
        return len(freed)

    def release(self, slot: int) -> int:
        """Drop every page mapping owned by ``slot``; pages whose refcount
        hits zero return to the free list.  Returns the number of pages
        actually freed (shared pages survive with the sibling slot)."""
        freed = []
        for p in self.page_table[slot]:
            if not p:
                continue
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                freed.append(int(p))
        self._free.extend(reversed(freed))
        self.free_count += len(freed)
        self.page_table[slot] = 0
        self._table_device = None
        return len(freed)

    # -- device state --------------------------------------------------------

    def _constrain(self, name: str, arr: jnp.ndarray) -> jnp.ndarray:
        """Re-commit a pool array updated by an EAGER op (COW copy, prefill
        scatter) to its mesh sharding — eager GSPMD output placement is not
        guaranteed to match the allocation spec, and the jit'd steps key
        their executables on input shardings."""
        if self._shardings is None:
            return arr
        return jax.device_put(arr, self._shardings[name])

    def table(self) -> jnp.ndarray:
        """The page table as a device array (cached until it changes)."""
        if self._table_device is None:
            self._table_device = jnp.asarray(self.page_table)
        return self._table_device

    def state(self) -> Dict[str, jnp.ndarray]:
        """The pool's KV arrays (pass into the jit'd decode step; pair with
        :meth:`adopt` for donation)."""
        return self.kv

    def adopt(self, kv: Dict[str, jnp.ndarray]) -> None:
        """Take ownership of the decode step's updated pool arrays."""
        assert set(kv) == set(self.kv), (set(kv), set(self.kv))
        self.kv = kv

    # -- block-sparse read budget --------------------------------------------

    def live_page_counts(self) -> np.ndarray:
        """Per-slot count of backed logical pages ([n_slots] int) — the
        live-page vector the scheduler turns into a read budget."""
        return (self.page_table > 0).sum(axis=1).astype(np.int32)

    def live_pages(self) -> np.ndarray:
        """Physical page ids currently mapped by at least one slot
        (refcount > 0) — what the quality observer samples."""
        return np.flatnonzero(self.refcount > 0)

    def bucket_pages(self, n_needed: int) -> int:
        """Round a page budget up to the next power of two (clamped to
        ``pages_per_slot``) so the pooled decode compiles one executable per
        bucket instead of one per sequence length."""
        return bucket_pow2(n_needed, self.pages_per_slot)

    def page_read_bytes(self) -> int:
        """Bytes one page costs to read across ALL attention layers (K + V
        + scales; int4 counts true packed nibble bytes) — the unit for the
        decode bytes-read metrics.  Only page-indexed arrays count: the
        int4 redistribution rows are per-pool constants, not page traffic."""
        return sum(self.kv[n].size * self.kv[n].dtype.itemsize
                   for n in self._page_keys) // self.n_pages

    # -- prefill write -------------------------------------------------------

    def write_prefill(self, slot: int, k: jnp.ndarray, v: jnp.ndarray, *,
                      start_pos: int = 0) -> None:
        """Scatter a prefilled dense cache slice (k/v ``[L, s, kvh, dh]``,
        compute dtype) into ``slot``'s pages, quantizing in int8 mode.  The
        slot must already own the pages covering [start_pos, s) (see
        :meth:`admit`).  ``start_pos`` skips positions covered by
        prefix-shared pages (they already hold identical K/V and are mapped
        read-only; writing them would corrupt the sibling slot) and must be
        page-aligned when anything remains to write.

        One indexed scatter per pool array (the tail of the slot's last
        page zero-pads): each eager ``.at[].set`` copies the whole pool
        array, so a per-page loop would cost O(pages) pool copies per
        admitted request."""
        s = k.shape[1]
        if start_pos >= s:
            return                      # fully covered by shared pages
        assert start_pos % self.page_size == 0, (
            start_pos, "prefill writes must start on a page boundary")
        first = start_pos // self.page_size
        if start_pos:
            k, v = k[:, start_pos:], v[:, start_pos:]
            s = s - start_pos
        parts = self.quantizer.quantize(k, v)
        n = self.pages_needed(s)
        pids = self.page_table[slot, first:first + n]
        assert np.all(pids > 0), (slot, "prefill write into unallocated page")
        assert np.all(self.refcount[pids] == 1), (
            slot, "prefill write into a shared page (needs copy-on-write)")
        pad = n * self.page_size - s
        for name, arr in parts.items():
            a = arr.astype(self.kv[name].dtype)
            if pad:
                a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            a = a.reshape(a.shape[0], n, self.page_size, *a.shape[2:])
            a = jnp.swapaxes(a, 2, 3)           # head-major [L, n, kvh, ps, *]
            self.kv[name] = self._constrain(
                name, self.kv[name].at[:, jnp.asarray(pids)].set(a))

    # -- accounting ----------------------------------------------------------

    def cache_bytes(self) -> int:
        """GLOBAL bytes held by the page pool (all pages, live or free,
        summed across every shard — ``jax`` keeps array sizes global under
        a mesh, so this number is mesh-invariant by construction and the
        CI-gated ``kv_bytes_read`` / ``bytes_per_token`` comparisons stay
        comparable across mesh sizes)."""
        return cache_bytes(self.kv)

    def cache_bytes_per_shard(self) -> int:
        """Bytes ONE mesh shard holds (== :meth:`cache_bytes` unsharded):
        the per-device HBM footprint — the number that actually has to fit,
        and the capacity-scaling win the KV-head sharding exists to
        deliver (~ global / mesh_size when kvh divides)."""
        return sum(SS.local_bytes(a) for a in self.kv.values())

    def stats(self, slot_lens: Optional[Dict[int, int]] = None) -> Dict[str, float]:
        """Occupancy + fragmentation + sharing counters.  ``slot_lens``
        ({slot: live tokens}) refines internal fragmentation: the fraction
        of allocated page capacity not holding a live token."""
        usable = self.n_pages - 1
        out = {
            "pages_total": usable,
            "pages_in_use": self.pages_in_use,
            "occupancy": self.pages_in_use / usable if usable else 0.0,
            "alloc_count": self.alloc_count,
            "free_count": self.free_count,
            "alloc_failures": self.alloc_failures,
            "cache_bytes": self.cache_bytes(),
            "cache_bytes_per_shard": self.cache_bytes_per_shard(),
            "kv_shards": self.kv_shards,
            "kv_mode": self.mode,
            # page bytes one token position costs across all layers (K + V
            # + scales) — fp > int8 > int4 at a fixed model shape
            "bytes_per_token": self.page_read_bytes() / self.page_size,
            "pages_shared": int((self.refcount > 1).sum()),
            "share_count": self.share_count,
            "cow_count": self.cow_count,
        }
        if slot_lens is not None:
            cap = self.pages_in_use * self.page_size
            live = sum(slot_lens.values())
            out["live_tokens"] = live
            # clamp at 0: prefix-shared pages serve several slots' tokens at
            # once, so live tokens can exceed the (deduplicated) capacity
            out["internal_fragmentation"] = (
                max(0.0, 1.0 - live / cap) if cap else 0.0)
        return out
