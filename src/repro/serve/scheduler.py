"""Continuous-batching scheduler over a paged KV pool.

The scheduler owns the serving control loop the engine used to inline:

  * **FIFO admission** — queued requests claim free slots as soon as pages
    are available (arrival steps optionally gate admission for load
    generators).  Admission allocates the prompt's pages and marks the
    slot PREFILLING; it never runs prompt compute itself.  Admission
    detects a shared prompt prefix with a live slot and maps the covered
    pages instead of allocating fresh ones (prefix sharing — lossless:
    causal K/V at position p depends only on tokens [0, p]); the chunked
    prefill then *skips both recompute and rewrite* of the shared
    positions — it starts at the first uncovered position and attends over
    the mapped pages;
  * **multi-slot chunked paged prefill, interleaved with decode** — each
    step advances up to ``prefill_slots`` prefilling slots by one
    ``prefill_chunk``-token chunk each, batched into ONE traced call
    (:func:`repro.models.transformer.prefill_chunk_paged` scatters every
    slot's chunk K/V straight into pool pages over a ``[slot, chunk]``
    block; there is no dense ``[1, T]`` prefill cache) *alongside* the
    pooled decode step, so a long-prompt flood neither stalls live decode
    slots nor serializes prompt work one slot at a time.  The call always
    runs at the full ``[n_slots, C]`` pool width — slots not advancing
    get zeroed table rows and empty write windows routing to the scratch
    page — so the slot count never enters the traced shapes.  The chunk
    picker is shortest-remaining-first with an **aging** credit
    (``prefill_aging`` remaining-tokens per waited step, admission order
    as the tie-break): short requests keep a low TTFT under a long-prompt
    flood, while the aging term bounds how long a long prompt can starve
    under a sustained short-request stream (``prefill_aging=0`` recovers
    pure SRF).  Chunk token counts bucket to powers of two (like decode
    page budgets), so the chunked prefill compiles once per
    (chunk-bucket, page-bucket) pair, never per prompt length or per
    number of advancing slots;
  * **one jit'd decode per step for the WHOLE pool** — slot positions ride
    a per-slot vector into :func:`repro.models.transformer.decode_step_paged`,
    so misaligned sequences batch instead of falling back to per-slot
    decode.  There is no alignment fast path to fall off of: every step is
    exactly one traced call regardless of slot positions.  Mid-prefill
    slots sit the decode out — their page-table rows are zeroed for the
    step, routing the (shape-stable) pool-wide write to the reserved
    scratch page;
  * **block-sparse page budget** — each step passes only the page-table
    columns the longest live *decoding* sequence needs (its live-page
    count from the pool, bucketed to powers of two so there is one
    compiled executable per bucket, not per length): a 16-token sequence
    in a 2048-capacity slot reads 1 page of K/V, not 128;
  * **copy-on-write** — before a decode token lands in a prefix-shared
    page the pool copies it to a private page, so the sibling slot's
    history is never corrupted;
  * **preemption** — when a growing sequence needs a page and the pool is
    exhausted, the live sequence holding the longest token range is
    evicted (pages freed, request requeued at the front) and later resumed
    by re-prefilling prompt + generated tokens — in chunks, so the replay
    resumes at a chunk boundary and never stalls the pool either.  With fp
    pages at the compute dtype the replay reproduces the evicted cache bit
    for bit; with int8 pages it is approximate (within quantization
    noise).  A slot preempted MID-PREFILL resumes from the **true chunk
    boundary**: its already-written prefill pages are detached from the
    slot (refcounts kept — :meth:`repro.serve.pool.PagePool.detach_prefix`)
    and travel with the queue entry, so re-admission re-installs them and
    the replay re-runs ZERO chunks — and because nothing is recomputed,
    the resumed stream is bit-exact in EVERY page mode, not just fp.
    Detached reservations are the first thing reclaimed if the pool wedges
    with nothing live to evict (the owning request then falls back to
    replay-from-chunk-0);
  * **self-speculative decoding** (``spec_mode="ngram"``) — a host-side
    prompt-lookup proposer drafts up to ``spec_k - 1`` tokens per live
    slot from its own prompt+output history (:mod:`repro.serve.spec`);
    ONE batched verify step scores every slot's ``[slot, k]`` draft block
    (:func:`repro.models.transformer.decode_verify_paged`), greedy
    acceptance keeps each slot's longest agreeing prefix plus the model's
    own next token, and rejected positions roll back for free — per-slot
    ``pos`` only advances over accepted tokens, so rejected page rows are
    simply overwritten later (COW pages are made private before the
    k-token write).  Because acceptance re-checks every draft token
    against the model's own argmax, fp-page output streams are bit-exact
    vs plain greedy decode — speculation changes step count, never
    tokens.  k buckets to pow2 so verify compiles once per (k, page)
    bucket pair;
  * **streaming** — each emitted token is pushed through the request's
    ``stream`` callback the step it is sampled;
  * **metrics** — tokens/s, TTFT (wall clock and step clock, also stamped
    onto each request), prefill chunk counts, prefill/decode interleaving
    and decode-stall counters, pool occupancy, fragmentation, decode KV
    bytes read (block-sparse vs the dense capacity gather) and sharing
    stats via :class:`repro.serve.metrics.ServeMetrics`.

The scheduler is **mesh-oblivious**: its state (slots, positions, page
tables, the FIFO queue) is host-side numpy, and the jit'd step callables
it drives are closed over any device mesh by the engine
(``ServeEngine`` + ``parallel/serve_sharding.py``).  Sharded pool arrays
flow through ``self.pool.kv`` as opaque values — nothing here branches on
``tp``, which is exactly why tensor-parallel streams can be bit-identical
to single-device ones.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import tokenizer as tok
from repro.obs.trace import NULL_RECORDER, NULL_SPAN
from repro.serve import spec
from repro.serve.metrics import ServeMetrics
from repro.serve.pool import PagePool, bucket_pow2


def bucket_chunk(n: int, cap: int) -> int:
    """Round a chunk's token count up to the next power of two, clamped to
    ``cap`` (the configured ``prefill_chunk``) — one compiled prefill
    executable per chunk bucket, never per prompt length.  Same rule as
    the decode page buckets (:func:`repro.serve.pool.bucket_pow2`)."""
    return bucket_pow2(n, cap)


@dataclasses.dataclass
class _Slot:
    req: object                 # repro.serve.engine.Request
    submit_t: float
    ids: np.ndarray             # the token ids this slot prefills with
    arrive_step: int            # step clock when the request FIRST arrived
    seq: int                    # admission order (prefill SRF tie-break)
    prefilling: bool = True     # still running chunked prefill
    pre_pos: int = 0            # next prompt position to compute
    pre_start: int = 0          # where this slot's chunked compute began
    write_from: int = 0         # first position NOT covered by shared pages
    # full known token stream (prompt + generated), the n-gram proposer's
    # lookup corpus — the last entry is the next decode input
    hist: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _QEntry:
    """One queued (or requeued) request plus everything its eventual
    admission needs.  First-arrival state (``submit_t`` / ``arrive_step``)
    is stamped once when the arrival step is reached and survives
    preemption requeues untouched — the replay-invariant TTFT face derives
    from it, never from replay-time snapshots."""
    req: object
    arrive: int                     # arrival-step gate (0 for requeues)
    submit_t: Optional[float] = None  # wall clock at first arrival
    arrive_step: int = 0            # step clock at first arrival
    # mid-prefill true resume: (detached page ids, pre_pos, write_from) —
    # the pages covering [0, pre_pos) stay alive (refcounts held by this
    # entry) so the replay re-runs zero chunks.  None = plain admission.
    resume: Optional[tuple] = None


class Scheduler:
    """Drives a request set to completion against one :class:`PagePool`.

    ``prefill_fn(tokens [n_slots, C], kv, page_table [n_slots, pb],
    start, write_lo, write_hi — all [n_slots] int32) ->
    (next_tokens [n_slots, C], new_kv)`` runs one bucketed chunk for each
    chosen prefilling slot against the paged pool in ONE call (the engine
    binds params/ctx/qparams and jits per (chunk, page) bucket pair;
    idle rows carry zeroed tables and empty write windows).  ``decode_fn(tokens, kv, page_table, pos) ->
    (next_tokens, new_kv)`` is the jit'd pool-wide step; ``page_table``
    arrives sliced to the step's page budget — the kernel side reads the
    budget off the table's shape.  ``verify_fn(tokens [b, k], kv,
    page_table, pos, n_valid) -> (next_tokens [b, k], new_kv)`` is the
    jit'd speculative verify block (required when ``spec_mode != "off"``;
    the engine jits it once per (k, page) bucket pair)."""

    def __init__(self, pool: PagePool,
                 prefill_fn: Callable, decode_fn: Callable,
                 verify_fn: Optional[Callable] = None, *,
                 eos: int = tok.EOS,
                 metrics: Optional[ServeMetrics] = None,
                 prefix_sharing: bool = True,
                 prefill_chunk: int = 32,
                 prefill_slots: int = 2,
                 prefill_aging: float = 1.0,
                 spec_mode: str = "off",
                 spec_k: int = 4,
                 recorder=None,
                 quality=None):
        self.pool = pool
        self.prefill = prefill_fn
        self.decode = decode_fn
        self._counts: dict = {}       # device counters of this iteration
        self.verify = verify_fn
        self.eos = eos
        self.metrics = metrics if metrics is not None else ServeMetrics()
        # flight recorder (repro.obs.trace): NULL_RECORDER = tracing off,
        # every hook an immediate no-op.  All recording is host-side —
        # nothing below ever runs inside a traced step.
        self.rec = recorder if recorder is not None else NULL_RECORDER
        self.quality = quality       # optional repro.obs.quality observer
        self._rids: dict = {}        # id(request) -> trace rid (submit order)
        self._step = 0               # current step clock (for hooks without
        #                              a step argument, e.g. _finish)
        self.prefix_sharing = prefix_sharing
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = int(prefill_chunk)
        if prefill_slots < 1:
            raise ValueError(f"prefill_slots must be >= 1, got {prefill_slots}")
        if prefill_aging < 0:
            raise ValueError(f"prefill_aging must be >= 0, got {prefill_aging}")
        # up to prefill_slots prefilling slots advance one chunk each per
        # step, in ONE traced call at the full pool width (the knob never
        # changes traced shapes); prefill_aging is the anti-starvation
        # credit: remaining-token equivalents forgiven per waited step
        self.prefill_slots = int(prefill_slots)
        self.prefill_aging = float(prefill_aging)
        if spec_mode not in spec.SPEC_MODES:
            raise ValueError(f"unknown spec_mode {spec_mode!r} "
                             f"(expected one of {spec.SPEC_MODES})")
        if spec_mode != "off" and verify_fn is None:
            raise ValueError("spec_mode needs a verify_fn (the jit'd "
                             "multi-token verify step)")
        if spec_mode != "off" and spec_k < 2:
            raise ValueError(f"spec_k must be >= 2, got {spec_k}")
        self.spec_mode = spec_mode
        self.spec_k = int(spec_k)
        n = pool.n_slots
        self.slots: List[Optional[_Slot]] = [None] * n
        self.pos = np.zeros(n, np.int32)        # per-slot live decode length
        self.last_tok = np.zeros(n, np.int32)
        self._admit_seq = 0
        # first-arrival accounting, keyed by request identity and written
        # exactly once per request: the global prefill-token clock at
        # arrival plus the request's OWN chunk tokens across every attempt.
        # ttft_prefill_tokens derives from these, so preemption replays
        # can never double-count into the CI-gated TTFT face.
        self._first: dict = {}
        self._qw_stamped: set = set()   # id(req): queue_wait observed once

    # -- public --------------------------------------------------------------

    def run(self, requests: Sequence, arrivals: Optional[Sequence[int]] = None):
        """Run all requests to completion.  ``arrivals`` (optional, one int
        per request) gates admission on the decode-step clock — the load
        generator's Poisson arrival hook; default: everything at step 0."""
        m = self.metrics
        m.start()
        m.cow_baseline = self.pool.cow_count
        if arrivals is None:
            arrivals = [0] * len(requests)
        if len(arrivals) != len(requests):
            raise ValueError(f"{len(requests)} requests but {len(arrivals)} "
                             "arrival steps (zip would silently drop work)")
        # pre-flight: reject oversized prompts BEFORE any pool allocation,
        # so a malformed request can't abort mid-run with pages held
        for req in requests:
            need = len(self._request_ids(req)) + 1
            if need > self.pool.capacity and not req.out_tokens:
                raise ValueError(
                    f"prompt of {need - 1} tokens exceeds slot capacity "
                    f"{self.pool.capacity - 1} (raise s_max)")
        # trace rids in submit order (stable across preemption/requeue:
        # keyed by request identity)
        for req in requests:
            self._rids.setdefault(id(req), len(self._rids))
        queue = collections.deque(
            _QEntry(req, int(arr)) for req, arr in
            sorted(zip(requests, arrivals), key=lambda p: p[1]))
        m.submitted += len(requests)
        step_clock = 0

        try:
            self._run_loop(queue, step_clock)
        except BaseException:
            # never leave the (engine-persistent) pool dirty: drop every
            # live slot AND every queued entry's detached page reservation
            # so later generate() calls start from a clean pool
            for i, s in enumerate(self.slots):
                if s is not None:
                    self.pool.release(i)
                    self.slots[i] = None
                    self.pos[i] = 0
            for e in queue:
                if e.resume is not None:
                    self.pool.drop_detached(e.resume[0])
                    e.resume = None
            raise
        m.stop()
        return list(requests)

    def _run_loop(self, queue, step_clock: int) -> None:
        while queue or any(self.slots):
            with self.rec.span("sched.iteration", step=step_clock):
                step_clock = self._iteration(queue, step_clock)

    def _iteration(self, queue, step_clock: int) -> int:
        """One pass of the loop: arrivals and admission, a prefill chunk
        step, page backing, the pooled decode (or verify), then the step's
        accounting.  Returns the next step clock; the clock stays put only
        when nothing is live or queued, which ends the loop.  Each piece of
        host work runs inside a recorder span (``sched.*``) so a profile
        can name the device's idle time between step programs."""
        m, rec = self.metrics, self.rec
        self._step = step_clock
        with rec.span("sched.admit"):
            # a request's TTFT clock starts when it ARRIVES (its arrival
            # step is reached), not when run() starts — otherwise the load
            # generator's arrival schedule would inflate the queueing delay
            now = None
            for entry in queue:
                if entry.submit_t is None and entry.arrive <= step_clock:
                    entry.submit_t = now = now or time.perf_counter()
                    entry.arrive_step = step_clock
                    # first-arrival snapshot: the global prefill-token
                    # clock now, plus an own-token accumulator — the
                    # replay-invariant basis for ttft_prefill_tokens
                    self._first[id(entry.req)] = {
                        "tok0": m.prefill_chunk_tokens, "own": 0}
                    if rec.enabled:
                        rid = self._rids[id(entry.req)]
                        rec.instant(rid, "QUEUED", "SUBMITTED", step_clock)
                        rec.begin(rid, "QUEUED", step_clock)
            self._admit(queue, step_clock)
            m.live_slots_peak = max(
                m.live_slots_peak, sum(s is not None for s in self.slots))
        if not any(self.slots):
            # everything pending is a future arrival; with nothing queued
            # either, the unchanged clock ends the loop
            return step_clock + 1 if queue else step_clock

        cow0 = self.pool.cow_count      # step-record COW delta baseline
        self._counts = {}               # the iteration's device counters
        # up to prefill_slots prefilling slots advance one chunk each,
        # batched into ONE traced call — the per-step prompt-token
        # budget that keeps decode flowing under a long-prompt flood
        # without serializing prompt work.  Returns the step-record
        # info (slots + buckets) or None; truthiness = "chunks ran".
        did_prefill = self._prefill_chunk_step(step_clock)
        # n-gram drafts first (host-side, no pool effects), so the
        # page-backing pass can cover each slot's whole k-token write
        drafts = {}
        if self.spec_mode != "off":
            with rec.span("sched.verify.propose"):
                drafts = self._propose_drafts()
        # back every live decode slot's next write position(s) (may
        # preempt on pool exhaustion)
        with rec.span("sched.pages"):
            self._ensure_pages(
                queue, {i: 1 + len(d) for i, d in drafts.items()})
        active = [i for i, s in enumerate(self.slots)
                  if s is not None and not s.prefilling]
        # page-backing may have preempted (or finished) a drafted slot
        drafts = {i: d for i, d in drafts.items() if i in set(active)}
        decode_ran = False
        verify_k = None
        bucket = 0
        if drafts:
            # speculative path: ONE verify call scores every slot's
            # draft block; accepted tokens emit in order
            verify_k, bucket = self._verify_step(active, drafts,
                                                 did_prefill, step_clock)
            decode_ran = True
        elif active:
            # ONE jit'd decode for the whole pool, per-slot positions
            # inside
            with rec.span("sched.decode.prepare"):
                bucket, table = self._decode_table(active)
                toks = jnp.asarray(self.last_tok)[:, None]
                pos = jnp.asarray(self.pos)
                m.record_attn_pages(self.pos, 1, bucket,
                                    self.pool.page_size)
            with (rec.span("sched.decode.dispatch", slots=len(active),
                           page_bucket=bucket)
                  if rec.enabled else NULL_SPAN):
                nxt, new_kv = self.decode(toks, self.pool.state(), table,
                                          pos)
                self.pool.adopt(new_kv)
            with rec.span("sched.decode.wait"):
                outs = self._fetch(nxt)
            with rec.span("sched.decode.emit"):
                m.decode_steps += 1
                m.decode_slot_steps += len(active)
                m.record_read(self.pool, bucket)
                if did_prefill:
                    m.interleaved_steps += 1
                for i in active:
                    self.pos[i] += 1
                    self._post_token(i, int(outs[i]))
            decode_ran = True
        with rec.span("sched.account"):
            if active and not decode_ran:
                # falsifiable stall gate: trips if a future change makes
                # the pooled decode conditional (e.g. prefill-exclusive
                # steps) while live decode slots wait — serve_bench --smoke
                # asserts this stays 0
                m.decode_stall_steps += 1
            if rec.enabled:
                # one scheduler record per active step: what ran and what
                # it cost — the trace's answer to "what was step N doing"
                pf = did_prefill or {}
                pf_slots = pf.get("slots", [])
                rec.step_record(
                    step_clock, decode_ran=decode_ran, slots=len(active),
                    page_bucket=bucket if decode_ran else 0,
                    verify_k=verify_k or 0,
                    prefill_slots=pf_slots,
                    prefill_slot=pf_slots[0] if pf_slots else None,
                    chunk_bucket=pf.get("chunk_bucket", 0),
                    prefill_page_bucket=pf.get("page_bucket", 0),
                    cow=self.pool.cow_count - cow0, **self._counts)
            if self.quality is not None:
                self.quality.maybe_sample_pool(self.pool, step_clock)
            live = {i: (int(self.pos[i]) if not s.prefilling else s.pre_pos)
                    for i, s in enumerate(self.slots) if s}
            m.sample_pool(self.pool.stats(live))
        return step_clock + 1

    def _fetch(self, out) -> np.ndarray:
        """A step's tokens on the host.  A step returns (tokens, counts):
        its device counters (an expert model's ``moe_*`` rows) come back
        in the same fetch and add to ServeMetrics and the iteration's
        step record."""
        toks, counts = jax.device_get(out)
        for name, v in counts.items():
            setattr(self.metrics, name, getattr(self.metrics, name) + int(v))
            self._counts[name] = self._counts.get(name, 0) + int(v)
        return toks

    # -- admission -----------------------------------------------------------

    def _request_ids(self, req) -> np.ndarray:
        """Prefill token ids: the prompt, plus — after a preemption — every
        generated token but the last (which becomes the next decode input)."""
        ids = tok.encode(req.prompt)
        if req.out_tokens:
            ids = np.concatenate(
                [ids, np.asarray(req.out_tokens[:-1], np.int32)])
        return ids

    def _shared_prefix(self, ids: np.ndarray):
        """Best prefix-share candidate among live slots: (src_slot,
        shared_pages, write_from, pending).

        Whole pages covered by the common prefix are always shareable.  The
        partial tail page is shareable only when the new prompt lies
        entirely inside the common prefix (``c == len(ids)``): the slot
        then writes nothing at prefill, and its first decode write into the
        shared tail triggers copy-on-write.

        A mid-prefill source has only written positions < ``pre_pos``;
        pages past that are allocated but hold no K/V yet.  Rather than
        admit the new request unshared (recomputing a prefix that is being
        written RIGHT NOW), admission reports ``pending=True`` and waits —
        the source advances one chunk per step, so within a few steps the
        prefix is shareable and the sharer skips its whole recompute."""
        if not self.prefix_sharing:
            return None, 0, 0, False
        ps = self.pool.page_size
        best, best_c = None, 0
        for i, st in enumerate(self.slots):
            if st is None:
                continue
            src = st.ids
            n = min(len(src), len(ids))
            c = int((np.cumprod(src[:n] == ids[:n])).sum())
            if c > best_c:
                best, best_c = i, c
        n_full = best_c // ps
        partial = best_c == len(ids) and best_c % ps != 0
        n_share = n_full + (1 if partial else 0)
        if best is None or n_share == 0:
            return None, 0, 0, False
        st = self.slots[best]
        written = st.pre_pos if st.prefilling else len(st.ids)
        # the sharer's first chunk reads every shared position, so the
        # source must have written through the shared range
        if written < (best_c if partial else n_full * ps):
            return None, 0, 0, True
        # shared pages must actually be backed in the source slot
        if not np.all(self.pool.page_table[best, :n_share] > 0):
            return None, 0, 0, False
        write_from = len(ids) if partial else n_full * ps
        return best, n_share, write_from, False

    def _reclaim_detached(self, queue) -> bool:
        """Drop the largest detached-page reservation among queued entries
        (its request reverts to replay-from-chunk-0) — the last-resort
        valve when admission finds the pool exhausted with nothing live to
        preempt.  Returns True when a reservation was dropped."""
        best = None
        for e in queue:
            if e.resume is not None and (
                    best is None or len(e.resume[0]) > len(best.resume[0])):
                best = e
        if best is None:
            return False
        self.pool.drop_detached(best.resume[0])
        best.resume = None
        return True

    def _admit(self, queue, step_clock: int) -> None:
        while queue and queue[0].arrive <= step_clock:
            free = [i for i, s in enumerate(self.slots) if s is None]
            if not free:
                return
            entry = queue[0]
            req = entry.req
            ids = self._request_ids(req)
            if len(ids) + 1 > self.pool.capacity:
                if req.out_tokens:      # resumed at capacity: done, truncated
                    queue.popleft()
                    req.done = True
                    self.metrics.completed += 1
                    self._stamp_finish(req, entry.arrive_step, step_clock)
                    if self.rec.enabled:
                        rid = self._rids[id(req)]
                        self.rec.end(rid, "QUEUED", step_clock)
                        self.rec.instant(rid, "DECODING", "FINISHED",
                                         step_clock, truncated=True)
                    continue
                raise ValueError(
                    f"prompt of {len(ids)} tokens exceeds slot capacity "
                    f"{self.pool.capacity - 1} (raise s_max)")
            slot = free[0]
            resume_from = None
            if entry.resume is not None:
                # true chunk-boundary resume: the entry's detached pages
                # (already holding [0, pre_pos)'s K/V) re-install at the
                # same logical positions; only the remainder allocates
                src, n_share = None, 0
                kept, r_pre, write_from = entry.resume
                admitted = self.pool.readmit(slot, len(ids), kept)
                if admitted:
                    entry.resume = None     # references moved to the table
                    resume_from = r_pre
                    self.metrics.prefill_resumes += 1
            else:
                src, n_share, write_from, pending = self._shared_prefix(ids)
                if pending:
                    return          # FIFO: wait for the source's chunks
                admitted = self.pool.admit(slot, len(ids), share_from=src,
                                           shared_pages=n_share)
            if not admitted:
                if not any(self.slots):
                    # nothing live to preempt: reclaim detached page
                    # reservations (largest first) before giving up —
                    # dropping one reverts that request to a plain replay
                    if self._reclaim_detached(queue):
                        continue        # retry with the freed pages
                    raise ValueError(
                        f"pool exhausted with no live sequences: {len(ids)} "
                        f"tokens need {self.pool.pages_needed(len(ids))} "
                        f"pages, {self.pool.pages_free} free")
                return                  # FIFO: wait for pages, don't skip
            queue.popleft()
            st = _Slot(req, entry.submit_t, ids, entry.arrive_step,
                       self._admit_seq)
            self._admit_seq += 1
            fresh0 = not req.out_tokens
            if fresh0 and id(req) not in self._qw_stamped:
                # queue wait (submit -> FIRST admission; a preemption
                # replay never re-stamps OR re-observes — the stamped-set
                # guards duck-typed requests without the attribute too) —
                # the latency component TTFT means hide
                self._qw_stamped.add(id(req))
                try:
                    req.queue_wait_steps = step_clock - entry.arrive_step
                except AttributeError:
                    pass
                self.metrics.observe("queue_wait_steps",
                                     step_clock - entry.arrive_step)
            if self.rec.enabled:
                rid = self._rids[id(req)]
                self.rec.end(rid, "QUEUED", step_clock)
                self.rec.instant(rid, "PREFILLING", "ADMITTED", step_clock,
                                 slot=slot, prompt_tokens=len(ids),
                                 pages=self.pool.pages_needed(len(ids)),
                                 shared_pages=n_share, replay=not fresh0,
                                 resume_from=resume_from or 0)
                self.rec.begin(rid, "PREFILLING", step_clock, slot=slot)
            st.write_from = write_from
            # proposer corpus: prompt + every generated token (a resumed
            # request's last token is the next decode input — ids stop one
            # short of it, the stream does not)
            st.hist = [int(t) for t in ids]
            if req.out_tokens:
                st.hist.append(int(req.out_tokens[-1]))
            fresh = not req.out_tokens
            # shared positions skip recompute entirely — their K/V is
            # already in the mapped pages.  A fresh prompt that lies fully
            # inside a shared prefix still runs one 1-token chunk at its
            # last position to sample the first output token; a resumed one
            # needs no compute at all.
            if resume_from is not None:
                # true resume: pick up at the exact chunk boundary the
                # preemption interrupted — the kept pages already hold
                # every position below it, so ZERO chunks re-run
                st.pre_pos = resume_from
            elif write_from < len(ids):
                st.pre_pos = write_from
            elif fresh:
                st.pre_pos = len(ids) - 1
            else:
                st.pre_pos = len(ids)
            st.pre_start = st.pre_pos
            self.slots[slot] = st
            self.pos[slot] = 0
            self.last_tok[slot] = 0
            if n_share:
                self.metrics.prefix_hits += 1
                self.metrics.shared_pages_mapped += n_share
            if st.pre_pos >= len(ids):          # resumed, fully shared
                self._activate(slot, None, step_clock)

    # -- chunked prefill -----------------------------------------------------

    def _prefill_pick(self, cands, step_clock: int):
        """The chunk picker: shortest-remaining-first with an aging credit
        (``prefill_aging`` remaining-token equivalents forgiven per step a
        request has waited since FIRST arrival), admission order as the
        tie-break.  Pure SRF starves a long prompt forever under a
        sustained short-request stream; with aging > 0 its effective key
        eventually undercuts every fresh short prompt, bounding its wait
        by ~remaining/aging steps.  Returns the top ``prefill_slots``."""
        def key(j):
            st = self.slots[j]
            remaining = len(st.ids) - st.pre_pos
            waited = step_clock - st.arrive_step
            return (remaining - self.prefill_aging * waited, st.seq)
        return sorted(cands, key=key)[: self.prefill_slots]

    def _prefill_chunk_step(self, step_clock: int):
        """Advance up to ``prefill_slots`` prefilling slots by one bucketed
        chunk each, in ONE traced call over a full-pool-width
        ``[n_slots, C]`` block (aging-adjusted shortest-remaining-first
        pick, :meth:`_prefill_pick`).  Slots not advancing — idle,
        decoding, or unchosen prefilling — ride along as all-padding rows
        with zeroed page-table rows and empty write windows, so their
        writes land on scratch page 0 and their outputs are discarded:
        the slot count never changes traced shapes, and the compile-count
        bound stays ``prefill_traces <= chunk_buckets x page_buckets``.
        Returns the step-record info dict (slots + buckets) when chunks
        ran, else None."""
        cands = [i for i, s in enumerate(self.slots)
                 if s is not None and s.prefilling]
        if not cands:
            return None
        rec = self.rec
        with rec.span("sched.prefill.prepare"):
            ns, cb, pb, toks, rest = self._prefill_args(cands, step_clock)
        with (rec.span("sched.prefill.dispatch", slots=len(ns),
                       chunk_bucket=cb, page_bucket=pb)
              if rec.enabled else NULL_SPAN):
            nxt, new_kv = self.prefill(toks, self.pool.state(), *rest)
            self.pool.adopt(new_kv)
        with rec.span("sched.prefill.wait"):
            outs = self._fetch(nxt)         # [n_slots, cb]
        with rec.span("sched.prefill.emit"):
            self._prefill_emit(ns, cb, pb, outs, step_clock)
        return {"slots": sorted(ns), "chunk_bucket": cb, "page_bucket": pb}

    def _prefill_args(self, cands, step_clock: int):
        """The chunk step's pick and its device inputs: (slot -> valid
        tokens, chunk bucket, page bucket, tokens, (page table, start,
        write_lo, write_hi))."""
        chosen = self._prefill_pick(cands, step_clock)
        m = self.metrics
        # anti-starvation face: the worst age any still-prefilling prompt
        # has reached (serve_bench gates this under the aging bound)
        m.prefill_wait_steps_max = max(
            m.prefill_wait_steps_max,
            max(step_clock - self.slots[j].arrive_step for j in cands))
        ns = {}                         # slot -> valid tokens this chunk
        for j in chosen:
            st = self.slots[j]
            ns[j] = min(self.prefill_chunk, len(st.ids) - st.pre_pos)
        # shared buckets: chunk shape = pow2 of the LARGEST chosen chunk,
        # page budget = pow2 of the largest chosen read range (positions
        # [0, done + cb)) — one compiled executable per (cb, pb) pair
        cb = bucket_chunk(max(ns.values()), self.prefill_chunk)
        ps = self.pool.page_size
        pb = self.pool.bucket_pages(max(
            math.ceil((self.slots[j].pre_pos + cb) / ps) for j in chosen))
        n_slots = self.pool.n_slots
        toks = np.zeros((n_slots, cb), np.int32)
        start = np.zeros(n_slots, np.int32)
        w_lo = np.zeros(n_slots, np.int32)
        w_hi = np.zeros(n_slots, np.int32)
        tab = np.zeros((n_slots, pb), np.int32)
        for j, n in ns.items():
            st = self.slots[j]
            done = st.pre_pos
            toks[j, :n] = st.ids[done:done + n]
            tab[j] = self.pool.page_table[j, :pb]
            start[j] = done
            # the write window never touches prefix-shared pages (they
            # are mapped read-only) nor the chunk's padding tail
            w_lo[j] = max(done, st.write_from)
            w_hi[j] = min(done + n, len(st.ids))
        m.record_attn_pages(start, cb, pb, ps)
        return ns, cb, pb, jnp.asarray(toks), (
            jnp.asarray(tab), jnp.asarray(start), jnp.asarray(w_lo),
            jnp.asarray(w_hi))

    def _prefill_emit(self, ns, cb: int, pb: int, outs,
                      step_clock: int) -> None:
        """Account a chunk step's tokens and activate every slot whose
        prompt it finished (their first token streams here)."""
        m = self.metrics
        m.prefill_steps += 1
        if len(ns) > 1:
            m.prefill_multi_steps += 1
        for j, n in ns.items():
            st = self.slots[j]
            m.prefill_chunks += 1
            m.prefill_chunk_tokens += n
            first = self._first.get(id(st.req))
            if first is not None:
                first["own"] += n
            st.pre_pos += n
            if self.rec.enabled:
                self.rec.instant(self._rids[id(st.req)], "PREFILLING",
                                 "CHUNK", step_clock, slot=j, tokens=n,
                                 chunk_bucket=cb, page_bucket=pb,
                                 done=st.pre_pos, total=len(st.ids))
            if st.pre_pos >= len(st.ids):
                self._activate(j, int(outs[j, n - 1]), step_clock)

    def _activate(self, slot: int, sampled: Optional[int],
                  step_clock: int) -> None:
        """Prefill complete: the slot joins the pooled decode.  ``sampled``
        is the token argmaxed at the prompt's last position (None for a
        resumed request — its next decode input is the last generated
        token, so nothing is sampled at prefill)."""
        st = self.slots[slot]
        st.prefilling = False
        self.pos[slot] = len(st.ids)
        m = self.metrics
        m.prefills += 1
        fresh = not st.req.out_tokens
        if self.rec.enabled:
            rid = self._rids[id(st.req)]
            self.rec.end(rid, "PREFILLING", step_clock)
            # DECODING opens BEFORE the first token posts, so a one-token
            # request's FINISHED lands inside an open DECODING span
            self.rec.begin(rid, "DECODING", step_clock, slot=slot)
            if fresh:
                self.rec.instant(rid, "DECODING", "FIRST_TOKEN", step_clock,
                                 ttft_steps=step_clock - st.arrive_step)
        if fresh:
            ttft = time.perf_counter() - st.submit_t
            m.ttft_s.append(ttft)
            m.ttft_steps.append(step_clock - st.arrive_step)
            m.observe("ttft_steps", step_clock - st.arrive_step)
            # other requests' prompt tokens prefilled between this
            # request's arrival and its first token — the deterministic
            # face of TTFT under prefill contention (chunking bounds it by
            # prefill_slots chunks per step; a whole-prompt prefill ahead
            # of a short request blows it up by the whole prompt).
            # Derived from FIRST-arrival state (global token clock at
            # arrival + this request's own chunk tokens across every
            # attempt), so preemption replays never double-count — and
            # with true chunk-boundary resume a mid-prefill preemption
            # re-runs zero chunks, leaving every request's stamp
            # replay-invariant.
            first = self._first.get(id(st.req), {
                "tok0": 0, "own": len(st.ids) - st.pre_start})
            waited = (m.prefill_chunk_tokens - first["tok0"] - first["own"])
            # stamp the request so load generators can split TTFT by class
            for name, val in (("ttft_s", ttft),
                              ("ttft_steps", step_clock - st.arrive_step),
                              ("ttft_prefill_tokens", waited)):
                try:
                    setattr(st.req, name, val)
                except AttributeError:
                    pass
            self._post_token(slot, int(sampled))
            if self.slots[slot] is None:
                return                  # one-token request: done at prefill
        self.last_tok[slot] = st.req.out_tokens[-1]

    # -- speculative decoding -------------------------------------------------

    def _propose_drafts(self) -> dict:
        """Host-side n-gram draft proposals for every live decode slot,
        clamped so a slot's 1 + draft tokens never outrun its cache
        capacity or its ``max_new_tokens`` budget.  Empty when nothing
        matches — the step then falls back to plain one-token decode."""
        drafts = {}
        for i, st in enumerate(self.slots):
            if st is None or st.prefilling:
                continue
            room_cap = self.pool.capacity - int(self.pos[i]) - 1
            room_out = st.req.max_new_tokens - len(st.req.out_tokens) - 1
            max_draft = min(self.spec_k - 1, room_cap, room_out)
            if max_draft <= 0:
                continue
            d = spec.propose_ngram(st.hist, max_draft)
            if d:
                drafts[i] = d
        return drafts

    def _decode_table(self, active):
        """The pooled step's page bucket and page table.  The read budget
        is the longest live decoding sequence's backed page count,
        bucketed so each bucket compiles exactly once (block-sparse
        reads)."""
        counts = self.pool.live_page_counts()
        bucket = self.pool.bucket_pages(max(int(counts[i]) for i in active))
        prefilling = [i for i, s in enumerate(self.slots)
                      if s is not None and s.prefilling]
        if not prefilling:
            # steady state: reuse the pool's cached device table
            return bucket, self.pool.table()[:, :bucket]
        # mid-prefill slots sit decode out: a zeroed table row routes the
        # pool-wide write to scratch page 0 and its (discarded) reads to
        # zeros — no shape change, no per-slot control flow
        table = self.pool.page_table[:, :bucket].copy()
        table[prefilling] = 0
        return bucket, jnp.asarray(table)

    def _verify_step(self, active, drafts, did_prefill, step_clock: int):
        """ONE batched verify over the pool: every active slot's committed
        token + draft rides a ``[slot, k]`` block (k bucketed to pow2 like
        page budgets, so verify compiles once per (k, page) bucket pair);
        greedy acceptance emits each slot's longest agreeing draft prefix
        plus the model's own next token.  Rejected positions need no
        rollback work: per-slot ``pos`` only advances over accepted
        tokens, and the rejected page rows are overwritten when the
        position reaches them (``_ensure_pages`` already COW'd every page
        the k-token write touches).  Returns (k bucket, page bucket)."""
        m, rec = self.metrics, self.rec
        with rec.span("sched.verify.prepare"):
            bucket, table = self._decode_table(active)
            kb = bucket_pow2(1 + max(len(d) for d in drafts.values()),
                             self.spec_k)
            n = self.pool.n_slots
            toks = np.zeros((n, kb), np.int32)
            n_valid = np.zeros(n, np.int32)
            for i in active:
                d = drafts.get(i, [])
                toks[i, 0] = self.last_tok[i]
                if d:
                    toks[i, 1:1 + len(d)] = d
                n_valid[i] = 1 + len(d)
            toks, pos, n_valid = (jnp.asarray(toks), jnp.asarray(self.pos),
                                  jnp.asarray(n_valid))
            m.record_attn_pages(self.pos, kb, bucket, self.pool.page_size)
        with (rec.span("sched.verify.dispatch", slots=len(active),
                       page_bucket=bucket, k_bucket=kb)
              if rec.enabled else NULL_SPAN):
            nxt, new_kv = self.verify(toks, self.pool.state(), table, pos,
                                      n_valid)
            self.pool.adopt(new_kv)
        with rec.span("sched.verify.wait"):
            outs = self._fetch(nxt)             # [n_slots, kb]
        with rec.span("sched.verify.emit"):
            m.decode_steps += 1
            m.decode_slot_steps += len(active)
            m.spec_verify_steps += 1
            m.record_read(self.pool, bucket)
            if did_prefill:
                m.interleaved_steps += 1
            for i in active:
                d = drafts.get(i, [])
                acc = spec.accept_length(d, outs[i])
                m.spec_proposed += len(d)
                m.spec_accepted += acc
                m.decode_steps_saved += acc
                if d and rec.enabled:
                    rec.instant(self._rids[id(self.slots[i].req)],
                                "VERIFY", "VERIFY", step_clock, slot=i,
                                k_bucket=kb, proposed=len(d), accepted=acc)
                # emitted stream = accepted draft prefix + the model's own
                # next token after it — exactly sequential greedy decode
                for t in outs[i, :acc + 1]:
                    self.pos[i] += 1
                    self._post_token(i, int(t))
                    if self.slots[i] is None:
                        break                   # EOS / budget mid-block
        return kb, bucket

    # -- paging / preemption --------------------------------------------------

    def _ensure_pages(self, queue, spans: Optional[dict] = None) -> None:
        """Back every live decode slot's next write position with a PRIVATE
        page (allocating, or copy-on-write when the page is prefix-shared);
        on exhaustion, preempt the live sequence holding the longest token
        range and retry.  ``spans`` widens a slot's write window to cover
        a speculative k-token block (positions ``pos .. pos+span-1`` may
        cross a page boundary — every touched page must be private BEFORE
        the write, or a rejected draft row would corrupt a prefix-sharing
        sibling's history).  Mid-prefill slots need no decode-write page —
        admission preallocated their prompt's pages."""
        spans = spans or {}
        ps = self.pool.page_size
        for i in range(len(self.slots)):
            if self.slots[i] is None or self.slots[i].prefilling:
                continue
            if self.pos[i] >= self.pool.capacity:
                self._finish(i)         # slot full: out of cache headroom
                continue
            lo = int(self.pos[i]) // ps
            hi = (int(self.pos[i]) + spans.get(i, 1) - 1) // ps
            for page_idx in range(lo, hi + 1):
                while self.slots[i] is not None \
                        and not self.pool.ensure_writable(i, page_idx):
                    live = [j for j, s in enumerate(self.slots)
                            if s is not None]
                    victim = max(live, key=self._held_tokens)
                    free0 = self.pool.pages_free
                    self._preempt(victim, queue)
                    if self.pool.pages_free <= free0:
                        # the victim's pages were detached (mid-prefill
                        # resume) or shared: eviction freed nothing, so
                        # reclaim a detached reservation before burning
                        # another victim
                        self._reclaim_detached(queue)
                if self.slots[i] is None:
                    break               # preempted while backing its pages

    def _held_tokens(self, slot: int) -> int:
        """Preemption-victim key: the token range a slot's pages cover (a
        mid-prefill slot holds pages for its WHOLE prompt, so eviction
        frees them all)."""
        st = self.slots[slot]
        return len(st.ids) if st.prefilling else int(self.pos[slot])

    def _preempt(self, slot: int, queue) -> None:
        st = self.slots[slot]
        # a mid-prefill victim resumes from the TRUE chunk boundary: the
        # pages holding content so far — its own chunks' [0, pre_pos) plus
        # any prefix-shared span — detach from the slot (refcounts kept,
        # ownership travels with the queue entry) instead of being freed,
        # so the eventual replay re-runs ZERO chunks.  A decode victim (or
        # an untouched prefill) takes the classic full-release + replay
        # path.  min(write_from, len(ids)) covers the fresh fully-shared
        # case, whose shared tail page holds K/V past pre_pos.
        resume = None
        if st.prefilling:
            valid = max(st.pre_pos, min(st.write_from, len(st.ids)))
            if valid > 0:
                kept = self.pool.detach_prefix(slot, valid)
                resume = (kept, st.pre_pos, st.write_from)
        if self.rec.enabled:
            rid = self._rids[id(st.req)]
            phase = "PREFILLING" if st.prefilling else "DECODING"
            self.rec.end(rid, phase, self._step, preempted=True)
            self.rec.instant(rid, phase, "PREEMPTED", self._step, slot=slot,
                             held_tokens=self._held_tokens(slot),
                             kept_pages=len(resume[0]) if resume else 0)
            # the request re-queues: its replay admission ends this span
            self.rec.begin(rid, "QUEUED", self._step)
        if resume is None:
            self.pool.release(slot)
        self.slots[slot] = None
        self.pos[slot] = 0
        self.metrics.preemptions += 1
        # replay resumes at a chunk boundary; first-arrival identity
        # (submit_t / arrive_step) rides the entry so TTFT clocks and the
        # aging credit keep counting from the ORIGINAL arrival
        queue.appendleft(_QEntry(st.req, 0, st.submit_t, st.arrive_step,
                                 resume=resume))

    # -- token bookkeeping ----------------------------------------------------

    def _post_token(self, slot: int, token: int) -> None:
        st = self.slots[slot]
        req = st.req
        req.out_tokens.append(token)
        st.hist.append(token)
        self.last_tok[slot] = token
        self.metrics.tokens_out += 1
        stream = getattr(req, "stream", None)
        if stream is not None:
            stream(token)
        if token == self.eos or len(req.out_tokens) >= req.max_new_tokens:
            self._finish(slot)

    def _stamp_finish(self, req, arrive_step: int, step_clock: int) -> None:
        """End-to-end latency accounting at request completion: submit ->
        finish on the step clock, plus the per-request decode-step count
        (both feed the p50/p95 histograms in the report)."""
        e2e = step_clock - arrive_step
        try:
            req.e2e_steps = e2e
        except AttributeError:
            pass
        self.metrics.observe("e2e_steps", e2e)

    def _finish(self, slot: int) -> None:
        st = self.slots[slot]
        st.req.done = True
        self._stamp_finish(st.req, st.arrive_step, self._step)
        if self.rec.enabled:
            rid = self._rids[id(st.req)]
            self.rec.instant(rid, "DECODING", "FINISHED", self._step,
                             tokens=len(st.req.out_tokens))
            self.rec.end(rid, "DECODING", self._step)
        self.pool.release(slot)
        self.slots[slot] = None
        self.pos[slot] = 0
        self.metrics.completed += 1
