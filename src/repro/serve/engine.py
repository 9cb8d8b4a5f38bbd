"""Batched serving engine: prefill + continuous-batching pooled decode.

``ServeEngine`` is the user-facing API; the machinery underneath is the
``repro.serve`` subsystem:

  * :class:`repro.serve.pool.PagePool` — paged KV-cache block pool (INT8
    pages + per-(position, head) scales by default, fp pages for parity);
  * :class:`repro.serve.scheduler.Scheduler` — FIFO admission with prefix
    sharing (common prompt prefixes map the same refcounted pages,
    copy-on-write on divergence), CHUNKED paged prefill (each step runs at
    most ``prefill_chunk`` prompt tokens for at most one request, written
    straight into pool pages and interleaved with decode — no dense
    ``[1, T]`` prefill cache), preemption, streaming, and ONE jit'd
    decode step per token for the whole slot pool with a per-slot position
    vector (misaligned sequences batch; there is no align-or-serialize
    fallback).  Decode reads are block-sparse: each step gathers only the
    bucketed page budget the longest live sequence needs, so short
    sequences never pay the slot-capacity read tax;
  * :class:`repro.serve.metrics.ServeMetrics` — tokens/s, TTFT, occupancy,
    decode KV bytes read (block-sparse vs dense) and sharing stats.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.context import QuantCtx, as_ctx
from repro.data import tokenizer as tok
from repro.models import transformer as T
from repro.models.common import ModelConfig
from repro.obs.trace import NULL_RECORDER
from repro.parallel import serve_sharding as SS
from repro.quantize import QuantArtifact
from repro.serve.metrics import ServeMetrics
from repro.serve.pool import PagePool
from repro.serve.scheduler import Scheduler


@dataclasses.dataclass
class Request:
    prompt: str
    max_new_tokens: int = 32
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # per-request streaming: called with each token the step it is sampled
    stream: Optional[Callable[[int], None]] = None
    # stamped by the scheduler when the first token is sampled (wall clock
    # since arrival / scheduler steps since arrival / OTHER requests'
    # prompt tokens prefilled in between — the deterministic face of TTFT
    # under prefill contention) — lets load generators split TTFT by class
    ttft_s: Optional[float] = None
    ttft_steps: Optional[int] = None
    ttft_prefill_tokens: Optional[int] = None
    # latency accounting (step clock): submit -> first admission, and
    # submit -> finish — the tail-latency quantities the p50/p95 histograms
    # aggregate (TTFT alone hides queue time and long decodes)
    queue_wait_steps: Optional[int] = None
    e2e_steps: Optional[int] = None


class ServeEngine:
    """CPU-scale reference engine (same step functions the dry-run lowers at
    pod scale).

    Quantized serving takes ONE object: ``ServeEngine(cfg, artifact)`` where
    ``artifact`` is a prequantized :class:`repro.quantize.QuantArtifact`
    (packed int8 weights + policy + calibrated state + fused kernel
    buffers), or ``ServeEngine(cfg, params, quant=spec)`` with ``spec`` any
    of QuantConfig / SitePolicy / QuantArtifact for quantize-at-use.

    Fused-backend sites (``QuantConfig.backend == 'fused'``) execute the
    packed single-GEMM MUXQ kernel path in prefill and decode — the stacked
    ``{site}@fused`` buffers ride the ``lax.scan`` layer loop, so the
    traced step never touches (or dequantizes) those sites' weight leaves.

    KV state lives in a paged pool: ``kv_mode='int8'`` stores pages as
    int8 + per-(position, head) scales (~2x+ cache capacity — the paper's
    §1 KV-memory motivation), ``kv_mode='int4'`` stores MUXQ'd
    nibble-packed pages (two values per byte + bf16 scales — exactly half
    the int8 page bytes; calibrated outlier channels are
    magnitude-redistributed via the artifact's ``kv_calib`` section, see
    :mod:`repro.serve.kvq`), ``kv_mode='fp'`` stores ``cache_dtype``
    pages (bit-exact parity against the dense cache path when
    ``cache_dtype`` matches).  The default (``kv_mode=None``) follows the
    weight path: int8 pages for quantized serving, fp pages for plain fp
    params — an unquantized model never silently gets a lossy cache; int4
    pages are always opt-in.
    ``cache_dtype`` (default bf16) sets the fp-page dtype — fp serving no
    longer pays a 2x fp32 cache tax.

    Prefill is **chunked, paged, and multi-slot**: prompts are admitted
    into pool pages and prefilled ``prefill_chunk`` tokens at a time
    (:func:`repro.models.transformer.prefill_chunk_paged`), each chunk
    writing its K/V straight into the slot's pages — there is no dense
    ``[1, T]`` prefill cache.  Each step, up to ``prefill_slots``
    prefilling slots advance one chunk each in ONE traced call (a
    ``[slot, chunk]`` block over the page table, always at the full pool
    width so the knob never changes traced shapes), interleaved with the
    pooled decode so a long-prompt flood never stalls live decode slots
    for more than one chunk step's worth of compute.  The chunk picker is
    shortest-remaining-first with an **aging** term (``prefill_aging``
    steps-waited credit per step) so a long prompt can't starve under a
    sustained short-request stream; preempted mid-prefill slots **resume
    from the true chunk boundary** (their already-written pages are kept
    across preemption, never re-run).  Chunk shapes bucket to powers of
    two like decode page budgets, so the chunked prefill compiles once
    per (chunk-bucket, page-bucket) pair (``prefill_traces`` /
    ``prefill_buckets`` mirror ``decode_traces`` / ``decode_buckets``).

    **Self-speculative decoding** (``spec_mode="ngram"``, default off):
    the scheduler drafts up to ``spec_k - 1`` tokens per live slot by
    prompt-lookup over the slot's own history and scores every slot's
    draft block in ONE jit'd verify step
    (:func:`repro.models.transformer.decode_verify_paged`); greedy
    acceptance keeps each slot's longest agreeing prefix, so fp-page
    output streams stay bit-exact vs plain greedy decode while repetitive
    workloads finish in fewer pooled steps.  ``verify_traces`` /
    ``verify_buckets`` bound compiles to one per (k, page) bucket pair.
    """

    def __init__(self, cfg: ModelConfig, params, max_batch: int = 4,
                 s_max: int = 512, quant=None, greedy: bool = True, *,
                 kv_mode: Optional[str] = None, page_size: int = 16,
                 n_pages: Optional[int] = None, cache_dtype=jnp.bfloat16,
                 prefix_sharing: bool = True, prefill_chunk: int = 32,
                 prefill_slots: int = 2, prefill_aging: float = 1.0,
                 spec_mode: str = "off", spec_k: int = 4,
                 recorder=None, quality=None, tp: Optional[int] = None):
        assert cfg.family in ("dense", "moe"), "engine supports decoder-only LMs"
        if isinstance(params, QuantArtifact):
            if quant is not None:
                raise ValueError("pass either an artifact as params or a "
                                 "quant spec, not both")
            quant, params = params, params.params
            if params is None:
                raise ValueError("artifact carries no packed weights; build "
                                 "it with prequantize=True or pass raw "
                                 "params plus quant=artifact")
        self.cfg, self.params = cfg, params
        self.max_batch, self.s_max = max_batch, s_max
        self.prefix_sharing = prefix_sharing
        # the artifact's KV-page calibration (int4 outlier redistribution)
        # — captured from the quant spec before it collapses into a ctx
        kv_calib = getattr(quant, "kv_calib", None) or None
        self.ctx, qparams = as_ctx(quant)
        self.qparams = qparams
        # the steps take (params, qparams) as their first ARGUMENT: closed
        # over, the packed int8 buffers would be baked into every compiled
        # bucket's executable as constants
        self.weights = (params, qparams)
        self.greedy = greedy
        self.cache_dtype = cache_dtype
        # fail at construction, not deep inside a traced layer loop: a policy
        # that routes THIS model's sites to the fused backend needs the
        # packed kernel buffers an artifact built with prequantize=True
        # carries (rules whose patterns match no site here stay inert)
        if isinstance(self.ctx, QuantCtx):
            bases = ["attn_qkv", "attn_q", "attn_kpe", "attn_out", "mlp_up",
                     "mlp_down"]
            if cfg.family == "moe":
                bases += ["moe_up", "moe_down"]
            names = bases + [f"layer{i}/{b}" for i in range(cfg.n_layers)
                             for b in bases]
            wants_fused = any(
                c.method != "fp" and getattr(c, "backend", "fake") == "fused"
                for c in map(self.ctx.policy.resolve, names))
            has_buffers = bool(self.ctx.kernel_buffers) or any(
                k.endswith("@fused") for k in (qparams or {}))
            if wants_fused and not has_buffers:
                raise ValueError(
                    "policy routes sites to the 'fused' backend but no "
                    "packed kernel buffers are available — build the "
                    "artifact via quantize_model(..., prequantize=True)")

        if kv_mode is None:
            kv_mode = "int8" if isinstance(self.ctx, QuantCtx) else "fp"
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = int(prefill_chunk)
        # multi-slot prefill: up to prefill_slots prefilling slots advance
        # one chunk each per step, batched into ONE traced call (the step
        # always runs at the full [n_slots, C] width, so the knob never
        # changes traced shapes); prefill_aging biases the chunk picker
        # toward long-waiting prompts (0 = pure shortest-remaining-first)
        if prefill_slots < 1:
            raise ValueError(f"prefill_slots must be >= 1, got {prefill_slots}")
        if prefill_aging < 0:
            raise ValueError(f"prefill_aging must be >= 0, got {prefill_aging}")
        self.prefill_slots = int(prefill_slots)
        self.prefill_aging = float(prefill_aging)
        # tensor-parallel serving: tp > 1 builds a ("model",) mesh, the pool
        # allocates its pages/scales/redist rows sharded on the kvh axis,
        # and the jit'd steps below wrap in shard_map.  tp=None/1 keeps the
        # mesh-free single-device path byte-for-byte (same closures, same
        # jaxprs — the default path compiles to today's executables).
        self.tp = 1 if tp is None else int(tp)
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        self.mesh = SS.serve_mesh(self.tp) if self.tp > 1 else None
        self.pool = PagePool(cfg, max_batch, s_max, page_size=page_size,
                             n_pages=n_pages, mode=kv_mode, dtype=cache_dtype,
                             kv_calib=kv_calib, mesh=self.mesh)
        # GQA fallback: a kvh the mesh doesn't divide drops the "model"
        # axis in fit_spec — the pool is replicated across the mesh and the
        # steps stay plain jit (no shard_map, no collectives; replicated
        # GSPMD compute is bit-identical to single-device)
        shard = (SS.HeadShard(SS.SERVE_AXIS, self.tp)
                 if self.pool.heads_sharded else None)
        self._shard = shard
        if spec_mode not in ("off", "ngram"):
            raise ValueError(f"unknown spec_mode {spec_mode!r} "
                             "(expected 'off' or 'ngram')")
        self.spec_mode = spec_mode
        self.spec_k = int(spec_k)
        self.metrics = self._fresh_metrics()  # last generate() run's metrics
        # observability (PR 8): a repro.obs.trace recorder (NULL_RECORDER =
        # tracing off, every hook a no-op) and an optional
        # repro.obs.quality.QualityObserver the scheduler samples the pool
        # into — both host-side only, never entering traced code
        self.recorder = recorder if recorder is not None else NULL_RECORDER
        self.quality = quality
        self.decode_traces = 0           # pooled-step (re)trace counter
        self.decode_buckets = set()      # page-budget buckets seen (lifetime)
        self.prefill_traces = 0          # chunked-prefill (re)trace counter
        self.prefill_buckets = set()     # (chunk, page) bucket pairs (lifetime)
        self.verify_traces = 0           # spec-verify (re)trace counter
        self.verify_buckets = set()      # (k, page) bucket pairs (lifetime)

        def tp_wrap(body, n_rest):
            """shard_map the step body when head-sharded, else pass through.

            Signature contract: ``body(weights, tokens, kv, *rest)`` with
            the pool tree at position 2.  weights/tokens/page tables/
            positions are replicated (``P()`` pytree prefixes); the pool
            tree carries the pool's allocation PartitionSpecs in AND out,
            so the shard_map'd step donates and returns pages exactly as
            sharded as it received them.  Weights stay replicated inside
            the body: the fused-QKV column layout is [q | k | v] head
            regions, which a contiguous "model" column shard would
            interleave, and a contraction-split wo psum is neither
            bit-exact nor compatible with MUXQ's per-token act-quant at
            attn_out (it needs the full channel vector) — the capacity
            win lives in the KV pages, which dominate serving HBM."""
            if shard is None:
                return body
            kv_specs = self.pool.kv_pspecs
            return jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(), P(), kv_specs) + (P(),) * n_rest,
                out_specs=(P(), kv_specs), check_vma=False)

        # an expert model's steps also count the routed rows that land on
        # held experts; they come back beside the step's tokens, in the
        # same host fetch (``Scheduler`` adds them to ServeMetrics)
        stats_kw = {"stats": True} if cfg.n_experts else {}

        def out(step_out, last: bool):
            logits, new_kv = step_out[:2]
            counts = step_out[2] if len(step_out) > 2 else {}
            nxt = jnp.argmax((logits[:, -1] if last else logits)
                             [..., : cfg.vocab_size], axis=-1)
            return (nxt.astype(jnp.int32), counts), new_kv

        def decode_body(weights, tokens, kv, page_table, pos):
            params, qparams = weights
            with SS.head_sharding(shard):
                return out(T.decode_step_paged(cfg, params, tokens, kv,
                                               page_table, pos, self.ctx,
                                               qparams=qparams, **stats_kw),
                           last=True)

        decode_step = tp_wrap(decode_body, 2)

        def decode(weights, tokens, kv, page_table, pos):
            self.decode_traces += 1      # python side effect: trace time only
            return decode_step(weights, tokens, kv, page_table, pos)

        # one compiled executable per page-budget bucket (the table's width):
        # the scheduler buckets ceil(pos/ps) to powers of two, so the step
        # retraces once per bucket, never per sequence length — the trace
        # counter increments in the OUTER jit'd fn, so the compile-count
        # invariant (traces == buckets seen) holds at every mesh size
        self._decode = jax.jit(decode, donate_argnums=(2,))

        def prefill_body(weights, tokens, kv, page_table, start, write_lo,
                         write_hi):
            params, qparams = weights
            with SS.head_sharding(shard):
                return out(T.prefill_chunk_paged(
                    cfg, params, tokens, kv, page_table, start, write_lo,
                    write_hi, self.ctx, qparams=qparams, **stats_kw),
                    last=False)

        prefill_step = tp_wrap(prefill_body, 4)

        def prefill(weights, tokens, kv, page_table, start, write_lo,
                    write_hi):
            self.prefill_traces += 1     # python side effect: trace time only
            return prefill_step(weights, tokens, kv, page_table, start,
                                write_lo, write_hi)

        # chunk shapes are bucketed like decode page budgets: the chunked
        # prefill compiles once per (chunk-bucket, page-bucket) pair —
        # start/write_lo/write_hi ride as traced scalars, never shapes
        self._prefill_step = jax.jit(prefill, donate_argnums=(2,))

        def verify_body(weights, tokens, kv, page_table, pos, n_valid):
            params, qparams = weights
            with SS.head_sharding(shard):
                return out(T.decode_verify_paged(
                    cfg, params, tokens, kv, page_table, pos, n_valid,
                    self.ctx, qparams=qparams, **stats_kw), last=False)

        verify_step = tp_wrap(verify_body, 3)

        def verify(weights, tokens, kv, page_table, pos, n_valid):
            self.verify_traces += 1      # python side effect: trace time only
            return verify_step(weights, tokens, kv, page_table, pos, n_valid)

        # the speculative k-token verify: k buckets to pow2 in the
        # scheduler and n_valid rides as a traced vector, so verify
        # compiles once per (k-bucket, page-bucket) pair
        self._verify_step = jax.jit(verify, donate_argnums=(2,))
        # mesh shape into the trace metadata (Chrome-trace process labels +
        # otherData) so traces from different mesh sizes are distinguishable
        if self.recorder.enabled:
            self.recorder.set_metadata(mesh_devices=self.tp,
                                       kv_shards=self.pool.kv_shards)

    # -- scheduler plumbing ---------------------------------------------------

    def _prefill_pool(self, tokens, kv, page_table, start, write_lo, write_hi):
        bucket = (int(tokens.shape[1]), int(page_table.shape[1]))
        self.prefill_buckets.add(bucket)
        before = self.prefill_traces
        out = self._prefill_step(self.weights, tokens, kv, page_table,
                                 start, write_lo, write_hi)
        if self.prefill_traces > before and self.recorder.enabled:
            self.recorder.compile_event("prefill", chunk_bucket=bucket[0],
                                        page_bucket=bucket[1],
                                        traces=self.prefill_traces)
        return out

    def _decode_pool(self, tokens, kv, page_table, pos):
        bucket = int(page_table.shape[1])
        self.decode_buckets.add(bucket)
        before = self.decode_traces
        out = self._decode(self.weights, tokens, kv, page_table, pos)
        if self.decode_traces > before and self.recorder.enabled:
            self.recorder.compile_event("decode", page_bucket=bucket,
                                        traces=self.decode_traces)
        return out

    def _verify_pool(self, tokens, kv, page_table, pos, n_valid):
        bucket = (int(tokens.shape[1]), int(page_table.shape[1]))
        self.verify_buckets.add(bucket)
        before = self.verify_traces
        out = self._verify_step(self.weights, tokens, kv, page_table, pos,
                                n_valid)
        if self.verify_traces > before and self.recorder.enabled:
            self.recorder.compile_event("verify", k_bucket=bucket[0],
                                        page_bucket=bucket[1],
                                        traces=self.verify_traces)
        return out

    # -- public ---------------------------------------------------------------

    def _fresh_metrics(self) -> ServeMetrics:
        """A per-run ServeMetrics with the mesh shape stamped into registry
        gauges (rides ``registry.snapshot()`` into --json-out and the bench
        artifacts; the Scheduler itself stays mesh-oblivious)."""
        m = ServeMetrics()
        m.registry.gauge("serve/mesh_devices").set(float(self.tp))
        m.registry.gauge("serve/kv_shards").set(float(self.pool.kv_shards))
        return m

    def scheduler(self) -> Scheduler:
        """A fresh scheduler over this engine's (persistent) page pool."""
        return Scheduler(self.pool, self._prefill_pool, self._decode_pool,
                         self._verify_pool, metrics=self._fresh_metrics(),
                         prefix_sharing=self.prefix_sharing,
                         prefill_chunk=self.prefill_chunk,
                         prefill_slots=self.prefill_slots,
                         prefill_aging=self.prefill_aging,
                         spec_mode=self.spec_mode, spec_k=self.spec_k,
                         recorder=self.recorder, quality=self.quality)

    def generate(self, requests: List[Request],
                 arrivals: Optional[Sequence[int]] = None) -> List[Request]:
        """Run all requests to completion with continuous batching.
        ``arrivals`` (optional, one decode-step index per request) delays
        admission — the load-generator hook."""
        sched = self.scheduler()
        sched.run(requests, arrivals)
        self.metrics = sched.metrics
        return requests

    @staticmethod
    def text(req: Request) -> str:
        return tok.decode(req.out_tokens)
