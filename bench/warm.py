"""The warm-up set: requests that reach every step program a mix can use.

The serving engine compiles one decode program per page bucket and one
prefill program per (chunk bucket, page bucket) pair.  A prefill step
advances up to ``prefill_slots`` prompts by one chunk each at one shared
shape: the chunk bucket is the power of two over the largest chunk, the
page bucket the power of two over the pages the furthest chunk reads.
So every pair that real traffic can reach is (c, bucket(ceil((p + c) /
page_size))) for a chunk bucket c and a chunk start p that some prompt of
the mix reaches, with a chunk of at most c tokens ending that prompt
there when c is below the full chunk.  Decode reads the pages of the
longest live context, so its buckets follow the context range.

Each warm-up run sends ONE request on its own, so nothing pairs with it
and each of its chunks lands on exactly the pair computed here: a prompt
of p + c tokens walks full chunks up to p and ends on a c-token chunk.
Its second token, where asked for, is one decode step at the context the
prompt ends on.

This is a model of the scheduler's bucketing, which has no public call
that lists the buckets a mix can reach.  Two things keep it honest: the
benchmark's tests run the real scheduler over the real mixes and compare
the buckets it reaches with these targets, and a run in which any program
is compiled inside the window fails (``run.BenchError``).
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple


def bucket(n: int, cap: int) -> int:
    n = max(1, min(n, cap))
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def _pow2s(cap: int) -> List[int]:
    out, b = [], 1
    while b < cap:
        out.append(b)
        b *= 2
    return out + [cap]


def _chunks(length: int, chunk: int, ps: int, pages: int):
    """(chunk bucket, page bucket) of each chunk of a lone prompt."""
    out, p = [], 0
    while p < length:
        n = min(chunk, length - p)
        c = bucket(n, chunk)
        out.append((c, bucket(math.ceil((p + c) / ps), pages)))
        p += n
    return out


def targets(b: Dict[str, int], serve: Dict) -> Tuple[set, set]:
    """Prefill (chunk, page) pairs and decode page buckets the mix reaches."""
    chunk, ps = serve["prefill_chunk"], serve["page_size"]
    pages = math.ceil(serve["s_max"] / ps)
    lo, hi = b["prompt_min"], b["prompt_max"]
    starts = set(range(0, hi, chunk))
    # where two prompts can prefill in one step: anywhere without a shared
    # system prompt; with one, only past its whole pages, since the first
    # prompt computes the prefix alone and every later one maps it
    paired = starts
    if b["shared"]:
        first = b["shared"] // ps * ps
        starts |= set(range(first, hi, chunk))
        paired = {p for p in starts if p >= first}

    def ends_here(p, n_lo, c):
        # a chunk of n_lo..c tokens at p ends some prompt of the mix
        return any(lo <= p + n <= hi for n in range(n_lo, c + 1))

    pairs = {(c, p) for p in starts for c in _pow2s(chunk)
             if (p + chunk <= hi if c == chunk
                 else ends_here(p, c // 2 + 1, c))}
    if serve["prefill_slots"] > 1:
        # a step's chunk bucket is the largest of its slots' and its page
        # bucket the furthest read of any: a slot starting at p pairs with
        # any chunk bucket another slot brings, as long as its own chunk
        # fits under it (a full one, or the last of its prompt)
        sizes = {c for c, _ in pairs}
        pairs |= {(c, p) for p in paired for c in sizes
                  if c == chunk or ends_here(p, 1, c)}
    prefill = {(c, bucket(math.ceil((p + c) / ps), pages)) for c, p in pairs}
    # decode at position pos reads ceil((pos + 1) / ps) pages; the first
    # decode of a prompt of L tokens is at pos = L
    last = hi + b["output_max"] - 2
    decode = {bucket(math.ceil((pos + 1) / ps), pages)
              for pos in range(lo, last + 1)}
    return prefill, decode


def runs(b: Dict[str, int], serve: Dict) -> List[Tuple[int, int]]:
    """(prompt tokens, tokens to decode) of each lone warm-up request, in
    the order they run; together they reach every target."""
    chunk, ps = serve["prefill_chunk"], serve["page_size"]
    pages = math.ceil(serve["s_max"] / ps)
    prefill, decode = targets(b, serve)
    out, seen_p, seen_d = [], set(), set()

    def add(length, new):
        out.append((length, new))
        seen_p.update(_chunks(length, chunk, ps, pages))
        if new > 1:
            seen_d.update(bucket(math.ceil((pos + 1) / ps), pages)
                          for pos in range(length, length + new - 1))

    # the shortest prompt that ends on each pair (longest walks first, so
    # their full chunks cover the cheap pairs on the way)
    ends = {}
    for p in range(0, b["prompt_max"] + chunk, chunk):
        for c in _pow2s(chunk):
            pair = (c, bucket(math.ceil((p + c) / ps), pages))
            if pair in prefill and pair not in ends:
                ends[pair] = p + c
    for pair, length in sorted(ends.items(), key=lambda kv: -kv[1]):
        if pair not in seen_p:
            d = bucket(math.ceil((length + 1) / ps), pages)
            add(length, 2 if d in decode and d not in seen_d else 1)
    # decode buckets past the longest prompt: decode up to the context
    for d in sorted(decode - seen_d):
        # the least position whose page count falls in bucket d
        pos = (d // 2) * ps if d > 1 else 1
        length = min(pos, b["prompt_max"])
        add(length, pos - length + 2)
    missing = (prefill - seen_p, decode - seen_d)
    if missing[0] or missing[1]:
        raise RuntimeError(f"warm-up misses buckets {missing}")
    return out
