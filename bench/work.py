"""Operations and bytes the model needs, counted from its sizes.

Counted from what the work requires, never from the operands a kernel
happens to be handed: idle slots, chunk padding, K padding and the walk
over a whole page bucket are not work, so they show up as lost roofline
share.  One multiply-add is two operations.

``sizes`` is :func:`bench.reference.<name>.sizes` of a configuration:
L layers, width d, h heads of dh, MLP width f, vocabulary V.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple


def linear_shapes(s) -> Tuple[Tuple[int, int], ...]:
    """(K, N) of the four projections of one layer: QKV, attention output,
    MLP up, MLP down."""
    d, f = s["d"], s["f"]
    return ((d, 3 * d), (d, d), (d, f), (f, d))


def linear_weights(s) -> int:
    """Matrix weights of all layers' projections (embedding excluded)."""
    return s["L"] * sum(k * n for k, n in linear_shapes(s))


def linear_bytes(s, *, calls: int, rows: int, weight_bytes: float,
                 act_bytes: float, out_bytes: float,
                 scale_bytes: float = 0.0) -> float:
    """Bytes of ``calls`` passes through every layer's four projections
    over ``rows`` tokens in all: each call reads every weight and, where
    the weights are quantized, one scale per output channel; each row is
    read once, written once, and carries one scale."""
    per_call = sum(k * n * weight_bytes + n * scale_bytes
                   for k, n in linear_shapes(s))
    per_row = sum(k * act_bytes + n * out_bytes + scale_bytes
                  for k, n in linear_shapes(s))
    return s["L"] * (calls * per_call + rows * per_row)


def kv_bytes_per_token(s, kv_mode: str) -> float:
    """Bytes of one cached position across all layers: K and V of every
    head, and for int8 pages one float32 scale per head and position each."""
    per_head = {"int8": s["dh"] + 4, "fp": 2 * s["dh"]}[kv_mode]
    return s["L"] * 2 * s["h"] * per_head


def attention_ops(s, keys: float) -> float:
    """Operations of one query attending ``keys`` positions in every layer
    (scores and the weighted sum of values)."""
    return 4.0 * s["L"] * s["h"] * s["dh"] * keys


def chunk_keys(n: int, done: int) -> float:
    """Keys attended, summed over the queries of a prefill chunk of ``n``
    tokens that ends at position ``done`` (query at p sees p + 1 keys)."""
    first = done - n
    return n * first + n * (n + 1) / 2.0


def window(s, *, kv_mode: str, quantized: bool, out_bytes: float,
           decode_steps: int, decode_slot_steps: int, prefill_steps: int,
           prefill_tokens: int, prefills: int, decode_keys: Iterable[int],
           chunks: Iterable[Tuple[int, int]]) -> Dict[str, float]:
    """Work of a serving window.

    ``decode_keys``: keys each decoded token attended (its context);
    ``chunks``: (tokens, end position) of each prefill chunk.  Linear work
    is one call per decode step over its active slots and one per prefill
    step over its valid chunk tokens; attention reads each live position's
    K/V once per call; the LM head runs for every sampled position (each
    decode token and the last prompt token of each finished prefill)."""
    wb, ab, sb = (1.0, 1.0, 4.0) if quantized else (2.0, 2.0, 0.0)
    tokens = decode_slot_steps + prefill_tokens
    lin_bytes = sum(linear_bytes(s, calls=calls, rows=rows, weight_bytes=wb,
                                 act_bytes=ab, out_bytes=out_bytes,
                                 scale_bytes=sb)
                    for calls, rows in ((decode_steps, decode_slot_steps),
                                        (prefill_steps, prefill_tokens)))
    bpt = kv_bytes_per_token(s, kv_mode)
    decode_keys = list(decode_keys)
    chunks = list(chunks)
    att_keys = float(sum(decode_keys)) + sum(chunk_keys(n, e) for n, e in chunks)
    att_bytes = bpt * (sum(decode_keys) + sum(e for _, e in chunks))
    sampled = len(decode_keys) + prefills
    head_ops = 2.0 * s["d"] * s["V"] * sampled
    return {
        "linear_ops": 2.0 * linear_weights(s) * tokens,
        "linear_bytes": lin_bytes,
        "attention_ops": attention_ops(s, att_keys),
        "attention_bytes": att_bytes,
        "model_ops": (2.0 * linear_weights(s) * tokens
                      + attention_ops(s, att_keys) + head_ops),
    }


def roofline_s(ops: float, bytes_: float, ops_peak: float, bw: float) -> float:
    """Least time the chip needs for the work: the larger of its two bounds."""
    return max(ops / ops_peak, bytes_ / bw)
