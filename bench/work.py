"""Operations and bytes a serving window needs, counted from the model's
own description of its work.

Counted from what the work requires, never from the operands a kernel
happens to be handed: idle slots, chunk padding, K padding and the walk
over a whole page bucket are not work, so they show up as lost roofline
share.  One multiply-add is two operations.

The description is ``work_model(spec)`` of the configuration's reference
(``bench/reference/<name>.py``; the fields are set out in the reference
contract at the top of ``gpt2.py``): its projection sites ("linear"), its
kinds of attention layer ("attention") and its LM head ("head").  Nothing
here knows a model's shape.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Tuple

# an int8 site's weights and rows: one byte a value, and a float32 scale
INT8_BYTES, SCALE_BYTES = 1.0, 4.0


def linear_weights(sites) -> float:
    """Weights one token passes through in ``sites`` (the description's
    "linear" entries) across their layers, each site weighed by the share
    of tokens that reach it."""
    return sum(s["layers"] * s["token_share"] * s["k"] * s["n"] for s in sites)


def linear_bytes(sites, *, calls: int, rows: int, quantized: bool,
                 dtype_bytes: float) -> float:
    """Bytes of ``calls`` passes through every layer of ``sites`` over
    ``rows`` tokens in all.  Each call reads each weight matrix a site may
    read; each row that reaches a site is read once and written once (in
    the configuration's dtype).  In a ``quantized`` configuration a site
    the description marks quantized is int8, with a float32 scale per
    output channel of a weight and per row; otherwise the weights and rows
    are in the configuration's dtype."""
    total = 0.0
    for s in sites:
        wb, sb = ((INT8_BYTES, SCALE_BYTES) if quantized and s["quantized"]
                  else (dtype_bytes, 0.0))
        k, n = s["k"], s["n"]
        per_call = s["matrices"] * (k * n * wb + n * sb)
        per_row = s["token_share"] * (k * wb + n * dtype_bytes + sb)
        total += s["layers"] * (calls * per_call + rows * per_row)
    return total


def _kv_bytes(group, kv_mode: str) -> float:
    if kv_mode not in group["kv_bytes"]:
        raise ValueError(f"the model's description gives no cached bytes "
                         f"for kv_mode {kv_mode!r}")
    return group["kv_bytes"][kv_mode]


def kv_bytes_per_token(model, kv_mode: str) -> float:
    """Bytes of one cached position across all attention layers."""
    return sum(g["layers"] * _kv_bytes(g, kv_mode) for g in model["attention"])


def _ops_per_key(group) -> float:
    """Operations of one query and one key in every layer of a group:
    its score and its share of the weighted sum of values."""
    return 2.0 * group["layers"] * group["heads"] * (group["qk_dim"]
                                                     + group["v_dim"])


def _capped(keys: float, window: Optional[int]) -> float:
    return keys if window is None else min(keys, window)


def attention_ops(model, keys: float) -> float:
    """Operations of one query that has ``keys`` positions before it
    (itself included), in every layer: a windowed layer attends at most
    its window."""
    return sum(_ops_per_key(g) * _capped(keys, g["window"])
               for g in model["attention"])


def _sum_capped(a: int, b: int, window: Optional[int]) -> float:
    """The sum of min(j, window) over j = a .. b (a >= 1)."""
    top = b if window is None else max(a - 1, min(b, window))
    return (top - a + 1) * (a + top) / 2.0 + (b - top) * (window or 0)


def chunk_keys(n: int, done: int, window: Optional[int] = None) -> float:
    """Keys attended, summed over the queries of a prefill chunk of ``n``
    tokens that ends at position ``done`` (query at p sees p + 1 keys, at
    most ``window``)."""
    return _sum_capped(done - n + 1, done, window)


def chunk_positions(n: int, done: int, window: Optional[int] = None) -> int:
    """Cached positions a prefill chunk of ``n`` tokens ending at ``done``
    reads: every one before it, or only those some query's window holds."""
    return done if window is None else min(done, n - 1 + window)


def head_ops(model) -> float:
    """Operations of the LM head for one sampled position."""
    return 2.0 * model["head"]["width"] * model["head"]["vocab"]


def window(model, *, kv_mode: str, quantized: bool, dtype_bytes: float,
           decode_steps: int, decode_slot_steps: int, prefill_steps: int,
           prefill_tokens: int, prefills: int, decode_keys: Iterable[int],
           chunks: Iterable[Tuple[int, int]]) -> Dict[str, float]:
    """Work of a serving window.

    ``decode_keys``: keys each decoded token attended (its context);
    ``chunks``: (tokens, end position) of each prefill chunk.  Linear work
    is one call per decode step over its active slots and one per prefill
    step over its valid chunk tokens; attention reads each live position's
    cached bytes once per call; the LM head runs for every sampled
    position (each decode token and the last prompt token of each
    finished prefill).  ``linear_*`` count every site, ``quantized_*`` the
    int8 ones (nothing in an unquantized configuration)."""
    tokens = decode_slot_steps + prefill_tokens
    decode_keys = list(decode_keys)
    chunks = list(chunks)
    out = {"attention_ops": 0.0, "attention_bytes": 0.0}
    int8 = [s for s in model["linear"] if quantized and s["quantized"]]
    for key, sites in (("linear", model["linear"]), ("quantized", int8)):
        out[f"{key}_ops"] = 2.0 * linear_weights(sites) * tokens
        out[f"{key}_bytes"] = sum(
            linear_bytes(sites, calls=calls, rows=rows, quantized=quantized,
                         dtype_bytes=dtype_bytes)
            for calls, rows in ((decode_steps, decode_slot_steps),
                                (prefill_steps, prefill_tokens)))
    for g in model["attention"]:
        win = g["window"]
        decode = sum(_capped(k, win) for k in decode_keys)
        keys = decode + sum(chunk_keys(n, e, win) for n, e in chunks)
        positions = decode + sum(chunk_positions(n, e, win) for n, e in chunks)
        out["attention_ops"] += _ops_per_key(g) * keys
        out["attention_bytes"] += g["layers"] * _kv_bytes(g, kv_mode) * positions
    sampled = len(decode_keys) + prefills
    out["model_ops"] = (out["linear_ops"] + out["attention_ops"]
                        + head_ops(model) * sampled)
    return out


def roofline_s(ops: float, bytes_: float, ops_peak: float, bw: float) -> float:
    """Least time the chip needs for the work: the larger of its two bounds."""
    return max(ops / ops_peak, bytes_ / bw)
