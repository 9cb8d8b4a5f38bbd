"""The one traffic generator: a mix's data file in, a seeded request list out.

A traffic file (``bench/traffic/<name>.json``) holds only parameters:

  source           where the length distributions come from (not read)
  scale            every length below is multiplied by this and rounded
                   (1 when absent): the cut of a public trace's lengths
                   to what the model's positions and the window hold
  backlog          requests queued at step 0 (a closed backlog: every
                   request is there before the window opens)
  block            lengths repeat in blocks of this many requests: each
                   block holds the same (prompt, output) length pairs
                   (stratified quantiles of the distributions below,
                   paired by one fixed shuffle), each block in an order of
                   its own drawn by a fixed shuffle.  The seed draws the
                   text alone: a window holds a few dozen requests, so a
                   seeded order would change the work it measures.
  prompt_tokens    {"median", "sigma", "min", "max"}: lognormal length of
                   each request's own prompt text, in tokens, BOS included
                   when there is no shared prefix, clipped to [min, max]
  output_tokens    the same for the number of tokens each request decodes
                   (EOS never stops a request, so this is exact)
  shared_prefix_tokens   length of one system prompt, BOS included, that
                   starts every prompt (0 for none; not scaled)
  open_after_finished    the window opens at the first token streamed once
                   this many requests have finished (0: at once), so that
                   the slots have filled and turn over before it

Text is random lowercase words; with the byte tokenizer one character is
one token, and every prompt starts with BOS.
"""
from __future__ import annotations

import json
import math
import statistics
from pathlib import Path
from typing import Dict, List

import numpy as np

LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)


def load(path) -> Dict:
    return json.loads(Path(path).read_text())


def _scaled(x: float, scale: float) -> int:
    return max(1, int(round(x * scale)))


def quantile_lengths(dist: Dict, n: int, scale: float = 1.0) -> List[int]:
    """``n`` stratified quantiles of a clipped lognormal, scaled, as whole
    tokens."""
    z = statistics.NormalDist()
    out = []
    for i in range(n):
        x = dist["median"] * math.exp(dist["sigma"] * z.inv_cdf((i + 0.5) / n))
        out.append(_scaled(min(max(x, dist["min"]), dist["max"]), scale))
    return out


def text(rng: np.random.Generator, n_chars: int) -> str:
    """``n_chars`` of random lowercase words separated by single spaces."""
    if n_chars <= 0:
        return ""
    chars = LETTERS[rng.integers(0, len(LETTERS), n_chars)].copy()
    # a space after every 2-9 letters
    i = int(rng.integers(2, 10))
    while i < n_chars - 1:
        chars[i] = ord(" ")
        i += int(rng.integers(3, 11))
    return chars.tobytes().decode("ascii")


def pairs(traffic: Dict) -> List[tuple]:
    """The block's (prompt tokens, output tokens) pairs, the same for
    every seed."""
    block, scale = int(traffic["block"]), float(traffic.get("scale", 1))
    prompts = quantile_lengths(traffic["prompt_tokens"], block, scale)
    outputs = quantile_lengths(traffic["output_tokens"], block, scale)
    return list(zip(prompts, np.random.default_rng(0).permutation(outputs)))


def requests(traffic: Dict, seed: int) -> List[Dict]:
    """The seeded request list: dicts with ``prompt`` (str),
    ``prompt_tokens`` (with BOS) and ``max_new`` (tokens to decode)."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(1)
    block = pairs(traffic)
    shared = int(traffic.get("shared_prefix_tokens", 0))
    # BOS is the prompt's first token: the system prompt carries it when
    # there is one, else each request's own text does
    system = text(rng, shared - 1) if shared else ""
    out = []
    n = int(traffic["backlog"])
    for _ in range(0, n, len(block)):
        for k in order.permutation(len(block)):
            p, o = block[k]
            own = int(p) - (0 if shared else 1)
            out.append({"prompt": system + text(rng, own),
                        "prompt_tokens": shared + own + (0 if shared else 1),
                        "max_new": int(o)})
    return out[:n]


def bounds(traffic: Dict) -> Dict[str, int]:
    """Least and most prompt tokens (prefix included) and output tokens
    the mix can send — what the warm-up has to cover."""
    shared = int(traffic.get("shared_prefix_tokens", 0))
    scale = float(traffic.get("scale", 1))
    p, o = traffic["prompt_tokens"], traffic["output_tokens"]
    return {"prompt_min": shared + _scaled(p["min"], scale),
            "prompt_max": shared + _scaled(p["max"], scale),
            "output_min": _scaled(o["min"], scale),
            "output_max": _scaled(o["max"], scale),
            "shared": shared}
