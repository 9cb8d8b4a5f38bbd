"""The traffic generator: seeded, within its bounds, whole-page prefixes."""
from __future__ import annotations

import pytest

import bench_testlib as L  # noqa: F401  (puts bench/ on sys.path)
import gen

# the benchmark's mix, and a test mix with a shared system prompt
MIXES = ("chat", "docqa")
BIG_SEED = 2 ** 31 + 123457


def _load(name):
    if name == "docqa":
        return gen.load(L.DATA / "tiny-docqa.json")
    return gen.load(L.BENCH / "traffic" / f"{name}.json")


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    t = _load(mix)
    a, b = gen.requests(t, BIG_SEED), gen.requests(t, BIG_SEED)
    assert a == b
    assert gen.requests(t, BIG_SEED + 1) != a
    assert len(a) == t["backlog"]


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_within_bounds(mix):
    t = _load(mix)
    b = gen.bounds(t)
    for r in gen.requests(t, 5):
        # BOS plus one byte per character
        assert r["prompt_tokens"] == 1 + len(r["prompt"].encode())
        assert b["prompt_min"] <= r["prompt_tokens"] <= b["prompt_max"]
        assert b["output_min"] <= r["max_new"] <= b["output_max"]


@pytest.mark.parametrize("mix", MIXES)
def test_every_block_offers_the_same_work(mix):
    """Seeds differ in text only: every block holds the same (prompt,
    output) pairs, and every seed sends them in the same order."""
    t = _load(mix)
    n = t["block"]

    def lengths(seed):
        return [(r["prompt_tokens"], r["max_new"])
                for r in gen.requests(t, seed)]

    a, b = lengths(1), lengths(BIG_SEED)
    assert a == b
    blocks = [sorted(a[i:i + n]) for i in range(0, len(a), n)]
    assert len(set(map(str, blocks))) == 1
    # each block has an order of its own, not sorted by length
    assert a[:n] != a[n:2 * n] and a[:n] != sorted(a[:n])


def test_shared_prefix_is_whole_pages():
    t = _load("docqa")
    reqs = gen.requests(t, BIG_SEED)
    shared = t["shared_prefix_tokens"]
    assert shared % 16 == 0            # 16-token pages
    prefix = reqs[0]["prompt"][: shared - 1]
    assert all(r["prompt"].startswith(prefix) for r in reqs)
    # the tails differ, so nothing past the prefix's pages is shared
    assert len({r["prompt"][shared - 1: shared + 15] for r in reqs[:64]}) > 60


def test_chat_prompts_share_nothing_but_bos():
    reqs = gen.requests(_load("chat"), BIG_SEED)[:128]
    assert len({r["prompt"][:8] for r in reqs}) == len(reqs)


def test_quantiles_are_monotone_and_clipped():
    d = {"median": 96, "sigma": 0.5, "min": 32, "max": 256}
    q = gen.quantile_lengths(d, 32)
    assert q == sorted(q) and q[0] >= 32 and q[-1] <= 256
    assert q[15] <= 96 <= q[16]


def test_scale_cuts_the_source_lengths():
    """chat's lengths are the trace's times ``scale``, bounds included."""
    t = _load("chat")
    b = gen.bounds(t)
    assert b["prompt_max"] == round(t["prompt_tokens"]["max"] * t["scale"])
    assert b["output_min"] == round(t["output_tokens"]["min"] * t["scale"])
    prompts = [p for p, _ in gen.pairs(t)]
    median = sorted(prompts)[len(prompts) // 2]
    assert abs(median - t["prompt_tokens"]["median"] * t["scale"]) <= 12
    # prompts run longer than outputs, as in the trace
    outputs = sorted(o for _, o in gen.pairs(t))
    assert median > 4 * outputs[len(outputs) // 2]
