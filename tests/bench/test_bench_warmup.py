"""Warm-up coverage: the warm-up set reaches every step program the cell's
mix reaches.  With EOS off the schedule depends on lengths only, never on
widths, so a test-size model with the cells' own serving settings runs the
real mixes here."""
from __future__ import annotations

import json

import numpy as np
import pytest

import bench_testlib as L
import gen
import warm

STEPS = 200


class _Stop(Exception):
    pass


def _engine(serve):
    import importlib.util

    import jax.numpy as jnp
    from repro.serve.engine import ServeEngine
    spec = json.loads((L.DATA / "tiny-bf16.json").read_text())
    m = importlib.util.spec_from_file_location("gpt2_ref_w", L.BENCH / "reference" / "gpt2.py")
    ref = importlib.util.module_from_spec(m)
    m.loader.exec_module(ref)
    spec["dtype"] = "float32"
    cfg = ref.model_config(spec)
    params = ref.init_weights(spec, 0, jnp.float32)
    return ServeEngine(cfg, params, **dict(serve, kv_mode="fp"))


def _mix(mix):
    if mix == "docqa":      # a shared system prompt, at test lengths
        return gen.load(L.DATA / "tiny-docqa.json")
    return gen.load(L.BENCH / "traffic" / f"{mix}.json")


def _serve(mix):
    return json.loads((L.BENCH / "configs" / "gpt2-large-muxq.json").read_text())["serve"]


@pytest.mark.parametrize("mix", ["chat", "docqa"])
def test_mix_stays_inside_the_warm_up_targets(mix):
    from repro.serve.engine import Request
    traffic = _mix(mix)
    serve = _serve(mix)
    eng = _engine(serve)
    steps = [0]

    def stream(tok):
        pass

    reqs = [Request(r["prompt"], max_new_tokens=r["max_new"], stream=stream)
            for r in gen.requests(traffic, 2 ** 31 + 99)[:400]]
    sched = eng.scheduler()
    sched.eos = -1
    decode = sched.decode

    def counted(*a):
        steps[0] += 1
        if steps[0] > STEPS:
            raise _Stop
        return decode(*a)

    sched.decode = counted
    with pytest.raises(_Stop):
        sched.run(reqs)
    prefill, dec = warm.targets(gen.bounds(traffic), serve)
    assert eng.prefill_buckets <= prefill
    assert eng.decode_buckets <= dec
    # the stretch reaches into the mix: every full-chunk page bucket
    assert {p for c, p in eng.prefill_buckets if c == 32} == \
        {p for c, p in prefill if c == 32}


@pytest.mark.parametrize("mix", ["chat", "docqa"])
def test_warm_up_runs_land_on_every_target(mix):
    from repro.serve.engine import Request
    traffic = _mix(mix)
    serve = _serve(mix)
    eng = _engine(serve)
    rng = np.random.default_rng(0)
    for length, new in warm.runs(gen.bounds(traffic), serve):
        sched = eng.scheduler()
        sched.eos = -1
        sched.run([Request(gen.text(rng, length - 1), max_new_tokens=new)])
    prefill, dec = warm.targets(gen.bounds(traffic), serve)
    assert eng.prefill_buckets == prefill
    assert eng.decode_buckets == dec


def test_single_prefill_slot_pairs_nothing():
    b = {"prompt_min": 40, "prompt_max": 100, "output_min": 1,
         "output_max": 4, "shared": 0}
    serve = {"prefill_chunk": 32, "page_size": 16, "s_max": 256,
             "prefill_slots": 1}
    prefill, _ = warm.targets(b, serve)
    # a lone prompt's last chunk of 1-2 tokens at 64 reads 5 pages
    assert (2, 8) in prefill and (32, 2) in prefill
    # no prompt ends in a 1-token chunk at 32 (it would be 33 < 40 tokens)
    assert (1, 4) not in prefill
