"""The check that decides ``correct``, driven end to end at test size on the
CPU: sound runs pass; the control and each fault a serving cell can have
fail; a run with no TPU prints no result."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

import bench_testlib as L


@pytest.fixture(scope="module")
def shared(tmp_path_factory):
    """One root and cache for the file: the MUXQ artifact is made once."""
    return tmp_path_factory.mktemp("bench")


@pytest.mark.parametrize("cell", [c[0] for c in L.CELLS])
def test_sound_run_is_correct(shared, cell):
    res = L.run_cell(shared, cell)
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {"output_tok_s", "itl_p95_ms", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["check"]["tokens_compared"]["value"] >= \
        res["check"]["tokens_compared"]["limit"]


def _fault(kind):
    """Wrap the paged decode step with one fault, where it is produced."""
    import jax.numpy as jnp
    from repro.models import transformer as T
    orig = T.decode_step_paged

    def broken(cfg, params, tokens, kv, *a, **k):
        logits, new_kv = orig(cfg, params, tokens, kv, *a, **k)
        if kind == "state_unchanged":
            return logits, kv
        if kind == "half_batch":
            half = logits[: logits.shape[0] // 2]
            return jnp.concatenate([half, half]), new_kv
        # a token altered: the runner-up column wins wherever argmax lands
        return jnp.roll(logits, 1, axis=-1), new_kv
    return broken


KINDS = ["state_unchanged", "half_batch", "token_altered"]
TINY = ["tiny-chat-muxq", "tiny-chat-bf16"]


def _exceeds_a_limit(check):
    """Some compared gap is over its limit (bf16 compares only the mean)."""
    return any(v["value"] > v["limit"] for k, v in check.items()
               if k.startswith("served_logit_gap"))


@pytest.mark.parametrize("cell", TINY)
@pytest.mark.parametrize("kind", KINDS)
def test_fault_in_the_timed_path_is_not_correct(shared, monkeypatch, kind,
                                                cell):
    from repro.models import transformer as T
    monkeypatch.setattr(T, "decode_step_paged", _fault(kind))
    res = L.run_cell(shared, cell)
    assert not res["correct"], res["check"]
    assert _exceeds_a_limit(res["check"])


@pytest.mark.parametrize("cell", TINY)
def test_control_fails_where_the_program_passes(shared, cell):
    """The reference one precision lower (int4 for MUXQ's int8, int8 for
    bf16), judged by the run's own check on the program's sequences."""
    seed = 2 ** 31 + 5
    assert L.run_cell(shared, cell, seed=seed)["correct"]
    res = L.run_cell(shared, cell, seed=seed, control=True)
    assert not res["correct"], res["check"]
    assert _exceeds_a_limit(res["check"])


def test_control_readings_go_through_the_check(shared):
    """bench/control.py: one set-up, the program and the control judged by
    run.check on each seed's window."""
    import control
    _, rows = control.readings("tiny-chat-muxq", [2 ** 31 + 5, 17], 1.0,
                               root=L.root_of(shared), cache=shared / "cache",
                               require_tpu=False)
    for row in rows:
        assert row["program"]["correct"] and not row["control"]["correct"]
        assert row["control"]["gap_max"] > row["program"]["gap_max"]


def _bench_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "chat-muxq", "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result(tmp_path):
    # a copy of the benchmark alone: BENCHMARK.json and the files under paths
    shutil.copy(L.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(L.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache"))
    proc = _bench_cmd(tmp_path)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr
