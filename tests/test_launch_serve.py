"""CLI flag plumbing for the serving launcher (`repro.launch.serve`).

Previously exercised only by hand: these tests pin that `--backend`,
`--kv-mode`, `--page-size`, `--n-pages`, `--prefill-chunk`,
`--prefill-slots`, `--prefill-aging`, `--spec-mode`,
`--spec-k`, `--max-batch` and `--s-max` reach `ServeEngine` unmangled (and
that `--quant`/`--backend` reach the quantization policy), by stubbing the
engine/quantizer at the launcher's module seam — no model compute runs.
The PR 8 observability flags (`--trace-out`, `--obs`, `--json-out`) are
covered the same way: recorder/observer construction and the trace/JSON
dumps happen in the launcher, so the stub seam exercises them fully."""
import json

import jax.numpy as jnp
import pytest

from repro.launch import serve as L


class _StubMetrics:
    def report(self):
        # every key the launcher's summary line reads
        return {k: 0.0 for k in (
            "tokens_per_sec", "decode_steps", "decode_batch_mean",
            "prefills", "prefill_chunks", "prefill_steps",
            "prefill_multi_steps", "prefill_batch_mean",
            "prefill_resumes", "interleaved_steps",
            "decode_stall_steps", "ttft_ms_mean", "pool_occupancy_mean",
            "pool_occupancy_peak", "fragmentation_mean", "cache_bytes",
            "kv_read_savings", "kv_bytes_read", "kv_bytes_read_dense",
            "prefix_hits", "cow_copies", "spec_verify_steps",
            "spec_proposed", "spec_accepted", "spec_acceptance",
            "decode_steps_saved")}


class _StubPool:
    mode = "stub"


class _StubEngine:
    """Captures constructor args; generate() marks requests done."""
    calls = []

    def __init__(self, cfg, params, **kw):
        self.cfg, self.params, self.kw = cfg, params, kw
        self.metrics, self.pool = _StubMetrics(), _StubPool()
        _StubEngine.calls.append(self)

    def generate(self, reqs, arrivals=None):
        for r in reqs:
            r.done = True
        return reqs

    @staticmethod
    def text(req):
        return ""


@pytest.fixture
def stubbed(monkeypatch):
    _StubEngine.calls = []
    captured = {}

    def fake_quantize_model(cfg, params, calib, policy, **kw):
        captured["policy"] = policy
        captured["quantize_kw"] = kw
        return "ARTIFACT"

    monkeypatch.setattr(L, "ServeEngine", _StubEngine)
    monkeypatch.setattr(L, "quantize_model", fake_quantize_model)
    return captured


def _engine_kw(argv, stubbed):
    # --reduced: the CPU rehearsal size (the stubs skip compute, but the
    # launcher still draws the arch's random weights)
    assert L.main(["--reduced", *argv]) == 0
    assert len(_StubEngine.calls) == 1
    return _StubEngine.calls[0]


@pytest.mark.parametrize("reduced", [False, True])
def test_arch_width_follows_reduced_flag(stubbed, monkeypatch, reduced):
    """The published widths by default; --reduced keeps the CPU rehearsal
    config (weights are stubbed out: no full-width tensor is drawn)."""
    from repro.configs import get_config
    monkeypatch.setattr(L.T, "init_params", lambda cfg, key: {})
    assert L.main(["--quant", "fp"] + (["--reduced"] if reduced else [])) == 0
    assert _StubEngine.calls[0].cfg == get_config("gpt2-small",
                                                  reduced=reduced)


def test_defaults_reach_engine(stubbed):
    eng = _engine_kw(["--quant", "fp"], stubbed)
    kw = eng.kw
    assert kw["max_batch"] == 2 and kw["s_max"] == 128
    assert kw["kv_mode"] is None            # auto
    assert kw["page_size"] == 16
    assert kw["n_pages"] is None
    assert kw["prefill_chunk"] == 32
    assert kw["prefill_slots"] == 2 and kw["prefill_aging"] == 1.0
    assert kw["cache_dtype"] == jnp.bfloat16
    assert eng.params is not None           # fp path: raw params, no artifact


def test_pool_flags_reach_engine_unmangled(stubbed):
    eng = _engine_kw(
        ["--quant", "fp", "--kv-mode", "int8", "--page-size", "4",
         "--n-pages", "99", "--prefill-chunk", "7", "--prefill-slots", "3",
         "--prefill-aging", "0.5", "--max-batch", "5",
         "--s-max", "256"], stubbed)
    kw = eng.kw
    assert kw["kv_mode"] == "int8"
    assert kw["page_size"] == 4
    assert kw["n_pages"] == 99
    assert kw["prefill_chunk"] == 7
    assert kw["prefill_slots"] == 3
    assert kw["prefill_aging"] == 0.5
    assert kw["max_batch"] == 5
    assert kw["s_max"] == 256


def test_kv_mode_int4_reaches_engine(stubbed):
    # quantized path: the artifact (carrying kv_calib) is the params arg and
    # the int4 page mode reaches the engine unmangled
    eng = _engine_kw(["--quant", "muxq", "--kv-mode", "int4"], stubbed)
    assert eng.kw["kv_mode"] == "int4"
    assert eng.params == "ARTIFACT"


def test_kv_mode_int4_fp_weights(stubbed):
    # int4 pages are opt-in and independent of the weight path
    eng = _engine_kw(["--quant", "fp", "--kv-mode", "int4"], stubbed)
    assert eng.kw["kv_mode"] == "int4"


def test_spec_flags_default_off(stubbed):
    eng = _engine_kw(["--quant", "fp"], stubbed)
    assert eng.kw["spec_mode"] == "off"
    assert eng.kw["spec_k"] == 4


def test_spec_flags_reach_engine_unmangled(stubbed):
    eng = _engine_kw(["--quant", "fp", "--spec-mode", "ngram",
                      "--spec-k", "6"], stubbed)
    assert eng.kw["spec_mode"] == "ngram"
    assert eng.kw["spec_k"] == 6


def test_spec_mode_rejects_unknown(stubbed):
    with pytest.raises(SystemExit):
        L.main(["--quant", "fp", "--spec-mode", "medusa"])
    assert not _StubEngine.calls


def test_quantized_path_passes_artifact_and_backend(stubbed):
    eng = _engine_kw(["--quant", "muxq", "--backend", "fused",
                      "--kv-mode", "fp"], stubbed)
    assert eng.params == "ARTIFACT"         # artifact IS the params arg
    assert eng.kw["kv_mode"] == "fp"
    policy = stubbed["policy"]
    spec = policy.resolve("mlp_up")
    assert spec.method == "muxq"
    assert spec.backend == "fused"
    assert spec.weight_granularity == "per_channel"  # fused packing contract
    assert stubbed["quantize_kw"]["pack_target"] == "both"


def test_fake_backend_policy(stubbed):
    _engine_kw(["--quant", "smoothquant"], stubbed)
    spec = stubbed["policy"].resolve("attn_qkv")
    assert spec.method == "smoothquant"
    assert getattr(spec, "backend", "fake") == "fake"


def test_pack_target_flag_reaches_quantizer(stubbed):
    _engine_kw(["--quant", "muxq", "--pack-target", "fused",
                "--backend", "fused"], stubbed)
    assert stubbed["quantize_kw"]["pack_target"] == "fused"


def test_fused_tree_pack_target_rejected(stubbed):
    with pytest.raises(SystemExit, match="pack-target"):
        L.main(["--reduced", "--quant", "muxq", "--backend", "fused",
                "--pack-target", "tree"])
    assert not _StubEngine.calls


def test_llm_int8_fused_rejected(stubbed):
    with pytest.raises(SystemExit, match="llm_int8"):
        L.main(["--reduced", "--quant", "llm_int8", "--backend", "fused"])
    assert not _StubEngine.calls


def test_observability_defaults_off(stubbed):
    from repro.kernels import dispatch
    eng = _engine_kw(["--quant", "fp"], stubbed)
    assert eng.kw["recorder"] is None       # engine falls back to the no-op
    assert eng.kw["quality"] is None
    assert dispatch.quality_observer() is None


def test_trace_out_reaches_engine_and_writes_chrome_json(stubbed, tmp_path):
    from repro.obs.trace import TraceRecorder
    out = tmp_path / "trace.json"
    eng = _engine_kw(["--quant", "fp", "--trace-out", str(out)], stubbed)
    assert isinstance(eng.kw["recorder"], TraceRecorder)
    doc = json.loads(out.read_text())
    assert isinstance(doc["traceEvents"], list)
    assert doc["otherData"]["dropped_events"] == 0


def test_obs_flag_installs_then_clears_observer(stubbed):
    from repro.kernels import dispatch
    from repro.obs.quality import QualityObserver
    eng = _engine_kw(["--quant", "fp", "--obs"], stubbed)
    assert isinstance(eng.kw["quality"], QualityObserver)
    # the launcher uninstalls the process-global hook before exiting
    assert dispatch.quality_observer() is None


def test_json_out_dumps_report_and_registry(stubbed, tmp_path):
    out = tmp_path / "metrics.json"
    _engine_kw(["--quant", "fp", "--json-out", str(out)], stubbed)
    doc = json.loads(out.read_text())
    assert set(doc) == {"report", "registry", "quality"}
    assert doc["registry"] == {}    # stub metrics carry no registry
    assert doc["quality"] == {}     # --obs not set
    assert doc["report"]["decode_steps"] == 0.0


def test_tp_default_single_device(stubbed):
    eng = _engine_kw(["--quant", "fp"], stubbed)
    assert eng.kw["tp"] == 1


def test_tp_flag_reaches_engine(stubbed):
    # tp=1 is the only size the single-device test process can validate at
    # the argparse seam; mesh construction itself is covered by
    # tests/test_serve_tp.py under forced host devices
    eng = _engine_kw(["--quant", "fp", "--tp", "1"], stubbed)
    assert eng.kw["tp"] == 1


def test_tp_exceeding_devices_rejected_before_engine(stubbed):
    with pytest.raises(SystemExit, match="device"):
        L.main(["--quant", "fp", "--tp", "64"])
    assert not _StubEngine.calls            # rejected at the flag seam


def test_tp_zero_rejected(stubbed):
    with pytest.raises(SystemExit, match="--tp"):
        L.main(["--quant", "fp", "--tp", "0"])
    assert not _StubEngine.calls


# -- persistent compile cache placement ---------------------------------------

@pytest.mark.parametrize("backend,env,want", [
    ("cpu", None, None),                     # tests / CPU rehearsals: off
    ("tpu", None, "repo"),                   # fixed in-checkout directory
    ("tpu", "/elsewhere/cache", "/elsewhere/cache"),  # the env var wins
])
def test_compile_cache_placement(monkeypatch, backend, env, want):
    """The entry points' cache goes where JAX_COMPILATION_CACHE_DIR says
    (setting nothing else), else to <repo>/.jax_cache (git-ignored), and
    stays off on the CPU.  The config update is captured, so the test
    never turns a cache on."""
    import jax
    from pathlib import Path
    from repro.launch import compile_cache as CC
    updates = []
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    repo = Path(__file__).resolve().parents[1]
    expect = str(repo / ".jax_cache") if want == "repo" else want
    assert CC.enable_compile_cache() == expect
    if want == "repo":
        assert updates == [("jax_compilation_cache_dir", expect)]
        assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    else:
        assert updates == []
