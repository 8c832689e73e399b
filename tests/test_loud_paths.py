"""No fallback hides the device: the compile-cache rule, and the paths
that must fail loudly on a TPU they cannot read (ISSUE 21).

Code that asks ``jax.default_backend()`` is steered onto its TPU branch in
the test (monkeypatch), never through a program option.
"""

import json
import os
import pathlib
import re
import subprocess
import sys
import types

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import bench  # noqa: E402
from gllm_tpu.config import (CacheConfig, EngineConfig,  # noqa: E402
                             SchedulerConfig)
from gllm_tpu.models.config import ModelConfig  # noqa: E402

TINY = dict(architecture="LlamaForCausalLM", vocab_size=128, hidden_size=32,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
            intermediate_size=64, max_position=128)


def _config(kv_cache_dtype="auto", **kw):
    return EngineConfig(
        load_format="dummy", dtype="float32", max_model_len=64,
        max_num_seqs=4,
        scheduler=SchedulerConfig(max_prefill_tokens=32, max_decode_seqs=4),
        cache=CacheConfig(page_size=4, num_pages=32,
                          kv_cache_dtype=kv_cache_dtype), **kw)


def _runner(model=TINY, **kw):
    from gllm_tpu.runner.runner import ModelRunner
    return ModelRunner(_config(**kw), ModelConfig(**model))


# ---- one compile-cache rule ------------------------------------------------

@pytest.fixture
def cache_config():
    """Restore jax's cache configuration around a test that sets it."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_dir_unset_is_the_checkouts_jax_cache(cache_config,
                                                    monkeypatch):
    from gllm_tpu.utils import enable_compilation_cache
    made = []
    monkeypatch.setattr(os, "makedirs", lambda d, **kw: made.append(d))
    jax.config.update("jax_compilation_cache_dir", None)
    want = str(REPO / ".jax_cache")
    assert enable_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert made == [want]
    # the skip thresholds are zeroed: small decode programs get cached
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_cache_dir_from_the_environment_is_left_alone(cache_config,
                                                      monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax already holds that directory
    (it reads the variable into its config) and the code sets no other."""
    from gllm_tpu.utils import enable_compilation_cache
    made = []
    monkeypatch.setattr(os, "makedirs", lambda d, **kw: made.append(d))
    env_dir = str(tmp_path / "from_env")
    jax.config.update("jax_compilation_cache_dir", env_dir)
    assert enable_compilation_cache() == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir
    assert made == []


def test_jax_reads_the_cache_dir_variable_itself(tmp_path):
    """The premise of the rule above, checked in a fresh process."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.config.jax_compilation_cache_dir)"],
        env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                 JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert out.stdout.strip() == str(tmp_path), out.stderr[-500:]


def test_retired_knobs_are_gone():
    """The cache-directory and assumed-HBM environment knobs no longer
    exist anywhere the program or its benchmarks could read them (names
    assembled here so that a search of the tree finds nothing)."""
    knobs = ["GLLM_TPU_" + tail for tail in ("XLA_CACHE", "HBM_BYTES")]
    hits = []
    for root in ("gllm_tpu", "benchmarks", "bench.py", "chip_smoke.py"):
        path = REPO / root
        files = [path] if path.is_file() else sorted(path.rglob("*.py"))
        for f in files:
            text = f.read_text()
            hits += [(str(f.relative_to(REPO)), knob)
                     for knob in knobs if knob in text]
    assert not hits


# ---- peak FLOP/s -----------------------------------------------------------

def _dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_peak_flops_raises_on_an_unlisted_tpu():
    from gllm_tpu.obs.spans import peak_flops
    assert peak_flops(_dev("tpu", "TPU v5 lite")) == pytest.approx(197e12)
    with pytest.raises(ValueError, match="TPU v9 mystery"):
        peak_flops(_dev("tpu", "TPU v9 mystery"))
    # CPU behaviour stays: no spec sheet, peak 0.0, MFU fields read null
    assert peak_flops(_dev("cpu", "cpu")) == 0.0


def test_engine_and_runner_read_no_peak_and_estimate_no_flops(monkeypatch):
    """The engine loop no longer estimates per step (PR 24): the peak
    table and the FLOPs model are bench.py's alone. An engine starts
    without consulting either, its step events carry phases and no
    estimate, and nothing under engine/ or runner/ names them."""
    from gllm_tpu.engine.llm import LLM
    from gllm_tpu.obs import spans
    from gllm_tpu.obs.steptrace import TRACE
    from gllm_tpu.sampling_params import SamplingParams

    def refuse(*a, **k):
        raise AssertionError("the engine consulted the peak table")
    monkeypatch.setattr(spans, "peak_flops", refuse)
    monkeypatch.setattr(spans.StepFlopsModel, "from_model_config", refuse)
    llm = LLM(config=_config(), model_cfg=ModelConfig(**TINY))
    mark = TRACE.mark()
    llm.generate(prompt_token_ids=[[3, 5, 7]],
                 sampling_params=SamplingParams(max_tokens=3,
                                                temperature=0.0,
                                                ignore_eos=True))
    steps = [e for e in TRACE.events(since=mark) if "ph" in e]
    assert steps and all("wait_ms" in e for e in steps)
    assert not any({"mfu", "hbm_gbps"} & set(e) for e in steps)
    hits = [str(f.relative_to(REPO))
            for sub in ("engine", "runner")
            for f in (REPO / "gllm_tpu" / sub).rglob("*.py")
            if re.search(r"StepFlopsModel|peak_flops", f.read_text())]
    assert not hits


# ---- KV pool sizing --------------------------------------------------------

class _Dev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def test_num_pages_cpu_default_stays():
    assert _runner().determine_num_pages() == 2048


@pytest.mark.parametrize("stats", [
    None, {}, RuntimeError("memory_stats unavailable")],
    ids=["none", "empty", "raises"])
def test_num_pages_raises_on_an_unreadable_tpu(monkeypatch, stats):
    """No 8 GiB assumption, no silent 2048 pages: a TPU that cannot
    report its memory is an error."""
    r = _runner()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev(stats)])
    with pytest.raises(RuntimeError):
        r.determine_num_pages()


def test_num_pages_from_a_readable_tpu(monkeypatch):
    r = _runner()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev(
        {"bytes_limit": 2 << 30, "bytes_in_use": 256 << 20})])
    free = (2 << 30) * 0.9 - (256 << 20) - (512 << 20)
    exact = int(free // r._kv_bytes_per_page())
    pages = r.determine_num_pages()
    # rounded down to 256 pages: a restart whose memory stats differ by
    # a few KB sizes the same pool, so its step programs hit the cache
    assert pages % 256 == 0 and exact - 256 < pages <= exact
    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev(
        {"bytes_limit": 2 << 30, "bytes_in_use": (256 << 20) + 4096})])
    assert r.determine_num_pages() == pages


def test_pp_num_pages_raises_on_an_unreadable_tpu(monkeypatch,
                                                  multi_device_cpu):
    from gllm_tpu.config import ParallelConfig
    from gllm_tpu.runner.pp_runner import PPModelRunner
    r = PPModelRunner(_config(parallel=ParallelConfig(pp=2)),
                      ModelConfig(**TINY))
    staged = [(s.cfg, None, None) for s in r.stages]
    assert r._determine_num_pages(r.stage_bounds, staged,
                                  lambda _r, i: [_Dev(None)]) == 2048
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="memory_stats"):
        r._determine_num_pages(r.stage_bounds, staged,
                               lambda _r, i: [_Dev(None)])


# ---- attention_impl and int8 KV on a TPU -----------------------------------

def test_auto_attention_warns_when_it_resolves_to_xla_on_tpu(monkeypatch,
                                                             caplog):
    from gllm_tpu.runner.runner import resolve_attn_impl
    cfg = ModelConfig(**TINY)
    assert resolve_attn_impl("auto", cfg, 1, 0, False) == "xla"   # CPU
    assert not caplog.records
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with caplog.at_level("WARNING"):
        assert resolve_attn_impl("auto", cfg, 1, 0, False) == "xla"
    assert "resolves to XLA on this TPU" in caplog.text
    assert "128-lane" in caplog.text
    with pytest.raises(NotImplementedError, match="128-lane"):
        resolve_attn_impl("pallas", cfg, 1, 0, False)
    assert resolve_attn_impl("auto", cfg, 1, 2, False) == "pallas"


def test_int8_kv_with_pallas_raises_on_tpu(monkeypatch):
    """The int8 kernels do not compile for the chip (Mosaic refuses the
    scale-row DMA): the config raises instead of running XLA silently."""
    # head_dim 64 lane-packs (x2), so auto takes the kernels on a TPU
    model = dict(TINY, head_dim=64, num_kv_heads=4)

    def runner(impl):
        return _runner(model, kv_cache_dtype="int8", attention_impl=impl)

    assert runner("pallas").kv_quant      # CPU (interpret mode): serves
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for impl in ("auto", "pallas"):
        with pytest.raises(NotImplementedError, match="int8"):
            runner(impl)
    # asking for XLA by name is the way to serve int8 on a TPU
    assert runner("xla").attn_impl == "xla"


def test_gdn_pallas_raises_on_unaligned_head_dims(monkeypatch):
    """impl='pallas' no longer falls through to the XLA scan."""
    import jax.numpy as jnp
    from gllm_tpu.ops.gdn import chunk_gated_delta_rule
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 64, 2, 32))
    g = jnp.zeros((1, 64, 2))
    with pytest.raises(NotImplementedError, match="128-lane"):
        chunk_gated_delta_rule(q, q, q, g, g, impl="pallas")


def test_tuning_device_tag_does_not_hide_an_unreadable_device(monkeypatch):
    from gllm_tpu.ops.pallas import tuning

    def boom():
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", boom)
    tuning.device_tag.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="no backend"):
            tuning.device_tag()
    finally:
        tuning.device_tag.cache_clear()


# ---- bench.py --------------------------------------------------------------

def _supervise(monkeypatch, capsys, rc, stdout=""):
    calls = []

    def fake_run(cmd, **kw):
        calls.append(cmd)
        return subprocess.CompletedProcess(cmd, rc, stdout=stdout)

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    code = bench.supervise(types.SimpleNamespace(tiny=False), [])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return code, json.loads(line), calls


def test_bench_supervisor_exits_nonzero_without_a_number(monkeypatch,
                                                         capsys):
    code, out, calls = _supervise(monkeypatch, capsys, rc=1,
                                  stdout="[bench phase] engine_build\n")
    assert code != 0
    assert out["failed"] is True and out["value"] == 0.0
    assert out["phase"] == "engine_build"
    # every rung was tried (twice: one retry each), none measured
    assert len(calls) == 2 * len(bench.PROFILES)


def test_bench_supervisor_stops_when_there_is_no_tpu(monkeypatch, capsys):
    code, out, calls = _supervise(monkeypatch, capsys, rc=bench.NO_TPU_RC)
    assert code != 0 and out["failed"] is True
    assert len(calls) == 1, "no rung can measure without a TPU"


def test_bench_supervisor_still_returns_zero_with_a_number(monkeypatch,
                                                           capsys):
    result = json.dumps({"metric": bench.METRIC, "value": 123.0,
                         "unit": "tok/s"})
    code, out, _ = _supervise(monkeypatch, capsys, rc=0, stdout=result)
    assert code == 0 and out["value"] == 123.0


def test_bench_refuses_to_measure_off_the_tpu():
    """Without --tiny the measurement fails on a CPU backend; it never
    carries on there."""
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--inner"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert out.returncode == bench.NO_TPU_RC
    assert "measures on a TPU" in out.stderr
    assert "RESULT" not in out.stdout


def test_chip_smoke_fails_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "device" not in last
    assert '"platform": "tpu"' not in out.stdout
