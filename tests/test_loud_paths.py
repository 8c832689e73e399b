"""No fallback hides the device: the compile-cache rule, and the paths
that must fail loudly on a TPU they cannot read (ISSUE 21).

Code that asks ``jax.default_backend()`` is steered onto its TPU branch in
the test (monkeypatch), never through a program option.
"""

import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

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
    for root in ("gllm_tpu", "benchmarks", "chip_smoke.py"):
        path = REPO / root
        files = [path] if path.is_file() else sorted(path.rglob("*.py"))
        for f in files:
            text = f.read_text()
            hits += [(str(f.relative_to(REPO)), knob)
                     for knob in knobs if knob in text]
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


def test_gdn_and_attention_choose_their_kernels_apart(monkeypatch, caplog):
    """GDN head dims of 96 / 192 no longer send the full-attention layers
    (heads of 128) to XLA on a TPU, and the start says which path each
    kind of layer took."""
    import logging
    from gllm_tpu.models.config import from_hf_config
    from gllm_tpu.ops.gdn import gdn_impl_for
    from gllm_tpu.runner.runner import pick_kv_pack, resolve_attn_impl
    cfg = from_hf_config(dict(
        architectures=["OlmoHybridForCausalLM"], vocab_size=512,
        hidden_size=3840, num_hidden_layers=4, num_attention_heads=30,
        num_key_value_heads=30, intermediate_size=128,
        layer_types=["linear_attention"] * 3 + ["full_attention"],
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with caplog.at_level(logging.INFO, logger="gllm_tpu.runner.runner"):
        impl = resolve_attn_impl("auto", cfg, 1, pick_kv_pack(cfg, False),
                                 False)
    assert impl == "pallas"
    assert "full attention -> pallas" in caplog.text
    assert "GDN recurrent step and chunk scan -> pallas" in caplog.text
    assert gdn_impl_for("pallas", False) == "pallas"
    # the slot pool is sharded under tp, the kernel is not partitioned
    assert gdn_impl_for("pallas", True) == "xla"
    assert gdn_impl_for("xla", False) == "xla"


def test_gdn_pallas_never_falls_through_to_the_xla_scan(monkeypatch):
    """impl='pallas' runs the Pallas kernels or raises: a kernel that
    fails is not replaced by the XLA scan or the XLA recurrent step behind
    the caller's back, at head dims of 96 / 192 (no alignment is asked of
    them any more) as at any other."""
    import jax.numpy as jnp
    from gllm_tpu.models import hybrid
    from gllm_tpu.models.config import from_hf_config
    from gllm_tpu.ops.pallas import gdn_recurrent, gdn_scan

    class Ran(Exception):
        pass

    def boom(*a, **k):
        raise Ran()

    cfg = from_hf_config(dict(
        architectures=["OlmoHybridForCausalLM"], vocab_size=64,
        hidden_size=32, num_hidden_layers=4, num_attention_heads=2,
        num_key_value_heads=2, intermediate_size=32,
        layer_types=["linear_attention"] * 3 + ["full_attention"],
        linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=96, linear_value_head_dim=192))
    T, S, P = 16, 2, 3
    conv_dim = 2 * (96 + 96 + 192)
    mixed = jnp.zeros((T, conv_dim))
    g = beta = jnp.zeros((T, 2))
    conv_state = jnp.zeros((P, 3, conv_dim))
    rec_state = jnp.zeros((P, 1, 96, 384))     # the two heads abreast
    assert cfg.ssm_slot_shapes[1] == rec_state.shape[1:]
    conv_w = jnp.zeros((conv_dim, 4))
    cu = jnp.asarray([0, 1, 16], jnp.int32)
    slots = jnp.asarray([1, 2], jnp.int32)
    monkeypatch.setattr(gdn_scan, "gdn_chunk_scan", boom)
    with pytest.raises(Ran):
        hybrid._gdn_chunk_rows(mixed, g, beta, cu, slots, 0, conv_state,
                               rec_state, conv_w, cfg, "pallas")
    hybrid._gdn_chunk_rows(mixed, g, beta, cu, slots, 0, conv_state,
                           rec_state, conv_w, cfg, "xla")
    monkeypatch.setattr(gdn_recurrent, "gdn_recurrent_step", boom)
    with pytest.raises(Ran):
        hybrid._gdn_recurrent_rows(mixed[:S], g[:S], beta[:S], slots,
                                   conv_state, rec_state, conv_w, cfg,
                                   "pallas")
    with pytest.raises(ValueError, match="impl"):
        hybrid._gdn_chunk_rows(mixed, g, beta, cu, slots, 0, conv_state,
                               rec_state, conv_w, cfg, "mosaic")


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


# ---- chip_smoke.py ---------------------------------------------------------

def test_chip_smoke_fails_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "device" not in last
    assert '"platform": "tpu"' not in out.stdout
