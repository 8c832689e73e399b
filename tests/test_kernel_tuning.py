"""Pallas block-size tuning table (VERDICT r03 missing #4).

The attention dispatch reads block sizes from
``gllm_tpu/ops/pallas/tuning.py`` (analogue of the reference's
``fused_moe_triton/configs/`` autotune tables); the table is layered:
BUILTIN defaults < committed tables.json < GLLM_TPU_TUNE_TABLE override.
"""

import importlib.util
import json
import os

from gllm_tpu.ops.pallas import tuning


def _load_kernel_tune():
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "benchmarks", "kernel_tune.py")
    spec = importlib.util.spec_from_file_location("_kernel_tune", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reset_caches():
    tuning._table.cache_clear()
    tuning.device_tag.cache_clear()


def test_builtin_defaults():
    _reset_caches()
    assert tuning.get("ragged") == {"q_block": 128, "kv_block": 256}
    assert tuning.get("decode") == {"kv_block": 256}


def test_env_override_layering(tmp_path, monkeypatch):
    _reset_caches()
    # device-specific beats default; partial override keeps other params
    table = {"default": {"ragged": {"kv_block": 512}},
             tuning.device_tag(): {"decode": {"kv_block": 128}}}
    p = tmp_path / "tune.json"
    p.write_text(json.dumps(table))
    monkeypatch.setenv("GLLM_TPU_TUNE_TABLE", str(p))
    tuning._table.cache_clear()
    assert tuning.get("ragged") == {"q_block": 128, "kv_block": 512}
    assert tuning.get("decode") == {"kv_block": 128}
    monkeypatch.delenv("GLLM_TPU_TUNE_TABLE")
    tuning._table.cache_clear()


def test_malformed_table_ignored(tmp_path, monkeypatch):
    _reset_caches()
    p = tmp_path / "bad.json"
    p.write_text("{not json")
    monkeypatch.setenv("GLLM_TPU_TUNE_TABLE", str(p))
    tuning._table.cache_clear()
    assert tuning.get("ragged") == {"q_block": 128, "kv_block": 256}
    monkeypatch.delenv("GLLM_TPU_TUNE_TABLE")
    tuning._table.cache_clear()


def test_device_tag_cpu():
    _reset_caches()
    # on the CPU test backend this resolves to some non-empty tag and the
    # lookup falls back to default cleanly
    assert tuning.device_tag()
    assert tuning.get("nonexistent_kernel") == {}


def test_committed_table_entries_carry_provenance():
    """Every committed tables.json entry must say which sweep artifact
    produced it (guards against a repeat of the round-5 silent
    tuning-table regression, where a hand-edited value shipped with no
    trail back to a measurement)."""
    with open(tuning._TABLES_PATH) as f:
        table = json.load(f)
    assert table, "committed tables.json is empty"
    for dev, kernels in table.items():
        for kern, params in kernels.items():
            comment = params.get("comment")
            assert isinstance(comment, str) and comment.strip(), (
                f"tables.json entry {dev}/{kern} lacks a provenance "
                f"'comment' naming the sweep artifact behind it")
            # provenance must point somewhere checkable, not just vibes
            assert any(tok in comment for tok in ("docs/", "r0", "sweep",
                                                  "kernel_tune")), (
                f"{dev}/{kern} comment names no artifact: {comment!r}")
            # ... and a named docs/ artifact must actually be committed
            repo = os.path.join(os.path.dirname(__file__), os.pardir)
            for tok in comment.split():
                if tok.startswith("docs/"):
                    path = tok.rstrip(".,;:)")
                    assert os.path.exists(os.path.join(repo, path)), (
                        f"{dev}/{kern} cites missing artifact {path!r}")
            # a kept-from-a-manual-A/B placeholder is not provenance —
            # the r05/r06 decode regression class (sweep broken, value
            # hand-carried with no measured artifact behind it)
            assert "manual" not in comment.lower(), (
                f"{dev}/{kern} provenance is a manual A/B placeholder: "
                f"{comment!r}")
            # and the entry must carry actual kernel params besides it
            assert any(k != "comment" for k in params), (dev, kern)


def test_get_strips_provenance_from_kwargs(monkeypatch, tmp_path):
    """tuning.get() must never leak the provenance annotation into
    kernel kwargs — on any layer, device-specific or default."""
    _reset_caches()
    table = {"default": {"ragged": {"kv_block": 512,
                                    "comment": "sweep artifact X"}},
             tuning.device_tag(): {"ragged": {"q_block": 64,
                                              "comment": "sweep Y"}}}
    p = tmp_path / "tune.json"
    p.write_text(json.dumps(table))
    monkeypatch.setenv("GLLM_TPU_TUNE_TABLE", str(p))
    tuning._table.cache_clear()
    got = tuning.get("ragged")
    assert "comment" not in got
    assert got == {"q_block": 64, "kv_block": 512}
    monkeypatch.delenv("GLLM_TPU_TUNE_TABLE")
    tuning._table.cache_clear()
    # the COMMITTED table must also come out comment-free
    for kern in ("ragged", "decode"):
        assert "comment" not in tuning.get(kern)


# ---------------------------------------------------------------------------
# sweep-body closure hygiene (no buffer rides a jaxpr as a constant)
# ---------------------------------------------------------------------------

_CONST_CAP_BYTES = 128 * 1024


def _jaxpr_consts(fn, *args):
    """Every constant the traced computation closes over, including
    constants of nested sub-jaxprs (jit bodies land inside a pjit eqn's
    ClosedJaxpr param, not the outer jaxpr's consts)."""
    import jax
    from jax.extend.core import ClosedJaxpr
    closed = jax.make_jaxpr(fn)(*args)
    consts = list(closed.consts)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for p in eqn.params.values():
                stack = [p]
                while stack:
                    x = stack.pop()
                    if isinstance(x, ClosedJaxpr):
                        consts.extend(x.consts)
                        walk(x.jaxpr)
                    elif isinstance(x, (list, tuple)):
                        stack.extend(x)

    walk(closed.jaxpr)
    return consts


def _big_consts(fn, *args):
    import numpy as np
    out = []
    for c in _jaxpr_consts(fn, *args):
        arr = np.asarray(c)
        if arr.nbytes > _CONST_CAP_BYTES:
            out.append((arr.shape, arr.dtype, arr.nbytes))
    return out


def test_const_detector_flags_closure_capture():
    """Self-check: a body that DOES capture a buffer must be flagged,
    so a jax upgrade that moves constants somewhere the walker misses
    fails loudly instead of hollowing out the guard below."""
    import jax
    import jax.numpy as jnp
    big = jnp.ones((512, 512), jnp.float32)          # 1 MiB

    @jax.jit
    def bad(q):
        return q @ big

    assert _big_consts(bad, jnp.ones((4, 512), jnp.float32))


def test_sweep_bodies_close_over_no_buffers():
    """The compiled sweep bodies must take the KV caches as ARGUMENTS,
    never closure constants: a captured GB-scale cache is baked into
    the compile request and the executable. Traced on a shrunken
    workload — capture is a structural property, not a size one."""
    kt = _load_kernel_tune()
    run_r, args_r = kt.build_ragged(64, 64, T=128, S=4, ctx=256)
    run_d, args_d, _ = kt.build_decode(64, gsz=2, S=8, ctx=256)
    for name, run, args in (("ragged", run_r, args_r),
                            ("decode", run_d, args_d)):
        # the caches must be in the argument list (the decode body also
        # takes its lengths and page table there)...
        assert len(args) == (5 if name == "decode" else 3), name
        # ...and nothing buffer-sized may ride the jaxpr as a constant
        big = _big_consts(run, *args)
        assert not big, (
            f"{name} sweep body closes over buffer-sized constants "
            f"{big}; pass them as arguments")
