"""Compile guards: the serving programs at the chip smoke model's widths,
compiled by the TPU compiler for a *described* v5e (no chip attached).

Interpret mode cannot see what Mosaic refuses (lane tiling, scoped VMEM)
or what a step costs to compile; these tests can, at no chip time. A
compile that passes is not a chip run — nothing executes here.

Code that asks ``jax.default_backend()`` would take its CPU branch during
such a compile, so the tests steer it (``on_tpu`` fixture) instead of the
program growing an option. Tier-1 keeps the cheap cases (decode kernel,
one decode step, one prefill step, the dense cell's decode and prefill
step read for what they do to the stacked weights); the long compiles
are ``slow`` and run as the step before any chip call:

    JAX_PLATFORMS=cpu python -m pytest tests/test_tpu_compile.py -m slow -q
"""

import dataclasses
import os
import re
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from gllm_tpu.config import (CacheConfig, EngineConfig,  # noqa: E402
                             ParallelConfig, SchedulerConfig)
from gllm_tpu.models.config import ModelConfig  # noqa: E402


def _topology():
    try:
        from jax.experimental import topologies
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(scope="module")
def topo():
    return _topology()


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer backend-asking code onto its TPU branch, keep the tuning
    table on the v5e entries, and keep described-chip executables (which
    cannot be read back without a chip) out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    from gllm_tpu.ops.pallas import tuning
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tuning, "device_tag", lambda: "tpu_v5_lite")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# The chip smoke's one-chip model (chip_smoke.py MODEL_1CHIP): the
# Llama-3.2-1B widths at full depth.
def smoke_model_cfg() -> ModelConfig:
    return ModelConfig(
        architecture="LlamaForCausalLM", vocab_size=128256,
        hidden_size=2048, num_layers=16, num_heads=32, num_kv_heads=8,
        head_dim=64, intermediate_size=8192, max_position=4096,
        rope_theta=500000.0, tie_word_embeddings=True)


# The four-chip smoke model (chip_smoke.py --chips 4): Qwen3-8B widths.
def qwen3_8b_cfg() -> ModelConfig:
    return ModelConfig(
        architecture="Qwen3ForCausalLM", vocab_size=151936,
        hidden_size=4096, num_layers=36, num_heads=32, num_kv_heads=8,
        head_dim=128, intermediate_size=12288, max_position=4096,
        rope_theta=1000000.0, qk_norm=True, tie_word_embeddings=False)


def _perfbench_hf(name: str) -> dict:
    """perfbench/configs/<name>.json: the config.json the cell serves."""
    import json
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench", "configs", name + ".json")
    with open(path) as f:
        return json.load(f)


# The benchmark's dense configuration: Qwen3-4B, every width and all 36
# layers as published.
def qwen3_4b_cfg() -> ModelConfig:
    from gllm_tpu.models.config import from_hf_config
    return from_hf_config(_perfbench_hf("qwen3-4b"))


def _structs(tree, sharding_of):
    """ShapeDtypeStructs of ``tree``; ``sharding_of`` is one sharding for
    every leaf, or a matching tree of shardings."""
    def struct(x, sh):
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sh)
    if isinstance(sharding_of, jax.sharding.Sharding):
        return jax.tree.map(lambda x: struct(x, sharding_of), tree)
    return jax.tree.map(struct, tree, sharding_of)


class Compiled(Exception):
    """Raised by the capture wrapper in place of running the program."""

    def __init__(self, compiled, seconds):
        super().__init__(f"compiled in {seconds:.1f}s")
        self.compiled, self.seconds = compiled, seconds


def capture_compile(runner, attr: str, default, params_sh=None, kv_sh=None):
    """Swap ``runner.<attr>`` (a jitted step program) for a wrapper that
    lowers and compiles it for the described chip(s) with the very
    arguments the dispatch path built, then raises :class:`Compiled`.
    Arguments 0 and 1 are (params, kv): they take ``params_sh`` / ``kv_sh``
    (sharding trees) when given; everything else takes ``default``."""
    fn = getattr(runner, attr)

    def wrapper(params, kv, *args, **static):
        t0 = time.monotonic()
        compiled = fn.lower(
            _structs(params, params_sh or default),
            _structs(kv, kv_sh or default),
            *_structs(args, default), **static).compile()
        raise Compiled(compiled, time.monotonic() - t0)

    setattr(runner, attr, wrapper)


def make_runner(model_cfg, topo, *, monkeypatch, num_pages=2048,
                attention_impl="pallas", max_model_len=4096, tp=1,
                **engine_kw):
    """A ModelRunner at real widths whose params and KV pool are shapes
    only (nothing is materialized on a described device, which cannot
    hold an array) and whose step programs compile for device 0 of the
    described topology — or, with ``tp``, for a mesh over its devices."""
    from jax.sharding import NamedSharding, PartitionSpec
    from gllm_tpu.models import get_model_def
    from gllm_tpu.parallel import shardings
    from gllm_tpu.parallel.mesh import make_mesh
    from gllm_tpu.runner import runner as runner_mod
    config = EngineConfig(
        load_format="dummy", dtype="bfloat16", max_model_len=max_model_len,
        attention_impl=attention_impl, parallel=ParallelConfig(tp=tp),
        scheduler=SchedulerConfig(), cache=CacheConfig(
            page_size=16, num_pages=num_pages,
            kv_cache_dtype=engine_kw.pop("kv_cache_dtype", "auto")),
        **engine_kw)
    config.validate()
    model_def = get_model_def(model_cfg)
    params = jax.eval_shape(lambda: model_def.init_params(
        model_cfg, seed=0, dtype=jnp.bfloat16))
    mesh = None
    default = jax.sharding.SingleDeviceSharding(topo.devices[0])
    params_sh = kv_sh = None
    if tp > 1:
        mesh = make_mesh(tp=tp, devices=topo.devices)
        named = lambda specs: jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        default = NamedSharding(mesh, PartitionSpec())
        params_sh = named(model_def.param_specs(model_cfg, tp))
        kv_sh = named(model_def.kv_specs(model_cfg, tp))
        monkeypatch.setattr(shardings, "shard_params",
                            lambda params, specs, mesh: params)
    monkeypatch.setattr(runner_mod, "build_in_place",
                        lambda make, mesh, specs, device=None:
                        jax.eval_shape(make))
    runner = runner_mod.ModelRunner(config, model_cfg, params=params,
                                    mesh=mesh)
    for attr in ("_step_fn", "_multi_step_fn", "_spec_multi_fn"):
        if getattr(runner, attr, None) is not None:
            capture_compile(runner, attr, default, params_sh, kv_sh)
    return runner


def decode_batch(runner, nseq: int, npages: int, temperature=0.0,
                 logprobs=None):
    from gllm_tpu.sampling_params import SamplingParams
    from gllm_tpu.scheduler import ScheduledBatch, ScheduledSeq
    from gllm_tpu.sequence import Sequence
    page = runner.config.cache.page_size
    ctx = npages * page - 1
    items = []
    for i in range(nseq):
        seq = Sequence(i, [1] * (ctx + 1), SamplingParams(
            temperature=temperature, top_p=0.95 if temperature else 1.0,
            max_tokens=64, logprobs=logprobs))
        seq.page_table = [1 + j for j in range(npages)]
        seq.num_computed_tokens = ctx
        items.append(ScheduledSeq(seq, 1, ctx))
    return ScheduledBatch(items)


def prefill_batch(runner, chunk: int, ndecode: int = 0, npages: int = 16,
                  prompt_logprobs=None, table_pages: int = 0):
    """One ``chunk``-token prefill row, led by ``ndecode`` decode rows (the
    engine packs decode rows first). ``table_pages`` widens the row's
    page table (a later chunk of a long prompt)."""
    from gllm_tpu.sampling_params import SamplingParams
    from gllm_tpu.scheduler import ScheduledSeq
    from gllm_tpu.sequence import Sequence
    from gllm_tpu.utils import cdiv
    page = runner.config.cache.page_size
    batch = decode_batch(runner, ndecode, npages) if ndecode else None
    seq = Sequence(ndecode, [1] * chunk, SamplingParams(
        temperature=0.0, max_tokens=4, prompt_logprobs=prompt_logprobs,
        logprobs=prompt_logprobs))
    seq.page_table = [1 + j for j in range(max(cdiv(chunk, page),
                                               table_pages))]
    seq.num_computed_tokens = 0
    items = (batch.items if batch else []) + [ScheduledSeq(seq, chunk, 0)]
    from gllm_tpu.scheduler import ScheduledBatch
    return ScheduledBatch(items)


def compile_of(call, *args, **kw) -> Compiled:
    with pytest.raises(Compiled) as ei:
        call(*args, **kw)
    return ei.value


GiB = 1 << 30


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def attention_calls(compiled) -> list:
    """The attention kernels' custom calls in the optimized HLO, by the
    names the trace's ``XLA Ops`` line shows them under (an operation's
    name there is its whole HLO line)."""
    return sorted(set(re.findall(
        r"^\s*(?:ROOT )?%(\w*attention\w*?)(?:\.\d+)? = \S+ custom-call\(",
        compiled.as_text(), re.M)))


# What the benchmark's readers hold a mixed step to (perfbench
# ``trace_patterns``: ``^%ragged_paged_attention`` is a mixed step's
# attention, ``^%paged_decode_attention`` marks a decode-only step): the
# chunk's call and the riding rows' call, the decode kernel under a name
# of the ragged kernel's family (ops/attention.DECODE_ROWS_NAME).
MIXED_STEP_CALLS = ["ragged_paged_attention",
                    "ragged_paged_attention_decode_rows"]


# ---- kernels alone ---------------------------------------------------------

def _kernel_args(topo, *, S, T, Hq=32, Hkv=8, D=64, pack=2, P=8192,
                 pages=256, page=16, kv_dtype=jnp.bfloat16):
    """Shapes of one layer's attention call on the packed smoke cache."""
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    q = sds((T, Hq, D * pack), jnp.bfloat16)
    kc = sds((P, page, Hkv // pack, D * pack), kv_dtype)
    return q, kc, kc, sds((S + 1,), jnp.int32), sds((S,), jnp.int32), \
        sds((S, pages), jnp.int32)


@pytest.mark.parametrize("geometry", [
    # the smoke model: head_dim 64 packed in pairs, 4 cache heads of 128
    dict(S=256, T=256),
    # the benchmark's decode-bound cells: 32 rows, head_dim 128 unpacked
    dict(S=32, T=32, Hkv=8, D=128, pack=1, P=2800),       # qwen3-4b
    dict(S=32, T=32, Hkv=32, D=128, pack=1, P=4320),      # olmo-hybrid-7b
    # a.x-k1: 64 query heads over ONE KV head, the latent row of 640 lanes
    # whose first 512 are the values (no V cache), 5 layers of 35072 pages
    dict(S=32, T=32, Hq=64, Hkv=1, D=640, pack=1, P=5 * 35072, pages=1088,
         v_dim=512),
    # falcon-h1-34b-instruct: 64 rows, five query heads a KV head, the
    # pages of 6 layers
    dict(S=64, T=64, Hq=20, Hkv=4, D=128, pack=1, P=6 * 8320),
    # lfm2-24b-a2b: 128 rows, heads of 64 packed in pairs, the pages of
    # the 10 attention layers
    dict(S=128, T=128, P=10 * 16640),
], ids=["smoke_packed", "dense_cell_hkv8", "hybrid_cell_hkv32",
        "mla_cell_hkv1", "par_cell_20x4", "sconv_cell_packed"])
def test_decode_kernel_compiles_for_v5e(topo, on_tpu, geometry):
    """Mosaic accepts the decode kernel's block update at every geometry
    served on the chip, with the block and group the table gives (under
    one KV head: its ``decode_mqa`` entry), under the step programs' own
    compiler options; and the view of the pool the kernel reads (heads
    folded into a page's rows) costs no copy of the cache."""
    from gllm_tpu.ops.pallas.decode_attention import paged_decode_attention
    from gllm_tpu.ops.pallas.tuning import decode_blocks
    from gllm_tpu.utils import tpu_compiler_options
    geometry = dict(geometry)
    v_dim = geometry.pop("v_dim", None)
    cfg = decode_blocks(geometry.get("Hkv", 8),
                        num_q_heads=geometry.get("Hq", 0))
    assert "group" in cfg, "expected the tpu_v5_lite table entry"
    if geometry.get("Hq") == 20:
        assert cfg["kv_block"] == 512, "expected the decode@20x4 entry"
    if v_dim:
        assert cfg["kv_block"] == 512, "expected the decode_mqa entry"
    q, kc, vc, _cu, kv_lens, pt = _kernel_args(topo, **geometry)
    fn = jax.jit(lambda q, k, v, kl, pt: paged_decode_attention(
        q, k, None if v_dim else v, kl, pt, scale=0.125, v_dim=v_dim,
        kv_block=cfg["kv_block"], group_size=int(cfg["group"])),
        compiler_options=tpu_compiler_options())
    compiled = fn.lower(q, kc, vc, kv_lens, pt).compile()
    assert has_kernel(compiled)
    copies = [ln for ln in compiled.as_text().splitlines()
              if " copy(" in ln and f"[{kc.shape[0]}," in ln]
    assert not copies, copies


def _ragged(topo, *, S: int, T: int, **kw):
    from gllm_tpu.ops.pallas.ragged_attention import ragged_paged_attention
    from gllm_tpu.ops.pallas.tuning import get as tuned
    from gllm_tpu.utils import tpu_compiler_options
    cfg = tuned("ragged")
    q, kc, vc, cu, kv_lens, pt = _kernel_args(topo, S=S, T=T, **kw)
    fn = jax.jit(lambda q, k, v, cu, kl, pt: ragged_paged_attention(
        q, k, v, cu, kl, pt, scale=0.125, q_block=cfg["q_block"],
        kv_block=cfg["kv_block"]),
        compiler_options=tpu_compiler_options())
    return fn.lower(q, kc, vc, cu, kv_lens, pt).compile()


@pytest.mark.parametrize("Hq,Hkv,window", [
    (32, 8, None),        # qwen3-4b
    (32, 32, None),       # olmo-hybrid-7b's attention layers
    (32, 2, None),        # nemotron-3-nano-30b-a3b
    (128, 8, None),       # command-a-plus-05-2026, a full layer
    (128, 8, 4096),       # ... a windowed one
    (20, 4, None),        # falcon-h1-34b-instruct: five query heads a KV head
], ids=["32x8", "32x32", "32x2", "128x8", "128x8_window", "20x4"])
def test_ragged_kernel_under_several_kv_heads_compiles_for_v5e(
        topo, on_tpu, Hq, Hkv, window):
    """Mosaic takes the ragged body under several KV heads (a KV head at
    a time out of the lane-folded block, 16-bit operands as stored, p in
    two parts, a rolled loop over the heads) at the cells' geometries and
    the blocks the table names for each: a 512-slot mixed step under the
    step programs' own compiler options. The view of the pool the kernel
    reads (heads folded into a page's lanes) costs no copy of the cache."""
    from gllm_tpu.ops.pallas.ragged_attention import (
        block_form, effective_q_block, ragged_paged_attention)
    from gllm_tpu.ops.pallas.tuning import ragged_blocks
    from gllm_tpu.utils import tpu_compiler_options
    blocks = ragged_blocks(Hq, Hkv)
    q, kc, vc, cu, kv_lens, pt = _kernel_args(
        topo, S=64, T=512, Hq=Hq, Hkv=Hkv, D=128, pack=1, P=4320,
        pages=1088 if window else 256)
    assert block_form(q.dtype, kc.dtype) == ("bfloat16", 2)
    fn = jax.jit(lambda q, k, v, cu, kl, pt: ragged_paged_attention(
        q, k, v, cu, kl, pt, scale=128 ** -0.5, window=window, **blocks),
        compiler_options=tpu_compiler_options())
    t0 = time.monotonic()
    compiled = fn.lower(q, kc, vc, cu, kv_lens, pt).compile()
    bq = effective_q_block(blocks["q_block"], blocks["kv_block"], Hq, 512,
                           Hkv, 128)
    print(f"\n[compile] ragged kernel {Hq}x{Hkv}"
          f"{' window' if window else ''}, 512 slots, q block {bq} "
          f"(asked {blocks['q_block']}), kv block {blocks['kv_block']}: "
          f"{time.monotonic() - t0:.1f}s")
    assert attention_calls(compiled) == ["ragged_paged_attention"]
    copies = [ln for ln in compiled.as_text().splitlines()
              if " copy(" in ln and f"[{kc.shape[0]}," in ln]
    assert not copies, copies


@pytest.mark.slow
def test_ragged_kernel_compiles_for_v5e(topo, on_tpu):
    assert has_kernel(_ragged(topo, S=8, T=2048))


def test_ragged_kernel_compiles_for_v5e_at_the_packed_cells_pool(topo,
                                                                 on_tpu):
    """The ragged kernel over heads of 64 packed in pairs at
    lfm2-24b-a2b's rows and pool: 128 rows, a 512-token mixed step, the
    pages of the 10 attention layers (the decode kernel's case at this
    geometry is ``sconv_cell_packed`` above)."""
    compiled = _ragged(topo, S=128, T=512, P=10 * 16640)
    assert attention_calls(compiled) == ["ragged_paged_attention"]


@pytest.mark.slow
def test_ragged_kernel_needs_the_scoped_vmem_option(topo, on_tpu,
                                                    monkeypatch):
    """The 64 MiB scoped-VMEM compile option is load-bearing: without it
    Mosaic refuses the prefill kernel. If this starts passing, the option
    (utils.tpu_compiler_options) can go."""
    from gllm_tpu import utils
    monkeypatch.setattr(utils, "tpu_compiler_options", lambda: None)
    with pytest.raises(Exception, match="(?i)vmem|memory"):
        _ragged(topo, S=8, T=2048)


# ---- whole step programs ---------------------------------------------------

def test_decode_step_compiles_for_v5e(topo, on_tpu, monkeypatch):
    runner = make_runner(smoke_model_cfg(), topo, monkeypatch=monkeypatch)
    assert runner.attn_impl == "pallas" and runner.kv_pack == 2
    c = compile_of(runner.step_async, decode_batch(runner, 256, 256))
    assert has_kernel(c.compiled)


def test_prefill_step_compiles_for_v5e(topo, on_tpu, monkeypatch):
    """A short prompt's prefill (the 16-token bucket): the ragged kernel
    inside the whole step. The full 2048-token chunk compiles for half a
    minute and is among the slow cases below."""
    runner = make_runner(smoke_model_cfg(), topo, monkeypatch=monkeypatch)
    c = compile_of(runner.step_async, prefill_batch(runner, 16))
    assert has_kernel(c.compiled)
    assert attention_calls(c.compiled) == MIXED_STEP_CALLS


# the kinds of step `qwen3-4b.reason` runs, at its pool of 2800 pages
DENSE_CELL_STEPS = {
    "decode": lambda r: decode_batch(r, 32, 64),
    "prefill": lambda r: prefill_batch(r, 16),
    "mixed": lambda r: prefill_batch(r, 512, ndecode=31, npages=64),
}


def _computations(text: str):
    """(header, instruction lines, fused?) of each computation of an
    optimized HLO module. A fused computation is one some ``fusion``
    calls: its lines are steps inside one device operation. The lines of
    the others (entry, loop bodies) are the operations the device runs."""
    fused = set(re.findall(r"\bcalls=(%[\w.\-]+)", text))
    header, lines = None, []
    for ln in text.splitlines():
        if header is None:
            if ln.endswith("{") and ln.startswith(("%", "ENTRY ")):
                header, lines = ln, []
        elif ln == "}":
            name = header.removeprefix("ENTRY ").split(" ", 1)[0]
            yield header, lines, name in fused
            header = None
        else:
            lines.append(ln)


@pytest.mark.parametrize("kind", [
    "decode", "prefill", pytest.param("mixed", marks=pytest.mark.slow)])
def test_dense_cell_projections_read_the_stack_in_place(topo, on_tpu,
                                                        monkeypatch, kind):
    """The q, k and v dots of the dense decoder read the stacked
    [36, 2560, out] weights where they lie, as the MLP's do: the stack is
    an operand of the fusion that holds the dot, and no operation of its
    own, fusion or copy, has one layer of a stack as its result. Without
    dense._attention's barrier there are three such pairs, 31.5 MB a
    layer, in every kind of step (docs/stacked_layers.md, which also says
    how a new projection is checked here)."""
    runner = make_runner(qwen3_4b_cfg(), topo, num_pages=2800,
                         monkeypatch=monkeypatch, max_num_seqs=64)
    c = compile_of(runner.step_async, DENSE_CELL_STEPS[kind](runner))
    print(f"\n[compile] qwen3-4b {kind}: {c.seconds:.1f}s")
    assert has_kernel(c.compiled)
    comps = list(_computations(c.compiled.as_text()))
    for out, ndots in ((4096, 1), (1024, 2)):      # q; k and v
        one_layer = re.compile(
            rf"= bf16\[(1,)?2560,{out}\]\S* (fusion|copy)\(")
        moved = [ln.strip()[:140] for _, lines, fused in comps if not fused
                 for ln in lines if one_layer.search(ln)]
        assert not moved, moved
        dots = [h for h, lines, fused in comps
                if fused and f": bf16[36,2560,{out}]" in h
                and any(" convolution(" in ln for ln in lines)]
        assert len(dots) == ndots, dots


FAST = dict(overlap_scheduling=True, pipelined_loop=True,
            decode_slot_batching=True, ondevice_finish=True,
            decode_chain_len=16)


def _spec_chain(r):
    return [dataclasses.replace(decode_batch(r, 8, 8), spec_block=True,
                                active_until=[64] * 8)] * 4


# variant -> (engine options, dispatch the program with a runner)
SMOKE_PROGRAMS = {
    "prefill_chunk": ({}, lambda r: r.step_async(prefill_batch(r, 2048))),
    "sampled_decode": ({}, lambda r: r.step_async(
        decode_batch(r, 8, 8, temperature=0.7))),
    "logprobs_decode": ({}, lambda r: r.step_async(
        decode_batch(r, 8, 8, logprobs=1))),
    "prompt_logprobs_prefill": ({}, lambda r: r.step_async(
        prefill_batch(r, 2048, prompt_logprobs=1))),
    "mixed": ({}, lambda r: r.step_async(
        prefill_batch(r, 2048, ndecode=7))),
    "fused_block": (dict(overlap_scheduling=True, decode_chain_len=16,
                         ondevice_finish=True),
                    lambda r: r.step_multi([decode_batch(r, 8, 8)] * 16)),
    "fast_fused_block": (FAST, lambda r: r.step_multi(
        [decode_batch(r, 8, 8)] * 16)),
    "spec_fused_block": (dict(FAST, spec_decode="ngram", spec_fused=True),
                         lambda r: r.step_spec_multi(_spec_chain(r))),
    # the reference arm: XLA attention, --maxp 256, a 2100-token prompt
    "xla_reference_chunk": (dict(attention_impl="xla"),
                            lambda r: r.step_async(prefill_batch(
                                r, 256, prompt_logprobs=1,
                                table_pages=140))),
}


@pytest.mark.slow
@pytest.mark.parametrize("variant", SMOKE_PROGRAMS)
def test_smoke_programs_compile_for_v5e(topo, on_tpu, monkeypatch, variant):
    """Every other program chip_smoke.py dispatches on one chip; prints
    the compile seconds of each (set-up cost: ROADMAP, "Set-up")."""
    engine_kw, dispatch = SMOKE_PROGRAMS[variant]
    runner = make_runner(smoke_model_cfg(), topo, monkeypatch=monkeypatch,
                         **engine_kw)
    c = compile_of(dispatch, runner)
    assert has_kernel(c.compiled) == (runner.attn_impl == "pallas")
    mem = c.compiled.memory_analysis()
    # 2.5 GB of weights + this test's 1 GiB pool + what the step needs
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12 * GiB
    print(f"\n[compile] {variant}: {c.seconds:.1f}s, "
          f"{mem.temp_size_in_bytes / GiB:.2f} GiB temp")


@pytest.mark.slow
def test_xla_attention_on_tpu_still_copies_the_whole_pool(topo, on_tpu,
                                                          monkeypatch):
    """Why chip_smoke.py gives its XLA reference arms a small explicit
    pool: compiled for the chip, every step of the XLA attention path
    carries temporaries of twice the K+V pool (the Pallas path: none). A
    pool sized from the device cannot run it. When this FAILS the path
    has been repaired and the reference arms can take the device's pool."""
    pool = 2048 * 16 * 16 * 8 * 64 * 2 * 2          # K+V bytes, 2048 pages
    temps = {}
    for impl in ("xla", "pallas"):
        runner = make_runner(smoke_model_cfg(), topo, attention_impl=impl,
                             monkeypatch=monkeypatch)
        c = compile_of(runner.step_async, decode_batch(runner, 8, 8))
        temps[impl] = c.compiled.memory_analysis().temp_size_in_bytes
    assert temps["pallas"] < 0.1 * pool
    assert temps["xla"] >= 1.9 * pool, temps


@pytest.mark.slow
@pytest.mark.parametrize("kernel", ["decode", "ragged"])
def test_int8_kv_kernels_are_still_refused_by_mosaic(topo, on_tpu, kernel):
    """Why kv_cache_dtype=int8 raises on the TPU Pallas path
    (runner._check_kv_quant): Mosaic refuses the per-page scale-row DMA
    into the [slots, ppb, Hkv] VMEM scratch. When this test FAILS the
    layout has been repaired: drop the config error and guard the int8
    kernels here instead."""
    from gllm_tpu.ops.pallas.decode_attention import paged_decode_attention
    from gllm_tpu.ops.pallas.ragged_attention import ragged_paged_attention
    from gllm_tpu.utils import tpu_compiler_options
    q, kc, vc, cu, kv_lens, pt = _kernel_args(topo, S=8, T=8,
                                              kv_dtype=jnp.int8)
    sc = jax.ShapeDtypeStruct((kc.shape[0], kc.shape[2]), jnp.float32,
                              sharding=kc.sharding)
    if kernel == "decode":
        fn = jax.jit(lambda q, k, v, cu, kl, pt, ks, vs:
                     paged_decode_attention(q, k, v, kl, pt, scale=0.125,
                                            k_scale=ks, v_scale=vs))
    else:
        fn = jax.jit(lambda q, k, v, cu, kl, pt, ks, vs:
                     ragged_paged_attention(
                         q, k, v, cu, kl, pt, scale=0.125, k_scale=ks,
                         v_scale=vs),
                     compiler_options=tpu_compiler_options())
    with pytest.raises(Exception, match="aligned to tiling"):
        fn.lower(q, kc, vc, cu, kv_lens, pt, sc, sc).compile()


# ---- four chips: Qwen3-8B widths under tp=4 --------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_tp4_step_compiles_for_a_v5e_2x2_mesh(topo, on_tpu, monkeypatch,
                                              impl):
    """chip_smoke.py --chips 4, arms (a) and (b): one program across four
    chips. Each device holds a quarter of the weights and KV, and the
    Pallas arm keeps its kernel under the tp shard_map."""
    model = qwen3_8b_cfg()
    runner = make_runner(model, topo, tp=4, attention_impl=impl,
                         num_pages=4096, monkeypatch=monkeypatch)
    assert runner.attn_impl == impl
    for name, batch in (("decode", decode_batch(runner, 8, 8)),
                        ("prefill", prefill_batch(runner, 2048))):
        c = compile_of(runner.step_async, batch)
        assert has_kernel(c.compiled) == (impl == "pallas")
        mem = c.compiled.memory_analysis()
        per_device = mem.argument_size_in_bytes
        # a quarter of 16.4 GB of weights + a 9.7 GB (4096-page) pool
        assert 5.5 * GiB < per_device < 7 * GiB, per_device
        assert per_device + mem.temp_size_in_bytes < 14 * GiB
        text = c.compiled.as_text()
        assert "all-reduce" in text      # row-parallel o_proj / down_proj
        print(f"\n[compile] tp4 {impl} {name}: {c.seconds:.1f}s, "
              f"{per_device / GiB:.2f} GiB of arguments per device, "
              f"{mem.temp_size_in_bytes / GiB:.2f} GiB temp")


@pytest.mark.slow
def test_pp4_stage_sized_step_compiles_for_v5e(topo, on_tpu, monkeypatch):
    """chip_smoke.py --chips 4, arm (c): a pp=4 stage is a one-chip
    program over 9 of the 36 layers; the first and last stages add the
    embedding and the head (this compiles both ends in one program)."""
    model = dataclasses.replace(qwen3_8b_cfg(), num_layers=9)
    runner = make_runner(model, topo, num_pages=8192,
                         monkeypatch=monkeypatch)
    c = compile_of(runner.step_async, prefill_batch(runner, 2048))
    assert has_kernel(c.compiled)
    mem = c.compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14 * GiB


# ---- the hybrid (GDN + full attention) step at Olmo-Hybrid's widths --------

def olmo_hybrid_cfg(layers: int = 16) -> ModelConfig:
    """perfbench/configs/olmo-hybrid-7b.json: published widths (GDN heads
    of 96 / 192, attention heads of 128), ``layers`` of the 32 layers."""
    from gllm_tpu.models.config import from_hf_config
    hf = _perfbench_hf("olmo-hybrid-7b")
    return from_hf_config(dict(hf, num_hidden_layers=layers,
                               layer_types=hf["layer_types"][:layers]))


def _with_slots(batch):
    for i, it in enumerate(batch.items):
        it.seq.ssm_slot = 1 + i
    return batch


def test_hybrid_steps_compile_for_v5e_with_attention_on_pallas(
        topo, on_tpu, monkeypatch):
    """The cell's two kinds of step at 96 / 192: a decode step of 32 rows
    and a mixed step of 31 decoding rows and one prompt of 512 tokens (the
    1024-token bucket). The full-attention layers run the Pallas kernels
    whatever the GDN head dims are; the mixed step's temporaries are sized
    by the 544 tokens of the step, not by 32 rows x 1024 slots: the old
    layout's float32 v alone was 32 x 1024 x 30 x 192 x 4 B = 0.75 GB a
    layer. Counted from shapes by the compiler; nothing runs."""
    runner = make_runner(olmo_hybrid_cfg(), topo, num_pages=4096,
                         monkeypatch=monkeypatch, max_num_seqs=32)
    assert runner.attn_impl == "pallas"
    slots = 33
    # the pools as the TPU stores them are what the runner sizes them as
    kv_args = sum(np.prod(x.shape) * x.dtype.itemsize
                  for x in (runner.kv.k, runner.kv.v))
    for name, batch, bound, calls in (
            ("decode", decode_batch(runner, 32, 64), 0.5 * GiB,
             ["paged_decode_attention"]),
            ("mixed", prefill_batch(runner, 512, ndecode=31, npages=64),
             1.25 * GiB, MIXED_STEP_CALLS)):
        c = compile_of(runner.step_async, _with_slots(batch))
        assert attention_calls(c.compiled) == calls
        mem = c.compiled.memory_analysis()
        print(f"\n[compile] olmo-hybrid {name}: {c.seconds:.1f}s, "
              f"{mem.argument_size_in_bytes / GiB:.2f} GiB of arguments, "
              f"{mem.temp_size_in_bytes / GiB:.3f} GiB temp")
        assert mem.temp_size_in_bytes < bound, mem.temp_size_in_bytes
        weights = 2 * 4100628480 + 2 * (16 * 2 + 4 * 2 + 12) * 3840
        state = mem.argument_size_in_bytes - weights - kv_args
        # 33 slots: what ModelRunner._ssm_pool_bytes says, within 1 %
        assert abs(state / runner._ssm_pool_bytes() - 1) < 0.01, (
            state, runner._ssm_pool_bytes(), slots)
        # and that is the states' ELEMENTS, within 1 %, beside the window
        # pool (whose slot axis lies in tiles of 8: 40): the states lie
        # two heads abreast (96 x 384) and the TPU pads none of their
        # lanes (a head alone, 96 x 192 in 96 x 256: 1.23 GB in all)
        elements = 12 * 4 * (slots * 30 * 96 * 192 + 40 * 3 * 11520)
        assert abs(state / elements - 1) < 0.01, (state, elements)


def test_gdn_recurrent_kernel_compiles_for_v5e_at_96_by_192(topo, on_tpu):
    """The decode kernel of the GDN layers alone, at the cell's shapes: 32
    rows of 30 heads of 96 x 192, two abreast, in a pool of 12 x 33 slots
    of 15 x 96 x 384. Mosaic takes the row-to-column conversion, the
    lane mask that spreads a head's column over its 192 of a group's 384
    lanes, and the stores that lay two heads' rows side by side; the
    pool among the arguments is its elements."""
    from gllm_tpu.ops.pallas.gdn_recurrent import gdn_recurrent_step
    from gllm_tpu.utils import tpu_compiler_options
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one)
    S, H, Dk, Dv, P = 32, 30, 96, 192, 396
    fn = jax.jit(gdn_recurrent_step.__wrapped__, donate_argnums=(5,),
                 compiler_options=tpu_compiler_options())
    compiled = fn.lower(sds((S, H, Dk)), sds((S, H, Dk)), sds((S, H, Dv)),
                        sds((S, H)), sds((S, H)),
                        sds((P, H // 2, Dk, 2 * Dv)),
                        sds((S,), jnp.int32)).compile()
    assert has_kernel(compiled)
    assert "gdn_recurrent_step" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes \
        == P * H * Dk * Dv * 4


def test_gdn_chunk_scan_kernel_compiles_for_v5e_at_96_by_192(topo, on_tpu):
    """The chunked rule's inter-chunk scan alone, at the cell's shapes:
    the 1024-token bucket's 32 chunks of 64 tokens, 30 heads of 96 x 192,
    two abreast, in place in a pool of 12 x 33 slots of 15 x 96 x 384.
    Mosaic takes the unaligned head dims in the operands' blocks and in
    the matrix products, and a head's 192 of the state's 384 lanes; the
    benchmark's ``gdn_chunk`` pattern still finds the call by its [30,
    chunks, 64, .] operands."""
    from gllm_tpu.ops.pallas.gdn_scan import gdn_chunk_scan
    from gllm_tpu.utils import tpu_compiler_options
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one)
    H, N, C, Dk, Dv, P = 30, 32, 64, 96, 192, 396
    fn = jax.jit(gdn_chunk_scan.__wrapped__, donate_argnums=(6,),
                 compiler_options=tpu_compiler_options())
    compiled = fn.lower(
        sds((H, N, C, Dk)), sds((H, N, Dk, C)), sds((H, N, C, Dv)),
        sds((H, N, C, Dk)), sds((H, N, C, C)), sds((H, N, 1, Dv)),
        sds((P, H // 2, Dk, 2 * Dv)), sds((N,), jnp.int32),
        sds((N,), jnp.int32)).compile()
    assert has_kernel(compiled)
    call, = [ln.strip().removeprefix("ROOT ")
             for ln in compiled.as_text().splitlines()
             if re.match(r"\s*(ROOT )?%gdn_chunk_scan[.\d]* = ", ln)]
    kernels = _perfbench_hf("olmo-hybrid-7b")["trace_patterns"]["kernels"]
    assert re.search(kernels["gdn_chunk"], call)
    assert re.search(kernels["gdn_chunk_scan"], call)


# ---- the latent-attention step at dots3-note-prev's widths ------------------

def dots3_cfg() -> ModelConfig:
    """perfbench/configs/dots3-note-prev.json: published widths (full
    layers of 128 heads over rows of 640 as stored, windowed layers of 64
    heads over rows of 1152, 32 held experts of 5120 x 1536), 5 layers."""
    from gllm_tpu.models.config import from_hf_config
    return from_hf_config(_perfbench_hf("dots3-note-prev"))


def _dots3_runner(topo, monkeypatch):
    return make_runner(dots3_cfg(), topo, num_pages=37120,
                       monkeypatch=monkeypatch, max_num_seqs=64,
                       max_model_len=9472, attention_impl="auto")


def _dots3_pools_are_what_the_startup_line_says(runner, pools: int):
    """The three pools as the TPU stores them within 1 % of
    ModelRunner.latent_pool_bytes."""
    said = sum(runner.latent_pool_bytes())
    assert abs(pools / said - 1) < 0.01, (pools, said)
    assert runner.latent_pool_bytes() == (
        2 * 37120 * 16 * 640 * 2, 2 * 37120 * 16 * 128 * 2,
        3 * 65 * 544 * 1152 * 2)


def _weight_bytes(runner) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(runner.params))


def test_dots3_pools_as_the_tpu_stores_them(topo, on_tpu, monkeypatch):
    """The guard on the start-up line's three pools, from a program that
    takes the caches and does nothing with them (a second of compiling:
    its arguments are the pools in the TPU's own layouts)."""
    runner = _dots3_runner(topo, monkeypatch)
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    touch = jax.jit(lambda kv: jax.tree.map(lambda a: a.ravel()[0], kv))
    mem = touch.lower(_structs(runner.kv, one)).compile().memory_analysis()
    _dots3_pools_are_what_the_startup_line_says(
        runner, mem.argument_size_in_bytes)


def _dsa_rows_calls(text: str) -> list:
    """The HLO lines of the masked decode calls (a selected-attention
    layer's decoding rows, ``models/deepseek.DSA_ROWS_NAME``)."""
    from gllm_tpu.models.deepseek import DSA_ROWS_NAME
    return [ln.strip() for ln in text.splitlines()
            if re.match(rf"\s*(ROOT )?%{DSA_ROWS_NAME}[.\d]* = ", ln)]


def _patterns_matching(line: str) -> list:
    """The configuration's ``trace_patterns.kernels`` (read, not edited)
    that match an operation's whole HLO line, as perfbench/trace_reduce.py
    applies them to the names on the trace's ``XLA Ops`` line."""
    kernels = _perfbench_hf("dots3-note-prev")["trace_patterns"]["kernels"]
    return sorted(k for k, pat in kernels.items() if re.search(pat, line))


def test_dots3_masked_decode_call_compiles_and_is_read_as_sparse_mla(
        topo, on_tpu):
    """The decoding rows' call of a full layer alone, at the cell's
    geometry (64 rows of 128 heads over the two full layers' flat pool of
    rows of 640 lanes, a page table of 592, the mask over its 9472
    positions) and the table's blocks for it: Mosaic takes the kernel, the
    pool is not copied, and the call's HLO line is what the benchmark's
    ``sparse_mla`` pattern finds (the pool's ``[.., 16, 640]``) and no
    other pattern does: the mask travels as ``s32[rows, blocks, block]``,
    which is neither the indexer's ``u32[`` nor a scores' shape."""
    from gllm_tpu.models.deepseek import DSA_ROWS_NAME
    from gllm_tpu.ops.pallas.decode_attention import paged_decode_attention
    from gllm_tpu.ops.pallas.tuning import decode_blocks
    from gllm_tpu.utils import tpu_compiler_options
    blocks = decode_blocks(1, chosen=True)
    assert blocks["kv_block"] == 1024, "expected the decode_mqa_chosen entry"
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    fn = jax.jit(lambda q, k, kl, pt, m: paged_decode_attention(
        q, k[:, :, None, :], None, kl, pt, scale=0.1, v_dim=512, chosen=m,
        kv_block=blocks["kv_block"], group_size=int(blocks["group"]),
        name=DSA_ROWS_NAME), compiler_options=tpu_compiler_options())
    text = fn.lower(
        sds((64, 128, 640), jnp.bfloat16),
        sds((2 * 37120, 16, 640), jnp.bfloat16), sds((64,), jnp.int32),
        sds((64, 592), jnp.int32), sds((64, 9472), jnp.bool_)
    ).compile().as_text()
    calls = _dsa_rows_calls(text)
    assert len(calls) == 1 and "tpu_custom_call" in calls[0]
    assert _patterns_matching(calls[0]) == ["sparse_mla"]
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and "[74240," in ln]


def _dots3_rows_are_on_the_masked_kernel(text: str):
    """Both full layers hold the masked decode call, each read as
    ``sparse_mla`` alone, and no operation gathers the 64 sequences' whole
    pages (``[64, 9472, 640]`` / ``[37888, 16, 640]``: 776 MB a layer when
    the rows were attended in XLA); the chunk loop's items gather one
    sequence's pages, a quarter to the whole of its table."""
    calls = _dsa_rows_calls(text)
    assert len(calls) == 2, calls
    for call in calls:
        assert _patterns_matching(call) == ["sparse_mla"], call
    assert not re.search(r"\[64,9472,640\]|\[37888,16,640\]", text)


@pytest.mark.slow
def test_dots3_decode_step_compiles_for_v5e(topo, on_tpu, monkeypatch):
    """The configuration's decode step (the cell holds rows and
    page-table width at their largest, --min-row-bucket 64 and
    --min-page-bucket 592, so this is its one decode program): 64 rows at
    contexts of 9216 tokens (576 pages: the 592-page bucket) through two
    DSA layers (index scores over
    [64, 64, 9472], top 2048, rows of 640 gathered) and three windowed
    ones (rings of 544 rows of 1152), 32 held experts a layer whose stacks
    are read in place. Counted from shapes by the compiler; nothing
    runs."""
    runner = _dots3_runner(topo, monkeypatch)
    c = compile_of(runner.step_async,
                   _with_slots(decode_batch(runner, 64, 576)))
    mem = c.compiled.memory_analysis()
    print(f"\n[compile] dots3 decode: {c.seconds:.1f}s, "
          f"{mem.argument_size_in_bytes / GiB:.2f} GiB of arguments, "
          f"{mem.temp_size_in_bytes / GiB:.3f} GiB temp")
    _dots3_pools_are_what_the_startup_line_says(
        runner, mem.argument_size_in_bytes - _weight_bytes(runner))
    # a full layer's rows stream through the masked decode kernel; the
    # 0.72 GiB copy of their pages (592 pages of 16 rows of 640) and the
    # float32 scores over it are gone: what is left is the indexer's keys
    # and scores
    assert mem.temp_size_in_bytes < 0.5 * GiB
    text = c.compiled.as_text()
    _dots3_rows_are_on_the_masked_kernel(text)
    # the grouped product is XLA's own kernel, and no layer's expert stack
    # is copied out of the run's (1.5 GB a layer and step when it was)
    assert "ragged-dot" in text and has_kernel(c.compiled)
    assert "bf16[32,5120,1536]{2,1,0:T(8,128)(2,1)} fusion(" not in text


@pytest.mark.slow
def test_dots3_mixed_step_compiles_for_v5e(topo, on_tpu, monkeypatch):
    """The configuration's largest mixed step: one 2048-token chunk at the
    end of a 9216-token context beside 63 decoding rows (2112 tokens). The chunk's
    selection and attention run 128 queries at a time, so no temporary
    grows with tokens x context: all of them fit beside 10 GB of weights
    and pools."""
    runner = _dots3_runner(topo, monkeypatch)
    batch = prefill_batch(runner, 2048, ndecode=63, npages=576,
                          table_pages=576)
    c = compile_of(runner.step_async, _with_slots(batch))
    mem = c.compiled.memory_analysis()
    print(f"\n[compile] dots3 mixed: {c.seconds:.1f}s, "
          f"{mem.argument_size_in_bytes / GiB:.2f} GiB of arguments, "
          f"{mem.temp_size_in_bytes / GiB:.3f} GiB temp")
    _dots3_pools_are_what_the_startup_line_says(
        runner, mem.argument_size_in_bytes - _weight_bytes(runner))
    assert mem.temp_size_in_bytes < 3.5 * GiB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14 * GiB
    _dots3_rows_are_on_the_masked_kernel(c.compiled.as_text())


def test_dots3_windowed_attention_compiles_for_v5e_at_the_chunk(topo,
                                                                on_tpu):
    """The windowed layers' attention alone at the cell's geometry: 2112
    tokens of 64 heads, 64 sequences, rings of 544 rows of 1152 as stored;
    a work item attends [ring | 640 rows of the step] DECOMPRESSED, 256
    lanes a key and 128 a value, and hands on [128, 64, 128]. What the
    change from the absorbed form is for (PR 50): no [tokens, 64, latent]
    tensor in the program, temporaries under 0.6 GiB where they were
    1.435, and under a third of the FLOPs (XLA's count for the described
    chip; it counts a loop's body once, so a 2048-token chunk's sixteen
    items are added here: the absorbed form as the parent ran it, w_uk
    folded into all T queries, sixteen items over [1184, 1152], w_uv out
    of all T results, its one-token rows left out)."""
    from gllm_tpu.batching import StepBatch
    from gllm_tpu.models import deepseek as ds
    from gllm_tpu.ops.attention import AttentionMetadata
    cfg = dots3_cfg()
    g = ds.geom(cfg, ds.SWA)
    assert (g.heads, g.width, g.lora, g.window) == (64, 1152, 1024, 513)
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    bf = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                             sharding=one)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    T, S, items = 2112, 64, 16
    lp = {"w_uk": bf(64, 192, 1024), "w_uv": bf(64, 1024, 128)}

    def attend(lp, q_nope, q_pe, entry, pos, cu, kv_lens, slots, ring):
        batch = StepBatch(
            token_ids=None, positions=pos, slot_mapping=None,
            logits_indices=None, sampling=None, ssm_slots=slots,
            attn=AttentionMetadata(cu, kv_lens, None, jnp.int32(S)))
        return ds._swa_attention(lp, q_nope, q_pe, entry, batch, ring, 0,
                                 max_q_len=T, g=g)

    def flops(compiled):
        cost = compiled.cost_analysis()
        return (cost[0] if isinstance(cost, list) else cost)["flops"]

    def flops_of(fn, *args):
        return flops(jax.jit(fn).lower(*args).compile())

    t0 = time.monotonic()
    compiled = jax.jit(attend).lower(
        lp, bf(T, 64, 192), bf(T, 64, 64), bf(T, 1152), i32(T), i32(S + 1),
        i32(S), i32(S), bf(3 * 65, 544, 1152)).compile()
    mem = compiled.memory_analysis()
    print(f"\n[compile] dots3 windowed attention: "
          f"{time.monotonic() - t0:.1f}s, "
          f"{mem.temp_size_in_bytes / GiB:.3f} GiB temp")
    assert mem.temp_size_in_bytes < 0.6 * GiB
    assert not re.search(r"\[(2112|2240),64,(1024|1088|1152)\]",
                         compiled.as_text())

    mask = jax.ShapeDtypeStruct((128, 1184), jnp.bool_, sharding=one)
    part = lambda n: (bf(n, 64, 256), bf(n, 64, 128),
                      jax.ShapeDtypeStruct((128, n), jnp.bool_, sharding=one))
    item = flops_of(
        lambda q, parts: ds._attend_heads(q, parts, scale=g.scale),
        bf(128, 64, 256), (part(544), part(640)))
    whole = flops(compiled)
    decompressed = whole + (items - 1) * item
    absorbed = (
        flops_of(lambda lp, q_nope, q_pe: ds._absorb(lp, q_nope, q_pe, g,
                                                     q_nope.dtype),
                 lp, bf(T, 64, 192), bf(T, 64, 64))
        + items * flops_of(lambda q, keys, m: ds._attend(
            q, keys, m, scale=g.scale, lora=g.lora),
            bf(128, 64, 1152), bf(1184, 1152), mask)
        + flops_of(ds._expand, lp, jax.ShapeDtypeStruct(
            (T, 64, 1024), jnp.float32, sharding=one)))
    print(f"[compile] dots3 windowed attention, GFLOP of a 2048-token "
          f"chunk beside 63 rows: absorbed {absorbed / 1e9:.1f}, "
          f"decompressed {decompressed / 1e9:.1f} ({item / 1e9:.1f} an "
          f"item, {whole / 1e9:.1f} the program with its loops' bodies "
          f"once)")
    assert decompressed < absorbed / 3
    assert item < 8e9


# ---- dense latent attention at a.x-k1's widths ------------------------------

def axk1_cfg() -> ModelConfig:
    """perfbench/configs/a.x-k1.json: published widths (64 heads over rows
    of 640 as stored, q-LoRA 1536, YaRN), 12 held experts of 7168 x 2048,
    5 layers."""
    from gllm_tpu.models.config import from_hf_config
    return from_hf_config(_perfbench_hf("a.x-k1"))


def _axk1_runner(topo, monkeypatch):
    from gllm_tpu.config import SchedulerConfig as SC
    flags = _perfbench_hf("a.x-k1")["server_flags"]
    val = lambda name: int(flags[flags.index(name) + 1])
    runner = make_runner(
        axk1_cfg(), topo, num_pages=val("--num-pages"),
        monkeypatch=monkeypatch, max_num_seqs=val("--max-num-seqs"),
        max_model_len=val("--max-model-len"), attention_impl="auto")
    assert runner.attn_impl == "pallas"
    return runner


def _mla_ragged(topo, q_block, kv_block, T=2080, S=32, pages=1088):
    from gllm_tpu.ops.pallas.ragged_attention import ragged_paged_attention
    from gllm_tpu.utils import tpu_compiler_options
    q, kc, _vc, cu, kv_lens, pt = _kernel_args(
        topo, S=S, T=T, Hq=64, Hkv=1, D=640, pack=1, P=5 * 35072,
        pages=pages)
    fn = jax.jit(lambda q, k, cu, kl, pt: ragged_paged_attention(
        q, k, None, cu, kl, pt, scale=0.1, q_block=q_block,
        kv_block=kv_block, v_dim=512),
        compiler_options=tpu_compiler_options())
    return fn.lower(q, kc, cu, kv_lens, pt).compile()


def test_mla_ragged_kernel_compiles_for_v5e_with_blocks_from_the_geometry(
        topo, on_tpu):
    """The ragged kernel at the cell's mixed step (512 token slots, 64
    heads over one KV head of 640 lanes, values the first 512) with the
    blocks ``tuning.ragged_blocks`` gives that geometry: q blocks of 16
    tokens = 1024 rows, kv blocks of 512 tokens."""
    from gllm_tpu.ops.pallas.tuning import get as tuned, ragged_blocks
    blocks = ragged_blocks(64, 1)
    assert blocks == {"q_block": 16, "kv_block": 512}
    assert ragged_blocks(32, 8) == tuned("ragged")
    t0 = time.monotonic()
    compiled = _mla_ragged(topo, T=512, **blocks)
    print(f"\n[compile] a.x-k1 ragged kernel, 512 slots: "
          f"{time.monotonic() - t0:.1f}s")
    assert has_kernel(compiled)
    assert "ragged_paged_attention" in compiled.as_text()


@pytest.mark.slow
def test_mla_ragged_kernel_is_refused_at_the_blocks_swept_for_8_kv_heads(
        topo, on_tpu):
    """Why the blocks follow the geometry: the pair the ``ragged`` entry
    held when latent attention came (512 x 128, swept at 8 KV heads of 128;
    128 x 128 after ``effective_q_block``) makes windows of [128, 64, 640]
    in and [128, 64, 512] out under one KV head of 640 lanes, and Mosaic
    runs out of VMEM (128.29 MB of 128 with float32 operands, before
    PR 37). If this starts passing, the kernel has shrunk and
    ``ragged_mqa`` may take larger blocks."""
    with pytest.raises(Exception, match="(?i)vmem|memory"):
        _mla_ragged(topo, q_block=512, kv_block=128)


@pytest.mark.slow
def test_axk1_decode_step_compiles_for_v5e(topo, on_tpu, monkeypatch):
    """The cell's one decode program: 32 rows at contexts of 17088 tokens
    (1068 pages: the 1088-page bucket) through five dense latent layers on
    ``paged_decode_attention``, 12 held experts a layer whose stacks are
    read in place. Counted from shapes by the compiler; nothing runs."""
    runner = _axk1_runner(topo, monkeypatch)
    c = compile_of(runner.step_async, decode_batch(runner, 32, 1068))
    mem = c.compiled.memory_analysis()
    print(f"\n[compile] a.x-k1 decode: {c.seconds:.1f}s, "
          f"{mem.argument_size_in_bytes / GiB:.2f} GiB of arguments, "
          f"{mem.temp_size_in_bytes / GiB:.3f} GiB temp")
    text = c.compiled.as_text()
    # operations by their names, as the trace shows them
    assert "%paged_decode_attention" in text and "%ragged-dot" in text
    assert "%ragged_paged_attention" not in text
    # weights 6.98 GB + the latent pool 3.59 GB, as the configuration's
    # ``derived`` has them, and nothing of the pool's size beside them
    derived = _perfbench_hf("a.x-k1")["derived"]
    want = derived["weight_bytes"] + derived["latent_pool_bytes"]
    assert abs(mem.argument_size_in_bytes / want - 1) < 0.01
    assert _weight_bytes(runner) == derived["weight_bytes"]
    assert mem.temp_size_in_bytes < 0.5 * GiB
    # no layer's expert stack is copied out of the run's
    assert "bf16[12,7168,2048]{2,1,0:T(8,128)(2,1)} fusion(" not in text


@pytest.mark.slow
@pytest.mark.parametrize("tokens", [320, 2048])
def test_axk1_mixed_step_compiles_for_v5e(topo, on_tpu, monkeypatch, tokens):
    """The cell's mixed steps: a question of 320 tokens (the 512-slot
    program, every request of the window) or a 2048-token chunk of the
    fill (the 2080-slot program) at the end of a 17088-token context
    beside 31 decoding rows, on ``ragged_paged_attention`` in the absorbed
    form with the blocks of the geometry."""
    runner = _axk1_runner(topo, monkeypatch)
    batch = prefill_batch(runner, tokens, ndecode=31, npages=1068,
                          table_pages=1068)
    c = compile_of(runner.step_async, batch)
    mem = c.compiled.memory_analysis()
    print(f"\n[compile] a.x-k1 mixed, {tokens} + 31 tokens: "
          f"{c.seconds:.1f}s, "
          f"{mem.argument_size_in_bytes / GiB:.2f} GiB of arguments, "
          f"{mem.temp_size_in_bytes / GiB:.3f} GiB temp")
    assert attention_calls(c.compiled) == MIXED_STEP_CALLS
    assert mem.temp_size_in_bytes < 2.0 * GiB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14 * GiB


# ---- the state-space hybrid at Nemotron 3 Nano's widths ---------------------

def nemotron_cfg() -> ModelConfig:
    """perfbench/configs/nemotron-3-nano-30b-a3b.json: published widths
    (Mamba-2 of 64 heads x 64 over a state of 128, GQA 32 / 2 of 128, 64
    held relu^2 experts of 2688 x 1856), the first 16 of 52 blocks."""
    from gllm_tpu.models.config import from_hf_config
    return from_hf_config(_perfbench_hf("nemotron-3-nano-30b-a3b"))


def _nemotron_runner(topo, monkeypatch):
    return make_runner(nemotron_cfg(), topo, num_pages=8320,
                       monkeypatch=monkeypatch, max_num_seqs=64,
                       max_model_len=4096, attention_impl="auto")


def _pallas_calls(compiled) -> list:
    # a kernel with two results has a tuple type, with blanks in it
    return sorted(set(re.findall(
        r"^\s*(?:ROOT )?%(mamba2_\w+?|gmm)(?:\.\d+)? = [^\n]*? custom-call\(",
        compiled.as_text(), re.M)))


def test_mamba2_recurrent_kernel_compiles_for_v5e_at_64_by_128(topo, on_tpu):
    """The decode kernel of the Mamba-2 layers alone, at the cell's
    shapes: 64 rows of 64 heads of 64 x 128 (8 groups) in a pool of 7 x 65
    slots. Mosaic takes the row-to-column turns and the half-lane rows."""
    from gllm_tpu.ops.pallas.mamba2_recurrent import mamba2_recurrent_step
    from gllm_tpu.utils import tpu_compiler_options
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one)
    S, H, P, N, G, slots = 64, 64, 64, 128, 8, 455
    fn = jax.jit(mamba2_recurrent_step.__wrapped__, donate_argnums=(4,),
                 compiler_options=tpu_compiler_options())
    compiled = fn.lower(sds((S, H, P)), sds((S, H)), sds((S, G, N)),
                        sds((S, G, N)), sds((slots, H, P, N)),
                        sds((S,), jnp.int32)).compile()
    assert has_kernel(compiled)
    assert "mamba2_recurrent_step" in compiled.as_text()


def test_mamba2_chunk_scan_kernel_compiles_for_v5e_at_64_by_128(topo,
                                                                on_tpu):
    """The chunked rule's inter-chunk scan alone, at the cell's largest
    layout: the 2112-token bucket's 32 chunks of 128 tokens, a group of 8
    heads a grid step, in place in a pool of 7 x 65 slots."""
    from gllm_tpu.ops.pallas.mamba2_scan import mamba2_chunk_scan
    from gllm_tpu.utils import tpu_compiler_options
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one)
    Nc, H, C, P, N, G, slots = 32, 64, 128, 64, 128, 8, 455
    fn = jax.jit(mamba2_chunk_scan.__wrapped__, donate_argnums=(5,),
                 compiler_options=tpu_compiler_options())
    compiled = fn.lower(
        sds((Nc, H, C, P)), sds((Nc, H, C, N)), sds((Nc, H, P, C)),
        sds((Nc, G, C, N)), sds((Nc, H, 1, N)), sds((slots, H, P, N)),
        sds((Nc,), jnp.int32), sds((Nc,), jnp.int32)).compile()
    assert has_kernel(compiled)
    assert "mamba2_chunk_scan" in compiled.as_text()


def _nemotron_step(runner, batch, calls, mamba, temp_bound):
    """Compile one step of the cell and hold it to: the attention kernels
    at 2 KV heads under 16 query heads each, the Mamba-2 kernels and the
    grouped product as Pallas calls, no copy of an expert or projection
    stack, the weights as the configuration derives them and the slot pool
    as ``_ssm_pool_bytes`` says, both within 1 %."""
    c = compile_of(runner.step_async, _with_slots(batch))
    text = c.compiled.as_text()
    assert attention_calls(c.compiled) == calls
    assert _pallas_calls(c.compiled) == ["gmm"] + mamba
    mem = c.compiled.memory_analysis()
    print(f"\n[compile] nemotron {calls[0]}: {c.seconds:.1f}s, "
          f"{mem.argument_size_in_bytes / GiB:.2f} GiB of arguments, "
          f"{mem.temp_size_in_bytes / GiB:.3f} GiB temp, "
          f"{mem.generated_code_size_in_bytes / 1e6:.1f} MB of code")
    assert mem.temp_size_in_bytes < temp_bound, mem.temp_size_in_bytes
    # a stack whose last dimension is not whole lanes would be laid out
    # transposed and copied back whole in every step (models/nemotron_h
    # ``lanes``): no copy of a [7, ...] bf16 stack is left
    assert not re.findall(r"= bf16\[7,[\d,]+\]\S* copy\(", text)
    derived = _perfbench_hf("nemotron-3-nano-30b-a3b")["derived"]
    weights = _weight_bytes(runner)
    assert weights == derived["weight_bytes"]
    kv_args = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in (runner.kv.k, runner.kv.v))
    assert kv_args == derived["kv_pool_bytes"]
    state = mem.argument_size_in_bytes - weights - kv_args
    assert abs(state / runner._ssm_pool_bytes() - 1) < 0.01, (
        state, runner._ssm_pool_bytes())
    assert abs(runner._ssm_pool_bytes() / derived["state_pool_bytes"]
               - 1) < 0.01


def test_nemotron_decode_step_compiles_for_v5e(topo, on_tpu, monkeypatch):
    """The cell's decode step: 64 rows at 129 pages in the 256-page
    bucket, 11.3 GiB of arguments (10.88 GB of weights as stored, 0.99 GB
    of state, 0.27 GB of KV) and 0.05 GiB of temporaries. Counted from
    shapes by the compiler; nothing runs."""
    runner = _nemotron_runner(topo, monkeypatch)
    assert runner.attn_impl == "pallas"
    _nemotron_step(runner, decode_batch(runner, 64, 129),
                   ["paged_decode_attention"], ["mamba2_recurrent_step"],
                   0.25 * GiB)


@pytest.mark.slow
def test_nemotron_largest_mixed_step_compiles_for_v5e(topo, on_tpu,
                                                      monkeypatch):
    """The cell's largest mixed step: a 2048-token chunk beside 63
    decoding rows (the 2112-token program, 32 chunks of 128 in the packed
    layout): both Mamba-2 kernels, both attention kernels, under 1.25 GiB
    of temporaries beside 11.3 GiB of arguments."""
    runner = _nemotron_runner(topo, monkeypatch)
    _nemotron_step(runner, prefill_batch(runner, 2048, ndecode=63,
                                         npages=129),
                   MIXED_STEP_CALLS,
                   ["mamba2_chunk_scan", "mamba2_recurrent_step"],
                   1.25 * GiB)


# ---- attention and Mamba-2 heads side by side at Falcon-H1-34B's widths -----

FALCON = "falcon-h1-34b-instruct"


@pytest.mark.parametrize("kernel", ["recurrent", "chunk_scan"])
def test_mamba2_kernels_compile_for_v5e_at_128_by_256(topo, on_tpu, kernel):
    """Both Mamba-2 kernels alone at the parallel-hybrid cell's shapes: 32
    heads of 128 x 256 in 2 groups (a state block of 4.19 MB a row, twice
    Nemotron 3 Nano's), 64 rows or the 2112-token bucket's 32 chunks of
    128, in place in a pool of 6 x 65 slots."""
    from gllm_tpu.ops.pallas.mamba2_recurrent import mamba2_recurrent_step
    from gllm_tpu.ops.pallas.mamba2_scan import mamba2_chunk_scan
    from gllm_tpu.utils import tpu_compiler_options
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one)
    S, Nc, H, C, P, N, G, slots = 64, 32, 32, 128, 128, 256, 2, 390
    t0 = time.monotonic()
    if kernel == "recurrent":
        fn = jax.jit(mamba2_recurrent_step.__wrapped__, donate_argnums=(4,),
                     compiler_options=tpu_compiler_options())
        compiled = fn.lower(sds((S, H, P)), sds((S, H)), sds((S, G, N)),
                            sds((S, G, N)), sds((slots, H, P, N)),
                            sds((S,), jnp.int32)).compile()
    else:
        fn = jax.jit(mamba2_chunk_scan.__wrapped__, donate_argnums=(5,),
                     compiler_options=tpu_compiler_options())
        compiled = fn.lower(
            sds((Nc, H, C, P)), sds((Nc, H, C, N)), sds((Nc, H, P, C)),
            sds((Nc, G, C, N)), sds((Nc, H, 1, N)), sds((slots, H, P, N)),
            sds((Nc,), jnp.int32), sds((Nc,), jnp.int32)).compile()
    print(f"\n[compile] mamba2 {kernel} at 32 x 128 x 256 / 2 groups: "
          f"{time.monotonic() - t0:.1f}s")
    assert has_kernel(compiled)
    assert f"mamba2_{kernel}" in compiled.as_text()


def _falcon_step(topo, monkeypatch, make_batch, calls, mamba, temp_bound):
    """Compile one step of the parallel-hybrid cell and hold it to: both
    halves' kernels as Pallas calls, the weights, the KV pool and the slot
    pool as the configuration derives them (one counter of layers for the
    pages and the slots), and the stacked weights read where they lie: no
    copy of a whole [6, ...] stack (a transposed layout: the 4.3 GB lesson
    of PR 41, models/nemotron_h.lanes) and no operation of its own whose
    result is one layer of a projection's stack (docs/stacked_layers.md)."""
    from gllm_tpu.models.config import from_hf_config
    cfg = from_hf_config(_perfbench_hf(FALCON))
    runner = make_runner(cfg, topo, num_pages=8320, monkeypatch=monkeypatch,
                         max_num_seqs=64, max_model_len=4096,
                         attention_impl="auto")
    assert runner.attn_impl == "pallas"
    c = compile_of(runner.step_async, _with_slots(make_batch(runner)))
    text = c.compiled.as_text()
    assert attention_calls(c.compiled) == calls
    assert _pallas_calls(c.compiled) == mamba
    mem = c.compiled.memory_analysis()
    print(f"\n[compile] falcon-h1 {calls[0]}: {c.seconds:.1f}s, "
          f"{mem.argument_size_in_bytes / GiB:.2f} GiB of arguments, "
          f"{mem.temp_size_in_bytes / GiB:.3f} GiB temp, "
          f"{mem.generated_code_size_in_bytes / 1e6:.1f} MB of code")
    assert mem.temp_size_in_bytes < temp_bound, mem.temp_size_in_bytes
    assert not re.findall(r"= bf16\[6,[\d,]+\]\S* copy\(", text)
    comps = list(_computations(text))
    one_layer = re.compile(
        r"= bf16\[(1,)?(5120|2560|4096|21504),(2560|512|9344|5120|21504)\]"
        r"\S* (fusion|copy)\(")
    moved = [ln.strip()[:140] for _, lines, fused in comps if not fused
             for ln in lines if one_layer.search(ln)]
    assert not moved, moved
    derived = _perfbench_hf(FALCON)["derived"]
    weights = _weight_bytes(runner)
    assert weights == derived["weight_bytes"]
    kv_args = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in (runner.kv.k, runner.kv.v))
    assert kv_args == derived["kv_pool_bytes"] \
        == runner.num_pages * runner._kv_bytes_per_page()
    state = mem.argument_size_in_bytes - weights - kv_args
    assert abs(state / runner._ssm_pool_bytes() - 1) < 0.01, (
        state, runner._ssm_pool_bytes())
    assert runner._ssm_pool_bytes() == derived["state_pool_bytes_as_stored"]
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.6 * GiB


@pytest.mark.slow
def test_falcon_h1_decode_step_compiles_for_v5e(topo, on_tpu, monkeypatch):
    """The cell's decode step: 64 rows at 129 pages in the 256-page
    bucket, 10.52 GB of weights, 1.66 GB of state and 1.64 GB of KV as
    arguments. Counted from shapes by the compiler; nothing runs."""
    _falcon_step(topo, monkeypatch, lambda r: decode_batch(r, 64, 129),
                 ["paged_decode_attention"], ["mamba2_recurrent_step"],
                 0.5 * GiB)


@pytest.mark.slow
def test_falcon_h1_largest_mixed_step_compiles_for_v5e(topo, on_tpu,
                                                       monkeypatch):
    """The cell's largest mixed step: a 2048-token chunk beside 63
    decoding rows (the 2112-token program, 32 chunks of 128 in the packed
    layout): both Mamba-2 kernels, both attention kernels."""
    _falcon_step(topo, monkeypatch,
                 lambda r: prefill_batch(r, 2048, ndecode=63, npages=129),
                 MIXED_STEP_CALLS,
                 ["mamba2_chunk_scan", "mamba2_recurrent_step"], 1.6 * GiB)


@pytest.mark.slow
def test_falcon_h1_probe_chunk_with_prompt_logprobs_fits_the_chip(
        topo, on_tpu, monkeypatch):
    """The comparison's long probe: a 2048-token chunk alone whose every
    row's logprob is asked for. Over a vocabulary of 261120 the chunk's
    float32 logits are 2 GB beside their bf16 product's 1 GB (the first
    chip run's out-of-memory: 15.89 GB of 15.75); computed 512 rows at a
    time (``runner.plp_block_rows``) the step keeps under 1.2 GiB of
    temporaries beside its 12.87 GiB of arguments."""
    _falcon_step(topo, monkeypatch,
                 lambda r: prefill_batch(r, 2048, npages=129,
                                         prompt_logprobs=1),
                 MIXED_STEP_CALLS,
                 ["mamba2_chunk_scan", "mamba2_recurrent_step"], 1.2 * GiB)


# ---- a gated short convolution beside packed GQA at LFM2-24B-A2B's widths ---

LFM2 = "lfm2-24b-a2b"


def _lfm2_step(topo, monkeypatch, make_batch, calls, grouped, temp_bound):
    """Compile one step of the short-convolution cell and hold it to: the
    attention kernels over the packed cache as Pallas calls and the held
    experts' products in the form ``grouped`` names (the Pallas ``gmm`` in
    a mixed step; in a decode-only step of 128 rows every held expert
    times every row, XLA's batched products over the stack in place), the
    weights, the KV pool and the window pool as the
    configuration derives them (``_ssm_pool_bytes`` within 1 % of the
    compiler's count), no copy of a whole stack or of the window pool, and
    no operation of its own whose result is one layer of a projection's
    stack (docs/stacked_layers.md)."""
    from gllm_tpu.models.config import from_hf_config
    cfg = from_hf_config(_perfbench_hf(LFM2))
    runner = make_runner(cfg, topo, num_pages=16640, monkeypatch=monkeypatch,
                         max_num_seqs=128, max_model_len=4096,
                         attention_impl="auto")
    assert runner.attn_impl == "pallas" and runner.kv_pack == 2
    assert runner.kv.k.shape == (10, 16640, 16, 4, 128)
    assert runner.kv.conv.shape == (30, 129, 2, 2048) and runner.kv.rec is None
    c = compile_of(runner.step_async, _with_slots(make_batch(runner)))
    text = c.compiled.as_text()
    assert attention_calls(c.compiled) == calls
    assert _pallas_calls(c.compiled) == grouped
    if not grouped:
        assert re.search(r"= bf16\[8,128,1536\]\S* fusion\(", text)
    mem = c.compiled.memory_analysis()
    print(f"\n[compile] lfm2 {calls[0]}: {c.seconds:.1f}s, "
          f"{mem.argument_size_in_bytes / GiB:.2f} GiB of arguments, "
          f"{mem.temp_size_in_bytes / GiB:.3f} GiB temp, "
          f"{mem.generated_code_size_in_bytes / 1e6:.1f} MB of code")
    assert mem.temp_size_in_bytes < temp_bound, mem.temp_size_in_bytes
    # no whole stack (30 conv, 10 attention, 38 expert, 2 dense layers)
    # and no window pool is copied; the taps' [30, 3, 2048] (368 KB,
    # retiled once a step) is the one stack under a megabyte
    stacks = [tuple(int(d) for d in dims.split(","))
              for dims in re.findall(
                  r"= bf16\[((?:30|10|38|2),[\d,]+)\]\S* copy\(", text)]
    assert all(2 * np.prod(s) < 1e6 for s in stacks), stacks
    assert not re.findall(r"= f32\[(30,129|3870),2,2048\]\S* copy\(", text)
    comps = list(_computations(text))
    one_layer = re.compile(
        r"= bf16\[(1,|8,|1,8,)?(2048|11776|1536),(6144|2048|512|11776|1536)"
        r"\]\S* (fusion|copy)\(")
    moved = [ln.strip()[:140] for _, lines, fused in comps if not fused
             for ln in lines if one_layer.search(ln)]
    assert not moved, moved
    derived = _perfbench_hf(LFM2)["derived"]
    weights = _weight_bytes(runner)
    assert weights == derived["weight_bytes"]
    kv_args = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in (runner.kv.k, runner.kv.v))
    assert kv_args == derived["kv_pool_bytes"] \
        == runner.num_pages * runner._kv_bytes_per_page()
    # what is left of the arguments: the window pool, the rotary table
    # and the step's batch (a quarter of a megabyte)
    rope = int(np.prod(runner.cos_sin.shape)) * runner.cos_sin.dtype.itemsize
    state = mem.argument_size_in_bytes - weights - kv_args - rope
    assert abs(state / runner._ssm_pool_bytes() - 1) < 0.01, (
        state, runner._ssm_pool_bytes())
    assert runner._ssm_pool_bytes() == derived["window_pool_bytes"]
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.6 * GiB


@pytest.mark.slow
def test_lfm2_decode_step_compiles_for_v5e(topo, on_tpu, monkeypatch):
    """The cell's decode step: 128 rows at 129 pages in the 256-page
    bucket, 7.29 GB of weights, 5.45 GB of KV and 0.07 GB of windows as
    arguments. Counted from shapes by the compiler; nothing runs."""
    _lfm2_step(topo, monkeypatch, lambda r: decode_batch(r, 128, 129),
               ["paged_decode_attention"], [], 0.5 * GiB)


@pytest.mark.slow
def test_lfm2_largest_mixed_step_compiles_for_v5e(topo, on_tpu,
                                                  monkeypatch):
    """The cell's largest mixed step: a 2048-token chunk beside 127
    decoding rows (the 2176-token program of the cell's --maxd 128; under
    this runner's default --maxd 256 the same batch takes the 2304-token
    bucket): both attention kernels, the operator's one gather over the
    flat token axis, no packed layout."""
    _lfm2_step(topo, monkeypatch,
               lambda r: prefill_batch(r, 2048, ndecode=127, npages=129),
               MIXED_STEP_CALLS, ["gmm"], 1.6 * GiB)


# ---- windowed GQA in pages at command-a-plus-05-2026's widths ---------------

COHERE = "command-a-plus-05-2026"


def cohere2_cfg() -> ModelConfig:
    """perfbench/configs/command-a-plus-05-2026.json: published widths (128
    query heads over 8 KV heads of 128, window 4096 in 3 of 4 layers, 16
    held experts of 4096 x 4096 and four shared ones), one period of the
    32 layers."""
    from gllm_tpu.models.config import from_hf_config
    return from_hf_config(_perfbench_hf(COHERE))


def _cohere2_runner(topo, monkeypatch):
    flags = _perfbench_hf(COHERE)["server_flags"]
    val = lambda name: int(flags[flags.index(name) + 1])
    runner = make_runner(
        cohere2_cfg(), topo, num_pages=val("--num-pages"),
        monkeypatch=monkeypatch, max_num_seqs=val("--max-num-seqs"),
        max_model_len=val("--max-model-len"), attention_impl="auto")
    assert runner.attn_impl == "pallas"
    return runner


@pytest.mark.parametrize("window", [4096, None], ids=["window", "full"])
def test_gqa_128_by_8_kernels_compile_for_v5e(topo, on_tpu, window):
    """Mosaic takes both kernels at the cell's geometry (128 query heads,
    sixteen a KV head, over 8 KV heads of 128; 16 rows under a table of
    1088 pages in a pool of 4 x 17280) with the blocks the table gives that
    geometry, with the window and without, and the windowed calls carry
    their own names."""
    from gllm_tpu.ops import attention
    from gllm_tpu.utils import tpu_compiler_options
    q, kc, vc, cu, kv_lens, pt = _kernel_args(
        topo, S=16, T=512, Hq=128, Hkv=8, D=128, pack=1, P=4 * 17280,
        pages=1088)
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def call(max_q_len, rows):
        fn = jax.jit(lambda q, k, v, cu, kl, pt: attention.paged_attention(
            q, k, v, attention.AttentionMetadata(cu, kl, pt, kl[0]),
            scale=128 ** -0.5, max_q_len=max_q_len, impl="pallas",
            window=window), compiler_options=tpu_compiler_options())
        qq = jax.ShapeDtypeStruct((rows,) + q.shape[1:], q.dtype,
                                  sharding=one)
        return attention_calls(fn.lower(qq, kc, vc, cu, kv_lens,
                                        pt).compile())

    # a KV head at a time: the q block at 128 heads is no longer the 16
    # tokens that the all-heads score tile clamped it to
    from gllm_tpu.ops.pallas.ragged_attention import effective_q_block
    from gllm_tpu.ops.pallas.tuning import ragged_blocks
    blocks = ragged_blocks(128, 8)
    assert effective_q_block(blocks["q_block"], blocks["kv_block"], 128,
                             512, 8, 128) > 16
    names = attention.WINDOW_NAMES
    assert call(1, 16) == ([names["decode"]] if window
                           else ["paged_decode_attention"])
    assert call(512, 512) == (
        sorted([names["ragged"], names["rows"]]) if window
        else MIXED_STEP_CALLS)


def _cohere2_step(runner, batch, calls, temp_bound):
    """Compile one step of the cell and hold it to: the full layer's and
    the windowed layers' attention calls under their names, the weights
    and the pool as the configuration derives them and as the start-up
    line says, both within 1 % of the compiler's argument count, and no
    copy of a layer's expert stack."""
    c = compile_of(runner.step_async, batch)
    text = c.compiled.as_text()
    mem = c.compiled.memory_analysis()
    print(f"\n[compile] {COHERE} {calls[0]}: {c.seconds:.1f}s, "
          f"{mem.argument_size_in_bytes / GiB:.2f} GiB of arguments, "
          f"{mem.temp_size_in_bytes / GiB:.3f} GiB temp, "
          f"{mem.generated_code_size_in_bytes / 1e6:.1f} MB of code")
    assert attention_calls(c.compiled) == sorted(calls)
    assert _pallas_calls(c.compiled) == ["gmm"]     # the held experts
    derived = _perfbench_hf(COHERE)["derived"]
    assert runner.weight_bytes() == derived["weight_bytes"]
    pool = runner.num_pages * runner._kv_bytes_per_page()
    assert pool == derived["kv_pool_bytes"]
    assert abs(mem.argument_size_in_bytes / (derived["weight_bytes"] + pool)
               - 1) < 0.01
    assert mem.temp_size_in_bytes < temp_bound, mem.temp_size_in_bytes
    assert "bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)} fusion(" not in text
    assert not re.findall(r"= bf16\[4,[\d,]+\]\S* copy\(", text)
    # no layer of a stacked leaf is cut out as an operation of its own
    # (a fusion or a copy whose result is one layer's matrix: 134 MB for
    # q or o, 537 MB for a shared matrix), as the dense cell's are not
    moved = [ln.strip()[:140]
             for _, lines, fused in _computations(text) if not fused
             for ln in lines if re.search(
                 r"= bf16\[(1,)?(4096|16384),(16384|4096|1024)\]\S* "
                 r"(fusion|copy)\(", ln)]
    assert not moved, moved


def test_cohere2_decode_step_compiles_for_v5e(topo, on_tpu, monkeypatch):
    """The cell's one decode program: 16 rows at contexts of 17088 tokens
    (1068 pages: the 1088-page bucket), three windowed layers on
    ``swa_paged_decode_attention`` and the full one on
    ``paged_decode_attention``, 16 held experts a layer read in place;
    9.47 GB of weights and 4.53 GB of pages. Counted from shapes by the
    compiler; nothing runs."""
    from gllm_tpu.ops.attention import WINDOW_NAMES
    runner = _cohere2_runner(topo, monkeypatch)
    _cohere2_step(runner, decode_batch(runner, 16, 1068),
                  ["paged_decode_attention", WINDOW_NAMES["decode"]],
                  0.5 * GiB)


@pytest.mark.slow
@pytest.mark.parametrize("tokens", [320, 2048])
def test_cohere2_mixed_step_compiles_for_v5e(topo, on_tpu, monkeypatch,
                                             tokens):
    """The cell's mixed steps: a question of 320 tokens (the 512-slot
    program, every request of the window) or a 2048-token chunk of the
    fill at the end of a 17088-token context beside 15 decoding rows."""
    from gllm_tpu.ops.attention import WINDOW_NAMES
    runner = _cohere2_runner(topo, monkeypatch)
    batch = prefill_batch(runner, tokens, ndecode=15, npages=1068,
                          table_pages=1068)
    _cohere2_step(runner, batch, MIXED_STEP_CALLS + [
        WINDOW_NAMES["ragged"], WINDOW_NAMES["rows"]], 2.0 * GiB)


# ---- the slot maintenance program at the three slot-pool cells' pools -------

@pytest.mark.parametrize("config,slots,dp", [
    (FALCON, 65, 1), ("olmo-hybrid-7b", 33, 1),
    ("nemotron-3-nano-30b-a3b", 65, 1), (FALCON, 33, 2),
    ("lfm2-24b-a2b", 129, 1)],
    ids=["falcon_256_lanes", "hybrid_384_lanes", "state_space_128_lanes",
         "falcon_dp_stacked", "window_alone"])
def test_slot_maintenance_moves_slots_not_the_pool(topo, on_tpu, config,
                                                   slots, dp):
    """`runner._ssm_apply` at a cell's pools as `ssm_slot_shapes` lays
    them, index lists of 4: the compiled program reads and writes far
    less than the pool and keeps less than a slot of temporaries. As a
    gather and a scatter over the slot axis it read 8.52 GB beside 1.64
    GB of temporaries at falcon's `[6, 65, 32, 128, 256]` and 6.17 GB at
    the hybrid's `[12, 33, 15, 96, 384]` (PR 49): XLA splits a last
    dimension of more than one 128-lane tile in a pass over the whole
    pool. A change of a pool's layout meets this guard first."""
    from gllm_tpu.models.config import from_hf_config
    from gllm_tpu.runner.runner import _ssm_apply, _ssm_apply_replica
    cfg = from_hf_config(_perfbench_hf(config))
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one)
    lead = (cfg.num_linear_layers, slots)
    window, state = cfg.ssm_slot_shapes
    conv = sds((dp,) * (dp > 1) + lead + window)
    # a window-only slot (lfm2_moe) hands no recurrent stack over: the
    # window pool is then the pool the bounds are held to
    rec = sds((dp,) * (dp > 1) + lead + state) if state else None
    largest = conv if rec is None else rec
    lists = [sds((4,), jnp.int32)] * 5
    t0 = time.monotonic()
    if dp > 1:
        compiled = _ssm_apply_replica.lower(
            conv, rec, sds((), jnp.int32), *lists).compile()
    else:
        compiled = _ssm_apply.lower(conv, rec, *lists).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    temp = compiled.memory_analysis().temp_size_in_bytes
    pool = 4 * int(np.prod(largest.shape))
    slot = pool // (dp * slots)
    print(f"\n[compile] slot maintenance, pool {largest.shape}: "
          f"{time.monotonic() - t0:.1f}s, bytes accessed "
          f"{cost['bytes accessed'] / 1e9:.3f} GB of a pool of "
          f"{pool / 1e9:.2f}, temporaries {temp / 1e6:.2f} MB of a slot's "
          f"{slot / 1e6:.1f}")
    assert cost["bytes accessed"] < pool / 2
    assert temp < slot
