"""The packed step batch (batching.pack / unpack, PR 25): what the host
builds reaches the step programs in ONE int32 buffer beside the tokens,
the step's PRNG key is folded inside the program, and the runner counts
the arrays it places (gllm_step_h2d_arrays_total).

Everything here runs on the CPU: counts and identity, never a time."""

import types

import jax
import numpy as np
import pytest

from gllm_tpu.batching import BatchLayout, PackedBatch, pack, unpack
from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.runner.prepare import BatchBuilder
from gllm_tpu.sampling_params import SamplingParams
from gllm_tpu.scheduler import ScheduledBatch, ScheduledSeq
from gllm_tpu.sequence import Sequence

PAGE = 4


def _config(**kw):
    return EngineConfig(
        load_format="dummy", dtype="float32", max_model_len=128,
        max_num_seqs=16, seed=3,
        scheduler=SchedulerConfig(max_prefill_tokens=32,
                                  max_decode_seqs=16),
        cache=CacheConfig(page_size=PAGE, num_pages=128), **kw)


def _builder(**kw):
    cfg_kw = {k: kw.pop(k) for k in ("spec_decode", "spec_k")
              if k in kw}
    return BatchBuilder(_config(**cfg_kw), PAGE, vocab_size=128,
                        hidden_size=8, **kw)


def _seq(i, n_tokens, computed, sp=None, **attrs):
    seq = Sequence(i, list(range(1, n_tokens + 1)),
                   sp or SamplingParams(max_tokens=8))
    seq.page_table = [1 + i * 8 + j for j in range(-(-n_tokens // PAGE))]
    seq.num_computed_tokens = computed
    for k, v in attrs.items():
        setattr(seq, k, v)
    return seq


def _decode_items(n, sp=None, **attrs):
    return [ScheduledSeq(_seq(i, 9 + i, 8 + i, sp, **attrs), 1, 8 + i)
            for i in range(n)]


def _mm(prompt_len, visual):
    """What the builder reads of a VL sequence's ``mm``: three rows of
    prompt positions, the visual-row index (-1: a text row) and the
    rows themselves."""
    vis_index = np.full(prompt_len, -1, np.int64)
    if visual:
        vis_index[2:5] = np.arange(3)
    return types.SimpleNamespace(
        mrope_positions=np.tile(np.arange(prompt_len), (3, 1)),
        mrope_delta=2, vis_index=vis_index,
        vis_embeds=np.arange(24, dtype=np.float32).reshape(3, 8) - 5.5)


def _case(name):
    """(builder, ScheduledBatch) of one round-trip case."""
    plain = SamplingParams(temperature=0.7, top_p=0.9, top_k=5,
                           min_p=0.05, max_tokens=8)
    if name == "decode":
        return _builder(), ScheduledBatch(_decode_items(5, plain))
    if name == "mixed":
        items = _decode_items(3, plain)
        items.append(ScheduledSeq(_seq(3, 13, 0), 13, 0))
        return _builder(), ScheduledBatch(items)
    if name == "seed":
        items = _decode_items(2) + [ScheduledSeq(_seq(
            2, 9, 8, SamplingParams(temperature=1.0, seed=7)), 1, 8)]
        return _builder(), ScheduledBatch(items)
    if name == "penalties":
        sp = SamplingParams(repetition_penalty=1.3, presence_penalty=0.5,
                            frequency_penalty=-0.25)
        return _builder(), ScheduledBatch(_decode_items(3, sp))
    if name == "logit_bias":
        sp = SamplingParams(logit_bias={3: -1.5, 90: 2.0, 17: 0.25})
        return _builder(), ScheduledBatch(_decode_items(2, sp))
    if name == "prompt_logprobs":
        sp = SamplingParams(prompt_logprobs=2, max_tokens=4)
        return _builder(), ScheduledBatch(
            [ScheduledSeq(_seq(0, 11, 0, sp), 11, 0)])
    if name == "spec_drafts":
        items = _decode_items(3)
        items[1] = ScheduledSeq(items[1].seq, 1, 9, draft_tokens=(5, 6))
        items[1].seq.page_table.append(60)    # room for the draft rows
        return (_builder(spec_decode="ngram", spec_k=3),
                ScheduledBatch(items))
    if name == "ssm_slots":
        items = [ScheduledSeq(_seq(i, 9, 8, ssm_slot=3 + i), 1, 8)
                 for i in range(3)]
        return _builder(use_ssm=True), ScheduledBatch(items)
    if name in ("vl_text_rows", "vl_visual_rows"):
        seq = _seq(0, 12, 0, mm=_mm(12, name == "vl_visual_rows"))
        return (_builder(use_mm=True, mm_embed_dim=8),
                ScheduledBatch([ScheduledSeq(seq, 12, 0)]))
    if name == "fused_stop_ids":
        sp = SamplingParams(max_tokens=8, stop_token_ids=[5, 9],
                            min_tokens=2)
        return _builder(), ScheduledBatch(_decode_items(3, sp))
    raise AssertionError(name)


CASES = ("decode", "mixed", "seed", "penalties", "logit_bias",
         "prompt_logprobs", "spec_drafts", "ssm_slots", "vl_text_rows",
         "vl_visual_rows", "fused_stop_ids")

# the optional leaves each case must really have brought along
PRESENT = {
    "seed": ("sampling.seed", "sampling.out_step"),
    "penalties": ("sampling.presence_penalty",
                  "sampling.frequency_penalty"),
    "logit_bias": ("sampling.bias_ids", "sampling.bias_vals"),
    "prompt_logprobs": ("plp_targets",),
    "spec_drafts": ("spec_rows", "spec_drafts"),
    "ssm_slots": ("ssm_slots",),
    "vl_text_rows": ("mrope_positions",),
    "vl_visual_rows": ("mrope_positions", "mm_mask"),
    "fused_stop_ids": ("sampling.stop_ids", "sampling.stop_from",
                       "x.active_until"),
}


def _host_batch(name):
    builder, sched = _case(name)
    host, _, _ = builder.build(sched)
    extra = {}
    if name == "fused_stop_ids":
        s_bucket = host.token_ids.shape[0]
        stop_ids, stop_from = builder.stop_sets(sched.items, s_bucket, [2])
        assert stop_ids is not None
        host = host._replace(sampling=host.sampling._replace(
            stop_ids=stop_ids, stop_from=stop_from))
        extra["active_until"] = np.arange(s_bucket, dtype=np.int32)
    return host, extra


def _assert_same_leaves(got, want):
    flat_g, tree_g = jax.tree.flatten(got)
    flat_w, tree_w = jax.tree.flatten(want)
    assert tree_g == tree_w, (tree_g, tree_w)
    for g, w in zip(flat_g, flat_w):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, (g.dtype,
                                                           w.dtype)
        # bit for bit: float fields travel as their bit patterns
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("name", CASES)
def test_unpack_of_pack_is_the_batch_leaf_for_leaf(name):
    host, extra = _host_batch(name)
    packed, layout = pack(host, (41,), **extra)
    assert isinstance(packed, PackedBatch)
    assert packed.packed.dtype == np.int32
    assert packed.packed.shape == (layout.size,)
    assert packed.token_ids is host.token_ids      # a leaf of its own
    assert packed.mm_embeds is host.mm_embeds
    assert (host.mm_embeds is not None) == (name == "vl_visual_rows")
    for field in PRESENT.get(name, ()):
        assert layout.has(field), (field, layout.fields)
    # inside a program (static slices of a traced buffer), as the step
    # programs do it, and on the host's arrays as they are
    for run in (jax.jit(lambda p: unpack(p, layout)),
                lambda p: unpack(p, layout)):
        batch, got_extra = run(packed)
        _assert_same_leaves(batch, host)
        assert np.asarray(got_extra.pop("step")).tolist() == [41]
        _assert_same_leaves(got_extra, extra)


@pytest.mark.parametrize("values", [
    (-0.0, 0.0, np.inf, -np.inf),
    (np.float32(1e-45), np.finfo(np.float32).max,
     np.finfo(np.float32).tiny, -1.0),
    (0.1, 0.7, 1.0 / 3.0, np.nan),
], ids=["signed_zero_and_inf", "denormal_and_extremes", "fractions_nan"])
def test_float32_fields_survive_the_bit_pattern_round_trip(values):
    host, _ = _host_batch("decode")
    temperature = np.zeros_like(host.sampling.temperature)
    temperature[:len(values)] = np.asarray(values, np.float32)
    host = host._replace(sampling=host.sampling._replace(
        temperature=temperature))
    packed, layout = pack(host, (1,))
    got = jax.jit(lambda p: unpack(p, layout)[0].sampling.temperature)(
        packed)
    assert np.asarray(got).dtype == np.float32
    assert np.asarray(got).tobytes() == temperature.tobytes()
    assert np.signbit(np.asarray(got)[0]) == np.signbit(temperature[0])


@pytest.mark.parametrize("name", CASES)
def test_one_signature_and_one_set_of_fields_is_one_layout(name):
    a, extra = _host_batch(name)
    b, _ = _host_batch(name)
    # other values, the same shapes: another step, other tokens
    b = b._replace(positions=b.positions + 1)
    la = pack(a, (1,), **extra)[1]
    lb = pack(b, (2,), **extra)[1]
    assert la == lb and hash(la) == hash(lb)
    assert isinstance(la, BatchLayout)
    # a dp replica's key folds two integers: another program, another
    # layout
    assert pack(a, (1, 0), **extra)[1] != la


@pytest.mark.parametrize("name", [c for c in CASES if c != "decode"])
def test_another_shape_or_another_field_is_another_layout(name):
    plain = pack(_host_batch("decode")[0], (1,))[1]
    host, extra = _host_batch(name)
    other = pack(host, (1,), **extra)[1]
    assert other != plain and hash(other) != hash(plain)


def test_a_wider_bucket_of_the_same_fields_is_another_layout():
    builder, sched = _case("decode")
    narrow = pack(builder.build(sched)[0], (1,))[1]
    wide = pack(builder.build(sched, force_signature=(16, 16, 1, 8))[0],
                (1,))[1]
    assert [f[0] for f in narrow.fields] == [f[0] for f in wide.fields]
    assert narrow != wide


# ---- the key, folded inside the program --------------------------------------

STEPS = (1, 2, 2 ** 31, 2 ** 32 - 1)


def _key_bits(key):
    return np.asarray(jax.random.key_data(key)).tolist()


@pytest.mark.parametrize("n", STEPS)
def test_in_program_key_is_fold_in_of_the_step(n):
    key = jax.random.key(5)
    host, _ = _host_batch("decode")
    packed, layout = pack(host, (n,))
    got = jax.jit(lambda p, k: unpack(p, layout, k)[0].sampling.step_key)(
        packed, key)
    assert _key_bits(got) == _key_bits(jax.random.fold_in(key, n))
    # with no key (a pipeline stage that never samples) there is none
    assert unpack(packed, layout)[0].sampling.step_key is None


@pytest.mark.parametrize("n", STEPS)
def test_in_program_key_of_a_dp_replica_folds_the_replica_next(n):
    key = jax.random.key(5)
    host, _ = _host_batch("decode")
    packs = [pack(host, (n, r)) for r in range(3)]
    assert len({layout for _, layout in packs}) == 1
    layout = packs[0][1]
    stacked = jax.tree.map(lambda *xs: np.stack(xs),
                           *[p for p, _ in packs])
    got = jax.jit(jax.vmap(
        lambda p: jax.random.key_data(
            unpack(p, layout, key)[0].sampling.step_key)))(stacked)
    want = [_key_bits(jax.random.fold_in(jax.random.fold_in(key, n), r))
            for r in range(3)]
    assert np.asarray(got).tolist() == want


@pytest.mark.parametrize("n", STEPS)
def test_in_program_keys_of_a_fused_block(n):
    from gllm_tpu.runner.runner import _fold_in_range
    key = jax.random.key(5)
    host, _ = _host_batch("decode")
    packed, layout = pack(host, (n,))
    k = 4

    def keys(p, rng):
        _, extra = unpack(p, layout)
        return jax.random.key_data(
            _fold_in_range(rng, extra["step"][0], k=k))

    got = np.asarray(jax.jit(keys)(packed, key)).tolist()
    # the block's ordinals wrap like the uint32 they travel as
    want = [_key_bits(jax.random.fold_in(key, (n + i) % 2 ** 32))
            for i in range(k)]
    assert got == want


# ---- through the engine ---------------------------------------------------------

def _model_cfg():
    return ModelConfig(
        architecture="LlamaForCausalLM", vocab_size=128, hidden_size=64,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        intermediate_size=128, max_position=256)


def _llm(**kw):
    from gllm_tpu.engine.llm import LLM
    return LLM(config=_config(**kw), model_cfg=_model_cfg())


def _sampled_requests():
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(1, 128, size=n)))
               for n in (5, 9, 13)]
    sps = [SamplingParams(temperature=0.8, top_p=0.9, max_tokens=24,
                          ignore_eos=True, seed=seed)
           for seed in (None, 11, None)]
    return prompts, sps


ENGINES = {
    # name: (engine flags, the runner method that must have run)
    "step_async": ({}, "step_async"),
    "chained": (dict(overlap_scheduling=True), "step_async_chained"),
    # fused blocks exist only under overlap scheduling: they chain
    "step_multi": (dict(overlap_scheduling=True, multi_step_decode=4),
                   "step_multi"),
}


@pytest.fixture(scope="module")
def sampled_tokens():
    """Temperature 0.8, top-p 0.9, 3 requests x 24 tokens, the middle
    one seeded, through each step path; every path folds its keys inside
    its own program."""
    out = {}
    for name, (flags, method) in ENGINES.items():
        llm = _llm(**flags)
        calls = []
        orig = getattr(llm.runner, method)
        setattr(llm.runner, method,
                lambda *a, _o=orig, _c=calls, **k: _c.append(1) or _o(*a,
                                                                      **k))
        prompts, sps = _sampled_requests()
        outs = llm.generate(prompt_token_ids=prompts, sampling_params=sps)
        assert calls, f"{name}: {method} never ran"
        out[name] = [o.output_token_ids for o in outs]
        assert all(len(t) == 24 for t in out[name])
    return out


@pytest.mark.parametrize("path,same_as", [
    # the identity the engine's own tests demand of these paths: a fused
    # block draws what its chained single steps draw (fold_in of
    # consecutive ordinals), unseeded rows too
    ("step_multi", "chained"),
])
def test_sampled_tokens_are_the_same_through_a_fused_block(
        sampled_tokens, path, same_as):
    assert sampled_tokens[path] == sampled_tokens[same_as]


@pytest.mark.parametrize("path", ["chained", "step_multi"])
def test_a_seeded_row_draws_the_same_through_every_step_path(
        sampled_tokens, path):
    # an overlapped loop dispatches in another order, so its unseeded
    # rows fold other ordinals than the sync loop's; a seeded row's key
    # is (seed, out_step) wherever it runs
    assert sampled_tokens[path][1] == sampled_tokens["step_async"][1]
    assert sampled_tokens[path][0] != sampled_tokens[path][1]


# ---- the counter ----------------------------------------------------------------

def _counters():
    from gllm_tpu.runner import runner as R
    return (R._M_H2D.get(),
            R._M_SAMPLER.get(program="greedy")
            + R._M_SAMPLER.get(program="sampled"),
            R._M_NEW_SHAPE.get())


@pytest.fixture(scope="module")
def runner():
    return _llm().runner


def _decode_batch(n=5):
    return ScheduledBatch(_decode_items(
        n, SamplingParams(temperature=0.0, max_tokens=8)))


@pytest.mark.parametrize("kind", ["decode", "mixed"])
def test_a_default_step_places_at_most_three_arrays(runner, kind):
    sched = _decode_batch()
    if kind == "mixed":
        sched = ScheduledBatch(sched.items + [ScheduledSeq(
            _seq(5, 13, 0, SamplingParams(temperature=0.0)), 13, 0)])
    h0, d0, _ = _counters()
    runner.collect(runner.step_async(sched))
    h1, d1, _ = _counters()
    assert d1 - d0 == 1
    # the packed batch and the tokens
    assert h1 - h0 == 2 <= 3


@pytest.mark.parametrize("splice", ["identity", "join", "reformed"])
def test_a_chained_step_runs_the_unchained_steps_program(runner, splice):
    first = runner.step_async(_decode_batch())      # builds the program
    programs = runner._step_fn._cache_size()
    h0, d0, s0 = _counters()
    sched = _decode_batch()
    if splice == "join":
        sched.host_rows = [1]
    elif splice == "reformed":
        sched.src_rows = [4, -1, 2, 0, -1]
    handle = runner.step_async(sched, prev_handle=first)
    tokens, _ = runner.collect(handle)
    runner.collect(first)
    h1, d1, s1 = _counters()
    assert len(tokens) == 5 and d1 - d0 == 1
    # one program per shape, chained or not: no new signature, and the
    # jit cache of the step function has what it had
    assert s1 == s0
    assert runner._step_fn._cache_size() == programs
    # an identity chain never places its host tokens; a join places them
    # and its row mask, a re-formed batch them and its index array
    assert h1 - h0 == {"identity": 1, "join": 3, "reformed": 3}[splice]


def test_the_manifest_names_the_counters_reader():
    """``runner.h2d_arrays_per_step`` is in BENCHMARK.json and
    perfbench/layer_metrics/ has its reader under that name (the check
    of every entry is tests/perfbench/test_manifest.py's)."""
    import importlib.util
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "runner.h2d_arrays_per_step"]
    assert entry == [{
        "name": "runner.h2d_arrays_per_step", "unit": "count",
        "better": "lower", "source": "program_counter", "layer": "runner",
        "moves": "output_tok_s",
        "workloads": ["qwen3-4b.reason", "olmo-hybrid-7b.reason",
                      "dots3-note-prev.longdoc", "a.x-k1.docqa",
                      "nemotron-3-nano-30b-a3b.reason",
                      "command-a-plus-05-2026.docqa",
                      "falcon-h1-34b-instruct.reason",
                      "lfm2-24b-a2b.reason"]}]
    path = os.path.join(root, "perfbench", "layer_metrics",
                        "runner.h2d_arrays_per_step.py")
    spec = importlib.util.spec_from_file_location("h2d_reader", path)
    reader = importlib.util.module_from_spec(spec)
    import sys
    sys.path.insert(0, os.path.join(root, "perfbench"))
    try:
        spec.loader.exec_module(reader)
    finally:
        sys.path.remove(os.path.join(root, "perfbench"))

    def prom(arrays, greedy, sampled):
        lines = [f'gllm_sampler_program_total{{program="greedy"}} {greedy}',
                 f'gllm_sampler_program_total{{program="sampled"}} '
                 f'{sampled}']
        if arrays is not None:
            lines.append(f"gllm_step_h2d_arrays_total {arrays}")
        return "\n".join(lines) + "\n"

    run = {"prom0": prom(10, 4, 1), "prom1": prom(50, 20, 5)}
    assert reader.read(run) == 2.0
    # a program without the counter (the parent commit): nothing to read
    assert reader.read({"prom0": prom(None, 4, 1),
                        "prom1": prom(None, 20, 5)}) is None
    assert reader.read({"prom0": None, "prom1": None}) is None
