"""dots3_note on the normal path at small sizes (CPU, float32, seeded random
weights): the expert layer as one share of eight, the blocked selection
against the dense latent path, and the fences (the windowed layers'
bounded storage, the counters and the comparison with the plain reference,
chunked prefill and then decode through the caches, share one served run in
tests/perfbench/test_reference_dots3.py)."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gllm_tpu.config import (CacheConfig, EngineConfig, ParallelConfig,
                             SchedulerConfig)
from gllm_tpu.models import deepseek
from gllm_tpu.models.config import from_hf_config
from gllm_tpu.sampling_params import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference(name="dots3_note"):
    path = os.path.join(ROOT, "perfbench", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("t_ref_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()

TINY = dict(
    model_type="dots3_note", vocab_size=256, hidden_size=64,
    num_hidden_layers=5, layer_types=[
        "full_attention", "full_attention", "sliding_attention",
        "sliding_attention", "sliding_attention"],
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=96,
    max_position_embeddings=512, rms_norm_eps=1e-5,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_theta=80000000,
    first_k_dense_replace=1, n_routed_experts=4, num_experts_per_tok=2,
    moe_intermediate_size=32, n_shared_experts=1, routed_scaling_factor=1,
    scoring_func="sigmoid", topk_method="noaux_tc", norm_topk_prob=True,
    index_n_heads=4, index_head_dim=16, index_topk=24,
    sliding_window_size=13, swa_num_attention_heads=2,
    swa_q_lora_rank=32, swa_kv_lora_rank=48, swa_qk_nope_head_dim=24,
    swa_qk_rope_head_dim=8, swa_v_head_dim=16, swa_rope_theta=50000,
    attention_gate_type="headwise", swa_attention_gate_type="headwise",
    apply_mla_qkv_lora_rescale=True,
    ep_share={"chips": 8, "rank": 0, "n_routed_experts": 32})


def engine(model=TINY, **kw):
    from gllm_tpu.engine.llm import LLM
    cache = kw.pop("cache", {})
    return LLM(config=EngineConfig(
        load_format="dummy", dtype="float32", max_model_len=256,
        max_num_seqs=8, **kw,
        scheduler=SchedulerConfig(max_prefill_tokens=32, max_decode_seqs=8),
        cache=CacheConfig(page_size=4, num_pages=256, **cache)),
        model_cfg=from_hf_config(model))


def test_config_reads_both_geometries_and_the_share():
    cfg = from_hf_config(TINY)
    assert cfg.architecture == "Dots3NoteForCausalLM"
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_first) == (32, 4, 0)
    assert from_hf_config(dict(TINY, ep_share=dict(
        TINY["ep_share"], rank=5))).expert_first == 20
    assert cfg.use_swa and cfg.use_dsa and not cfg.use_hybrid
    assert (cfg.num_attn_layers, cfg.num_swa_layers) == (2, 3)
    assert deepseek.layer_runs(cfg) == (
        ("full_attention", "dense", 1), ("full_attention", "moe", 1),
        ("sliding_attention", "moe", 3))
    full, swa = deepseek.geom(cfg), deepseek.geom(cfg, deepseek.SWA)
    assert (full.heads, full.lora, full.window) == (4, 32, 0)
    assert (swa.heads, swa.lora, swa.nope, swa.window) == (2, 48, 24, 13)
    assert swa.scale == 32 ** -0.5 and full.scale == 24 ** -0.5
    # rows as stored are whole lanes; a ring is ceil(13 / 4) + 1 pages
    assert cfg.mla_cache_width == cfg.swa_cache_width == 128
    assert cfg.swa_ring_len(4) == 20 and cfg.swa_ring_len(16) == 32
    with pytest.raises(ValueError, match="ep_share"):
        from_hf_config(dict(TINY, ep_share=dict(TINY["ep_share"], chips=4)))


def test_published_config_file_keeps_the_catalogs_widths():
    import json
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "dots3-note-prev.json")) as f:
        hf = json.load(f)
    cfg = from_hf_config(hf)
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (5120, 128, 1024, 512, 128, 64, 128)
    assert (cfg.swa_num_heads, cfg.swa_q_lora_rank, cfg.swa_kv_lora_rank,
            cfg.swa_qk_nope_head_dim, cfg.swa_qk_rope_head_dim,
            cfg.swa_v_head_dim, cfg.sliding_window) == (
                64, 1024, 1024, 192, 64, 128, 513)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        64, 128, 2048)
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.intermediate_size) == (
                256, 32, 8, 1536, 13824)
    assert (cfg.mla_cache_width, cfg.swa_cache_width) == (640, 1152)
    assert cfg.swa_ring_len(16) == 34 * 16
    assert cfg.attn_gate == cfg.swa_attn_gate == "headwise"
    assert cfg.mla_lora_rescale and cfg.rope_theta == 8e7
    assert cfg.swa_rope_theta == 5e4 and cfg.rms_norm_eps == 1e-5


# ---- the expert layer as a share ------------------------------------------

# A.X-K1's router at small widths: sigmoid, the plain top-8 of all 192
# (``topk_method`` "none" beside inert ``n_group`` / ``topk_group``),
# normalised, times 2.5; one of 16 chips holds 12 experts
TINY_AXK1 = dict(
    model_type="axk1", vocab_size=256, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=96,
    max_position_embeddings=512, rms_norm_eps=1e-6, q_lora_rank=32,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=10000, first_k_dense_replace=1, n_routed_experts=12,
    num_experts_per_tok=8, moe_intermediate_size=32, n_shared_experts=1,
    routed_scaling_factor=2.5, scoring_func="sigmoid", topk_method="none",
    n_group=8, topk_group=4, norm_topk_prob=True,
    ep_share={"chips": 16, "rank": 0, "n_routed_experts": 192})


def _moe_layer(seed=3, bias=None, tiny=TINY, e=32):
    """An uncut expert layer at ``tiny``'s sizes: (uncut model dict,
    reference layer dict with all ``e`` experts, x [T, H])."""
    uncut = dict(tiny, n_routed_experts=e)
    del uncut["ep_share"]
    rng = np.random.default_rng(seed)
    h, i = 64, 32

    def w(*shape, scale):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)
    layer = dict(
        router=w(h, e, scale=h ** -0.5),
        e_bias=jnp.zeros((e,), jnp.float32) if bias is None else bias,
        w_gate=w(e, h, i, scale=h ** -0.5), w_up=w(e, h, i, scale=h ** -0.5),
        w_down=w(e, i, h, scale=i ** -0.5),
        shared_gate_proj=w(h, i, scale=h ** -0.5),
        shared_up_proj=w(h, i, scale=h ** -0.5),
        shared_down_proj=w(i, h, scale=i ** -0.5))
    return uncut, layer, w(37, h, scale=1.0)


@pytest.mark.parametrize("case", ["even", "skewed", "axk1_16x12"])
def test_eight_shares_and_the_shared_expert_once_make_the_uncut_layer(case):
    """The guide's share test: the routed parts that the eight shares of
    four experts give, plus what every chip computes alike (the shared
    expert) counted once, add up to the uncut reference's layer. ``skewed``
    biases the router onto share 0, so that it holds more than the quarter
    of all assignments one pass of its loop takes. ``axk1_16x12``: sixteen
    shares of twelve of 192 experts at A.X-K1's router (sigmoid, the plain
    top-8, scaling 2.5, no bias), against that model's own reference."""
    bias, tiny, ref, chips, held_n, k = None, TINY, REF, 8, 4, 2
    if case == "skewed":
        bias = jnp.zeros((32,), jnp.float32).at[:4].set(5.0)
    if case == "axk1_16x12":
        tiny, ref, chips, held_n, k = (TINY_AXK1, _reference("axk1"), 16,
                                       12, 8)
    uncut, layer, x = _moe_layer(bias=bias, tiny=tiny, e=chips * held_n)
    if case == "axk1_16x12":
        del layer["e_bias"]     # topk_method "none": the layer has none
    with jax.default_matmul_precision("highest"):
        want = (ref.routed_part(uncut, x, layer, ref._mm)
                + ref.shared_part(x, layer, ref._mm))
        valid = jnp.arange(x.shape[0]) < 33        # four padding rows
        shared = deepseek._shared_expert(layer, x)
        total, held_sum = shared.astype(jnp.float32), 0
        for rank in range(chips):
            cfg = from_hf_config(dict(tiny, ep_share=dict(
                tiny["ep_share"], rank=rank)))
            assert cfg.route_groups == 0
            lp = dict(layer, **{
                name: layer[name][held_n * rank:held_n * (rank + 1)]
                for name in ("w_gate", "w_up", "w_down")})
            out, stats = deepseek._moe_block(lp, x, cfg, valid)
            total = total + (out - shared)
            held, absent, touched, layers = (int(v) for v in stats)
            assert held + absent == 33 * k and layers == 1
            assert 0 <= touched <= held_n
            if case == "skewed" and rank == 0:
                assert held > 37 * 2 // 4       # a second pass of the loop
            held_sum += held
        assert held_sum == 33 * k
    np.testing.assert_allclose(np.asarray(total[:33]), np.asarray(want[:33]),
                               rtol=2e-5, atol=2e-5)


def test_whole_expert_layer_is_what_it_was():
    """``experts_held`` 0 (every DeepSeek config): the layer computes all
    its experts, as the reference does with all of them held."""
    uncut, layer, x = _moe_layer(seed=4)
    cfg = dataclasses.replace(from_hf_config(TINY), experts_held=0)
    with jax.default_matmul_precision("highest"):
        out, stats = deepseek._moe_block(layer, x, cfg)
        want = (REF.routed_part(uncut, x, layer, REF._mm)
                + REF.shared_part(x, layer, REF._mm))
    assert stats is None
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---- selection ---------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_selection_that_takes_everything_equals_the_dense_latent_path(impl):
    """Contexts under ``index_topk``: the blocked selection takes every
    visible position and has to give the dense latent path's tokens (the
    same parameters; the dense path never reads the indexer's). Tier-1 holds
    the same code to the same claim at DeepSeek-V3.2's keys:
    tests/test_dsa.py::test_dsa_sparse_equals_dense_when_topk_covers.
    ``pallas`` (interpret mode): the decoding rows under a mask that takes
    everything on the decode kernel, against the same kernel unmasked on
    the dense side."""
    model = dict(TINY, index_topk=200, layer_types=["full_attention"] * 5,
                 num_hidden_layers=5)
    cfg = from_hf_config(model)
    dense = dataclasses.replace(cfg, index_topk=0)
    params = deepseek.init_params(cfg, seed=5, dtype=jnp.float32)
    from gllm_tpu.engine.llm import LLM

    def run(mcfg):
        llm = LLM(config=EngineConfig(
            load_format="dummy", dtype="float32", max_model_len=256,
            max_num_seqs=8, attention_impl=impl,
            scheduler=SchedulerConfig(max_prefill_tokens=32,
                                      max_decode_seqs=8),
            cache=CacheConfig(page_size=4, num_pages=256)),
            model_cfg=mcfg, params=params)
        rng = np.random.default_rng(2)
        prompts = [[int(t) for t in rng.integers(2, 256, n)]
                   for n in (70, 9)]
        return [o.output_token_ids for o in llm.generate(
            prompt_token_ids=prompts, sampling_params=SamplingParams(
                temperature=0.0, max_tokens=12, ignore_eos=True))]
    assert run(cfg) == run(dense)


# ---- fences ----------------------------------------------------------------

@pytest.mark.parametrize("kw, what", [
    (dict(cache=dict(enable_prefix_caching=True)), "prefix-caching"),
    (dict(cache=dict(kv_host_pool_gb=0.5)), "host or disk KV tier"),
    (dict(spec_decode="ngram"), "spec-decode"),
    (dict(multi_step_decode=4), "multi-step"),
    (dict(ondevice_finish=True, overlap_scheduling=True), "multi-step"),
    (dict(parallel=ParallelConfig(tp=2)), "tp / pp / dp"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_windowed_layers_refuse_what_needs_old_rows_again(kw, what):
    with pytest.raises(ValueError, match="windowed latent") as ei:
        engine(**kw)
    assert what in str(ei.value)


def test_no_checkpoint_rules_for_windowed_latent_layers(tmp_path):
    from gllm_tpu.models import loader
    with pytest.raises(NotImplementedError, match="load-format dummy"):
        loader.load_deepseek_params(str(tmp_path), from_hf_config(TINY))


@pytest.mark.parametrize("floor, bucket", [(None, 64), (512, 512),
                                           (4096, 2048 + 64)])
def test_min_token_bucket_is_the_floor_of_a_mixed_step(floor, bucket):
    """``--min-token-bucket`` (16 unless given) is the smallest token
    bucket of a mixed step for every model: 40 new tokens beside seven
    decoding rows take the 64-token bucket, the floor where one is set,
    and never more than the largest step there is. A decode step's
    bucket is its rows' either way."""
    from gllm_tpu.entrypoints.api_server import (build_engine_config,
                                                 make_parser)
    from gllm_tpu.runner.prepare import BatchBuilder
    from gllm_tpu.scheduler import ScheduledBatch, ScheduledSeq
    from gllm_tpu.sequence import Sequence
    argv = ["--model", "m", "--maxd", "64", "--max-num-seqs", "64"]
    if floor:
        argv += ["--min-token-bucket", str(floor)]
    config = build_engine_config(make_parser().parse_args(argv))
    assert config.scheduler.min_token_bucket == (floor or 16)

    def batch(lens):
        items = []
        for i, n in enumerate(lens):
            seq = Sequence(i, [1] * n, SamplingParams(max_tokens=4))
            seq.page_table = list(range(1, 2 + n // 16))
            items.append(ScheduledSeq(seq, n, 0))
        return ScheduledBatch(items)

    builder = BatchBuilder(config, 16)
    assert builder.shape_signature(batch([1] * 7 + [40]))[0] == bucket
    assert builder.shape_signature(batch([1] * 7))[:3] == (8, 8, 1)


@pytest.mark.parametrize("argv, mixed, decode", [
    ([], (64, 8, 64, 4), (8, 8, 1, 4)),
    (["--min-row-bucket", "32"], (64, 32, 64, 4), (32, 32, 1, 4)),
    (["--min-row-bucket", "64", "--min-page-bucket", "1024"],
     (64, 64, 64, 16), (64, 64, 1, 16)),
    (["--min-row-bucket", "4096", "--min-token-bucket", "512"],
     (512, 64, 512, 4), (64, 64, 1, 4)),
])
def test_min_row_and_page_buckets_are_floors_of_every_step(argv, mixed,
                                                           decode):
    """``--min-row-bucket`` and ``--min-page-bucket`` (8 and 4 unless
    given) are the smallest row bucket and page-table width of a step,
    for every model, each capped at the largest there is (``--max-num-
    seqs`` rows; ``--max-model-len`` 256 in pages of 16): a server that
    sets both to its largest builds one program a token bucket. A step
    has a token for every row, so the row floor is a floor of the token
    bucket too."""
    from gllm_tpu.entrypoints.api_server import (build_engine_config,
                                                 make_parser)
    from gllm_tpu.runner.prepare import BatchBuilder
    from gllm_tpu.scheduler import ScheduledBatch, ScheduledSeq
    from gllm_tpu.sequence import Sequence
    config = build_engine_config(make_parser().parse_args(
        ["--model", "m", "--maxd", "64", "--max-num-seqs", "64",
         "--max-model-len", "256"] + argv))

    def batch(lens):
        items = []
        for i, n in enumerate(lens):
            seq = Sequence(i, [1] * n, SamplingParams(max_tokens=4))
            seq.page_table = list(range(1, 2 + n // 16))
            items.append(ScheduledSeq(seq, n, 0))
        return ScheduledBatch(items)

    builder = BatchBuilder(config, 16)
    assert builder.shape_signature(batch([1] * 7 + [40])) == mixed
    assert builder.shape_signature(batch([1] * 7)) == decode


# ---- a windowed layer's chunk: decompressed keys against the absorbed form --

def _swa_step(lp, g, rows, ring, seqs, T, S, max_q_len):
    """One step of ``_swa_attention`` over ``seqs`` ((slot, positions
    before the step, tokens), ...), padded to T tokens and S sequences;
    ``rows``: slot -> (q_nope, q_pe, entry) of every position. Returns
    (out [tokens, H, v], ring)."""
    from gllm_tpu.batching import StepBatch
    from gllm_tpu.ops.attention import AttentionMetadata
    n = sum(q for _, _, q in seqs)
    take = lambda i: np.concatenate(
        [rows[s][i][c:c + q] for s, c, q in seqs]
        + [np.zeros((T - n,) + rows[seqs[0][0]][i].shape[1:], np.float32)])
    pad = lambda a, to: np.asarray(list(a) + [0] * (to - len(a)), np.int32)
    q_lens = [q for _, _, q in seqs]
    batch = StepBatch(
        token_ids=None, slot_mapping=None, logits_indices=None, sampling=None,
        positions=pad([p for _, c, q in seqs for p in range(c, c + q)], T),
        ssm_slots=pad([s for s, _, _ in seqs], S),
        attn=AttentionMetadata(
            np.concatenate([[0], np.cumsum(pad(q_lens, S))]).astype(np.int32),
            pad([c + q for _, c, q in seqs], S), None, np.int32(len(seqs))))
    out, ring = _SWA_JIT(lp, take(0), take(1), take(2), batch, ring, 0,
                         max_q_len=max_q_len, g=g)
    return np.asarray(out)[:n], ring


_SWA_JIT = jax.jit(deepseek._swa_attention,
                   static_argnames=("max_q_len", "g"))


@pytest.mark.parametrize("seqs", [
    pytest.param([(0, 40)], id="first_chunk_empty_ring"),
    pytest.param([(300, 300)], id="later_chunk_full_ring"),
    pytest.param([(7, 5)], id="shorter_than_one_item"),
    pytest.param([(30, 150), (0, 131)], id="two_chunked_sequences"),
    pytest.param([(50, 1), (9, 140), (3, 1), (0, 1)],
                 id="chunk_beside_decoding_rows"),
    pytest.param([(21, 13)], id="chunk_of_exactly_window_tokens"),
])
def test_a_chunk_attends_decompressed_what_its_tokens_attend_absorbed(seqs):
    """A step that carries a chunk (decompressed keys for the chunk's
    tokens, one loop item of 128 queries after the other) against the same
    tokens one decode-only step each (the absorbed form), float32 at the
    rehearsal's widths: the results and the rings they leave."""
    cfg = from_hf_config(TINY)
    g = deepseek.geom(cfg, deepseek.SWA)
    assert (g.heads, g.lora, g.nope, g.rope, g.v, g.window) == (
        2, 48, 24, 8, 16, 13)
    R = cfg.swa_ring_len(8)
    rng = np.random.default_rng(len(seqs) * 1000 + seqs[0][1])
    draw = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    lp = {"w_uk": draw(g.heads, g.nope, g.lora) * g.lora ** -0.5,
          "w_uv": draw(g.heads, g.lora, g.v) * g.lora ** -0.5}
    seqs = [(slot, c, q) for slot, (c, q) in enumerate(seqs, start=1)]
    rows = {}
    for slot, c, q in seqs:
        entry = np.zeros((c + q, g.width), np.float32)
        entry[:, :g.lora + g.rope] = draw(c + q, g.lora + g.rope)
        rows[slot] = (draw(c + q, g.heads, g.nope),
                      draw(c + q, g.heads, g.rope), entry)
    n_slots = len(seqs) + 1

    # every position a decode-only step of its own, from an empty ring
    ring = jnp.zeros((n_slots, R, g.width), jnp.float32)
    want = []
    for p in range(max(c + q for _, c, q in seqs)):
        live = [(s, p, 1) for s, c, q in seqs if p < c + q]
        out, new = _swa_step(lp, g, rows, ring, live, len(seqs), len(seqs),
                             max_q_len=1)
        want.append({s: out[i] for i, (s, _, _) in enumerate(live)})
        ring = new
    after = np.asarray(ring)

    # the rings as each sequence's context left them, then the step
    ring = np.zeros((n_slots, R, g.width), np.float32)
    for slot, c, _ in seqs:
        for p in range(max(0, c - R), c):
            ring[slot, p % R] = rows[slot][2][p]
    n = sum(q for _, _, q in seqs)
    got, ring = _swa_step(lp, g, rows, jnp.asarray(ring), seqs,
                          -(-(n + 3) // 8) * 8, len(seqs) + 2,
                          max_q_len=max(q for _, _, q in seqs))
    at = 0
    for slot, c, q in seqs:
        np.testing.assert_allclose(
            got[at:at + q], np.stack([want[p][slot] for p in range(c, c + q)]),
            rtol=1e-5, atol=1e-5, err_msg=f"slot {slot}")
        at += q
    np.testing.assert_array_equal(np.asarray(ring)[1:], after[1:])


def test_tokens_are_counted_by_the_form_that_attended_them():
    """A prompt of 40 tokens in chunks of 32 and 8 (each more than one
    token: decompressed), then the sampled tokens fed back one a step
    (absorbed; the last is never fed), times three windowed layers."""
    got = lambda: {f: deepseek._M_SWA_TOKENS.get(form=f)
                   for f in ("decompressed", "absorbed")}
    llm, before = engine(), got()
    llm.generate(prompt_token_ids=[list(range(2, 42))],
                 sampling_params=[SamplingParams(
                     temperature=0.0, max_tokens=5, ignore_eos=True)])
    assert {f: n - before[f] for f, n in got().items()} == {
        "decompressed": 3 * 40, "absorbed": 3 * 4}
