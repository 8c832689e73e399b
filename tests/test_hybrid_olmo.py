"""Olmo-Hybrid (GDN + plain full attention in the OLMo 3 block) on the
normal path, at a small size on the CPU: seeded random weights, logits not
tokens. The comparison with the plain reference is
tests/perfbench/test_reference_olmo_hybrid.py; here: what the architecture
forced in the program."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.models import hybrid
from gllm_tpu.models.config import from_hf_config
from gllm_tpu.ops import gdn
from gllm_tpu.sampling_params import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "perfbench", "configs",
                       "olmo-hybrid-7b.json")) as f:
    PUBLISHED = json.load(f)
TINY = dict(PUBLISHED, **PUBLISHED["rehearsal"]["model"])
SEED = 2 ** 31 + 9


def make_llm(attention_impl="auto", model=TINY, **sched):
    from gllm_tpu.engine.llm import LLM
    return LLM(config=EngineConfig(
        load_format="dummy", dtype="float32", seed=SEED, max_model_len=256,
        max_num_seqs=8, attention_impl=attention_impl,
        scheduler=SchedulerConfig(max_decode_seqs=8, **sched),
        cache=CacheConfig(page_size=4, num_pages=256)),
        model_cfg=from_hf_config(model))


def test_config_reads_the_catalogs_keys():
    cfg = from_hf_config({k: v for k, v in PUBLISHED.items()
                          if k != "architectures"})    # model_type alone
    assert cfg.architecture == "OlmoHybridForCausalLM" and cfg.use_hybrid
    assert (cfg.num_layers, cfg.num_attn_layers, cfg.num_linear_layers) == \
        (16, 4, 12)
    assert (cfg.linear_key_head_dim, cfg.linear_value_head_dim) == (96, 192)
    assert cfg.head_dim == 128 and cfg.num_kv_heads == 30
    assert cfg.kv_cache_heads == 32        # the cache's tile-aligned count
    assert cfg.linear_allow_neg_eigval and not cfg.use_rope
    assert cfg.norm_after and cfg.qk_norm_full
    assert not cfg.attn_output_gate and not cfg.gdn_grouped_proj
    assert hybrid.period_pattern(cfg) == ("linear_attention",) * 3 + (
        "full_attention",)
    # the Qwen3-Next block keeps its defaults
    q = from_hf_config(dict(TINY, architectures=["Qwen3NextForCausalLM"]))
    assert q.use_rope and q.attn_output_gate and not q.norm_after
    assert not q.linear_allow_neg_eigval and q.gdn_grouped_proj


def test_mixed_step_rows_equal_the_same_rows_run_apart():
    """A prefill budget of 16 tokens: prompt A (8 tokens) decodes while
    prompt B (60 tokens) is still prefilling, so steps 2-5 each carry a
    decoding row and a prefilling row: the recurrent step and the packed
    chunked rule side by side. Every logprob must be what the same
    sequence gives when it is served alone, to 5e-3 of a logprob: the
    chunks fall elsewhere, and the chunked rule's float32 sums in another
    order differ by up to 1.4e-3 here (the reference comparison reads the
    same 1e-3 at its worst position); a row that took the wrong state or
    the wrong rule is off by 1e-1 and more."""
    rng = np.random.default_rng(4)
    a = [int(t) for t in rng.integers(2, 500, 8)]
    b = [int(t) for t in rng.integers(2, 500, 60)]
    spa = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True,
                         logprobs=3)
    spb = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True,
                         logprobs=3, prompt_logprobs=1)

    def served(llm, prompts, sps):
        outs = llm.generate(prompt_token_ids=prompts, sampling_params=sps)
        return [(o.output_token_ids,
                 np.array([lps for _, _, lps in o.logprobs]),
                 None if o.prompt_logprobs is None else
                 np.array([float(t[0]) for t in o.prompt_logprobs[1:]]))
                for o in outs]

    together = served(make_llm(max_prefill_tokens=16), [a, b], [spa, spb])
    alone_a = served(make_llm(max_prefill_tokens=16), [a], [spa])[0]
    alone_b = served(make_llm(max_prefill_tokens=16), [b], [spb])[0]
    for got, want in zip(together, (alone_a, alone_b)):
        assert got[0] == want[0]
        np.testing.assert_allclose(got[1], want[1], atol=5e-3)
        if want[2] is not None:
            np.testing.assert_allclose(got[2], want[2], atol=5e-3)


@pytest.mark.parametrize("value_head_dim,rec_slot", [
    (24, (4, 12, 24)), (64, (2, 12, 128))], ids=["alone", "abreast"])
def test_pallas_kernels_serve_what_xla_serves(value_head_dim, rec_slot):
    """``attention_impl="pallas"`` on the CPU (interpret mode): the paged
    attention kernels with 4 KV heads and the two in-place GDN kernels,
    in decode-only and in mixed steps, against the XLA paths, over a slot
    pool whose states lie each head alone or two abreast
    (``ops/gdn.pack_state``: the XLA rule unpacks what it gathers)."""
    model = dict(TINY, linear_value_head_dim=value_head_dim)
    assert from_hf_config(model).ssm_slot_shapes[1] == rec_slot
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(2, 500, n)] for n in (8, 30)]
    sp = SamplingParams(temperature=0.0, max_tokens=5, ignore_eos=True,
                        logprobs=3)
    got, want = ([(o.output_token_ids,
                   np.array([lps for _, _, lps in o.logprobs]))
                  for o in make_llm(impl, model=model,
                                    max_prefill_tokens=16).generate(
                      prompt_token_ids=prompts, sampling_params=[sp, sp])]
                 for impl in ("pallas", "xla"))
    for g, w in zip(got, want):
        assert g[0] == w[0]
        np.testing.assert_allclose(g[1], w[1], atol=2e-3)


def test_beta_above_one_occurs_in_the_served_model():
    cfg = from_hf_config(TINY)
    params = hybrid.init_params(cfg, seed=SEED, dtype=jnp.float32)
    lp = jax.tree.map(lambda x: x[0], params["gdn_layers"])
    x = params["embed"][jnp.arange(2, 130)]
    _, _, g, beta = hybrid._gdn_project(lp, x, cfg)
    assert float(beta.max()) > 1.5 and float(beta.min()) >= 0.0
    assert 0.25 < float((beta > 1.0).mean()) < 0.75
    assert float(g.max()) < 0.0
    q = dataclasses.replace(cfg, linear_allow_neg_eigval=False)
    assert float(hybrid._gdn_project(lp, x, q)[3].max()) <= 1.0


@pytest.mark.parametrize("lens", [[40, 1, 70, 17], [64, 64], [5]])
def test_packed_chunk_rule_is_the_recurrent_step_token_by_token(lens):
    """``chunk_gated_delta_rule_packed`` over sequences cut into chunks and
    laid end to end, with states entering, against the recurrent step run
    token by token over each sequence, at head dims that are neither equal
    nor 128-aligned and beta up to 2. (The per-sequence
    ``chunk_gated_delta_rule``, which tests/test_gdn_ops.py holds to the
    transformers rule, is this same packed rule behind a reshape.)"""
    rng = np.random.default_rng(7)
    H, Dk, Dv, C = 3, 12, 24, 16
    n_ch = [-(-n // C) for n in lens]
    N = sum(n_ch) + 2                                  # two spare chunks
    q = np.zeros((N, C, H, Dk), np.float32)
    k, v = np.zeros_like(q), np.zeros((N, C, H, Dv), np.float32)
    g, beta = np.zeros((N, C, H), np.float32), np.zeros((N, C, H), np.float32)
    row = np.full(N, len(lens), np.int32)
    first = np.zeros(N, bool)
    states = rng.standard_normal((len(lens) + 1, H, Dk, Dv)).astype(
        np.float32)
    want_out, want_state, c0 = [], [], 0
    for r, n in enumerate(lens):
        sq, sk = (rng.standard_normal((n, H, Dk)).astype(np.float32)
                  for _ in range(2))
        sv = rng.standard_normal((n, H, Dv)).astype(np.float32)
        sg = -rng.uniform(0.05, 1.0, (n, H)).astype(np.float32)
        sb = rng.uniform(0.0, 2.0, (n, H)).astype(np.float32)
        state, outs = jnp.asarray(states[r])[None], []
        for t in range(n):
            o, state = gdn.recurrent_gated_delta_step(
                *(jnp.asarray(a[t])[None] for a in (sq, sk, sv, sg, sb)),
                state)
            outs.append(np.asarray(o[0]))
        want_out.append(np.stack(outs))
        want_state.append(np.asarray(state[0]))
        for arr, src in ((q, sq), (k, sk), (v, sv), (g, sg), (beta, sb)):
            flat = arr[c0:c0 + n_ch[r]].reshape((n_ch[r] * C,)
                                                + arr.shape[2:])
            flat[:n] = src
            arr[c0:c0 + n_ch[r]] = flat.reshape(arr[c0:c0 + n_ch[r]].shape)
        row[c0:c0 + n_ch[r]] = r
        first[c0] = True
        c0 += n_ch[r]
    out, new = gdn.chunk_gated_delta_rule_packed(
        *(jnp.asarray(a) for a in (q, k, v, g, beta, row, first, states)))
    c0 = 0
    for r, n in enumerate(lens):
        got = np.asarray(out[c0:c0 + n_ch[r]]).reshape(-1, H, Dv)[:n]
        np.testing.assert_allclose(got, want_out[r], rtol=1e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(new[r]), want_state[r],
                                   rtol=1e-3, atol=2e-4)
        c0 += n_ch[r]


def test_recurrent_step_with_beta_up_to_two_matches_transformers():
    import torch
    from transformers.models.qwen3_next.modeling_qwen3_next import (
        torch_recurrent_gated_delta_rule)
    rng = np.random.default_rng(8)
    S, H, Dk, Dv = 5, 3, 12, 24
    q, k = (rng.standard_normal((S, H, Dk)).astype(np.float32)
            for _ in range(2))
    v = rng.standard_normal((S, H, Dv)).astype(np.float32)
    g = -rng.uniform(0.05, 1.0, (S, H)).astype(np.float32)
    beta = rng.uniform(1.0, 2.0, (S, H)).astype(np.float32)
    state = rng.standard_normal((S, H, Dk, Dv)).astype(np.float32)
    out, new = gdn.recurrent_gated_delta_step(
        *(jnp.asarray(a) for a in (q, k, v, g, beta, state)))
    want, want_state = torch_recurrent_gated_delta_rule(
        *(torch.from_numpy(a)[:, None] for a in (q, k, v, g, beta)),
        initial_state=torch.from_numpy(state), output_final_state=True,
        use_qk_l2norm_in_kernel=True)
    np.testing.assert_allclose(np.asarray(out), want[:, 0].numpy(),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new), want_state.numpy(),
                               rtol=2e-4, atol=2e-5)


def test_packed_layout_is_sized_by_the_tokens_that_prefill():
    # 31 decoding rows and a prompt of 512 in the 1024-token bucket: 16
    # chunks of real tokens at most, 32 in the layout (2048 token slots;
    # rows x longest row was 32 x 1024 = 32768)
    assert gdn.gdn_chunk_slots(1024, 32) == (32, 64)
    assert gdn.gdn_chunk_slots(256, 8) == (8, 64)
    assert gdn.gdn_chunk_slots(16, 8) == (2, 16)
    assert gdn.gdn_chunks_needed([1] * 31 + [512], 64) == 8
    assert gdn.gdn_chunks_needed([65, 2, 1, 64], 64) == 4


def test_signature_takes_the_bucket_whose_layout_holds_the_step():
    from gllm_tpu.runner.prepare import BatchBuilder
    from gllm_tpu.scheduler import ScheduledBatch, ScheduledSeq
    from gllm_tpu.sequence import Sequence
    config = EngineConfig(load_format="dummy", max_model_len=256,
                          max_num_seqs=32,
                          cache=CacheConfig(page_size=4, num_pages=256))

    def batch(lens):
        items = []
        for i, n in enumerate(lens):
            seq = Sequence(i, [1] * n, SamplingParams(max_tokens=4))
            seq.page_table = list(range(1, 2 + n // 4))
            items.append(ScheduledSeq(seq, n, 0))
        return ScheduledBatch(items)

    dense = BatchBuilder(config, 4)
    ssm = BatchBuilder(config, 4, use_ssm=True)
    # one prompt beside decoding rows: the same bucket as a dense model's
    one = batch([1] * 7 + [40])
    assert ssm.shape_signature(one) == dense.shape_signature(one)
    # twelve prompts of 5 tokens: 60 tokens, the 64-token bucket holds one
    # chunk and one more; twelve chunks need the 512-token bucket (8 + 8)
    many = batch([5] * 12)
    assert dense.shape_signature(many)[0] == 64
    t, s, q, _ = ssm.shape_signature(many)
    assert (t, s, q) == (512, 16, 512)
    n, c = gdn.gdn_chunk_slots(t, s)
    assert gdn.gdn_chunks_needed([5] * 12, c) <= n


def test_largest_bucket_holds_every_step_the_scheduler_forms():
    """At the default --maxd 256 / --maxp 2048: a hundred waiting prompts
    of 20 tokens would need 100 chunks, the largest layout (2304 tokens)
    has 72; the scheduler stops at the 36 rows with more than one new
    token that layout takes beside its tokens, so ``shape_signature``
    never leaves the buckets the token budget defines (it used to double
    the token bucket to 4 x 2304, a shape nothing warms up and twice and
    more what the pool's sizing reserved) and the reserve for the chunked
    rule's temporaries is that of the largest layout."""
    from gllm_tpu.memory_manager import make_memory_manager
    from gllm_tpu.runner.prepare import BatchBuilder
    from gllm_tpu.scheduler import Scheduler
    from gllm_tpu.sequence import Sequence
    config = EngineConfig(load_format="dummy", max_model_len=4096,
                          cache=CacheConfig(page_size=16, num_pages=4096))
    sc = config.scheduler
    assert (sc.max_decode_seqs, sc.max_prefill_tokens) == (256, 2048)
    builder = BatchBuilder(config, 16, use_ssm=True)
    assert builder.max_tokens == 2304
    cap = gdn.gdn_chunk_rows_cap(builder.max_tokens)
    assert cap == 36
    mm = make_memory_manager(4096, 16, False, ssm_working_slots=257)
    sched = Scheduler(config, mm)
    for i in range(100):
        sched.add_seq(Sequence(i, [3] * 20, SamplingParams(max_tokens=4)))
    seen = 0
    while sched.has_unfinished:
        batch = sched.schedule_once()
        rows = [it.num_new_tokens for it in batch.items]
        assert sum(r > 1 for r in rows) <= cap
        t, s, q, _ = builder.shape_signature(batch)
        assert t <= builder.max_tokens
        n, c = gdn.gdn_chunk_slots(t, s)
        assert gdn.gdn_chunks_needed(rows, c) <= n
        assert n * c <= 72 * 64
        seen = max(seen, sum(r > 1 for r in rows))
        sched.process_output(batch, [7] * batch.num_seqs, 2)
    assert seen == cap
    # the worst step under the cap: 31 rows of 65 tokens (two chunks
    # each) and 5 of 2 beside 220 decoding rows
    worst = [65] * 31 + [2] * 5 + [1] * 220
    n, c = gdn.gdn_chunk_slots(builder.max_tokens, 256)
    assert gdn.gdn_chunks_needed(worst, c) == 67 <= n == 72
    # a step over the cap is refused aloud, not given a shape of its own
    from gllm_tpu.scheduler import ScheduledBatch, ScheduledSeq
    items = []
    for i in range(100):
        seq = Sequence(i, [3] * 20, SamplingParams(max_tokens=4))
        seq.page_table = [1, 2]
        items.append(ScheduledSeq(seq, 20, 0))
    with pytest.raises(ValueError, match="cap was bypassed"):
        builder.shape_signature(ScheduledBatch(items))


def test_slot_pool_is_sized_by_the_bytes_the_tpu_stores():
    from gllm_tpu.runner.runner import ModelRunner
    cfg = from_hf_config(PUBLISHED)
    fake = type("R", (), dict(model_cfg=cfg, ssm_working_slots=32,
                              ssm_snapshot_slots=0))()
    # 33 slots x 12 layers: 15 pairs of heads of 96 x 384 (192 lanes
    # alone would lie in two tiles of 128: 30 x 96 x 256, 1234206720 B in
    # all) and the 11520 x 3 window with the slot axis rounded up to 40
    assert ModelRunner._ssm_pool_bytes(fake) == \
        12 * 4 * (33 * 30 * 96 * 192 + 40 * 11520 * 3) == 942243840
    # heads that do not pair up lie each alone, padded as they were
    odd = type("R", (), dict(model_cfg=dataclasses.replace(
        cfg, linear_num_key_heads=29, linear_num_value_heads=29),
        ssm_working_slots=32, ssm_snapshot_slots=0))()
    assert odd.model_cfg.ssm_slot_shapes[1] == (29, 96, 192)
    assert ModelRunner._ssm_pool_bytes(odd) == \
        12 * 4 * (33 * 29 * 96 * 256 + 40 * 11136 * 3)
    dense = type("R", (), dict(model_cfg=dataclasses.replace(
        cfg, layer_types=()), ssm_working_slots=0, ssm_snapshot_slots=0))()
    assert ModelRunner._ssm_pool_bytes(dense) == 0


def test_checkpoint_names_load_into_the_stacked_layout(tmp_path):
    """``hybrid_rules`` on a synthetic tiny checkpoint written under the
    names OLMo 3 and fla's GatedDeltaNet use."""
    from safetensors.numpy import save_file
    cfg = from_hf_config(TINY)
    rng = np.random.default_rng(11)
    want = jax.tree.map(
        lambda s: rng.standard_normal(s.shape).astype(np.float32),
        jax.eval_shape(lambda: hybrid.init_params(cfg, dtype=jnp.float32)))
    kd = cfg.linear_num_key_heads * cfg.linear_key_head_dim
    vd = cfg.linear_num_value_heads * cfg.linear_value_head_dim
    nv = cfg.linear_num_value_heads
    tensors = {"model.embed_tokens.weight": want["embed"],
               "model.norm.weight": want["final_norm"],
               "lm_head.weight": want["lm_head"].T}
    ia = ig = 0
    for i, kind in enumerate(cfg.layer_types):
        pre, mlp = f"model.layers.{i}.", want["mlp_layers"]
        for name in ("gate_proj", "up_proj", "down_proj"):
            tensors[pre + f"mlp.{name}.weight"] = mlp[name][i].T
        tensors[pre + "post_attention_layernorm.weight"] = \
            mlp["post_attn_norm"][i]
        tensors[pre + "post_feedforward_layernorm.weight"] = \
            mlp["post_mlp_norm"][i]
        if kind == "full_attention":
            a = want["attn_layers"]
            for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
                tensors[pre + f"self_attn.{name}.weight"] = a[name][ia].T
            tensors[pre + "self_attn.q_norm.weight"] = a["q_norm"][ia]
            tensors[pre + "self_attn.k_norm.weight"] = a["k_norm"][ia]
            ia += 1
        else:
            gl, la = want["gdn_layers"], pre + "linear_attn."
            qkvz, ba, conv = gl["in_qkvz"][ig], gl["in_ba"][ig], \
                gl["conv_w"][ig]
            for name, lo, hi in (("q_proj", 0, kd), ("k_proj", kd, 2 * kd),
                                 ("v_proj", 2 * kd, 2 * kd + vd),
                                 ("g_proj", 2 * kd + vd, 2 * kd + 2 * vd)):
                tensors[la + name + ".weight"] = qkvz[:, lo:hi].T
            tensors[la + "b_proj.weight"] = ba[:, :nv].T
            tensors[la + "a_proj.weight"] = ba[:, nv:].T
            for name, lo, hi in (("q_conv1d", 0, kd), ("k_conv1d", kd, 2 * kd),
                                 ("v_conv1d", 2 * kd, 2 * kd + vd)):
                tensors[la + name + ".weight"] = conv[lo:hi][:, None, :]
            tensors[la + "A_log"] = gl["a_log"][ig]
            tensors[la + "dt_bias"] = gl["dt_bias"][ig]
            tensors[la + "o_norm.weight"] = gl["gdn_norm"][ig]
            tensors[la + "o_proj.weight"] = gl["out_proj"][ig].T
            ig += 1
    save_file({k: np.ascontiguousarray(v) for k, v in tensors.items()},
              str(tmp_path / "model.safetensors"))
    got = hybrid.load_params(str(tmp_path), cfg, dtype=jnp.float32)
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    assert len(flat_want) == len(flat_got)
    for path, leaf in flat_want:
        np.testing.assert_array_equal(np.asarray(flat_got[path]), leaf,
                                      err_msg=jax.tree_util.keystr(path))


def test_scopes_name_the_gdn_operations_in_the_compiled_step():
    """The four scopes the device trace is read by are in the metadata of
    the step's HLO, for a decode-only step and for a mixed one."""
    cfg = from_hf_config(TINY)
    params = jax.eval_shape(lambda: hybrid.init_params(
        cfg, dtype=jnp.float32))
    kv = jax.eval_shape(lambda: hybrid.init_kv_cache(
        cfg, 16, 4, jnp.float32, num_slots=5))
    from gllm_tpu.batching import StepBatch
    from gllm_tpu.ops.attention import AttentionMetadata

    def text(T, S, max_q):
        batch = StepBatch(
            token_ids=jnp.zeros(T, jnp.int32),
            positions=jnp.zeros(T, jnp.int32),
            slot_mapping=jnp.zeros(T, jnp.int32),
            logits_indices=jnp.zeros(S, jnp.int32),
            attn=AttentionMetadata(
                cu_q_lens=jnp.zeros(S + 1, jnp.int32),
                kv_lens=jnp.zeros(S, jnp.int32),
                page_table=jnp.zeros((S, 4), jnp.int32),
                num_seqs=jnp.int32(S)),
            sampling=None, ssm_slots=jnp.zeros(S, jnp.int32))
        fn = jax.jit(lambda p, kv, b: hybrid.forward(
            p, kv, b, cfg, cos_sin=None, attn_impl="xla", max_q_len=max_q))
        return fn.lower(params, kv, batch).compile().as_text()

    decode, mixed = text(8, 8, 1), text(64, 8, 64)
    for scope in ("gdn_conv", "gdn_recurrent"):
        assert scope in decode and scope in mixed
    for scope in ("gdn_chunk_local", "gdn_chunk_scan"):
        assert scope in mixed and scope not in decode


def test_counters_and_the_slot_gauge_move_with_a_served_request():
    from gllm_tpu.memory_manager import _M_SSM_INTENTS, _M_SSM_SLOTS
    from gllm_tpu.runner.prepare import (_M_GDN_CHUNK_SLOTS,
                                         _M_GDN_CHUNK_TOKENS, _M_GDN_ROWS)

    def read():
        return dict(chunk=_M_GDN_ROWS.get(path="chunk"),
                    recurrent=_M_GDN_ROWS.get(path="recurrent"),
                    tokens=_M_GDN_CHUNK_TOKENS.get(),
                    slots=_M_GDN_CHUNK_SLOTS.get(),
                    zero=_M_SSM_INTENTS.get(kind="zero"))
    before = read()
    llm = make_llm(max_prefill_tokens=16)
    llm.generate(prompt_token_ids=[list(range(2, 42))],
                 sampling_params=SamplingParams(temperature=0.0,
                                                max_tokens=6,
                                                ignore_eos=True))
    llm.memory_manager.drain_ssm_intents()
    grew = {k: v - before[k] for k, v in read().items()}
    # 40 tokens in chunks of 16, 16, 8: three rows through the chunked
    # rule in layouts of 2 chunks x 16 tokens, then five decode steps
    assert grew["chunk"] == 3 and grew["recurrent"] == 5
    assert grew["tokens"] == 40 and grew["slots"] == 3 * 32
    assert _M_SSM_SLOTS.get() == 0              # the slot went back,
    assert grew["zero"] == 1                    # to be zeroed


def test_slot_maintenance_has_one_shape_for_up_to_four_of_a_kind():
    llm = make_llm()
    mm = llm.memory_manager
    shapes = set()
    for zeros in ([3], [3, 4], [1, 2, 3], [1, 2, 3, 4]):
        mm.ssm_intents = [("zero", z, 0) for z in zeros]
        for _, arrays in llm.runner._drained_ssm_ops():
            shapes.add(tuple(a.shape for a in arrays))
    assert shapes == {((4,),) * 5}
