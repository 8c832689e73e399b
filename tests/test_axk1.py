"""skt/A.X-K1 (``model_type: axk1``) on the normal path at small sizes (CPU,
seeded random weights): the configuration file against the catalog's row,
the keys that are read and those that are inert, the two Pallas attention
kernels in interpret mode against the XLA path at a toy MLA geometry (one
KV head, values the keys' first lanes) for a decode step and for a mixed
step, the blocks that follow the geometry, and the counter of latent rows.
The comparison with the plain reference (chunked prefill then decode
through the cache, with and without a cached prefix, YaRN, the lower
precision) is tests/perfbench/test_reference_axk1.py's; the router's four
methods are tests/test_deepseek.py's; the sixteen shares
tests/test_dots3_note.py's."""

import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gllm_tpu.models import deepseek
from gllm_tpu.models.config import from_hf_config
from gllm_tpu.ops.attention import AttentionMetadata, paged_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the catalog's row (model-configs guide, architectures.jsonl, "A.X-K1";
# source https://huggingface.co/skt/A.X-K1/blob/main/config.json)
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "axk1", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
    "topk_method": "none", "v_head_dim": 128, "vocab_size": 163840}


def _config_file():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "a.x-k1.json")) as f:
        return json.load(f)


def test_configuration_file_holds_the_catalogs_row_key_by_key():
    hf = _config_file()
    differs = sorted(k for k, v in CATALOG.items() if hf.get(k) != v)
    assert differs == sorted(hf["reduced"]) == [
        "max_position_embeddings", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    assert set(hf["reduced_why"]) == set(hf["reduced"])
    assert hf["ep_share"] == {"chips": 16, "rank": 0,
                              "n_routed_experts": CATALOG["n_routed_experts"]}
    assert hf["vocab_size"] * 8 == CATALOG["vocab_size"]
    cfg = from_hf_config(hf)
    assert cfg.architecture == "AXK1ForCausalLM"
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (7168, 64, 1536, 512, 128, 64, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_first,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.intermediate_size, cfg.n_shared_experts) == (
                192, 12, 0, 8, 2048, 18432, 1)
    assert (cfg.routed_scaling_factor, cfg.scoring_func, cfg.topk_method,
            cfg.norm_topk_prob) == (2.5, "sigmoid", "none", True)
    assert cfg.mla_cache_width == 640 and cfg.rms_norm_eps == 1e-6
    assert cfg.use_mla and not (cfg.use_dsa or cfg.use_swa or cfg.use_hybrid)
    assert deepseek.layer_runs(cfg) == (
        ("full_attention", "dense", 1), ("full_attention", "moe", 4))
    assert deepseek.has_stats(cfg)
    from gllm_tpu.models import get_model_def
    assert get_model_def(cfg).family == "deepseek"


def test_inert_keys_change_nothing_and_the_method_decides_the_groups():
    hf = {k: v for k, v in _config_file().items()
          if k in CATALOG or k == "ep_share"}
    cfg = from_hf_config(hf)
    assert cfg.n_group == 8 and cfg.topk_group == 4 and cfg.route_groups == 0
    for key in ("ep_size", "seq_aux", "moe_layer_freq"):
        assert from_hf_config({k: v for k, v in hf.items()
                               if k != key}) == cfg, key
    # the same keys under a method that has a group limit do limit it
    assert from_hf_config(dict(
        hf, topk_method="group_limited_greedy")).route_groups == 8
    assert from_hf_config(dict(hf, topk_method="noaux_tc")).route_groups == 8
    params = jax.eval_shape(lambda: deepseek.init_params(from_hf_config(
        dict(hf, hidden_size=64, intermediate_size=64,
             moe_intermediate_size=32, vocab_size=64)), 0))
    assert "e_bias" not in params["moe_layers"]
    assert params["moe_layers"]["router"].shape == (4, 64, 192)
    assert params["moe_layers"]["w_gate"].shape == (4, 12, 64, 32)


def test_derived_sizes_are_the_arithmetic_of_the_widths():
    hf = _config_file()
    d = hf["derived"]
    h, ql, lora, rope, nope, v, hq = 7168, 1536, 512, 64, 128, 128, 64
    attn = (h * ql + ql * hq * (nope + rope) + h * (lora + rope)
            + hq * lora * (nope + v) + hq * v * h)
    assert attn == d["attention_params_per_layer"] == 101122048
    expert = 3 * h * 2048
    moe = attn + h * 192 + expert + 12 * expert
    assert moe == d["expert_layer_params"] == 675020800
    layer0 = attn + 3 * h * 18432
    assert layer0 == d["dense_layer_params"] == 497483776
    emb = 2 * 20480 * h
    norms = 5 * (2 * h + ql + lora) + h
    assert d["params"] == layer0 + 4 * moe + emb + norms == 3491257344
    assert d["weight_bytes"] == 2 * d["params"]
    cfg = from_hf_config(hf)
    shapes = jax.eval_shape(lambda: deepseek.init_params(cfg, 0))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(shapes)) == d["params"]
    pages = int(hf["server_flags"][hf["server_flags"].index("--num-pages")
                                   + 1])
    assert d["latent_bytes_per_token"] == 5 * cfg.mla_cache_width * 2 == 6400
    assert d["latent_pool_bytes"] == pages * 16 * 6400
    assert d["expert_bytes"] == 2 * expert
    assert d["fixed_weight_bytes_per_decode_step"] == 2 * (
        5 * attn + 3 * h * 18432 + 4 * (h * 192 + expert) + 20480 * h)
    # 32 callers with the longest document, question and answer and the
    # page a step reserves ahead fit the pool and a page table's width
    flags = hf["server_flags"]
    val = lambda name: int(flags[flags.index(name) + 1])
    per_caller = -(-(16384 + 448 + 256) // 16) + 1
    assert 32 * per_caller <= pages
    assert per_caller <= val("--min-page-bucket") == val(
        "--max-model-len") // 16
    assert val("--max-num-seqs") == val("--maxd") == val(
        "--min-row-bucket") == 32


# ---- the kernels at a toy MLA geometry -------------------------------------

def _toy_mla(rng, dtype, *, q_lens, contexts, heads=8, lora=32, rope=8,
             page=4):
    """q [T, heads, width], the latent pool [P, page, 1, width] and the
    metadata of a step whose sequence s brings ``q_lens[s]`` new tokens on
    top of ``contexts[s]`` cached rows."""
    width = 64                       # lora + rope padded, as the cache is
    S, T = len(q_lens), sum(q_lens)
    kv = [c + n for c, n in zip(contexts, q_lens)]
    per = max(-(-k // page) for k in kv)
    pt = 1 + np.arange(S * per, dtype=np.int32).reshape(S, per)
    pool = np.zeros((S * per + 1, page, 1, width), np.float32)
    pool[1:, :, :, :lora + rope] = rng.standard_normal(
        (S * per, page, 1, lora + rope))
    q = np.zeros((T, heads, width), np.float32)
    q[..., :lora + rope] = rng.standard_normal((T, heads, lora + rope))
    md = AttentionMetadata(
        jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32),
        jnp.asarray(kv, jnp.int32), jnp.asarray(pt), jnp.int32(S))
    return jnp.asarray(q, dtype), jnp.asarray(pool, dtype), md, lora


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                       ("bfloat16", 2e-2)])
@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_pallas_kernels_match_xla_at_a_toy_mla_geometry(step, dtype, tol):
    """One KV head under 8 query heads, values the keys' first 32 lanes:
    ``paged_decode_attention`` for a decode step, ``ragged_paged_attention``
    for a mixed one (three decoding rows and a 9-token chunk behind 21
    cached rows, q blocks that span sequences), against the XLA path. In
    bfloat16 q, the cache and p enter the products as stored (one MXU
    pass), so the two differ by bfloat16's rounding of p."""
    rng = np.random.default_rng(3)
    if step == "decode":
        q, pool, md, lora = _toy_mla(rng, dtype, q_lens=[1, 1, 1, 1],
                                     contexts=[30, 7, 16, 1])
        max_q = 1
    else:
        q, pool, md, lora = _toy_mla(rng, dtype, q_lens=[1, 1, 1, 9],
                                     contexts=[30, 7, 16, 21])
        max_q = 9
    kw = dict(scale=40 ** -0.5 * 1.8133, max_q_len=max_q, v_dim=lora)
    want = paged_attention(q, pool, None, md, impl="xla", **kw)
    got = paged_attention(q, pool, None, md, impl="pallas", **kw)
    assert got.shape == want.shape == (q.shape[0], 8, lora)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_blocks_follow_the_geometry_and_are_announced(monkeypatch, caplog):
    from gllm_tpu.ops.pallas import tuning
    from gllm_tpu.runner.runner import resolve_attn_impl
    monkeypatch.setattr(tuning, "device_tag", lambda: "tpu_v5_lite")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # several KV heads: the table's pair as swept; one KV head: rows
    assert tuning.ragged_blocks(32, 8) == tuning.get("ragged")
    assert tuning.ragged_blocks(64, 1) == {"q_block": 16, "kv_block": 512}
    assert tuning.ragged_blocks(128, 1) == {"q_block": 8, "kv_block": 512}
    assert tuning.ragged_blocks(16, 1)["q_block"] == 64
    assert tuning.decode_blocks(8) == tuning.get("decode")
    assert tuning.decode_blocks(1)["kv_block"] == 512
    cfg = from_hf_config(_config_file())
    with caplog.at_level(logging.INFO, logger="gllm_tpu.runner.runner"):
        assert resolve_attn_impl("auto", cfg, 1, 1, False) == "pallas"
    said = [r.getMessage() for r in caplog.records
            if "latent attention" in r.getMessage()]
    assert len(said) == 1, caplog.text
    assert "decode steps -> pallas paged_decode_attention (kv_block 512" \
        in said[0]
    assert "mixed steps -> pallas ragged_paged_attention (q_block 16, " \
        "kv_block 512" in said[0]
    assert said[0].endswith("the decode kernel")
    # a latent rank that is no multiple of 128 lanes: XLA, and it says why
    caplog.clear()
    import dataclasses
    odd = dataclasses.replace(cfg, kv_lora_rank=96)
    with caplog.at_level(logging.INFO, logger="gllm_tpu.runner.runner"):
        assert resolve_attn_impl("auto", odd, 1, 0, False) == "xla"
    assert any("mixed steps -> xla" in r.getMessage()
               for r in caplog.records)


def test_latent_rows_are_counted_from_kv_lens_by_kind_of_step():
    cfg = from_hf_config(_config_file())
    before = {k: deepseek._M_MLA_ROWS.get(step=k)
              for k in ("decode", "mixed")}
    deepseek.count_rows_read(cfg, np.asarray([100, 50, 0, 0]), True)
    deepseek.count_rows_read(cfg, np.asarray([100, 50, 400]), False)
    assert deepseek._M_MLA_ROWS.get(step="decode") - before["decode"] == \
        150 * 5
    assert deepseek._M_MLA_ROWS.get(step="mixed") - before["mixed"] == \
        550 * 5
    # a selection or a window bounds the other kinds' reads: the runner
    # counts for dense latent attention only
    import dataclasses
    assert cfg.dense_mla
    assert not dataclasses.replace(cfg, index_topk=4,
                                   index_n_heads=2).dense_mla
    assert not dataclasses.replace(
        cfg, layer_types=("sliding_attention",) * 5).dense_mla
