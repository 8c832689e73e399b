"""Pallas decode kernel vs the XLA reference (interpret mode on CPU)."""

import numpy as np
import pytest

import jax.numpy as jnp

from gllm_tpu.ops.pallas.decode_attention import (BlockUpdate,
                                                  block_update,
                                                  paged_decode_attention)


def build_case(rng, shapes, Hq, Hkv, D, page, num_pages):
    """shapes: list of kv_len per seq (q_len=1 each)."""
    S = len(shapes)
    k_cache = rng.standard_normal((num_pages, page, Hkv, D)).astype(
        np.float32)
    v_cache = rng.standard_normal((num_pages, page, Hkv, D)).astype(
        np.float32)
    max_pages = max(-(-kv // page) for kv in shapes if kv) if any(shapes) else 1
    pt = np.zeros((S, max_pages), np.int32)
    next_page = 1
    for i, kv in enumerate(shapes):
        n = -(-kv // page)
        pt[i, :n] = np.arange(next_page, next_page + n)
        next_page += n
    assert next_page <= num_pages
    q = rng.standard_normal((S, Hq, D)).astype(np.float32)
    return q, k_cache, v_cache, np.asarray(shapes, np.int32), pt


def dense_decode_ref(q, k_cache, v_cache, kv_lens, pt, page, scale):
    """In the inputs' dtype (float64 inputs give a float64 reference)."""
    S, Hq, D = q.shape
    Hkv = k_cache.shape[2]
    group = Hq // Hkv
    out = np.zeros_like(q)
    for s in range(S):
        kv = int(kv_lens[s])
        if kv == 0:
            continue
        pages = pt[s]
        k = np.concatenate([k_cache[p] for p in pages])[:kv]  # [kv, Hkv, D]
        v = np.concatenate([v_cache[p] for p in pages])[:kv]
        for h in range(Hq):
            sc = (q[s, h] @ k[:, h // group].T) * scale
            p_ = np.exp(sc - sc.max())
            p_ /= p_.sum()
            out[s, h] = p_ @ v[:, h // group]
    return out


@pytest.mark.parametrize("case", [
    dict(shapes=[7], Hq=4, Hkv=2, D=64, page=4, pages=8),
    dict(shapes=[5, 16, 1, 33], Hq=8, Hkv=2, D=64, page=8, pages=16),
    dict(shapes=[100, 3], Hq=4, Hkv=4, D=128, page=16, pages=16),
    # padded rows (kv_len 0) interleaved
    dict(shapes=[9, 0, 12, 0], Hq=4, Hkv=1, D=64, page=4, pages=12),
])
def test_matches_dense_reference(case):
    rng = np.random.default_rng(42)
    q, kc, vc, kv_lens, pt = build_case(
        rng, case["shapes"], case["Hq"], case["Hkv"], case["D"],
        case["page"], case["pages"])
    scale = case["D"] ** -0.5
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(kv_lens), jnp.asarray(pt), scale=scale,
        kv_block=32, interpret=True)
    want = dense_decode_ref(q, kc, vc, kv_lens, pt, case["page"], scale)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    assert not np.isnan(np.asarray(got)).any()


def test_multiple_kv_blocks_online_softmax():
    # context spanning many blocks exercises the running max/sum rescale
    rng = np.random.default_rng(0)
    q, kc, vc, kv_lens, pt = build_case(rng, [250], 4, 2, 64, 8, 40)
    scale = 0.125
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(kv_lens), jnp.asarray(pt), scale=scale,
        kv_block=16, interpret=True)
    want = dense_decode_ref(q, kc, vc, kv_lens, pt, 8, scale)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_engine_e2e_with_pallas_decode(tmp_path):
    """Full engine with attention_impl='pallas' (decode via the kernel in
    interpret mode on CPU) must reproduce the xla-impl greedy output."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM
    from gllm_tpu.config import CacheConfig, EngineConfig
    from gllm_tpu.engine.llm import LLM
    from gllm_tpu.sampling_params import SamplingParams

    torch.manual_seed(5)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
        max_position_embeddings=128, eos_token_id=0, attention_bias=False))
    model.save_pretrained(tmp_path, safe_serialization=True)

    def run(impl):
        cfg = EngineConfig(model=str(tmp_path), dtype="float32",
                           max_model_len=64, attention_impl=impl,
                           cache=CacheConfig(page_size=4, num_pages=64))
        return [o.output_token_ids for o in LLM(config=cfg).generate(
            prompt_token_ids=[[5, 9, 23], [71, 2, 8, 14, 5]],
            sampling_params=SamplingParams(temperature=0.0, max_tokens=6,
                                           ignore_eos=True))]

    assert run("pallas") == run("xla")


@pytest.mark.parametrize("gsz", [2, 4])
@pytest.mark.parametrize("case", [
    dict(shapes=[5, 16, 1, 33], Hq=8, Hkv=2, D=64, page=8, pages=16),
    # padded rows + S not a multiple of the group size
    dict(shapes=[9, 0, 12, 0, 27], Hq=4, Hkv=2, D=64, page=4, pages=24),
    dict(shapes=[100, 3], Hq=4, Hkv=4, D=128, page=16, pages=16),
])
def test_grouped_matches_dense_reference(case, gsz):
    """The grouped kernel (gsz seqs per program, one DMA slot each,
    round-robin fetch) must be numerically identical to the per-seq
    kernel's oracle across ragged contexts, padded rows, and group
    padding."""
    rng = np.random.default_rng(11)
    q, kc, vc, kv_lens, pt = build_case(
        rng, case["shapes"], case["Hq"], case["Hkv"], case["D"],
        case["page"], case["pages"])
    scale = case["D"] ** -0.5
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(kv_lens), jnp.asarray(pt), scale=scale,
        kv_block=16, interpret=True, group_size=gsz)
    want = dense_decode_ref(q, kc, vc, kv_lens, pt, case["page"], scale)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("gsz", [1, 4])
def test_grouped_mqa_shared_kv(gsz):
    """MQA (squeezed head axis) + shared-KV (MLA absorbed: v = leading
    lanes of k) through the grouped path."""
    rng = np.random.default_rng(3)
    Hq, D, Dv, page = 8, 128, 64, 8
    shapes = [12, 0, 30]
    S = len(shapes)
    num_pages = 16
    k_cache = rng.standard_normal((num_pages, page, 1, D)).astype(np.float32)
    max_pages = max(-(-kv // page) for kv in shapes)
    pt = np.zeros((S, max_pages), np.int32)
    nxt = 1
    for i, kv in enumerate(shapes):
        n = -(-kv // page)
        pt[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    q = rng.standard_normal((S, Hq, D)).astype(np.float32)
    kv_lens = np.asarray(shapes, np.int32)
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_cache), None,
        jnp.asarray(kv_lens), jnp.asarray(pt), scale=D ** -0.5,
        kv_block=16, interpret=True, v_dim=Dv, group_size=gsz)
    want = np.zeros((S, Hq, Dv), np.float32)
    for s, kv in enumerate(shapes):
        if not kv:
            continue
        k = np.concatenate([k_cache[p] for p in pt[s]])[:kv, 0]  # [kv, D]
        v = k[:, :Dv]
        for h in range(Hq):
            sc = (q[s, h] @ k.T) * D ** -0.5
            p_ = np.exp(sc - sc.max())
            p_ /= p_.sum()
            want[s, h] = p_ @ v
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


# ---- the benchmark cells' geometries, in the dtype they are served in ------

CELL_BLOCK = 64     # tokens a kv block; the lengths below sit on its edges


def _decode_groups():
    """group_size 1 (the double-buffer kernel), 2, and the tuning
    table's entry for the v5e."""
    import json
    import os
    from gllm_tpu.ops.pallas import tuning
    with open(os.path.join(os.path.dirname(tuning.__file__),
                           "tables.json")) as f:
        tuned = json.load(f)["tpu_v5_lite"]["decode"]["group"]
    return sorted({1, 2, int(tuned)})


def _as_f64(x):
    return np.asarray(jnp.asarray(x, jnp.float32), np.float64)


@pytest.mark.parametrize("gsz", _decode_groups())
@pytest.mark.parametrize("Hkv", [8, 32], ids=["dense_hkv8", "hybrid_hkv32"])
def test_cell_geometries_bf16_match_float64_reference(Hkv, gsz):
    """(Hq 32, Hkv 8) and (Hq 32, Hkv 32) at head_dim 128, pages of 16,
    bf16 q and cache: lengths 0, 1, one short of, on and one past a block
    edge, and several blocks. The reference is float64 arithmetic on the
    same bf16 inputs. Tolerance: K and V enter the MXU as stored and q as
    it arrives, so every product is exact in the float32 accumulator; p
    goes in as two bf16 parts (2**-17 relative); sums of at most 200
    float32 terms add ~1e-6; what is left is the one rounding of the
    result to bf16, half an ulp = 2**-9 relative. 2**-8 leaves a factor
    of two."""
    rng = np.random.default_rng(28)
    shapes = [0, 1, CELL_BLOCK - 1, CELL_BLOCK, CELL_BLOCK + 1,
              3 * CELL_BLOCK + 7]
    q, kc, vc, kv_lens, pt = build_case(rng, shapes, 32, Hkv, 128, 16, 40)
    q, kc, vc = (jnp.asarray(x, jnp.bfloat16) for x in (q, kc, vc))
    scale = 128 ** -0.5
    got = paged_decode_attention(
        q, kc, vc, jnp.asarray(kv_lens), jnp.asarray(pt), scale=scale,
        kv_block=CELL_BLOCK, interpret=True, group_size=gsz)
    assert got.dtype == jnp.bfloat16
    want = dense_decode_ref(_as_f64(q), _as_f64(kc), _as_f64(vc), kv_lens,
                            pt, 16, scale)
    np.testing.assert_allclose(_as_f64(got), want, rtol=2 ** -8, atol=1e-5)
    assert not np.asarray(got[0]).any()            # the empty row reads 0


@pytest.mark.parametrize("name,dtype,Hq,Hkv,want_form", [
    # one kv head a query head (the hybrid cell's full-attention layers)
    ("g1", "bfloat16", 8, 8, BlockUpdate("bfloat16", 2, 8, 1)),
    # grouped queries (the dense cell)
    ("g4", "bfloat16", 8, 2, BlockUpdate("bfloat16", 2, 2, 4)),
    # a float32 cache keeps float32 operands
    ("f32", "float32", 8, 2, BlockUpdate("float32", 1, 2, 4)),
    # one latent head, values in its leading lanes: no own-head mask
    ("mqa", "bfloat16", 8, 1, BlockUpdate("bfloat16", 2, 1, 8)),
    # int8 blocks are dequantized in VMEM: float32 operands
    ("quant", "bfloat16", 8, 2, BlockUpdate("float32", 1, 2, 4)),
])
def test_block_update_form_is_chosen_from_the_call(name, dtype, Hq, Hkv,
                                                   want_form):
    """The one entry point gives the block update its form from the
    head counts, the dtypes and the quantization it sees, and every form
    agrees with float64 arithmetic on the same inputs."""
    rng = np.random.default_rng(5)
    D, Dv, page = 128, 128, 8
    shapes = [21, 0, 40, 8]
    q, kc, vc, kv_lens, pt = build_case(rng, shapes, Hq, Hkv, D, page, 16)
    q = jnp.asarray(q, dtype)
    kw = dict(v_dim=None)
    if name == "quant":
        ks = rng.uniform(0.01, 0.02, (16, Hkv)).astype(np.float32)
        vs = rng.uniform(0.01, 0.02, (16, Hkv)).astype(np.float32)
        kc = rng.integers(-127, 128, kc.shape).astype(np.int8)
        vc = rng.integers(-127, 128, vc.shape).astype(np.int8)
        kw.update(k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
        kc_j, vc_j = jnp.asarray(kc), jnp.asarray(vc)
        k_ref = kc.astype(np.float64) * ks[:, None, :, None]
        v_ref = vc.astype(np.float64) * vs[:, None, :, None]
    elif name == "mqa":
        Dv = 64
        kc_j, vc_j = jnp.asarray(kc, dtype), None
        kw.update(v_dim=Dv)
        k_ref = _as_f64(kc_j)
        v_ref = k_ref[..., :Dv]
    else:
        kc_j, vc_j = jnp.asarray(kc, dtype), jnp.asarray(vc, dtype)
        k_ref, v_ref = _as_f64(kc_j), _as_f64(vc_j)
    assert block_update(q.dtype, kc_j.dtype, Hq, Hkv,
                        quant=name == "quant") == want_form
    got = paged_decode_attention(
        q, kc_j, vc_j, jnp.asarray(kv_lens), jnp.asarray(pt),
        scale=D ** -0.5, kv_block=16, interpret=True, group_size=2, **kw)
    q64 = _as_f64(q)
    want = np.zeros((len(shapes), Hq, Dv))
    for s, kv in enumerate(shapes):
        if kv:
            k = np.concatenate([k_ref[p] for p in pt[s]])[:kv]
            v = np.concatenate([v_ref[p] for p in pt[s]])[:kv]
            for h in range(Hq):
                sc = (q64[s, h] @ k[:, h // (Hq // Hkv)].T) * D ** -0.5
                p_ = np.exp(sc - sc.max())
                want[s, h] = (p_ / p_.sum()) @ v[:, h // (Hq // Hkv)]
    # the result is rounded once to q's dtype (half a bf16 ulp: 2**-9)
    rtol = 2 ** -8 if dtype == "bfloat16" else 2e-4
    np.testing.assert_allclose(_as_f64(got), want, rtol=rtol, atol=1e-5)


@pytest.mark.parametrize("shared_kv", [False, True], ids=["kv", "latent"])
def test_pages_left_unfetched_never_reach_the_result(shared_kv):
    """A context's last block is fetched to its last page only. What the
    rest of the buffer holds is masked out of the scores, but its value
    rows would meet probabilities of exactly 0 in the MXU, and 0 x NaN
    is NaN: with VMEM that starts as NaNs (the TPU interpreter's
    ``uninitialized_memory``) the result must stay finite and right."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.default_rng(1)
    shapes = [5, 70, 0, 33]          # blocks of 32: every last block partial
    Hkv, Dv = (1, 64) if shared_kv else (2, 128)
    q, kc, vc, kv_lens, pt = build_case(rng, shapes, 8, Hkv, 128, 8, 40)
    q, kc, vc = (jnp.asarray(x, jnp.bfloat16) for x in (q, kc, vc))
    got = paged_decode_attention(
        q, kc, None if shared_kv else vc, jnp.asarray(kv_lens),
        jnp.asarray(pt), scale=128 ** -0.5, kv_block=32, group_size=2,
        v_dim=Dv if shared_kv else None,
        interpret=pltpu.InterpretParams(uninitialized_memory="nan"))
    k64 = _as_f64(kc)
    want = dense_decode_ref(_as_f64(q), k64,
                            k64 if shared_kv else _as_f64(vc), kv_lens,
                            pt, 8, 128 ** -0.5)[..., :Dv]
    assert np.isfinite(_as_f64(got)).all()
    np.testing.assert_allclose(_as_f64(got), want, rtol=2 ** -8, atol=1e-5)


# ---- under a selection's mask (``chosen``) ----------------------------------

def _chosen_group():
    """The group the v5e table gives the masked call."""
    import json
    import os
    from gllm_tpu.ops.pallas import tuning
    with open(os.path.join(os.path.dirname(tuning.__file__),
                           "tables.json")) as f:
        table = json.load(f)["tpu_v5_lite"]
    return int({**table["decode_mqa"],
                **table.get("decode_mqa_chosen", {})}["group"])


def chosen_ref(q, k_cache, v_cache, kv_lens, pt, chosen, scale):
    """``dense_decode_ref`` over the chosen positions of each context; a
    row with none gives zeros."""
    S, Hq, _ = q.shape
    group = Hq // k_cache.shape[2]
    out = np.zeros((S, Hq, v_cache.shape[-1]), q.dtype)
    for s in range(S):
        kv = int(kv_lens[s])
        keep = np.flatnonzero(chosen[s, :kv])
        if not keep.size:
            continue
        k = np.concatenate([k_cache[p] for p in pt[s]])[keep]
        v = np.concatenate([v_cache[p] for p in pt[s]])[keep]
        for h in range(Hq):
            sc = (q[s, h] @ k[:, h // group].T) * scale
            p_ = np.exp(sc - sc.max())
            out[s, h] = (p_ / p_.sum()) @ v[:, h // group]
    return out


def _latent_case(rng, shapes, Hq=128, W=640, page=16):
    """dots3-note-prev's full layers: ``Hq`` heads over ONE KV head of
    ``W`` lanes in bf16, pages of 16; a mask that keeps ~40 % of each
    row's positions."""
    pages = 2 + sum(-(-kv // page) for kv in shapes)
    q, kc, _, kv_lens, pt = build_case(rng, shapes, Hq, 1, W, page, pages)
    q, kc = jnp.asarray(q, jnp.bfloat16), jnp.asarray(kc, jnp.bfloat16)
    chosen = rng.random((len(shapes), pt.shape[1] * page)) < 0.4
    return q, kc, kv_lens, pt, chosen


CHOSEN_ROWS = ["drawn", "first_block_empty", "last_block_empty",
               "nothing_chosen", "context_0"]


@pytest.mark.parametrize("gsz", sorted({1, _chosen_group()}))
@pytest.mark.parametrize("row", CHOSEN_ROWS)
def test_chosen_positions_at_the_latent_cell_geometry(row, gsz):
    """128 heads x 640 lanes, values the first 512, one KV head, bf16,
    against float64 arithmetic on the same inputs: the middle row of
    three is the case (a block of the context, the first or the last,
    in which nothing is chosen; a row with nothing chosen at all, which
    reads zeros; a row of context 0), between two live rows whose
    results it must not touch."""
    rng = np.random.default_rng(43)
    B = CELL_BLOCK
    shapes = [2 * B + 5, 0 if row == "context_0" else 3 * B - 9, B + 1]
    q, kc, kv_lens, pt, chosen = _latent_case(rng, shapes)
    if row == "first_block_empty":
        chosen[1, :B] = False
    elif row == "last_block_empty":
        chosen[1, 2 * B:] = False
    elif row == "nothing_chosen":
        chosen[1] = False
    got = paged_decode_attention(
        q, kc, None, jnp.asarray(kv_lens), jnp.asarray(pt),
        scale=192 ** -0.5, kv_block=B, interpret=True, v_dim=512,
        group_size=gsz, chosen=jnp.asarray(chosen))
    assert got.shape == (3, 128, 512) and got.dtype == jnp.bfloat16
    k64 = _as_f64(kc)
    want = chosen_ref(_as_f64(q), k64, k64[..., :512], kv_lens, pt, chosen,
                      192 ** -0.5)
    assert np.isfinite(_as_f64(got)).all()
    np.testing.assert_allclose(_as_f64(got), want, rtol=2 ** -8, atol=1e-5)
    if row in ("nothing_chosen", "context_0"):
        assert not np.asarray(got[1]).any()
    assert np.asarray(got[0]).any() and np.asarray(got[2]).any()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_everything_chosen_is_the_unmasked_call_bit_for_bit(dtype):
    rng = np.random.default_rng(7)
    shapes = [CELL_BLOCK + 3, 0, 3 * CELL_BLOCK, 17]
    q, kc, kv_lens, pt, chosen = _latent_case(rng, shapes, Hq=16, W=256)
    q, kc = q.astype(dtype), kc.astype(dtype)
    call = lambda **kw: np.asarray(paged_decode_attention(
        q, kc, None, jnp.asarray(kv_lens), jnp.asarray(pt), scale=0.1,
        kv_block=CELL_BLOCK, interpret=True, v_dim=128, group_size=2,
        **kw).astype(jnp.float32))
    np.testing.assert_array_equal(
        call(chosen=jnp.ones(chosen.shape, bool)), call())
    assert not np.array_equal(call(chosen=jnp.asarray(chosen)), call())


def test_chosen_positions_under_folded_kv_heads():
    """Several KV heads in a block's rows: a position's choice holds for
    every head's row of it."""
    rng = np.random.default_rng(9)
    shapes = [37, 0, 64, 5]
    q, kc, vc, kv_lens, pt = build_case(rng, shapes, 8, 2, 64, 8, 24)
    chosen = rng.random((4, pt.shape[1] * 8)) < 0.5
    chosen[3] = False
    got = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(kv_lens), jnp.asarray(pt), scale=0.125, kv_block=16,
        interpret=True, group_size=2, chosen=jnp.asarray(chosen))
    want = chosen_ref(q, kc, vc, kv_lens, pt, chosen, 0.125)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_chosen_positions_with_pages_left_unfetched():
    """The rule of ``test_pages_left_unfetched_never_reach_the_result``
    under a mask: VMEM that starts as NaNs, last blocks fetched to their
    last page, and a block whose chosen positions all lie past the
    context's end."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.default_rng(1)
    shapes = [5, 70, 0, 33]                 # blocks of 32
    q, kc, kv_lens, pt, chosen = _latent_case(rng, shapes, Hq=8, W=128,
                                              page=8)
    chosen[3, 32:33] = False                # block 1 of row 3: none in reach
    got = paged_decode_attention(
        q, kc, None, jnp.asarray(kv_lens), jnp.asarray(pt),
        scale=128 ** -0.5, kv_block=32, group_size=2, v_dim=64,
        chosen=jnp.asarray(chosen),
        interpret=pltpu.InterpretParams(uninitialized_memory="nan"))
    k64 = _as_f64(kc)
    want = chosen_ref(_as_f64(q), k64, k64[..., :64], kv_lens, pt, chosen,
                      128 ** -0.5)
    assert np.isfinite(_as_f64(got)).all()
    np.testing.assert_allclose(_as_f64(got), want, rtol=2 ** -8, atol=1e-5)


def test_a_mask_of_another_extent_is_refused():
    rng = np.random.default_rng(0)
    q, kc, kv_lens, pt, chosen = _latent_case(rng, [20, 9], Hq=8, W=128)
    with pytest.raises(ValueError, match="chosen"):
        paged_decode_attention(
            q, kc, None, jnp.asarray(kv_lens), jnp.asarray(pt), scale=0.1,
            kv_block=32, interpret=True, v_dim=64,
            chosen=jnp.ones((3, chosen.shape[1]), bool))


def test_without_a_mask_the_program_has_no_such_operand():
    """Absence is a fact of the trace: the unmasked call's kernel takes
    the operands it always took (contexts, page table, q, K, V), the
    masked one the selection besides."""
    import jax
    rng = np.random.default_rng(0)
    q, kc, vc, kv_lens, pt = build_case(rng, [20, 9], 8, 2, 64, 8, 8)
    args = [jnp.asarray(a) for a in (q, kc, vc, kv_lens, pt)]
    chosen = jnp.ones((2, pt.shape[1] * 8), bool)

    def kernel_eqn(**kw):
        jaxpr = jax.make_jaxpr(lambda *a: paged_decode_attention(
            *a, scale=0.125, kv_block=16, interpret=True, **kw))(*args)
        inner = jaxpr.jaxpr.eqns[-1].params["jaxpr"]
        eqns = [e for e in inner.eqns if e.primitive.name == "pallas_call"]
        assert len(eqns) == 1
        return eqns[0]

    plain, masked = kernel_eqn(), kernel_eqn(chosen=chosen)
    assert len(plain.invars) == 5 and len(masked.invars) == 6
    assert len(str(plain.params["jaxpr"])) < len(str(masked.params["jaxpr"]))
