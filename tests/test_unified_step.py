"""Unified mixed-batch step (--unified-step, ISSUE 12).

Three layers under test (docs/overlap_scheduling.md#unified-step):

- KERNEL: the unified ragged kernel (``unified=True``) is the single
  attention program for every paged step — interpret-mode parity against
  BOTH legacy oracles (the per-sequence decode kernel for pure-decode
  batches, the XLA gather reference everywhere), f32 and int8 KV,
  including the AMLA mul-by-add rescaling numerics bounds.
- RUNNER/PREPARE: the shape-signature space collapses to one
  (row bucket × token bucket) family — max_q rides the token bucket,
  pure decode is the t == s point, mixed batches pad to the single
  schedulable maximum.
- ENGINE: chains absorb prefill chunks through mixed re-forms; greedy +
  seeded token streams are byte-identical to the flag-off engine under
  arrival/finish/preemption churn, and the retired
  ``reason="waiting"`` break class stays at zero.
"""

import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from gllm_tpu.config import (CacheConfig, EngineConfig, ParallelConfig,
                             SchedulerConfig)
from gllm_tpu.engine.llm import LLM
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.obs.steptrace import TRACE, summarize
from gllm_tpu.ops.attention import AttentionMetadata, _xla_paged_attention
from gllm_tpu.ops.pallas.decode_attention import paged_decode_attention
from gllm_tpu.ops.pallas.ragged_attention import (_decode_prefix_len,
                                                  ragged_paged_attention)
from gllm_tpu.sampling_params import SamplingParams


# ---------------------------------------------------------------------------
# kernel parity (interpret mode)
# ---------------------------------------------------------------------------

def build_case(rng, seqs, Hq, Hkv, D, page, num_pages, pad_seqs=0,
               int8=False):
    """seqs: list of (q_len, kv_len); decode rows must come first to
    mirror the scheduler's packing (the decode-prefix contract)."""
    S = len(seqs) + pad_seqs
    T = sum(q for q, _ in seqs)
    if int8:
        kc = rng.integers(-127, 127,
                          size=(num_pages, page, Hkv, D)).astype(np.int8)
        vc = rng.integers(-127, 127,
                          size=(num_pages, page, Hkv, D)).astype(np.int8)
        ks = rng.uniform(0.01, 0.02,
                         size=(num_pages, Hkv)).astype(np.float32)
        vs = rng.uniform(0.01, 0.02,
                         size=(num_pages, Hkv)).astype(np.float32)
    else:
        kc = rng.standard_normal((num_pages, page, Hkv, D)).astype(
            np.float32)
        vc = rng.standard_normal((num_pages, page, Hkv, D)).astype(
            np.float32)
        ks = vs = None
    max_pages = max(-(-kv // page) for _, kv in seqs)
    pt = np.zeros((S, max_pages), np.int32)
    cu = np.zeros(S + 1, np.int32)
    kv_lens = np.zeros(S, np.int32)
    nxt, off = 1, 0
    for i, (q_len, kv_len) in enumerate(seqs):
        n = -(-kv_len // page)
        pt[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
        kv_lens[i] = kv_len
        off += q_len
        cu[i + 1] = off
    cu[len(seqs) + 1:] = off
    assert nxt <= num_pages
    q = rng.standard_normal((T, Hq, D)).astype(np.float32)
    md = AttentionMetadata(
        cu_q_lens=jnp.asarray(cu), kv_lens=jnp.asarray(kv_lens),
        page_table=jnp.asarray(pt),
        num_seqs=jnp.asarray(len(seqs), jnp.int32))
    return q, kc, vc, ks, vs, md


DECODE_SEQS = [(1, k) for k in [3, 9, 1, 14, 6, 2, 30, 8, 12, 5, 22, 17]]
MIXED_SEQS = [(1, k) for k in [3, 9, 14, 6, 30, 8]] + [(5, 9), (7, 7)]


@pytest.mark.parametrize("gsz", [1, 3, 4, 8])
def test_unified_pure_decode_matches_both_oracles(gsz):
    """Pure-decode ragged batch through the unified kernel == the legacy
    per-sequence decode kernel == the XLA reference — the decode-class
    grouped path at several interleave depths incl. partial groups."""
    rng = np.random.default_rng(7)
    q, kc, vc, _, _, md = build_case(rng, DECODE_SEQS, 8, 2, 32, 4, 96)
    scale = 0.2
    want = _xla_paged_attention(jnp.asarray(q), jnp.asarray(kc),
                                jnp.asarray(vc), md, scale=scale,
                                max_q_len=1)
    oracle = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), md.kv_lens,
        md.page_table, scale=scale, kv_block=16, interpret=True)
    got = ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), md.cu_q_lens,
        md.kv_lens, md.page_table, scale=scale, q_block=8, kv_block=16,
        interpret=True, unified=True, group_size=gsz)
    np.testing.assert_allclose(np.asarray(oracle), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # AMLA quantizes the running max (exact power-of-two rescales): the
    # result is the same softmax computed with a different — exact —
    # normalizer split, so parity is tight but not bitwise
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    assert not np.isnan(np.asarray(got)).any()


def test_unified_mixed_matches_ragged_and_xla_oracles():
    rng = np.random.default_rng(3)
    q, kc, vc, _, _, md = build_case(rng, MIXED_SEQS, 8, 2, 32, 4, 64)
    scale = 0.2
    want = _xla_paged_attention(jnp.asarray(q), jnp.asarray(kc),
                                jnp.asarray(vc), md, scale=scale,
                                max_q_len=7)
    legacy = ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), md.cu_q_lens,
        md.kv_lens, md.page_table, scale=scale, q_block=8, kv_block=16,
        interpret=True)
    got = ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), md.cu_q_lens,
        md.kv_lens, md.page_table, scale=scale, q_block=8, kv_block=16,
        interpret=True, unified=True, group_size=4)
    np.testing.assert_allclose(np.asarray(legacy), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("seqs", [DECODE_SEQS, MIXED_SEQS])
def test_unified_int8_kv_matches_xla_dequant_oracle(seqs):
    """int8 KV through the unified kernel (scale rows riding the page
    DMAs, in-VMEM dequant) vs the XLA gathered-page dequant oracle —
    decode-class and ragged-class blocks both."""
    rng = np.random.default_rng(5)
    q, kc, vc, ks, vs, md = build_case(rng, seqs, 8, 2, 32, 4, 96,
                                       int8=True)
    scale = 0.2
    max_q = max(ql for ql, _ in seqs)
    want = _xla_paged_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), md,
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs), scale=scale,
        max_q_len=max_q)
    got = ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), md.cu_q_lens,
        md.kv_lens, md.page_table, scale=scale, q_block=8, kv_block=16,
        interpret=True, unified=True, group_size=3,
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_amla_rescaling_numerics_bounds():
    """AMLA on vs off on the same unified batch: both must sit within
    oracle tolerance, and the classic (amla=False) arm must match the
    XLA oracle at the legacy tolerance — the mul-by-add trick changes
    only the normalizer split, never the math."""
    rng = np.random.default_rng(11)
    # wide score dynamic range: big scale stresses the exponent-field
    # rescale (underflow flush, -inf first blocks)
    q, kc, vc, _, _, md = build_case(rng, MIXED_SEQS, 4, 2, 32, 4, 64)
    scale = 1.7
    want = _xla_paged_attention(jnp.asarray(q), jnp.asarray(kc),
                                jnp.asarray(vc), md, scale=scale,
                                max_q_len=7)
    classic = ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), md.cu_q_lens,
        md.kv_lens, md.page_table, scale=scale, q_block=8, kv_block=8,
        interpret=True, unified=True, group_size=2, amla=False)
    amla = ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), md.cu_q_lens,
        md.kv_lens, md.page_table, scale=scale, q_block=8, kv_block=8,
        interpret=True, unified=True, group_size=2, amla=True)
    np.testing.assert_allclose(np.asarray(classic), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(amla), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    assert not np.isnan(np.asarray(amla)).any()


def test_unified_mqa_and_padded_tail():
    """MQA (Hkv == 1, squeezed-head 2-D path) decode-class blocks +
    padded seq rows beyond the real batch."""
    rng = np.random.default_rng(13)
    seqs = [(1, 5), (1, 9), (1, 13), (6, 6)]
    q, kc, vc, _, _, md = build_case(rng, seqs, 4, 1, 64, 4, 16,
                                     pad_seqs=3)
    want = _xla_paged_attention(jnp.asarray(q), jnp.asarray(kc),
                                jnp.asarray(vc), md, scale=0.2,
                                max_q_len=6)
    got = ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc), md.cu_q_lens,
        md.kv_lens, md.page_table, scale=0.2, q_block=4, kv_block=8,
        interpret=True, unified=True, group_size=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_decode_prefix_len_derivation():
    """The per-block row class derives from cu_q_lens alone: the decode
    prefix is the longest run of one-token sequences."""
    cu = jnp.asarray([0, 1, 2, 3, 8, 9, 9, 9], jnp.int32)  # 3 decode,
    assert int(_decode_prefix_len(cu, 7)) == 3              # then a chunk
    cu = jnp.asarray([0, 1, 2, 3, 4, 4, 4], jnp.int32)     # pure decode
    assert int(_decode_prefix_len(cu, 6)) == 4              # (+ padding)
    cu = jnp.asarray([0, 5, 6, 7], jnp.int32)               # prefill first
    assert int(_decode_prefix_len(cu, 3)) == 0


# ---------------------------------------------------------------------------
# prepare: one signature family
# ---------------------------------------------------------------------------

def _builder(unified):
    from gllm_tpu.runner.prepare import BatchBuilder
    cfg = EngineConfig(max_num_seqs=32, unified_step=unified,
                       scheduler=SchedulerConfig(max_prefill_tokens=128,
                                                 max_decode_seqs=16),
                       cache=CacheConfig(page_size=4, num_pages=64))
    return BatchBuilder(cfg, 4, vocab_size=128)


def _sched_batch(rows):
    """rows: list of (q_len, computed_before)."""
    from gllm_tpu.scheduler import ScheduledBatch, ScheduledSeq
    from gllm_tpu.sequence import Sequence
    items = []
    for i, (n, before) in enumerate(rows):
        seq = Sequence(i, [1] * (before + n + 1), SamplingParams())
        seq.page_table = [1] * (-(-(before + n) // 4))
        seq.num_computed_tokens = before
        items.append(ScheduledSeq(seq, n, before))
    return ScheduledBatch(items)


def test_signature_collapses_to_one_family():
    b = _builder(True)
    # pure decode: the t == s point of the q == t family
    t, s, q, p = b.shape_signature(_sched_batch([(1, 6)] * 6))
    assert (t, s, q) == (8, 8, 8)
    # mixed: token axis pads to the ONE schedulable maximum
    t2, s2, q2, _ = b.shape_signature(_sched_batch([(1, 6)] * 6
                                                   + [(20, 0)]))
    assert q2 == t2 == b.max_tokens
    assert s2 == 8
    # legacy split for contrast: a q=1 decode population of its own
    lb = _builder(False)
    _, _, q3, _ = lb.shape_signature(_sched_batch([(1, 6)] * 6))
    assert q3 == 1


# ---------------------------------------------------------------------------
# engine: absorb, identity, retired break class
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_cfg():
    return ModelConfig(
        architecture="LlamaForCausalLM", vocab_size=512, hidden_size=64,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        intermediate_size=128, max_position=256)


def make_llm(model_cfg, *, unified, overlap=True, num_pages=256,
             eos=(7,), depth=2, **kw):
    cfg = EngineConfig(
        load_format="dummy", dtype="float32", max_model_len=64,
        max_num_seqs=8, overlap_scheduling=overlap,
        unified_step=unified, overlap_depth=depth,
        pipelined_loop=(overlap and not unified),  # unified lifts it
        scheduler=SchedulerConfig(max_prefill_tokens=32,
                                  max_decode_seqs=8),
        cache=CacheConfig(page_size=4, num_pages=num_pages), **kw)
    llm = LLM(config=cfg, model_cfg=model_cfg)
    if eos:
        llm.eos_token_ids = frozenset(eos)
    return llm


def check_no_leak(llm):
    assert llm.memory_manager.num_free_pages == \
        llm.memory_manager.allocator.num_total


def churn_run(model_cfg, unified, *, seeded=False, msd=1, slots=False,
              num_pages=256, n=10, depth=2, topo=None):
    """Arrivals land MID-CHAIN (the phase-boundary edge the unified step
    absorbs); optional page pressure exercises the no-preempt re-form
    fallback."""
    llm = make_llm(model_cfg, unified=unified, num_pages=num_pages,
                   multi_step_decode=msd, decode_slot_batching=slots,
                   ondevice_finish=slots, depth=depth,
                   parallel=ParallelConfig(**(topo or {})))
    rng = np.random.default_rng(11)
    seqs, nseq, it = [], 0, 0
    arrivals = {0: 3, 2: 2, 5: 2, 9: 1, 14: 2}
    while nseq < n or llm.has_unfinished:
        for _ in range(arrivals.get(it, 0)):
            if nseq >= n:
                break
            ids = [int(x) for x in
                   rng.integers(2, 250, size=int(rng.integers(3, 20)))]
            sp = (SamplingParams(temperature=0.8, seed=100 + nseq,
                                 max_tokens=int(rng.integers(4, 24)))
                  if seeded else
                  SamplingParams(temperature=0.0,
                                 max_tokens=int(rng.integers(4, 24))))
            s = llm._allocate_seq(ids, sp)
            seqs.append(s)
            llm.add_seq(s)
            nseq += 1
        llm.step()
        it += 1
        assert it < 3000, "engine stopped making progress"
    check_no_leak(llm)
    assert not llm._in_flight
    return [(s.token_ids[:], s.finish_reason) for s in seqs], llm


@pytest.mark.parametrize("kw", [
    {},                                     # arrivals only
    {"seeded": True},                       # seeded draws
    {"msd": 4, "slots": True},              # fused + slots + odf
    {"num_pages": 24},                      # + preemption pressure
    {"num_pages": 24, "msd": 4},            # fused + preemption
])
def test_unified_matches_legacy_under_churn(model_cfg, kw):
    base, _ = churn_run(model_cfg, False, **kw)
    uni, llm = churn_run(model_cfg, True, **kw)
    assert base == uni
    if kw.get("num_pages"):
        assert llm.scheduler.num_preemptions > 0


@pytest.mark.slow       # fresh engine per arm × 6 rows — tier-1 keeps the
                        # topology identity core in test_fast_path_topology.py
@pytest.mark.parametrize("topo,kw", [
    (dict(pp=2), {}),
    (dict(pp=2), dict(slots=True)),      # slot membership rides pp
    (dict(dp=2), {}),
], ids=["pp2", "pp2-slots", "dp2"])
@pytest.mark.parametrize("seeded", [False, True],
                         ids=["greedy", "seeded"])
def test_unified_matches_legacy_under_churn_multi_device(
        model_cfg, multi_device_cpu, topo, kw, seeded):
    """The churn identity matrix over topology (ISSUE 20): at pp=2 and
    dp=2 on the forced multi-device CPU host the unified dispatch family
    commits the same streams as the split family — both arms ride the
    lifted pipelined loop, so this also pins reform-chaining across
    stages / replicas against the per-topology legacy dispatch."""
    base, _ = churn_run(model_cfg, False, seeded=seeded, topo=topo, **kw)
    uni, _ = churn_run(model_cfg, True, seeded=seeded, topo=topo, **kw)
    assert base == uni


def test_unified_zero_waiting_breaks_and_mixed_steps(model_cfg):
    """The retired break class stays at zero while arrivals land
    mid-chain, every collected step records the unified kind, and mixed
    unified steps (chains absorbing prefill) actually happen."""
    mark = TRACE.mark()
    _, _ = churn_run(model_cfg, True, msd=4, slots=True)
    s = summarize(TRACE.events(since=mark))
    assert s["chain_breaks_by_reason"].get("waiting", 0) == 0
    step_kinds = set(s["by_kind"]) - {"fused_block"}
    assert step_kinds == {"unified_step"}, s["by_kind"]
    assert s["mixed_step_frac"] and s["mixed_step_frac"] > 0
    # legacy control on the same workload DOES hit the waiting class —
    # the absorb path is load-bearing, not vacuously green
    mark = TRACE.mark()
    churn_run(model_cfg, False, msd=4, slots=True)
    s2 = summarize(TRACE.events(since=mark))
    assert s2["chain_breaks_by_reason"].get("waiting", 0) > 0
    assert s2["mixed_step_frac"] is None


def test_unified_sync_loop_byte_identical(model_cfg):
    """--unified-step without overlap scheduling: signature collapse +
    kernel routing only — streams byte-identical to legacy sync."""
    rng = np.random.default_rng(3)
    prompts = [[int(x) for x in rng.integers(2, 500, size=int(m))]
               for m in rng.integers(3, 14, size=5)]
    sps = [SamplingParams(temperature=0.0, max_tokens=int(m),
                          ignore_eos=True)
           for m in rng.integers(4, 16, size=5)]

    def run(unified):
        llm = make_llm(model_cfg, unified=unified, overlap=False, eos=())
        outs = llm.generate(prompt_token_ids=[list(p) for p in prompts],
                            sampling_params=sps)
        check_no_leak(llm)
        return [(o.output_token_ids, o.finish_reason) for o in outs]

    assert run(False) == run(True)


def test_unified_reform_splices_and_absorbs(model_cfg):
    """Structural: under pressure the unified loop dispatches MIXED
    re-formed batches (src_rows with both promised decode rows and
    host-known prefill rows) instead of yielding."""
    llm = make_llm(model_cfg, unified=True, multi_step_decode=4,
                   decode_slot_batching=True, ondevice_finish=True)
    mixed_reforms = []
    orig = llm.scheduler.schedule_reform

    def spy(prev, allow_prefill=False):
        out = orig(prev, allow_prefill=allow_prefill)
        if out is not None and any(
                it.num_new_tokens > 1
                or it.computed_before < it.seq.prompt_len
                for it in out.items):
            mixed_reforms.append(out)
        return out

    llm.scheduler.schedule_reform = spy
    rng = np.random.default_rng(11)
    nseq, it = 0, 0
    arrivals = {0: 3, 4: 2, 8: 2}
    while nseq < 7 or llm.has_unfinished:
        for _ in range(arrivals.get(it, 0)):
            ids = [int(x) for x in
                   rng.integers(2, 250, size=int(rng.integers(6, 20)))]
            llm.add_seq(llm._allocate_seq(
                ids, SamplingParams(temperature=0.0, max_tokens=12,
                                    ignore_eos=True)))
            nseq += 1
        llm.step()
        it += 1
        assert it < 2000
    check_no_leak(llm)
    assert mixed_reforms, "no chain absorbed a prefill chunk"
    # at least one mixed re-form carries BOTH a promised decode row
    # (spliced from the previous entry's on-device tokens) and a
    # host-known prefill row — the chain absorbing an arrival
    absorbing = [b for b in mixed_reforms
                 if b.src_rows is not None
                 and any(src >= 0 for src in b.src_rows)
                 and any(src < 0 for src in b.src_rows)]
    assert absorbing, "no mixed re-form carried promised decode rows " \
                      "next to prefill rows"
    for b in mixed_reforms:
        # decode prefix first: the kernel's row-class contract
        qlens = [it.num_new_tokens for it in b.items]
        first_chunk = next((i for i, it in enumerate(b.items)
                            if it.num_new_tokens > 1
                            or it.computed_before < it.seq.prompt_len),
                           len(qlens))
        assert all(n == 1 for n in qlens[:first_chunk])


def test_dispatch_shape_acceptance(model_cfg):
    """Acceptance (ISSUE 12): on a staggered-arrival churn workload the
    unified step warms STRICTLY fewer distinct dispatch signatures than
    the split engine (one family vs the decode+mixed populations and
    their token ladder), runs no more unfused decode steps, and retires
    the 'waiting' break class — all deterministic counts, not wall
    fractions (the wall-based unfused_frac is already ≈0 in both arms
    since the pipelined loop landed)."""
    def arm(unified):
        llm = make_llm(model_cfg, unified=unified, multi_step_decode=4,
                       decode_slot_batching=True, ondevice_finish=True,
                       chain_under_prefill=0 if unified else 4)
        rng = np.random.default_rng(7)
        nseq, it = 0, 0
        arrivals = {0: 3, 2: 2, 5: 2, 9: 1, 14: 2}
        mark = TRACE.mark()
        while nseq < 10 or llm.has_unfinished:
            for _ in range(arrivals.get(it, 0)):
                if nseq >= 10:
                    break
                ids = [int(x) for x in
                       rng.integers(2, 250,
                                    size=int(rng.integers(3, 20)))]
                llm.add_seq(llm._allocate_seq(
                    ids, SamplingParams(temperature=0.0, ignore_eos=True,
                                        max_tokens=int(
                                            rng.integers(4, 24)))))
                nseq += 1
            llm.step()
            it += 1
            assert it < 3000
        s = summarize(TRACE.events(since=mark))
        return (llm.runner.num_shape_signatures,
                s["decode_steps_unfused"],
                s["chain_breaks_by_reason"])

    sigs_on, unfused_on, breaks_on = arm(True)
    sigs_off, unfused_off, breaks_off = arm(False)
    assert sigs_on < sigs_off, (sigs_on, sigs_off)
    assert unfused_on <= unfused_off, (unfused_on, unfused_off)
    assert breaks_on.get("waiting", 0) == 0


def test_inflight_depth_knob(model_cfg):
    """--inflight-depth is a real knob: at depth 3 the pipelined loop
    sustains a strictly deeper run-ahead than at the default 2 on a
    decode-saturated workload."""
    def mean_depth(depth):
        llm = make_llm(model_cfg, unified=True, depth=depth, eos=())
        rng = np.random.default_rng(5)
        prompts = [[int(x) for x in rng.integers(2, 500, size=6)]
                   for _ in range(6)]
        sps = [SamplingParams(temperature=0.0, max_tokens=40,
                              ignore_eos=True) for _ in range(6)]
        llm.generate(prompt_token_ids=prompts, sampling_params=sps)
        mark = TRACE.mark()
        llm.generate(prompt_token_ids=prompts, sampling_params=sps)
        return summarize(TRACE.events(since=mark))["mean_inflight_depth"]

    d2, d3 = mean_depth(2), mean_depth(3)
    assert d3 > d2, (d2, d3)
    assert d3 > 1.0, d3


def test_config_deprecates_chain_under_prefill():
    import logging
    cfg = EngineConfig(overlap_scheduling=True, unified_step=True,
                       chain_under_prefill=8)
    with warnings.catch_warnings():
        logging.disable(logging.NOTSET)
        cfg.validate()
    assert cfg.chain_under_prefill == 0          # deprecated no-op
    assert cfg.pipelined_loop                    # lifted under overlap


def test_config_unified_without_overlap_stays_sync():
    cfg = EngineConfig(unified_step=True)
    cfg.validate()
    assert not cfg.pipelined_loop and not cfg.overlap_scheduling


def test_config_rejects_bad_inflight_depth():
    cfg = EngineConfig(overlap_depth=0)
    with pytest.raises(ValueError, match="inflight-depth"):
        cfg.validate()
