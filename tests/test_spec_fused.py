"""Fused on-device speculation (--spec-fused, ISSUE 13).

Contract (docs/speculative_decoding.md#fused): draft+verify run INSIDE
the chained multi-step dispatch — the runner drafts from a device-
resident recent-token ring, verifies q_len=k+1 rows in-loop, and one
dispatch emits up to K·(spec_k+1) tokens. Greedy token streams are
byte-identical to host-driven spec decode AND to plain decode (both by
the argmax-verification argument); sampled rows keep the rejection-
sampling distribution guarantee. schedule_chain accepts spec rows, so
the chain_breaks reason="spec" class is retired (asserted zero), and
dispatches-per-token lands strictly below BOTH host-driven spec and
non-spec chained decode on a draft-friendly workload.
"""

import numpy as np
import pytest
import torch

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.engine.llm import LLM
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.obs.steptrace import TRACE, summarize
from gllm_tpu.sampling_params import SamplingParams

# Greedy models on random weights loop quickly → the draft-friendly
# regime; one structureless prompt exercises cold proposals too.
PROMPTS = [
    [5, 9, 23, 5, 9, 23, 5, 9],
    [7, 7, 7, 7],
    list(range(1, 30)),
    [101, 3, 101, 3, 101],
]

TINY = ModelConfig(architecture="LlamaForCausalLM", vocab_size=128,
                   hidden_size=64, num_layers=2, num_heads=4,
                   num_kv_heads=2, head_dim=16, intermediate_size=96,
                   max_position=512, eos_token_id=0)


def mk(ckpt=None, *, num_pages=128, kv_dtype="auto", **kw):
    cfg = EngineConfig(
        model=ckpt or "", load_format="auto" if ckpt else "dummy",
        dtype="float32", max_model_len=256,
        cache=CacheConfig(page_size=4, num_pages=num_pages,
                          kv_cache_dtype=kv_dtype), **kw)
    if ckpt:
        return LLM(config=cfg)
    return LLM(config=cfg, model_cfg=TINY)


FUSED = dict(spec_decode="ngram", spec_k=4, spec_ngram=2, spec_fused=True,
             multi_step_decode=4)


def run(llm, n=24, prompts=PROMPTS, **spkw):
    spkw.setdefault("ignore_eos", True)
    spkw.setdefault("temperature", 0.0)
    outs = llm.generate(prompt_token_ids=[list(p) for p in prompts],
                        sampling_params=SamplingParams(max_tokens=n,
                                                       **spkw))
    return [(o.output_token_ids, o.finish_reason) for o in outs]


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(7)
    d = str(tmp_path_factory.mktemp("tiny_spec_fused"))
    LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
        max_position_embeddings=512, eos_token_id=0,
        attention_bias=False)).save_pretrained(d, safe_serialization=True)
    return d


# ---- device proposer / ring units ------------------------------------------

def test_ngram_propose_matches_host_proposer():
    """The on-device sliding-window proposer is EXACT against the host
    proposer over the same window, for every (n, k) and ring fill."""
    import jax.numpy as jnp
    from gllm_tpu.ops.sampling import ngram_propose
    from gllm_tpu.scheduler import propose_ngram_drafts
    R = 32
    rng = np.random.default_rng(0)
    cases = [[5, 6, 7, 8, 5, 6], [1, 2, 3, 4], [5, 6, 9, 5, 6, 1, 5, 6],
             [7] * 5, list(rng.integers(0, 9, size=40)), [5, 9] * 20, [3]]
    for toks in cases:
        toks = [int(t) for t in toks]
        tail = toks[-R:]
        ring = np.full((1, R), -1, np.int32)
        ring[0, R - len(tail):] = tail
        rlen = np.asarray([len(tail)], np.int32)
        for n in (1, 2, 3):
            for k in (1, 3, 4):
                dev = ngram_propose(jnp.asarray(ring), jnp.asarray(rlen),
                                    n=n, k=k)
                dev = tuple(int(t) for t in np.asarray(dev)[0] if t >= 0)
                assert dev == propose_ngram_drafts(tail, n, k), \
                    (toks, n, k)


def test_ring_shift_in_variable_counts():
    import jax.numpy as jnp
    from gllm_tpu.ops.sampling import ring_shift_in
    ring = jnp.asarray(np.full((2, 8), -1, np.int32))
    rlen = jnp.zeros(2, jnp.int32)
    ring, rlen = ring_shift_in(ring, rlen,
                               jnp.asarray([[1, 2, 3], [4, 5, 6]]),
                               jnp.asarray([2, 0]))
    ring = np.asarray(ring)
    assert list(ring[0][-2:]) == [1, 2] and int(np.asarray(rlen)[0]) == 2
    # count 0 is the identity (the chain-splice trick)
    assert int(np.asarray(rlen)[1]) == 0 and ring[1][-1] == -1
    # rollover: a full ring keeps only the newest R tokens
    r2 = jnp.asarray(np.arange(8, dtype=np.int32)[None, :])
    l2 = jnp.asarray([8], jnp.int32)
    r2, l2 = ring_shift_in(r2, l2, jnp.asarray([[9, 10]]),
                           jnp.asarray([2]))
    assert list(np.asarray(r2)[0]) == [2, 3, 4, 5, 6, 7, 9, 10]
    assert int(np.asarray(l2)[0]) == 8


# ---- e2e: identity + the dispatch headline ---------------------------------

def test_fused_byte_identity_and_dispatch_drop(ckpt):
    """The acceptance headline: greedy streams byte-identical to plain
    decode, to host-driven spec, and to non-spec chained decode — while
    dispatches-per-token lands STRICTLY below both host-driven spec and
    the non-spec chain on a draft-friendly workload, with zero
    chain_breaks{reason='spec'} (the retired class)."""
    base = mk(ckpt)
    want = [t for t, _ in run(base, n=32)]
    tokens = sum(len(t) for t in want)
    del base

    host = mk(ckpt, spec_decode="ngram", spec_k=4, spec_ngram=2,
              overlap_scheduling=True, multi_step_decode=4)
    assert [t for t, _ in run(host, n=32)] == want
    host_dpt = host.runner.num_dispatches / tokens
    del host

    chain = mk(ckpt, overlap_scheduling=True, multi_step_decode=4,
               decode_slot_batching=True, ondevice_finish=True)
    assert [t for t, _ in run(chain, n=32)] == want
    chain_dpt = chain.runner.num_dispatches / tokens
    del chain

    mark = TRACE.mark()
    fused = mk(ckpt, **{**FUSED, "decode_chain_len": 4},
               decode_slot_batching=True, ondevice_finish=True)
    assert [t for t, _ in run(fused, n=32)] == want
    fused_dpt = fused.runner.num_dispatches / tokens
    summ = summarize(TRACE.events(since=mark))
    assert (summ.get("chain_breaks_by_reason") or {}).get("spec", 0) == 0, \
        "retired reason='spec' break fired under --spec-fused"
    st = fused.scheduler.spec_stats
    assert st["proposed"] > 0 and st["accepted"] > 0
    assert fused_dpt < host_dpt, (fused_dpt, host_dpt)
    assert fused_dpt < chain_dpt, (fused_dpt, chain_dpt)
    # window observability: acceptance + amortization land in summarize
    assert summ.get("spec_accept_rate") is not None
    assert summ.get("tokens_per_dispatch") > 1.0


def test_fused_eos_and_length_identity(ckpt):
    """EOS inside an accepted run and max-token caps truncate exactly
    like the plain engine (finish reasons included)."""
    base = mk(ckpt)
    want = run(base, n=19, ignore_eos=False)
    del base
    fused = mk(ckpt, **FUSED, ondevice_finish=True,
               decode_slot_batching=True)
    assert run(fused, n=19, ignore_eos=False) == want


# ---- composition matrix ----------------------------------------------------

@pytest.mark.parametrize("flags", [
    dict(),
    dict(ondevice_finish=True),
    dict(decode_slot_batching=True),
    dict(ondevice_finish=True, decode_slot_batching=True),
    dict(pipelined_loop=True, decode_slot_batching=True,
         ondevice_finish=True),
], ids=["plain", "odf", "slots", "odf_slots", "pipelined"])
def test_fused_composition_matrix(flags):
    """spec_fused × {ondevice_finish, decode_slot_batching,
    pipelined_loop}: greedy byte-identity to the plain
    engine, including EOS, stop-token + min_tokens arming, and the
    max_model_len boundary."""
    base = mk()
    want = run(base)
    want_eos = run(base, n=19, ignore_eos=False)
    want_stop = run(base, stop_token_ids=[44, 17], min_tokens=6,
                    ignore_eos=False)
    longp = ([11, 13] * 120)[:238]
    want_len = run(base, n=64, prompts=[longp])
    del base
    llm = mk(**FUSED, **flags)
    assert run(llm) == want
    assert run(llm, n=19, ignore_eos=False) == want_eos
    assert run(llm, stop_token_ids=[44, 17], min_tokens=6,
               ignore_eos=False) == want_stop
    assert run(llm, n=64, prompts=[longp]) == want_len


def test_fused_int8_kv_composes():
    """spec_fused × int8 KV cache: the quantizing write path serves the
    in-loop verify rows; the run completes with full emission (int8
    numerics are agreement-bounded, not byte-identical — the
    kv_quantization contract)."""
    llm = mk(kv_dtype="int8", **FUSED, ondevice_finish=True)
    got = run(llm)
    assert sum(len(t) for t, _ in got) == len(PROMPTS) * 24
    assert all(r == "length" for _, r in got)
    assert llm.scheduler.spec_stats["proposed"] > 0


def test_fused_preemption_churn_identity():
    """A tiny KV pool forces preemption churn mid-chain; re-admitted
    sequences re-seed their ring from committed tokens and stay
    byte-identical."""
    base = mk(num_pages=28)
    want = run(base)
    del base
    llm = mk(num_pages=28, **FUSED, decode_slot_batching=True,
             ondevice_finish=True)
    assert run(llm) == want


def test_fused_arrival_churn_joins_identity():
    """Staggered arrivals under slots + pipelined loop: joins re-seed
    host-known ring rows mid-chain, finishes become holes, and streams
    stay byte-identical — with zero retired-class breaks."""
    def churn(**kw):
        cfg = EngineConfig(
            load_format="dummy", dtype="float32", max_model_len=256,
            scheduler=SchedulerConfig(max_prefill_tokens=64,
                                      max_decode_seqs=8),
            cache=CacheConfig(page_size=4, num_pages=256), **kw)
        llm = LLM(config=cfg, model_cfg=TINY)
        arrivals = {0: 2, 2: 2, 5: 2, 9: 2, 14: 1}
        seqs, nseq, it = [], 0, 0
        while nseq < 9 or llm.has_unfinished:
            for _ in range(arrivals.get(it, 0)):
                ids = [5, 9] * (3 + nseq % 4)
                s = llm._allocate_seq(list(ids), SamplingParams(
                    temperature=0.0, ignore_eos=(nseq % 3 != 0),
                    max_tokens=12 + 4 * (nseq % 5)))
                llm.add_seq(s)
                seqs.append(s)
                nseq += 1
            llm.step()
            it += 1
            assert it < 3000, "churn wedged"
        return [(s.output_token_ids, s.finish_reason) for s in seqs]

    want = churn()
    mark = TRACE.mark()
    got = churn(**FUSED, decode_slot_batching=True, ondevice_finish=True,
                pipelined_loop=True)
    assert got == want
    breaks = summarize(TRACE.events(since=mark)).get(
        "chain_breaks_by_reason") or {}
    assert breaks.get("spec", 0) == 0


# ---- sampled rows ----------------------------------------------------------

def test_fused_seeded_deterministic():
    """Seeded sampled rows draw from fold_in(seed, out_step) — the fused
    run is reproducible run-to-run (realization differs from the
    non-spec engine by contract; the distribution oracle is below)."""
    a = run(mk(**FUSED), temperature=0.9, seed=11)
    b = run(mk(**FUSED), temperature=0.9, seed=11)
    assert a == b


def test_fused_sampled_distribution_preserved(ckpt):
    """The distribution-preservation oracle against the PLAIN engine:
    fused rejection sampling against the on-device one-hot proposal
    keeps the target distribution (tolerance derived from the run count
    — see test_spec_decode._l1_tolerance)."""
    from tests.test_spec_decode import _l1_tolerance, _spec_distribution_l1
    # roomy pool: spec chains allocate worst-case (k+1)-token strides,
    # and a tight pool breaks them with reason='pages' (sync decode
    # doesn't draft under the fused flag — speculation would sit out)
    llm = mk(ckpt, num_pages=512, **FUSED)
    base = mk(ckpt)
    l1, support, total, hists = _spec_distribution_l1(llm, base, 40, 6)
    assert llm.scheduler.spec_stats["proposed"] > 0
    tol = _l1_tolerance(support, total)
    assert l1 < tol, f"L1 {l1:.3f} >= tol {tol:.3f} ({hists})"


# ---- promise bookkeeping ---------------------------------------------------

def test_futuremap_trims_exactly_the_overpromise():
    """A spec block promised worst-case frontiers; at collect the actual
    counts are known — FutureMap.trim_overpromise rebases in-flight
    descendants by EXACTLY the over-promised token count, keeping later
    entries' schedule-relative strides (an upper bound of their own
    parent) instead of collapsing them onto the committed frontier."""
    from gllm_tpu.engine.pipeline import FutureMap, InFlight
    from gllm_tpu.scheduler import ScheduledBatch, ScheduledSeq
    from gllm_tpu.sequence import Sequence

    seq = Sequence(0, [1, 2, 3], SamplingParams(max_tokens=64))
    mult = 5                              # spec_k + 1
    # block A (collected): scheduled off frontier 10 with K=2 links
    # promising up to 2*mult tokens; it actually committed 4.
    seq.num_computed_tokens = 14          # 10 + 4 committed
    # block B in flight: scheduled off A's upper bound 10 + 2*mult = 20
    b_links = [ScheduledBatch([ScheduledSeq(seq, 1, 20 + j * mult)],
                              spec_block=True) for j in range(2)]
    # block C chained off B's upper bound 20 + 2*mult = 30
    c_links = [ScheduledBatch([ScheduledSeq(seq, 1, 30 + j * mult)],
                              spec_block=True) for j in range(2)]
    inflight = [InFlight(b_links, None, 0.0, None, chained=True),
                InFlight(c_links, None, 0.0, None, chained=True)]
    trimmed = FutureMap.trim_overpromise(
        inflight, {0: seq.num_computed_tokens})
    # over-promise accrued ONCE: B's base 20 vs committed 14 → 6 tokens
    assert trimmed == 6
    assert [it.computed_before for b in b_links for it in b.items] \
        == [14, 19]
    # C rebases by the SAME delta (stride relative to B preserved)
    assert [it.computed_before for b in c_links for it in b.items] \
        == [24, 29]
    # idempotent w.r.t. already-valid entries: nothing left to trim
    assert FutureMap.trim_overpromise(inflight, {0: 14}) == 0


# ---- gating / flags --------------------------------------------------------

def test_spec_fused_requires_ngram():
    with pytest.raises(ValueError, match="spec_decode"):
        EngineConfig(load_format="dummy", spec_fused=True).validate()


def test_spec_fused_lifts_overlap_and_chain_len():
    cfg = EngineConfig(load_format="dummy", spec_decode="ngram",
                       spec_fused=True)
    cfg.validate()
    assert cfg.overlap_scheduling and cfg.multi_step_decode > 1


def test_spec_fused_unsupported_topologies_error_loudly():
    """Flags never silently no-op (ISSUE 20): spec_fused × pp>1 and
    × dp>1 are genuinely unsupported (the fused block is ONE device
    program — it can span neither stage programs nor the stacked
    replica carry), so config.validate() refuses with a per-combination
    ValueError instead of the retired warn-and-clear path."""
    from gllm_tpu.config import ParallelConfig
    for par, pat in ((ParallelConfig(pp=2), "pp > 1"),
                     (ParallelConfig(dp=2), "dp > 1")):
        cfg = EngineConfig(load_format="dummy", spec_decode="ngram",
                           spec_fused=True, parallel=par)
        with pytest.raises(ValueError, match=pat):
            cfg.validate()


def test_fast_paths_refuse_pp_times_dp():
    """pipelined_loop (asked for, or lifted from nothing: it lifts
    overlap_scheduling itself) composes with pp OR dp, not the combined
    grid — an error, not a silent legacy fallback."""
    from gllm_tpu.config import ParallelConfig
    cfg = EngineConfig(load_format="dummy", pipelined_loop=True,
                       parallel=ParallelConfig(pp=2, dp=2))
    with pytest.raises(ValueError, match="pp>1 OR dp>1"):
        cfg.validate()
    assert cfg.overlap_scheduling


def test_spec_fused_hybrid_model_errors_in_engine():
    """spec_fused × hybrid GDN is the model-level genuinely-incompatible
    case: the engine refuses with a ValueError (the SSM state cannot
    replay a discarded block) instead of warning and running host-driven
    speculation under a flag that claims otherwise."""
    hybrid = ModelConfig(
        architecture="Qwen3NextForCausalLM", vocab_size=128,
        hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, intermediate_size=96, max_position=512,
        eos_token_id=0,
        layer_types=("linear_attention", "full_attention"),
        linear_num_value_heads=4, linear_num_key_heads=2,
        linear_key_head_dim=8, linear_value_head_dim=8)
    cfg = EngineConfig(
        model="", load_format="dummy", dtype="float32",
        max_model_len=256, cache=CacheConfig(page_size=4, num_pages=64),
        **FUSED)
    with pytest.raises(ValueError, match="hybrid"):
        LLM(config=cfg, model_cfg=hybrid)


def test_spec_fused_enforce_eager_clears():
    cfg = EngineConfig(load_format="dummy", spec_decode="ngram",
                       spec_fused=True, enforce_eager=True)
    cfg.validate()
    assert not cfg.spec_fused and not cfg.overlap_scheduling


def test_fused_flag_off_is_host_driven_legacy():
    """spec_fused=False with spec on: host drafting still proposes (the
    pre-flag engine, byte for byte — the retired break class fires as
    before under overlap)."""
    llm = mk(spec_decode="ngram", spec_k=4, spec_ngram=2)
    got = run(llm)
    assert llm.scheduler.spec_stats["proposed"] > 0
    base = mk()
    assert got == run(base)


def test_fused_metrics_counter_moves():
    from gllm_tpu.obs import metrics as obs
    m = obs.REGISTRY.get("gllm_spec_fused_tokens_total")
    before = sum(m.get(kind=k) for k in ("accepted", "rejected",
                                         "correction"))
    llm = mk(**FUSED)
    run(llm)
    after = sum(m.get(kind=k) for k in ("accepted", "rejected",
                                        "correction"))
    assert after > before


# ---- quarantine under spec-fused chains (ISSUE 14 satellite) ---------------

@pytest.mark.chaos
def test_chaos_step_failure_inside_spec_fused_chain_unwinds_clean():
    """A step exception while a --spec-fused multi-step block is in
    flight: quarantine must unwind the FutureMap in-flight entries AND
    the per-slot spec ring state (the ring rides the handle aux — a
    cleared entry must never splice into the next chain) without
    leaking a page, and a fresh run on the same engine must be
    byte-identical to a clean engine's."""
    from gllm_tpu import faults
    llm = mk(num_pages=64, **FUSED, decode_slot_batching=True,
             ondevice_finish=True, pipelined_loop=True)
    baseline = llm.memory_manager.allocator.num_free
    want = run(mk(num_pages=64))
    for p in PROMPTS:
        llm.add_seq(llm._allocate_seq(list(p), SamplingParams(
            temperature=0.0, max_tokens=24, ignore_eos=True)))
    # let spec chains form and run ahead, then poison one step
    for _ in range(3):
        llm.step()
    assert llm._in_flight, "no spec chain in flight — test is inert"
    faults.FAULTS.arm("step_exception:0:1")
    try:
        with pytest.raises(faults.InjectedFault):
            for _ in range(80):
                llm.step()
    finally:
        faults.FAULTS.reset()
    dropped = llm.quarantine_step_failure()
    assert dropped
    # FutureMap in-flight entries unwound, chain/spec carry cleared
    assert not llm._in_flight and llm._chain_tip is None
    assert not llm.has_unfinished
    # zero leaked pages (slot holes, verify-row strides, spec
    # over-promise headroom all returned)
    assert llm.memory_manager.allocator.num_free == baseline
    # the SAME engine serves a fresh workload byte-identically — the
    # per-slot recent-token ring re-seeds from committed tokens at the
    # next chain root, never from the quarantined block's carry
    assert run(llm) == want
