"""Pipelined engine loop (--pipelined-loop) correctness.

The contract (docs/overlap_scheduling.md#pipelined-loop): with the flag
ON, greedy and seeded token streams are byte-identical to the flag-off
loop under arrival / finish / preemption churn — speculative re-forms
off promised token counts never change what commits, only when the
schedule/build/dispatch work happens; promised-vs-actual divergence
(EOS/stop the host could not predict) invalidates and rebuilds exactly
the speculated entries. With the flag OFF the engine is today's loop,
byte for byte (the existing overlap identity tests cover that arm
unmodified).
"""

import numpy as np
import pytest

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.engine.llm import LLM
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.obs.steptrace import TRACE, summarize
from gllm_tpu.sampling_params import SamplingParams


@pytest.fixture(scope="module")
def model_cfg():
    # dummy-weight tiny Llama: deterministic (seeded init), no HF/torch
    return ModelConfig(
        architecture="LlamaForCausalLM", vocab_size=512, hidden_size=64,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        intermediate_size=128, max_position=256)


def make_llm(model_cfg, *, pipelined, num_pages=256, max_model_len=128,
             max_num_seqs=8, eos=(), **kw):
    cfg = EngineConfig(
        load_format="dummy", dtype="float32",
        max_model_len=max_model_len, max_num_seqs=max_num_seqs,
        overlap_scheduling=True, pipelined_loop=pipelined,
        scheduler=SchedulerConfig(max_prefill_tokens=32,
                                  max_decode_seqs=max_num_seqs),
        cache=CacheConfig(page_size=4, num_pages=num_pages), **kw)
    llm = LLM(config=cfg, model_cfg=model_cfg)
    if eos:
        llm.eos_token_ids = frozenset(eos)
    return llm


def check_no_leak(llm):
    assert llm.memory_manager.num_free_pages == \
        llm.memory_manager.allocator.num_total


def run(model_cfg, pipelined, prompts, sps, **kw):
    llm = make_llm(model_cfg, pipelined=pipelined, **kw)
    outs = llm.generate(prompt_token_ids=[list(p) for p in prompts],
                        sampling_params=sps)
    check_no_leak(llm)
    assert not llm._in_flight
    return [(o.output_token_ids, o.finish_reason) for o in outs], llm


def staggered_workload(rng, n=6, vocab=500):
    prompts = [[int(x) for x in rng.integers(2, vocab, size=int(m))]
               for m in rng.integers(3, 14, size=n)]
    sps = [SamplingParams(temperature=0.0, max_tokens=int(m),
                          ignore_eos=True)
           for m in rng.integers(4, 24, size=n)]
    return prompts, sps


def test_pipelined_matches_sync_staggered_lengths(model_cfg):
    """Staggered max_tokens: every finish breaks the chain; the
    speculative re-form must commit exactly the sync loop's tokens
    (length deaths are host-predicted — no divergence possible)."""
    prompts, sps = staggered_workload(np.random.default_rng(3))
    base, _ = run(model_cfg, False, prompts, sps)
    pip, llm = run(model_cfg, True, prompts, sps)
    assert base == pip
    assert llm.futures.rebuilds == 0       # predicted deaths never diverge


def test_pipelined_matches_sync_with_eos(model_cfg):
    """Natural (host-detected) EOS mid-pipeline: divergence may
    invalidate speculated entries; committed streams stay identical."""
    rng = np.random.default_rng(5)
    prompts = [[int(x) for x in rng.integers(2, 60, size=int(m))]
               for m in rng.integers(3, 12, size=6)]
    sps = [SamplingParams(temperature=0.0, max_tokens=40)
           for _ in range(6)]
    # an organically common greedy token as EOS → finishes mid-stream
    probe, _ = run(model_cfg, False, prompts, sps)
    toks = [t for o, _ in probe for t in o]
    eos = max(set(toks), key=toks.count)
    base, _ = run(model_cfg, False, prompts, sps, eos=[eos])
    pip, _ = run(model_cfg, True, prompts, sps, eos=[eos])
    assert base == pip
    assert any(r == "stop" for _, r in pip)      # EOS actually fired


def test_pipelined_matches_sync_fused_slots_odf(model_cfg):
    """Pipelined loop composed with fused blocks + persistent slots +
    on-device finish — the full-profile bench stack."""
    prompts, sps = staggered_workload(np.random.default_rng(7))
    kw = dict(multi_step_decode=4, decode_slot_batching=True,
              ondevice_finish=True)
    base, _ = run(model_cfg, False, prompts, sps, **kw)
    pip, _ = run(model_cfg, True, prompts, sps, **kw)
    assert base == pip


def test_pipelined_matches_sync_seeded(model_cfg):
    """Seeded sampling: draws are a pure function of (seed, out_step),
    which the promised frontier advances exactly — byte-identical even
    across speculative re-forms and rebuilds."""
    rng = np.random.default_rng(9)
    prompts = [[int(x) for x in rng.integers(2, 500, size=int(m))]
               for m in rng.integers(3, 12, size=4)]
    sps = [SamplingParams(temperature=0.8, seed=100 + i,
                          max_tokens=int(m), ignore_eos=True)
           for i, m in enumerate(rng.integers(6, 20, size=4))]
    base, _ = run(model_cfg, False, prompts, sps)
    pip, _ = run(model_cfg, True, prompts, sps)
    assert base == pip


def churn_run(model_cfg, pipelined, *, num_pages=256, seeded=False,
              msd=1, slots=False):
    """Drive step() by hand with staggered arrivals (and optional page
    pressure) — the chain-yield, admission, and preemption paths all
    fire while speculative entries are in flight."""
    llm = make_llm(model_cfg, pipelined=pipelined, num_pages=num_pages,
                   max_model_len=64, eos=[7], multi_step_decode=msd,
                   decode_slot_batching=slots, ondevice_finish=slots)
    rng = np.random.default_rng(11)
    seqs, nseq, it = [], 0, 0
    arrivals = {0: 3, 2: 2, 5: 2, 9: 1}
    while nseq < 8 or llm.has_unfinished:
        for _ in range(arrivals.get(it, 0)):
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
        assert it < 2000, "engine stopped making progress"
    check_no_leak(llm)
    return [(s.token_ids[:], s.finish_reason) for s in seqs], llm


@pytest.mark.parametrize("kw", [
    {},                                    # arrivals only
    {"num_pages": 24},                     # + preemption pressure
    {"seeded": True},
    {"msd": 4, "slots": True},             # fused + persistent slots
    {"num_pages": 24, "msd": 4},           # fused + preemption
])
def test_pipelined_matches_sync_under_churn(model_cfg, kw):
    base, _ = churn_run(model_cfg, False, **kw)
    pip, llm = churn_run(model_cfg, True, **kw)
    assert base == pip
    if kw.get("num_pages"):
        # the pressure arm must actually exercise preemption
        assert llm.scheduler.num_preemptions > 0


def test_reconciliation_rebuilds_exactly_the_speculated_step(model_cfg):
    """Deterministic promised-vs-actual divergence: seq A finishes by a
    stop token at output index 1, seq B at index 2 — A's finish breaks
    the chain, the engine speculates [B] off promised counts, and B's
    finish (committing from an entry already in flight) invalidates
    exactly that speculated entry. Tokens stay identical to sync and
    the invalidated work is the only discarded dispatch."""
    pa, pb = [5, 17, 93], [9, 41, 3, 77]
    probe, _ = run(model_cfg, False, [pa, pb],
                   [SamplingParams(temperature=0.0, max_tokens=8,
                                   ignore_eos=True)] * 2)
    ca, cb = probe[0][0], probe[1][0]
    assume = (ca[0] != ca[1] and cb[2] not in (cb[0], cb[1]))
    assert assume, "probe continuations degenerate; pick other prompts"
    sps = [SamplingParams(temperature=0.0, max_tokens=20,
                          stop_token_ids=[ca[1]]),
           SamplingParams(temperature=0.0, max_tokens=20,
                          stop_token_ids=[cb[2]])]
    base, _ = run(model_cfg, False, [pa, pb], sps)

    llm = make_llm(model_cfg, pipelined=True)
    discarded = []
    orig_discard = llm.scheduler.discard_batch
    llm.scheduler.discard_batch = lambda b: (discarded.append(b),
                                             orig_discard(b))[1]
    mark = TRACE.mark()
    outs = llm.generate(prompt_token_ids=[list(pa), list(pb)],
                        sampling_params=sps)
    check_no_leak(llm)
    pip = [(o.output_token_ids, o.finish_reason) for o in outs]
    assert pip == base
    assert llm.futures.divergences == 1
    assert llm.futures.rebuilds == 1
    # exactly the speculated entry was discarded: one batch, carrying a
    # promise splice map (src_rows), holding only B's row
    assert len(discarded) == 1
    b = discarded[0]
    b = b[0] if isinstance(b, list) else b
    assert b.src_rows is not None
    assert [it.seq.seq_id for it in b.items] == [outs[1].seq_id]
    stalls = summarize(TRACE.events(since=mark))["loop_stalls_by_reason"]
    assert stalls.get("rebuild") == 1


def test_invalidated_entry_never_becomes_a_chain_tip(model_cfg):
    """Regression: an invalidated speculative entry still holds
    RUNNING sequences (only ONE of its promises died); chaining or
    re-forming off it would build on a discarded frontier and commit
    streams that skip a token. With a third long-running sequence
    riding in the speculated batch, the rebuild must re-derive its
    tokens from committed state — byte-identical to sync."""
    pa, pb, pc = [5, 17, 93], [9, 41, 3, 77], [22, 8, 51]
    probe, _ = run(model_cfg, False, [pa, pb, pc],
                   [SamplingParams(temperature=0.0, max_tokens=8,
                                   ignore_eos=True)] * 3)
    ca, cb = probe[0][0], probe[1][0]
    assert ca[0] != ca[1] and cb[2] not in (cb[0], cb[1])
    sps = [SamplingParams(temperature=0.0, max_tokens=20,
                          stop_token_ids=[ca[1]]),
           SamplingParams(temperature=0.0, max_tokens=20,
                          stop_token_ids=[cb[2]]),
           SamplingParams(temperature=0.0, max_tokens=16,
                          ignore_eos=True)]
    base, _ = run(model_cfg, False, [pa, pb, pc], sps)
    pip, llm = run(model_cfg, True, [pa, pb, pc], sps)
    assert pip == base
    assert llm.futures.rebuilds >= 1      # the divergence actually fired


def test_sync_loop_records_no_stall_events(model_cfg):
    """loop_stall is a pipelined-only vocabulary: the flag-off loop must
    not emit it (flag-off == today's engine, observability included)."""
    prompts, sps = staggered_workload(np.random.default_rng(13))
    mark = TRACE.mark()
    run(model_cfg, False, prompts, sps)
    assert not TRACE.events(since=mark, kinds=["loop_stall"])


def test_reform_batches_splice_from_device(model_cfg):
    """Structural: the pipelined arm actually schedules speculative
    re-forms (src_rows batches) across finish-driven chain breaks
    instead of draining — and every one of them commits or is
    reconciled, never silently dropped."""
    prompts, sps = staggered_workload(np.random.default_rng(17))
    llm = make_llm(model_cfg, pipelined=True)
    reforms = []
    orig = llm.scheduler.schedule_reform
    def spy(prev):
        b = orig(prev)
        if b is not None:
            reforms.append(b)
        return b
    llm.scheduler.schedule_reform = spy
    llm.generate(prompt_token_ids=[list(p) for p in prompts],
                 sampling_params=sps)
    check_no_leak(llm)
    assert reforms, "staggered finishes never triggered a re-form"
    assert all(b.src_rows is not None for b in reforms)


@pytest.mark.parametrize("msd", [1, 4])
def test_pipelined_runs_further_ahead_at_decode_saturation(model_cfg, msd):
    """Acceptance (ISSUE 11), in what the ring counts and no clock: on a
    decode-saturated workload with staggered finishes the pipelined loop
    commits the flag-off loop's tokens in the same dispatches and
    sustains a deeper run-ahead (the re-form keeps entries in flight
    across breaks the sync loop drains on). Whether that buys time is a
    chip question (perfbench/run.py)."""
    rng = np.random.default_rng(0)
    prompts = [[int(x) for x in rng.integers(1, 500, size=int(m))]
               for m in rng.integers(8, 32, size=12)]
    mts = rng.integers(16, 64, size=12)

    def arm(pipelined):
        sps = [SamplingParams(temperature=0.0, max_tokens=int(m),
                              ignore_eos=True) for m in mts]
        llm = make_llm(model_cfg, pipelined=pipelined,
                       max_model_len=256, num_pages=1024,
                       max_num_seqs=16, multi_step_decode=msd)
        mark = TRACE.mark()
        outs = llm.generate(prompt_token_ids=[list(p) for p in prompts],
                            sampling_params=sps)
        s = summarize(TRACE.events(since=mark))
        return s, [o.output_token_ids for o in outs]

    s_sync, toks_sync = arm(False)
    s_pip, toks_pip = arm(True)
    assert toks_sync == toks_pip
    steps = {k: row["steps"] for k, row in s_pip["by_kind"].items()}
    assert steps == {k: row["steps"] for k, row in s_sync["by_kind"].items()}
    assert s_pip["mean_inflight_depth"] > s_sync["mean_inflight_depth"]
    # a fill pass stalls at most once, and on this workload never on a
    # rebuild (length deaths are host-predicted); the sync loop, whose
    # vocabulary this is not, records none
    assert s_sync["loop_stalls"] == 0
    assert 0 < s_pip["loop_stalls"] <= sum(steps.values())
    assert "rebuild" not in s_pip["loop_stalls_by_reason"]


def test_reconcile_cascade_stops_at_a_valid_sync_root():
    """FutureMap unit: a chained entry descending from a LATER valid
    sync-rooted batch must survive an earlier entry's invalidation —
    the cascade models chain parentage, not deque order."""
    from gllm_tpu.engine.pipeline import FutureMap, InFlight

    def e(**kw):
        return InFlight(None, object(), 0.0, None, **kw)

    fm = FutureMap()
    reform = e(chained=True, promises=frozenset({7}))
    prefill = e()                               # interleaved, no root
    root = e(roots=True)                        # fresh sync decode root
    chain_off_root = e(chained=True)
    entries = [reform, prefill, root, chain_off_root]
    assert fm.reconcile(entries, frozenset({7})) == 1
    assert reform.invalid
    assert not prefill.invalid and not root.invalid
    assert not chain_off_root.invalid           # parent is the valid root
    # without a root in between, the cascade takes the chained entry
    fm2 = FutureMap()
    r2, c2 = (e(chained=True, promises=frozenset({7})),
              e(chained=True))
    assert fm2.reconcile([r2, e(), c2], frozenset({7})) == 2
    assert r2.invalid and c2.invalid


def test_reform_budget_skip_beats_penalty_refusal(model_cfg):
    """Scheduler unit: a penalized decode-ready candidate BEYOND the
    decode budget must not refuse the whole re-form (the sync path
    could not seat it either); under budget it still refuses so the
    sync pass can seat it."""
    from gllm_tpu.memory_manager import make_memory_manager
    from gllm_tpu.scheduler import ScheduledBatch, ScheduledSeq, Scheduler
    from gllm_tpu.sequence import Sequence, SequenceStatus

    def setup(budget):
        cfg = EngineConfig(
            load_format="dummy", max_model_len=128, max_num_seqs=8,
            overlap_scheduling=True, pipelined_loop=True,
            scheduler=SchedulerConfig(max_prefill_tokens=32,
                                      max_decode_seqs=budget),
            cache=CacheConfig(page_size=4, num_pages=64))
        mm = make_memory_manager(64, 4, False)
        sched = Scheduler(cfg, mm)
        # one in-flight decode row (the chain tip's item)
        a = Sequence(0, [1] * 6, SamplingParams(temperature=0.0,
                                                max_tokens=20,
                                                ignore_eos=True))
        a.status = SequenceStatus.RUNNING
        a.num_computed_tokens = 5
        mm.allocate_seq_pages(a, 1)
        a.num_in_flight = 1
        sched.running.append(a)
        # a decode-ready PENALIZED candidate (not in flight)
        b = Sequence(1, [1] * 5, SamplingParams(temperature=0.0,
                                                max_tokens=20,
                                                repetition_penalty=1.3,
                                                ignore_eos=True))
        b.status = SequenceStatus.RUNNING
        b.num_computed_tokens = 4
        mm.allocate_seq_pages(b, 1)
        sched.running.append(b)
        prev = ScheduledBatch([ScheduledSeq(a, 1, 5)])
        return sched, prev

    sched, prev = setup(budget=1)      # batch already at budget
    batch = sched.schedule_reform(prev)
    assert batch is not None, sched.reform_fail_reason
    assert [it.seq.seq_id for it in batch.items] == [0]
    sched2, prev2 = setup(budget=2)    # room for the penalized seq
    assert sched2.schedule_reform(prev2) is None
    assert sched2.reform_fail_reason == "shape"


def test_pipelined_flag_lifts_overlap(model_cfg):
    cfg = EngineConfig(load_format="dummy", pipelined_loop=True)
    cfg.validate()
    assert cfg.overlap_scheduling
    cfg2 = EngineConfig(load_format="dummy", pipelined_loop=True,
                        enforce_eager=True)
    cfg2.validate()
    assert not cfg2.pipelined_loop and not cfg2.overlap_scheduling


def test_quarantine_clears_speculative_entries(model_cfg):
    """A step exception with speculative entries in flight: quarantine
    must drop them (pages freed, no dangling promises) and the engine
    must idle clean — the PR-7 fault-isolation contract extends to the
    pipelined loop."""
    from gllm_tpu import faults
    llm = make_llm(model_cfg, pipelined=True)
    prompts, sps = staggered_workload(np.random.default_rng(23), n=4)
    for ids, sp in zip(prompts, sps):
        llm.add_seq(llm._allocate_seq(list(ids), sp))
    # let the pipeline fill + run a few steps, then poison one step
    for _ in range(4):
        llm.step()
    faults.FAULTS.arm("step_exception:0:1")
    try:
        with pytest.raises(faults.InjectedFault):
            for _ in range(50):
                llm.step()
    finally:
        faults.FAULTS.reset()
    dropped = llm.quarantine_step_failure()
    assert dropped
    assert not llm._in_flight and llm._chain_tip is None
    check_no_leak(llm)
    assert not llm.has_unfinished
