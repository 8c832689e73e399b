"""Fast path × topology (ISSUE 20): the pipelined loop across pp / dp.

The oracle is the same one every other distributed mode answers to
(tests/test_pipeline_parallel.py): byte-identity of greedy AND seeded
token streams against the pp=1/dp=1 runs — here under arrival/finish
churn with ``--pipelined-loop`` on, on the forced
multi-device CPU host platform. Flag-off must stay byte-identical to
the legacy sync pipeline (the lift cannot perturb the default path).

Per-stage throttled batches: with ``token_throttling`` + pp=2 every
stage's dispatch carries its microbatch's family (pp_stage events hold
``family`` on EVERY stage index); the re-form refusal class the
per-microbatch decode budget introduces (``pp_budget``) gets its own
reason string and loop_stall steptrace row
(docs/overlap_scheduling.md#topology-matrix).
"""

import numpy as np
import pytest
import torch

from gllm_tpu.config import (CacheConfig, EngineConfig, ParallelConfig,
                             SchedulerConfig)
from gllm_tpu.engine.llm import LLM
from gllm_tpu.obs.steptrace import TRACE, summarize
from gllm_tpu.sampling_params import SamplingParams
from gllm_tpu.sequence import SequenceStatus

TINY = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=8, num_key_value_heads=4, intermediate_size=96,
    max_position_embeddings=256, rms_norm_eps=1e-6, rope_theta=10000.0,
    tie_word_embeddings=False, eos_token_id=0,
)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(23)
    d = tmp_path_factory.mktemp("topo_llama")
    LlamaForCausalLM(LlamaConfig(**TINY, attention_bias=False)
                     ).save_pretrained(d, safe_serialization=True)
    return str(d)


def make_llm(ckpt, *, pp=1, dp=1, tp=1, fast=True,
             method="chunked_prefill", num_pages=256):
    cfg = EngineConfig(
        model=ckpt, dtype="float32", max_model_len=128, max_num_seqs=8,
        overlap_scheduling=fast, pipelined_loop=fast,
        overlap_depth=2,
        scheduler=SchedulerConfig(schedule_method=method,
                                  max_prefill_tokens=32,
                                  max_decode_seqs=8),
        cache=CacheConfig(page_size=4, num_pages=num_pages),
        parallel=ParallelConfig(pp=pp, dp=dp, tp=tp))
    return LLM(config=cfg)


def churn(ckpt, *, pp=1, dp=1, tp=1, fast=True, seeded=False,
          method="chunked_prefill", n=8, hook=None):
    """Arrival/finish churn: requests land MID-FLIGHT (the re-form /
    super-step edges), finishes are a mix of host-predictable length
    deaths and EOS stops the promise registry must reconcile."""
    llm = make_llm(ckpt, pp=pp, dp=dp, tp=tp, fast=fast, method=method)
    # eos churn: greedy streams on random tiny weights revisit low token
    # ids often, so a small eos set produces genuine early finishes
    llm.eos_token_ids = frozenset({0, 7})
    state = hook(llm) if hook is not None else None
    rng = np.random.default_rng(17)
    seqs, nseq, it = [], 0, 0
    arrivals = {0: 3, 2: 2, 5: 2, 9: 1}
    while nseq < n or llm.has_unfinished:
        for _ in range(arrivals.get(it, 0)):
            if nseq >= n:
                break
            ids = [int(x) for x in
                   rng.integers(2, 120, size=int(rng.integers(3, 12)))]
            sp = (SamplingParams(temperature=0.8, seed=100 + nseq,
                                 max_tokens=int(rng.integers(4, 14)))
                  if seeded else
                  SamplingParams(temperature=0.0,
                                 max_tokens=int(rng.integers(4, 14))))
            s = llm._allocate_seq(ids, sp)
            seqs.append(s)
            llm.add_seq(s)
            nseq += 1
        llm.step()
        it += 1
        assert it < 3000, "engine stopped making progress"
    assert not llm._in_flight
    for sch in llm.schedulers:
        assert not sch.has_unfinished
    streams = [(s.token_ids[:], s.finish_reason) for s in seqs]
    return (streams, state) if hook is not None else (streams, llm)


def _count_reforms(llm):
    """Spy: count successful speculative re-forms across all replica
    schedulers — the fast arms must actually run ahead (a run that
    degraded to drain-and-sync would pass identity vacuously)."""
    state = {"reforms": 0}
    for sch in llm.schedulers:
        orig = sch.schedule_reform

        def spy(prev, _orig=orig):
            out = _orig(prev)
            if out is not None:
                state["reforms"] += 1
            return out

        sch.schedule_reform = spy
    return state


# ---------------------------------------------------------------------------
# byte-identity: pp=2 and dp=2 vs the single-runner stream
#
# Each churn arm compiles a fresh engine, so these run tens of seconds on
# the forced-host-device CPU platform.  Tier-1 keeps one e2e identity run
# per topology axis (dp2 greedy here; pp2 identity rides
# test_pp_budget_refusal_records_stall_row and the throttled test);
# the rest are `slow` — run explicitly with `-m slow` or no marker filter.
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("seeded", [False, True],
                         ids=["greedy", "seeded"])
def test_pp2_fast_path_byte_identical(ckpt, multi_device_cpu, seeded):
    base, _ = churn(ckpt, pp=1, fast=False, seeded=seeded)
    legacy, _ = churn(ckpt, pp=2, fast=False, seeded=seeded)
    assert legacy == base           # flag-off pp stays byte-identical
    fast, spy = churn(ckpt, pp=2, fast=True, seeded=seeded,
                      hook=_count_reforms)
    assert fast == base
    assert spy["reforms"] > 0, "pp fast arm never ran ahead"


@pytest.mark.parametrize("seeded", [
    False,
    pytest.param(True, marks=pytest.mark.slow),
], ids=["greedy", "seeded"])
def test_dp2_fast_path_byte_identical(ckpt, multi_device_cpu, seeded):
    base, _ = churn(ckpt, dp=1, fast=False, seeded=seeded)
    legacy, _ = churn(ckpt, dp=2, fast=False, seeded=seeded)
    assert legacy == base           # flag-off dp stays byte-identical
    fast, spy = churn(ckpt, dp=2, fast=True, seeded=seeded,
                      hook=_count_reforms)
    assert fast == base
    assert spy["reforms"] > 0, "dp fast arm never ran ahead"


@pytest.mark.slow
def test_pp2_tp2_fast_path_byte_identical(ckpt, multi_device_cpu):
    """pp×tp grid under the fast path: the pipelined lift rides the
    per-stage tp shard_map unchanged."""
    base, _ = churn(ckpt, pp=1, fast=False)
    fast, _ = churn(ckpt, pp=2, tp=2, fast=True)
    assert fast == base


# ---------------------------------------------------------------------------
# per-stage throttled batches (token_throttling + pp)
# ---------------------------------------------------------------------------

def test_pp2_throttled_family_on_every_stage(ckpt, multi_device_cpu):
    """token_throttling + pp=2 + the pipelined loop: the stream is the
    single runner's, and every pipeline stage's dispatch event carries
    the family of the microbatch it ran."""
    base, _ = churn(ckpt, pp=1, fast=False, method="token_throttling")
    mark = TRACE.mark()
    fast, llm = churn(ckpt, pp=2, fast=True, method="token_throttling")
    assert fast == base
    ev = TRACE.events(since=mark)
    s = summarize(ev)
    assert set(s["by_kind"]) <= {"prefill", "decode"}, s["by_kind"]
    stage_ev = [e for e in ev if e.get("kind") == "pp_stage"]
    assert stage_ev, "no per-stage dispatch events recorded"
    assert {e["stage"] for e in stage_ev} == {0, 1}
    for stage in (0, 1):
        assert {e["family"] for e in stage_ev if e["stage"] == stage} \
            == {"prefill", "decode"}
    # per-stage in-flight gauge drained back to zero with the pipeline
    assert llm.runner._mb_inflight == 0


def test_reform_refuses_over_budget_rows(ckpt, multi_device_cpu):
    """The genuine pp_budget arithmetic: finishes in OTHER microbatches
    shrink the per-stage decode budget (cdiv(n_decode, pp)) below a
    promised row count, and the re-form refuses with its OWN reason
    instead of dropping promised rows or unbalancing the stages."""
    llm = make_llm(ckpt, pp=2, fast=False, method="token_throttling")
    sched = llm.scheduler
    seqs = []
    for i in range(4):
        s = llm._allocate_seq(
            [3, 5, 7, 9, 11, 13],
            SamplingParams(temperature=0.0, max_tokens=32,
                           ignore_eos=True))
        # decode-ready mid-generation: pages cover the next token so the
        # budget check is the ONLY thing standing between base and a
        # successful re-form
        s.num_computed_tokens = s.num_tokens - 1
        s.page_table = [1, 1]
        s.status = SequenceStatus.RUNNING
        sched.running.append(s)
        seqs.append(s)
    prev = sched.schedule_once()
    assert prev is not None
    assert len(prev.items) == 2          # cdiv(4 decode, pp=2)
    # the two seqs the OTHER microbatch owns finish → n_decode halves
    sched.running = [s for s in sched.running if s.num_in_flight]
    assert sched.schedule_reform(prev) is None
    assert sched.reform_fail_reason == "pp_budget"
    sched.discard_batch(prev)
    assert all(s.num_in_flight == 0 for s in seqs)


def test_pp_budget_refusal_records_stall_row(ckpt, multi_device_cpu):
    """Engine plumbing for the new refusal class: a pp_budget re-form
    refusal surfaces as its own loop_stall steptrace row (not folded
    into 'readback'), and the run still commits the byte-identical
    stream via the drain-and-sync fallback."""
    base, _ = churn(ckpt, pp=1, fast=False, method="token_throttling")

    def hook(llm):
        state = {"fired": 0}
        orig = llm.scheduler.schedule_reform

        def spy(prev):
            if state["fired"] < 2 and len(prev.items) >= 2:
                state["fired"] += 1
                return llm.scheduler._reform_fail("pp_budget")
            return orig(prev)

        llm.scheduler.schedule_reform = spy
        return state

    mark = TRACE.mark()
    fast, state = churn(ckpt, pp=2, fast=True,
                        method="token_throttling", hook=hook)
    assert fast == base
    assert state["fired"] > 0
    s = summarize(TRACE.events(since=mark))
    assert s["loop_stalls_by_reason"].get("pp_budget", 0) >= 1, \
        s["loop_stalls_by_reason"]
