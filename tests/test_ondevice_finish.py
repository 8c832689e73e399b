"""On-device finish detection in fused decode blocks (ISSUE 6).

The fused multi-step scan compares each sampled token against per-row
EOS/stop-token sets on device, folds the result into a carried alive
mask (frozen position, dummy-page KV writes — the same freeze machinery
length deaths use), and the block driver early-exits once every row is
dead. Token streams must be byte-identical to the legacy host-side
finish path in every mode: the device only stops computing tokens the
host would have discarded anyway.

All engines here run dummy weights (seeded init → deterministic logits)
on the CPU backend.
"""

import dataclasses

import numpy as np
import pytest

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.engine.llm import LLM
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.sampling_params import SamplingParams

MODEL_CFG = ModelConfig(
    architecture="LlamaForCausalLM", vocab_size=256, hidden_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
    intermediate_size=128, max_position=256)

PROMPTS = [[3, 14, 15], [9, 2, 6, 5, 3], [58, 9]]


def make_llm(eos=(), **kw):
    cfg = EngineConfig(
        load_format="dummy", dtype="float32", max_model_len=128,
        max_num_seqs=8,
        scheduler=SchedulerConfig(max_prefill_tokens=64, max_decode_seqs=8),
        cache=CacheConfig(page_size=4, num_pages=128), **kw)
    llm = LLM(config=cfg, model_cfg=MODEL_CFG)
    llm.eos_token_ids = frozenset(eos)
    return llm


def run(sps, prompts=PROMPTS, eos=(), **kw):
    llm = make_llm(eos, **kw)
    if isinstance(sps, SamplingParams):
        sps = [dataclasses.replace(sps) for _ in prompts]
    else:
        sps = [dataclasses.replace(s) for s in sps]
    outs = llm.generate(prompt_token_ids=[list(p) for p in prompts],
                        sampling_params=sps)
    assert llm.memory_manager.num_free_pages == \
        llm.memory_manager.allocator.num_total  # no page leaks
    return [(o.output_token_ids, o.finish_reason) for o in outs]


ODF = dict(overlap_scheduling=True, multi_step_decode=8,
           ondevice_finish=True)
LEGACY = dict(overlap_scheduling=True, multi_step_decode=8)


@pytest.fixture(scope="module")
def organic():
    """(eos_id, stop_id): tokens the greedy dummy model actually emits at
    output positions 2 and 4 for PROMPTS[0] — deaths land mid-block."""
    toks = run(SamplingParams(temperature=0.0, max_tokens=10,
                              ignore_eos=True),
               prompts=[PROMPTS[0]])[0][0]
    return toks[2], toks[4]


# ---------------------------------------------------------------------------
# byte-identity vs legacy host-side finish
# ---------------------------------------------------------------------------

def test_eos_midblock_byte_identity(organic):
    eos = [organic[0]]
    sp = SamplingParams(temperature=0.0, max_tokens=30)
    want = run(sp, eos=eos)                       # sync engine
    assert run(sp, eos=eos, **LEGACY) == want     # host-side finish
    assert run(sp, eos=eos, **ODF) == want        # on-device finish


def test_stop_token_midblock_byte_identity(organic):
    sp = SamplingParams(temperature=0.0, max_tokens=30,
                        stop_token_ids=[organic[1]])
    want = run(sp)
    got = run(sp, **ODF)
    assert got == want
    assert got[0][1] == "stop" and len(got[0][0]) == 5


def test_length_cap_byte_identity():
    for max_tokens in (1, 23):
        sp = SamplingParams(temperature=0.0, max_tokens=max_tokens,
                            ignore_eos=True)
        want = run(sp)
        got = run(sp, **ODF)
        assert got == want
        assert all(r == "length" for _, r in got)


def test_seeded_sampling_byte_identity(organic):
    eos = [organic[0]]
    sps = [SamplingParams(temperature=0.9, seed=7, max_tokens=24),
           SamplingParams(temperature=0.7, seed=11, max_tokens=24),
           SamplingParams(temperature=0.0, max_tokens=24)]
    want = run(sps, eos=eos)
    assert run(sps, eos=eos, **ODF) == want


def test_min_tokens_arms_detection_like_host(organic):
    # the idx-2 eos must be ignored until min_tokens output tokens exist,
    # on device exactly like Sequence.check_finish host-side
    eos = [organic[0]]
    sp = SamplingParams(temperature=0.0, max_tokens=12, min_tokens=6)
    want = run(sp, prompts=[PROMPTS[0]], eos=eos)
    got = run(sp, prompts=[PROMPTS[0]], eos=eos, **ODF)
    assert got == want
    assert len(got[0][0]) > 3          # idx-2 eos did not finish it


def test_slot_batching_composes(organic):
    eos = [organic[0]]
    sps = [SamplingParams(temperature=0.8, seed=3, max_tokens=30),
           SamplingParams(temperature=0.0, max_tokens=30),
           SamplingParams(temperature=0.0, max_tokens=30,
                          stop_token_ids=[organic[1]])]
    want = run(sps, eos=eos)
    assert run(sps, eos=eos, decode_slot_batching=True,
               chain_under_prefill=8, **ODF) == want


def test_flag_off_byte_identity(organic):
    # ondevice_finish=False must stay byte-identical legacy (same scan
    # program as before the flag existed)
    eos = [organic[0]]
    sp = SamplingParams(temperature=0.0, max_tokens=30)
    assert run(sp, eos=eos, **LEGACY) == run(sp, eos=eos)


# ---------------------------------------------------------------------------
# early exit + finish-step plumb-back
# ---------------------------------------------------------------------------

def test_early_exit_when_all_rows_die(organic):
    """A block whose rows all finish early must stop executing sub-steps
    (k_exec < scheduled k in the steptrace event) and still produce the
    sync engine's exact tokens."""
    from gllm_tpu.obs.steptrace import TRACE, summarize
    eos = [organic[0]]
    sp = SamplingParams(temperature=0.0, max_tokens=30)
    want = run(sp, prompts=[PROMPTS[0]], eos=eos)
    mark = TRACE.mark()
    got = run(sp, prompts=[PROMPTS[0]], eos=eos, **ODF)
    assert got == want and got[0][1] == "stop"
    evs = TRACE.events(since=mark, kinds=("fused_block",))
    assert evs, "no fused blocks formed"
    assert all("k_exec" in e for e in evs)
    assert any(e["k_exec"] < e["k"] for e in evs), evs
    # the summarizer aggregates the dead-substep share
    assert summarize(evs)["dead_substep_frac"] is not None


def test_dead_substep_frac_counts_dead_rows(organic):
    """Mixed block: one row dies at eos while others run to max_tokens —
    the dead rows the block still executes show up as dead_substeps."""
    from gllm_tpu.obs.steptrace import TRACE
    eos = [organic[0]]
    sps = [SamplingParams(temperature=0.0, max_tokens=30),         # dies
           SamplingParams(temperature=0.0, max_tokens=30,
                          ignore_eos=True)]                        # runs
    mark = TRACE.mark()
    want = run(sps, prompts=PROMPTS[:2], eos=eos)
    mark = TRACE.mark()
    got = run(sps, prompts=PROMPTS[:2], eos=eos, **ODF)
    assert got == want
    evs = TRACE.events(since=mark, kinds=("fused_block",))
    assert sum(e.get("dead_substeps", 0) for e in evs) > 0, evs


def test_ondevice_finish_metrics(organic):
    from gllm_tpu.obs import metrics as obs
    m = obs.REGISTRY.get("gllm_ondevice_finish_total")
    eos = [organic[0]]
    sps = [SamplingParams(temperature=0.0, max_tokens=30),
           SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True),
           SamplingParams(temperature=0.0, max_tokens=30,
                          stop_token_ids=[organic[1]], ignore_eos=True)]
    before = {k: m.get(kind=k) for k in ("eos", "stop", "length")}
    # the stop-token row re-runs PROMPTS[0], whose greedy continuation
    # the organic stop id was discovered from
    run(sps, prompts=[PROMPTS[0], PROMPTS[1], PROMPTS[0]], eos=eos, **ODF)
    assert m.get(kind="eos") == before["eos"] + 1
    assert m.get(kind="stop") == before["stop"] + 1
    assert m.get(kind="length") == before["length"] + 1


# ---------------------------------------------------------------------------
# interpret-mode (pallas) parity
# ---------------------------------------------------------------------------

def test_pallas_interpret_parity(organic):
    eos = [organic[0]]
    sp = SamplingParams(temperature=0.0, max_tokens=20)
    want = run(sp, prompts=PROMPTS[:2], eos=eos, attention_impl="pallas")
    got = run(sp, prompts=PROMPTS[:2], eos=eos, attention_impl="pallas",
              **ODF)
    assert got == want


# ---------------------------------------------------------------------------
# stop-set builder units
# ---------------------------------------------------------------------------

def test_stop_sets_builder():
    from gllm_tpu.scheduler import ScheduledSeq
    from gllm_tpu.sequence import Sequence
    llm = make_llm()
    b = llm.runner.builder
    s1 = Sequence(0, [1, 2, 3], SamplingParams(max_tokens=8,
                                               stop_token_ids=[7, 5]))
    s2 = Sequence(1, [1, 2], SamplingParams(max_tokens=8, ignore_eos=True))
    s3 = Sequence(2, [1, 2], SamplingParams(max_tokens=8, min_tokens=6))
    items = [ScheduledSeq(s, 1, s.prompt_len) for s in (s1, s2, s3)]
    ids, frm = b.stop_sets(items, 8, frozenset([9]))
    assert ids.shape == (8, 8) and ids.dtype == np.int32
    assert sorted(ids[0][ids[0] >= 0].tolist()) == [5, 7, 9]
    assert (ids[1] == -1).all()            # ignore_eos, no stop ids
    assert sorted(ids[2][ids[2] >= 0].tolist()) == [9]
    assert (ids[3:] == -1).all()           # bucket padding rows
    assert frm[0] == 0 and frm[1] == 0
    # min_tokens=6, prompt_len=2, computed_before=2 → armed from step 4
    assert frm[2] == 6 + 2 - 2 - 2
    # no row carries any id → the device compare is skipped entirely
    s4 = Sequence(3, [1], SamplingParams(max_tokens=4, ignore_eos=True))
    assert b.stop_sets([ScheduledSeq(s4, 1, 1)], 8, frozenset([9])) \
        == (None, None)


def test_hole_rows_contribute_no_stop_ids():
    """Persistent-slot HOLE rows are dead for the whole block — they
    must not widen (or create) the stop-id bucket, or the first finish
    in an all-ignore_eos workload would flip the fused block's compile
    signature mid-run."""
    from gllm_tpu.scheduler import ScheduledSeq
    from gllm_tpu.sequence import Sequence, make_hole_seq
    llm = make_llm()
    b = llm.runner.builder
    live = Sequence(0, [1, 2], SamplingParams(max_tokens=8,
                                              ignore_eos=True))
    items = [ScheduledSeq(live, 1, 2), ScheduledSeq(make_hole_seq(), 1, 1)]
    assert b.stop_sets(items, 8, frozenset([9])) == (None, None)


def test_device_stop_ids():
    seq = SamplingParams(stop_token_ids=[4], ignore_eos=False)
    from gllm_tpu.sequence import Sequence
    s = Sequence(0, [1], SamplingParams(stop_token_ids=[4, 2]))
    assert s.device_stop_ids(frozenset([9, 2])) == [2, 4, 9]
    s2 = Sequence(1, [1], SamplingParams(stop_token_ids=[4],
                                         ignore_eos=True))
    assert s2.device_stop_ids(frozenset([9])) == [4]


# ---------------------------------------------------------------------------
# config resolution
# ---------------------------------------------------------------------------

def test_decode_chain_len_resolution():
    cfg = EngineConfig(overlap_scheduling=True, decode_chain_len=24)
    cfg.validate()
    assert cfg.multi_step_decode == 24
    # ondevice_finish raises an unset chain length to 16
    cfg = EngineConfig(overlap_scheduling=True, ondevice_finish=True)
    cfg.validate()
    assert cfg.multi_step_decode == 16
    # an explicit multi_step_decode is respected
    cfg = EngineConfig(overlap_scheduling=True, ondevice_finish=True,
                       multi_step_decode=4)
    cfg.validate()
    assert cfg.multi_step_decode == 4
    # enforce_eager strips the whole feature set
    cfg = EngineConfig(overlap_scheduling=True, ondevice_finish=True,
                       decode_chain_len=16, enforce_eager=True)
    cfg.validate()
    assert cfg.multi_step_decode == 1 and not cfg.ondevice_finish
    with pytest.raises(ValueError):
        EngineConfig(decode_chain_len=0).validate()


# ---------------------------------------------------------------------------
# closure hygiene: the new jitted body (PR-4 guard extension)
# ---------------------------------------------------------------------------

def test_multi_step_body_closes_over_no_buffers(organic):
    """The on-device-finish multi-step program must take params/KV/batch
    as ARGUMENTS, never closure constants (a captured buffer is baked
    into the compile request and the executable)."""
    import jax
    import jax.numpy as jnp
    from test_kernel_tuning import _big_consts

    from gllm_tpu.batching import pack
    from gllm_tpu.scheduler import ScheduledBatch, ScheduledSeq
    from gllm_tpu.sequence import Sequence

    llm = make_llm(eos=[organic[0]], **ODF)
    runner = llm.runner
    seq = Sequence(0, [1, 2, 3, 4],
                   SamplingParams(temperature=0.0, max_tokens=8))
    seq.page_table = [1, 2]
    seq.num_computed_tokens = 3
    items = [ScheduledSeq(seq, 1, 3)]
    batch, max_q, tc = runner.builder.build(ScheduledBatch(items))
    assert max_q == 1 and tc is None
    s_bucket = batch.token_ids.shape[0]
    stop_ids, stop_from = runner.builder.stop_sets(
        items, s_bucket, runner.eos_token_ids)
    batch = batch._replace(sampling=batch.sampling._replace(
        stop_ids=stop_ids, stop_from=stop_from))
    packed, layout = pack(batch, (1,),
                          active_until=np.full(s_bucket, 4, np.int32))

    def fn(params, kv, b, cos_sin, key):
        return runner._multi_step_fn(params, kv, b, cos_sin, key,
                                     layout=layout, num_steps=4,
                                     all_greedy=True, ondevice_finish=True)

    big = _big_consts(fn, runner.params, runner.kv,
                      jax.tree.map(jnp.asarray, packed), runner.cos_sin,
                      runner.rng_key)
    assert not big, (
        f"multi-step ondevice-finish body closes over buffer-sized "
        f"constants (shape, dtype, nbytes): {big}")
