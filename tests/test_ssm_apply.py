"""The slot maintenance program (`runner._ssm_apply`, `_ssm_apply_replica`)
against a plain numpy rendering of its three classes: every snapshot, then
every zero, then every restore, a slot at a time. The intents go through a
real `MemoryManager` and the runners' own `_drained_ssm_ops` /
`_apply_ssm_intents`, so the pow2 padding with the dummy slot 0 is the
served one. What the program costs on the chip is read by the compile
guard in tests/test_tpu_compile.py (`-k slot_maintenance`)."""

import types
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np
import pytest

from gllm_tpu.memory_manager import MemoryManager
from gllm_tpu.runner.pp_runner import PPModelRunner
from gllm_tpu.runner.runner import _M_SSM_APPLY, ModelRunner
from gllm_tpu.sequence import Sequence

WORKING, SNAPS = 6, 4           # slots 1-6 and 7-10 beside the dummy 0
SLOTS = 1 + WORKING + SNAPS

# a slot of the recurrent pool by the lanes of its last dimension: one
# tile (the state-space cell's), two (falcon's), three (the hybrid's g
# heads abreast)
REC = {128: (2, 8, 128), 256: (2, 8, 256), 384: (1, 8, 384)}


class Pools(NamedTuple):
    conv: object
    rec: object


def make_pools(lanes, layers=2, dp=None, seed=0):
    rng = np.random.default_rng(seed)
    lead = (layers, SLOTS) if dp is None else (dp, layers, SLOTS)
    return Pools(rng.standard_normal(lead + (3, 16)).astype(np.float32),
                 rng.standard_normal(lead + REC[lanes]).astype(np.float32))


def plain(pools, intents, replica=None):
    """The three classes in numpy, on copies."""
    out = Pools(*(p.copy() for p in pools))
    for pool in out:
        view = pool if replica is None else pool[replica]
        for kind in ("snapshot", "zero", "restore"):
            for k, a, b in intents:
                if k != kind:
                    continue
                if kind == "zero":
                    view[:, a] = 0.0
                else:
                    view[:, b] = view[:, a]
    return out


def manager(intents):
    mm = MemoryManager(8, 4, ssm_working_slots=WORKING,
                       ssm_snapshot_slots=SNAPS)
    mm.ssm_intents = list(intents)
    return mm


def fake_runner(mm, **attrs):
    """What `_drained_ssm_ops` and `_apply_ssm_intents` read of a runner."""
    fake = types.SimpleNamespace(memory_manager=mm, memory_managers=None,
                                 **attrs)
    fake._drained_ssm_ops = lambda: ModelRunner._drained_ssm_ops(fake)
    return fake


def served(pools, mm, dp=1):
    fake = fake_runner(mm, dp=dp, kv=Pools(*map(jnp.asarray, pools)),
                       model_cfg=types.SimpleNamespace(use_hybrid=True))
    if dp > 1:
        # replica 0 has nothing pending, replica 1 has the intents
        fake.memory_managers = [manager([]), mm]
    ModelRunner._apply_ssm_intents(fake)
    return Pools(*(np.asarray(p) for p in fake.kv))


SCENARIOS = {
    "one_zero_among_padding": [("zero", 3, 0)],
    "a_full_list": (
        [("snapshot", w, 6 + w) for w in (1, 2, 3, 4)]
        + [("zero", w, 0) for w in (1, 2, 5, 6)]
        + [("restore", s, w) for s, w in ((7, 1), (8, 2), (9, 5), (10, 6))]),
    "restore_reads_this_calls_snapshot": [("snapshot", 2, 7),
                                          ("restore", 7, 4)],
    "the_same_slot_zeroed_twice": [("zero", 3, 0), ("zero", 3, 0)],
    "snapshot_of_a_slot_freed_in_the_same_call": [("zero", 2, 0),
                                                  ("snapshot", 2, 8)],
    "restore_into_a_slot_zeroed_in_the_same_call": [("restore", 9, 3),
                                                    ("zero", 3, 0)],
    "five_of_a_kind_takes_the_list_of_eight": [
        ("zero", w, 0) for w in (1, 2, 3, 4, 5)],
    "every_class_one_entry": [("restore", 10, 1), ("zero", 4, 0),
                              ("snapshot", 5, 7)],
}


@pytest.mark.parametrize("lanes", sorted(REC))
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_program_against_the_three_classes_in_numpy(scenario, lanes):
    intents = SCENARIOS[scenario]
    before = make_pools(lanes)
    calls = _M_SSM_APPLY.get()
    got = served(before, manager(intents))
    assert _M_SSM_APPLY.get() - calls == 1
    want = plain(before, intents)
    for g, w, b in zip(got, want, before):
        np.testing.assert_array_equal(g[:, 1:], w[:, 1:])
        assert np.array_equal(g[:, 0], b[:, 0]) or not g[:, 0].any()
    # the scenario did something the rendering shows
    assert any(not np.array_equal(w, b) for w, b in zip(want, before))


def test_nothing_pending_dispatches_nothing():
    before = make_pools(256)
    calls = _M_SSM_APPLY.get()
    got = served(before, manager([]))
    assert _M_SSM_APPLY.get() == calls
    for g, b in zip(got, before):
        np.testing.assert_array_equal(g, b)


@pytest.mark.parametrize("lanes", [128, 384])
def test_stale_restore_dropped_where_the_slot_is_freed(lanes):
    """A restore into a slot whose sequence is freed before the drain is
    dropped by `_free_ssm`; the slot's next tenant finds zeros, and the
    program is not handed the restore at all."""
    mm = manager([])
    seq = Sequence(0, [1, 2, 3], None)
    seq._ssm_restore_snap = 8
    mm.prepare_seq(seq)
    slot = seq.ssm_slot
    assert ("restore", 8, slot) in mm.ssm_intents
    mm._free_ssm(seq)
    assert mm.ssm_intents == [("zero", slot, 0)]
    before = make_pools(lanes)
    got = served(before, mm)
    for g, b in zip(got, before):
        assert not g[:, slot].any()
        others = [s for s in range(1, SLOTS) if s != slot]
        np.testing.assert_array_equal(g[:, others], b[:, others])


@pytest.mark.parametrize("lanes", [256, 384])
@pytest.mark.parametrize("scenario", ["a_full_list",
                                      "restore_reads_this_calls_snapshot"])
def test_dp_stacked_pools_move_in_one_replica_alone(scenario, lanes):
    intents = SCENARIOS[scenario]
    before = make_pools(lanes, dp=2)
    got = served(before, manager(intents), dp=2)
    want = plain(before, intents, replica=1)
    for g, w, b in zip(got, want, before):
        np.testing.assert_array_equal(g[0], b[0])
        np.testing.assert_array_equal(g[1][:, 1:], w[1][:, 1:])
        assert not np.array_equal(g[1], b[1])


@pytest.mark.parametrize("lanes", [128, 256])
def test_pp_runner_applies_to_every_hybrid_stages_pools(lanes):
    """Slot ids are global, each stage holds its own layers' pools; a
    stage without recurrent layers is passed over."""
    intents = SCENARIOS["a_full_list"]
    mm = manager(intents)
    before = [make_pools(lanes, layers=n, seed=n) for n in (1, 3)]

    def stage(linear, kv):
        return types.SimpleNamespace(
            cfg=types.SimpleNamespace(num_linear_layers=linear), kv=kv)
    stages = [stage(1, Pools(*map(jnp.asarray, before[0]))),
              stage(0, None),
              stage(3, Pools(*map(jnp.asarray, before[1])))]
    fake = fake_runner(mm, replicas=[stages])
    calls = _M_SSM_APPLY.get()
    PPModelRunner._apply_ssm_intents(fake)
    assert _M_SSM_APPLY.get() - calls == 2
    assert stages[1].kv is None
    for st, b in zip((stages[0], stages[2]), before):
        want = plain(b, intents)
        for g, w in zip(st.kv, want):
            np.testing.assert_array_equal(np.asarray(g)[:, 1:], w[:, 1:])
