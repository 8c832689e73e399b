"""The engine loop's order (docs/observability.md phase catalog): a
collected step's outputs reach the handler queues AFTER the next step is
on the device and BEFORE that step's collect, and at once wherever the
loop will not launch.

Most cases drive ``ServingEngine`` with a scripted LLM double (one token
a sequence a step, every call logged, hooks on the engine thread at the
instants a deadline, an abort or a recovery could land), so the order is
read from a log and not from a clock. The last group runs the real
engine: streams through ``ServingEngine`` equal ``LLM.generate``'s.
"""

import queue
import threading
import time
import types

import pytest
import torch

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.engine import serving_engine as se
from gllm_tpu.engine.llm import LLM
from gllm_tpu.engine.serving_engine import ServingEngine
from gllm_tpu.faults import FAULTS
from gllm_tpu.sampling_params import SamplingParams
from gllm_tpu.scheduler import SeqOutput
from gllm_tpu.sequence import Sequence, SequenceStatus


def token_of(seq) -> int:
    """The double's 'model': a function of the prompt and the position,
    so a replayed sequence (a new seq id) continues the same stream."""
    return (sum(seq.token_ids[:seq.prompt_len]) * 31
            + seq.num_output_tokens * 7) % 997 + 1


def expected_tokens(prompt, n):
    seq = Sequence(0, prompt, SamplingParams(max_tokens=n))
    out = []
    for _ in range(n):
        out.append(token_of(seq))
        seq.append_token(out[-1])
    return out


class ScriptedLLM:
    """What ``ServingEngine`` needs of an ``LLM``, scripted. ``step`` is
    one pass: aborts land (as ``Scheduler._process_aborts``), the running
    sequences are 'dispatched' (logged), the seam runs, the 'collect'
    appends one token a sequence."""

    tokenizer = None
    model_cfg = None

    def __init__(self):
        self.config = types.SimpleNamespace(tracing=False)
        self._next_seq_id = 0
        self.running = []
        self._aborted = set()
        self.n = 0                  # steps dispatched
        self.log = []               # ("dispatch" | "collect" | ..., step)
        self.step_of = {}           # id(outputs list) -> step
        self.empty_after = set()    # steps after which one pass launches nothing
        self.raise_in_fill = None   # step whose fill pass raises
        self.raise_in_collect = None
        self.on_dispatch = None     # hook(step): fill pass done, seam not yet run
        self.on_collected = None    # hook(step, outputs): right after the collect

    def _allocate_seq(self, token_ids, sp):
        seq = Sequence(self._next_seq_id, token_ids, sp,
                       arrival_time=time.monotonic())
        self._next_seq_id += 1
        return seq

    def add_seq(self, seq):
        seq.status = SequenceStatus.RUNNING
        self.running.append(seq)

    @property
    def has_unfinished(self):
        return bool(self.running)

    def abort(self, seq_id):
        self._aborted.add(seq_id)

    def quarantine_step_failure(self, everything=False):
        failed = [s.seq_id for s in self.running]
        for s in self.running:
            s.status = SequenceStatus.ABORTED
        self.running = []
        return failed

    def close(self):
        pass

    def step(self, after_dispatch=None, hold_launch=None):
        for s in list(self.running):
            if s.seq_id in self._aborted:
                s.status = SequenceStatus.ABORTED
                self.running.remove(s)
        if not self.running:
            return []
        if self.n in self.empty_after:
            self.empty_after.discard(self.n)
            self.log.append(("empty", self.n))
            return []
        n = self.n + 1
        if self.raise_in_fill == n:
            self.raise_in_fill = None
            raise RuntimeError("fill pass failed")
        self.n = n
        self.log.append(("dispatch", n))
        if self.on_dispatch is not None:
            self.on_dispatch(n)
        if after_dispatch is not None:
            after_dispatch()
        if self.raise_in_collect == n:
            raise RuntimeError("collect failed")
        self.log.append(("collect", n))
        outs = []
        for s in list(self.running):
            tok = token_of(s)
            s.append_token(tok)
            fin = s.check_finish(())
            if fin is not None:
                s.status = SequenceStatus.FINISHED
                s.finish_reason = fin
                self.running.remove(s)
            outs.append(SeqOutput(s, tok, fin))
        self.step_of[id(outs)] = n
        if self.on_collected is not None:
            self.on_collected(n, outs)
        return outs


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


@pytest.fixture
def rig():
    """(llm, engine) with the engine's ``_deliver`` logged into the
    double's log as ("deliver", step)."""
    made = []

    def make(**kw):
        llm = ScriptedLLM()
        eng = ServingEngine(llm, **kw)
        made.append(eng)
        real = eng._deliver

        def _deliver(l, outputs):
            l.log.append(("deliver", l.step_of[id(outputs)]))
            real(l, outputs)

        eng._deliver = _deliver
        return llm, eng

    yield make
    for eng in made:
        eng.shutdown()


def sp(n):
    return SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True)


def collect(handle, timeout=20.0):
    out = []
    t = threading.Thread(target=lambda: out.extend(handle), daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "stream never terminated"
    return out


def drain_now(handle):
    """What sits on the handle's queue right now."""
    out = []
    while True:
        try:
            out.append(handle.chunks.get_nowait())
        except queue.Empty:
            return out


def wait_until(cond, timeout=20.0, what="condition"):
    limit = time.monotonic() + timeout
    while time.monotonic() < limit:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {what}")


def deliver_counts():
    return (se._M_DELIVER.get(when="after_dispatch"),
            se._M_DELIVER.get(when="flush"))


# ---- (a) the order ----------------------------------------------------------

@pytest.mark.parametrize("lengths", [(4,), (2, 5, 3)],
                         ids=["one_stream", "three_streams"])
def test_dispatch_then_deliver_then_collect(rig, lengths):
    llm, eng = rig()
    before = deliver_counts()
    prompts = [[3 + i, 9, 4] for i in range(len(lengths))]
    # step 1 waits for every submit, so the engine never idles (and
    # flushes) between two of them
    submitted = threading.Event()
    llm.on_dispatch = lambda n: submitted.wait(20.0)
    handles = [eng.submit(p, sp(n)) for p, n in zip(prompts, lengths)]
    submitted.set()
    streams = [collect(h) for h in handles]
    for p, n, chunks in zip(prompts, lengths, streams):
        assert [c.token_id for c in chunks] == expected_tokens(p, n)
        # a stream that finished while others ran on is closed by its own
        # final chunk, never by _reap_aborted's ``abort``
        assert [c.finish_reason for c in chunks] == [None] * (n - 1) \
            + ["length"]
    wait_until(lambda: not eng._handles, what="handles closed")
    last = llm.n
    pos = {e: i for i, e in enumerate(llm.log)}
    for n in range(1, last):
        assert pos[("dispatch", n + 1)] < pos[("deliver", n)] \
            < pos[("collect", n + 1)], llm.log
    # the last step's outputs had nothing to hide behind
    assert pos[("deliver", last)] > pos[("collect", last)]
    assert [e for e in llm.log if e[0] == "deliver"] \
        == [("deliver", n) for n in range(1, last + 1)]
    after = deliver_counts()
    assert after[0] - before[0] == last - 1
    assert after[1] - before[1] == 1


# ---- (b) flushed at once where nothing is launched --------------------------

def test_flush_when_the_last_sequence_finished(rig):
    llm, eng = rig()
    chunks = collect(eng.submit([5, 6], sp(3)))
    assert [c.finish_reason for c in chunks] == [None, None, "length"]
    assert llm.log[-2:] == [("collect", 3), ("deliver", 3)]


def test_flush_when_step_launches_nothing(rig):
    llm, eng = rig()
    llm.empty_after = {2}
    chunks = collect(eng.submit([5, 6], sp(4)))
    assert [c.token_id for c in chunks] == expected_tokens([5, 6], 4)
    i = llm.log.index(("empty", 2))
    # step 2's outputs went out in the pass that launched nothing, not
    # behind step 3's dispatch
    assert llm.log[i - 1:i + 3] == [("collect", 2), ("empty", 2),
                                    ("deliver", 2), ("dispatch", 3)]


def test_flush_on_drain(rig):
    llm, eng = rig()
    h = eng.submit([5, 6], sp(5))
    eng.shutdown(drain=True, timeout=20.0)
    chunks = drain_now(h)
    assert [c.token_id for c in chunks] == expected_tokens([5, 6], 5)
    assert chunks[-1].finish_reason == "length"


def test_flush_at_loop_exit(rig):
    """``_stop`` with a middle token pending: the token goes out, then
    the terminal chunk of the shutdown."""
    llm, eng = rig()

    def stop(n, outs):
        if n == 3:
            eng._stop = True
    llm.on_collected = stop
    chunks = collect(eng.submit([5, 6], sp(9)))
    assert [c.token_id for c in chunks[:-1]] == expected_tokens([5, 6], 3)
    assert chunks[-1].finish_reason == "abort"
    assert chunks[-1].error == "engine stopped"


@pytest.mark.parametrize("where", ["fill", "collect"])
def test_flush_before_a_failed_step_is_quarantined(rig, where):
    llm, eng = rig()
    setattr(llm, "raise_in_" + where, 3)
    chunks = collect(eng.submit([5, 6], sp(9)))
    # steps 1 and 2 were collected and committed: their tokens are real
    assert [c.token_id for c in chunks[:-1]] == expected_tokens([5, 6], 2)
    assert chunks[-1].finish_reason == "error"
    assert "failed" in chunks[-1].error
    assert ("deliver", 2) in llm.log
    assert eng.is_alive


def test_a_failing_hand_over_is_not_a_failed_step(rig):
    """An exception out of ``_deliver`` at the seam kills the loop, as it
    did when ``_deliver`` followed the step: it must not be taken for a
    failure of the step in flight and quarantine that batch."""
    llm, eng = rig()
    logged = eng._deliver

    def broken(l, outputs):
        if l.step_of[id(outputs)] == 2:
            raise ValueError("deliver broke")
        logged(l, outputs)
    eng._deliver = broken
    failures = se._M_STEP_FAIL.get()
    chunks = collect(eng.submit([5, 6], sp(9)))
    assert [c.token_id for c in chunks[:-1]] == expected_tokens([5, 6], 1)
    assert chunks[-1].finish_reason == "abort"
    assert se._M_STEP_FAIL.get() == failures
    wait_until(lambda: not eng._thread.is_alive(), what="loop death")


# ---- (c) a deadline or an abort between collect and hand-over ---------------

@pytest.mark.parametrize("pending", ["final", "middle"])
@pytest.mark.parametrize("event", ["deadline", "abort"])
def test_pending_chunk_precedes_deadline_and_abort(rig, event, pending):
    llm, eng = rig()
    total, at = (3, 3) if pending == "final" else (9, 2)

    def strike(n, outs):
        if n == at:
            if event == "deadline":
                eng._deadlines[0] = 0.0       # seq id 0: long past
            else:
                eng.abort(0)
    llm.on_collected = strike
    expired = se._M_DEADLINE.get()
    h = eng.submit([5, 6], sp(total))
    assert h.seq_id == 0
    chunks = collect(h)
    want = expected_tokens([5, 6], at)
    if pending == "final":
        # the stream had ended before the event: its own end wins
        assert [c.token_id for c in chunks] == want
        assert chunks[-1].finish_reason == "length"
        assert se._M_DEADLINE.get() == expired
    else:
        assert [c.token_id for c in chunks[:-1]] == want
        assert [c.finish_reason for c in chunks[:-1]] == [None] * at
        assert chunks[-1].token_id is None
        assert chunks[-1].finish_reason == event
    wait_until(lambda: not eng._handles and not eng._seqs,
               what="request closed")
    time.sleep(0.05)
    assert drain_now(h) == []     # exactly one terminal chunk


# ---- (d) a superseded generation delivers nothing ---------------------------

@pytest.mark.parametrize("when", ["in_fill", "in_collect"])
def test_superseded_generation_drops_pending(rig, when):
    llm, eng = rig()

    def supersede(n, *_):
        if n == (3 if when == "in_fill" else 2):
            eng._gen += 1
    setattr(llm, "on_dispatch" if when == "in_fill" else "on_collected",
            supersede)
    h = eng.submit([5, 6], sp(9))
    wait_until(lambda: not eng._thread.is_alive(), what="loop exit")
    chunks = drain_now(h)
    # step 1 was delivered behind step 2's dispatch; step 2's outputs
    # were pending (in_fill) or just collected (in_collect) when the
    # generation moved on: dropped, and the handle left to its new owner
    assert [c.token_id for c in chunks] == expected_tokens([5, 6], 1)
    assert all(c.finish_reason is None for c in chunks)
    assert ("deliver", 2) not in llm.log
    assert h.seq_id in eng._handles


# ---- (e) the journal holds exactly what was delivered -----------------------

def test_journal_is_the_delivered_tokens_at_every_instant(rig):
    llm, eng = rig(engine_recovery=True, llm_factory=ScriptedLLM)
    box = {"seen": []}
    real = eng._deliver

    def _deliver(l, outputs):
        real(l, outputs)
        box["seen"].extend(o.new_token_id for o in outputs)
    eng._deliver = _deliver

    def check(n, *_):
        entry = eng._journal._entries.get(0)
        box.setdefault("reads", []).append(
            (list(entry.committed) if entry else None, list(box["seen"])))
    llm.on_dispatch = check        # committed in the scheduler, not yet
    llm.on_collected = check       # in the journal: the one-dispatch window
    chunks = collect(eng.submit([5, 6], sp(6)))
    assert [c.token_id for c in chunks] == expected_tokens([5, 6], 6)
    assert len(box["reads"]) == 12
    for committed, seen in box["reads"]:
        assert committed == seen
    # at the seam of step n the journal holds n - 2 tokens and the
    # scheduler n - 1: the window; after it, both hold n - 1
    assert [len(c) for c, _ in box["reads"]] \
        == [0, 0, 0, 1, 1, 2, 2, 3, 3, 4, 4, 5]
    wait_until(lambda: len(eng._journal) == 0, what="journal entry popped")


def test_crash_with_outputs_pending_replays_exactly(rig):
    """The loop dies with a step's outputs collected and not handed over:
    they were never in the journal, so the replay recomputes them — no
    token lost, none sent twice."""
    llm, eng = rig(engine_recovery=True, llm_factory=ScriptedLLM)
    eng.supervisor.backoff_s = 0.01

    def crash(n, outs):
        if n == 3:
            FAULTS.arm("engine_hard_crash:0:1")
    llm.on_collected = crash
    chunks = collect(eng.submit([5, 6], sp(7)))
    assert [c.token_id for c in chunks] == expected_tokens([5, 6], 7)
    assert chunks[-1].finish_reason == "length"
    # the supervisor counts a recovery once the replay is under way, on
    # its own thread: the scripted replay can finish first
    wait_until(lambda: eng.supervisor.recoveries >= 1,
               what="the recovery counted")
    assert eng.supervisor.recoveries == 1
    assert ("deliver", 3) not in llm.log     # died pending


# ---- (f) the real engine: ServingEngine == LLM.generate ---------------------

TINY = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    max_position_embeddings=512, rms_norm_eps=1e-6, rope_theta=10000.0,
    tie_word_embeddings=False, eos_token_id=0, bos_token_id=1,
)
PROMPTS = [[5, 17, 93, 41], [7, 7, 21], [88, 2, 64, 31, 9, 12], [3]]
LENGTHS = [12, 5, 9, 16]


@pytest.fixture(scope="module")
def tiny_ckpt(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(11)
    model = LlamaForCausalLM(LlamaConfig(**TINY, attention_bias=False))
    d = tmp_path_factory.mktemp("deliver_order_model")
    model.save_pretrained(d, safe_serialization=True)
    return str(d)


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["serial", "overlap"])
@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_streams_equal_generate(tiny_ckpt, sampled, overlap):
    cfg = EngineConfig(
        model=tiny_ckpt, dtype="float32", max_model_len=256,
        scheduler=SchedulerConfig(),
        cache=CacheConfig(page_size=4, num_pages=128),
        overlap_scheduling=overlap)
    cfg.validate()

    def params():
        return [SamplingParams(temperature=0.8, top_p=0.9, seed=100 + i,
                               max_tokens=n, ignore_eos=True)
                if sampled else
                SamplingParams(temperature=0.0, max_tokens=n,
                               ignore_eos=True)
                for i, n in enumerate(LENGTHS)]

    llm = LLM(config=cfg)
    want = [o.output_token_ids for o in llm.generate(
        prompt_token_ids=PROMPTS, sampling_params=params())]
    assert [len(w) for w in want] == LENGTHS
    eng = ServingEngine(llm)
    try:
        handles = [eng.submit(p, s) for p, s in zip(PROMPTS, params())]
        streams = [collect(h, timeout=120.0) for h in handles]
    finally:
        eng.shutdown()
    for chunks, w in zip(streams, want):
        assert [c.token_id for c in chunks] == w
        assert [c.finish_reason for c in chunks] \
            == [None] * (len(w) - 1) + ["length"]
        assert [c.num_output_tokens for c in chunks] \
            == list(range(1, len(w) + 1))
