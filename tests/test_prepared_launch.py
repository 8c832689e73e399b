"""The prepared launch of the default loop
(docs/overlap_scheduling.md#prepared-launch).

A decode step is scheduled, built and placed under the step before it
and launched from that step's collect, before its output. The contract:
token streams (and logprobs) are what the plain loop (``enforce_eager``)
gives, under arrival / finish / abort churn, under a pool so small that
rows are preempted, and behind a prefix hit; a request that is on the
caller's intake queue at the collect rides the very next program; a
token that ends a row drops the prepared step, and no step is prepared
behind a row that ends where the host can see it coming (a stop string's
scan, the row's length); what a drop leaves behind
is what the plain loop's pass leaves; a fired step places one host array
where a step built in the gap places two; and no step program is seen
that the plain loop does not see.
"""

import collections
import contextlib
import dataclasses
import json
import os
import time

import numpy as np
import pytest

from gllm_tpu.config import (CacheConfig, EngineConfig, ParallelConfig,
                             SchedulerConfig)
from gllm_tpu.engine.llm import LLM, prepares_next_step
from gllm_tpu.models.config import ModelConfig, from_hf_config
from gllm_tpu.obs import metrics as obs
from gllm_tpu.obs.steptrace import TRACE
from gllm_tpu.sampling_params import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUTCOMES = ("fired", "dropped_arrival", "dropped_finish", "dropped_other")


def _rehearsal_model(name, **over):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as f:
        published = json.load(f)
    return from_hf_config({**published, **published["rehearsal"]["model"],
                           **over})


def _model(family):
    """The six cells' families at their configurations' rehearsal widths
    (read from the files, cut to a layer of each kind), and two toys."""
    if family == "dense":
        return ModelConfig(
            architecture="LlamaForCausalLM", vocab_size=512, hidden_size=64,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            intermediate_size=128, max_position=256)
    if family == "hybrid":            # one period: three GDN layers, one full
        return _rehearsal_model(
            "olmo-hybrid-7b", num_hidden_layers=4,
            layer_types=["linear_attention"] * 3 + ["full_attention"])
    if family == "latent":            # a full (DSA) and a windowed layer
        return _rehearsal_model(
            "dots3-note-prev", num_hidden_layers=2,
            layer_types=["full_attention", "sliding_attention"])
    if family == "windowed":          # a window of 24 rows in the paged pool
        return _rehearsal_model(
            "command-a-plus-05-2026", num_hidden_layers=2,
            layer_types=["sliding_attention", "full_attention"])
    if family == "mamba":             # Mamba-2, experts, attention: one each
        return _rehearsal_model(
            "nemotron-3-nano-30b-a3b", num_hidden_layers=3,
            hybrid_override_pattern="ME*")
    if family == "dense_latent":      # a dense and an expert layer under MLA
        return _rehearsal_model("a.x-k1", num_hidden_layers=2)
    assert family == "qwen3next"      # a GDN head alone in a slot, one full
    from test_hybrid_qwen3next import BASE
    return from_hf_config(dict(
        BASE, architectures=["Qwen3NextForCausalLM"], vocab_size=512,
        num_hidden_layers=2,
        layer_types=["linear_attention", "full_attention"]))


# served under the prefix cache, as their cells are
PREFIX_CACHED = ("windowed", "dense_latent")


def make_llm(family, eager=False, prefix_cache=False, num_pages=256):
    """Rows, pages and a mixed step's tokens held at their largest, as a
    server that runs full holds them: two step programs a sampling mode
    (decode, mixed), so that the file's compiles stay a few seconds."""
    return LLM(config=EngineConfig(
        load_format="dummy", dtype="float32", max_model_len=128,
        max_num_seqs=8, enforce_eager=eager,
        scheduler=SchedulerConfig(max_prefill_tokens=32, max_decode_seqs=8,
                                  min_row_bucket=8, min_page_bucket=32,
                                  min_token_bucket=64),
        cache=CacheConfig(page_size=4, num_pages=num_pages,
                          enable_prefix_caching=prefix_cache)),
        model_cfg=_model(family))


@pytest.fixture(scope="module")
def engines():
    """One engine a family and set-up, built on first use and kept.
    ``twin``: a second one of the same, for the plain arm where an arm
    leaves something behind that the next would meet (cached pages under
    the prefix cache; the admission ratio a preemption resets): each arm
    has its own engine, and both see the same history."""
    made = {}

    def get(family, twin=False, **kw):
        kw.setdefault("prefix_cache", family in PREFIX_CACHED)
        key = (family, twin, *sorted(kw.items()))
        if key not in made:
            made[key] = make_llm(family, **kw)
        return made[key]
    return get


def both_arms(engines, family, twins=None, **kw):
    """(the engine of the plain arm, the engine of the prepared arm): one
    engine, or twins (under the prefix cache, unless said otherwise)."""
    llm = engines(family, **kw)
    if twins is None:
        twins = llm.config.cache.enable_prefix_caching
    return (engines(family, twin=True, **kw) if twins else llm), llm


@contextlib.contextmanager
def plain_order(llm):
    """The plain arm on the SAME engine: ``enforce_eager`` differs from
    the default configuration in nothing but this gate (the last test),
    so shutting it is that arm without compiling its programs a second
    time; the dense case checks it against a real ``enforce_eager``
    engine."""
    assert llm._prepares
    llm._prepares = False
    try:
        yield llm
    finally:
        llm._prepares = True


def counter(name, **labels):
    return obs.REGISTRY.get(name).get(**labels)


def prepared_counts():
    return {o: counter("gllm_prepared_steps_total", outcome=o)
            for o in OUTCOMES}


def growth(before):
    return {o: n - before[o] for o, n in prepared_counts().items()}


class Driver:
    """The serving loop's pass, by hand and on one thread: drain the
    intake queue, ``LLM.step`` with both seams, keep the outputs. A
    script puts requests on the queue and aborts them at given passes:
    before the pass, at its ``after_dispatch`` seam (the next step is
    prepared by then) or inside the collect. A request that comes BEFORE
    a pass whose step was launched from the last collect rides the step
    after it, one later than in the plain loop (the serving loop's
    window for that is its ``output`` phase); the other two keep the
    loops in step.

    ``clock``: whose clock decides whether there is room to prepare in.
    None: nobody's, a step is prepared wherever the batch allows it (as
    a multihost engine's hosts decide, who have to decide alike), so a
    run is the same on any machine. ``"device"``: the engine's own, with
    a device that outlasts the host (the collect blocks for 30 ms);
    ``"host"``: the same, with the host the slower side (5 ms of host
    work a pass)."""

    def __init__(self, llm, clock=None):
        self.llm = llm
        self.clock = clock
        self.queue = collections.deque()
        self.seqs, self.tokens, self.reasons = {}, {}, {}
        self.seams = []         # per pass: what the loop held after its
        #                         dispatch, before anything was prepared
        self.programs = []      # per launch: (keys in it, arrays placed
        #                         since the launch before)
        self.passes = 0

    def submit(self, key, prompt, sp):
        seq = self.llm._allocate_seq(list(prompt), sp)
        self.seqs[key] = seq
        self.tokens[key] = []
        self.queue.append(seq)

    def act(self, events):
        for ev in events:
            if ev[0] == "add":
                self.submit(*ev[1:])
            else:
                self.llm.abort(self.seqs[ev[1]].seq_id)

    def key_of(self, seq):
        return next(k for k, s in self.seqs.items() if s is seq)

    def first_program(self, key):
        return next(i for i, (keys, _) in enumerate(self.programs)
                    if key in keys)

    def run(self, script, max_passes=400):
        """``script``: {pass: {"before" | "seam" | "collect": [events]}}."""
        runner = self.llm.runner
        real_collect, real_launch = runner.collect, runner._launch_step
        h2d = [counter("gllm_step_h2d_arrays_total")]

        def launch(prep, build):
            h2d.append(counter("gllm_step_h2d_arrays_total"))
            self.programs.append((
                sorted(self.key_of(it.seq) for it in prep.sched_batch.items),
                h2d[-1] - h2d[-2]))
            return real_launch(prep, build)

        runner._launch_step = launch
        llm = self.llm
        own, room = llm._own_clock, llm._room_to_prepare
        llm._own_clock = self.clock is not None
        llm._room_to_prepare = not llm._own_clock
        try:
            return self._run(script, max_passes, real_collect)
        finally:
            runner.collect, runner._launch_step = real_collect, real_launch
            llm._own_clock, llm._room_to_prepare = own, room
            llm.__dict__.pop("_prepare_next", None)

    def _run(self, script, max_passes, real_collect):
        llm, mm = self.llm, self.llm.memory_manager
        real_prepare = type(llm)._prepare_next.__get__(llm)

        def held():
            self.seams.append((
                len(self.programs), mm.num_free_pages,
                tuple((k, s.num_in_flight, s.num_tokens)
                      for k, s in sorted(self.seqs.items())
                      if not s.is_finished and s not in self.queue)))

        def prepare(entry):
            held()
            return real_prepare(entry)

        if llm._prepares and self.clock is None:
            llm._prepare_next = prepare
        while self.passes < max_passes:
            now = script.get(self.passes, {})
            self.act(now.get("before", ()))
            while self.queue:
                llm.add_seq(self.queue.popleft())
            if not llm.has_unfinished:
                if not any(p >= self.passes for p in script):
                    break
                self.passes += 1
                continue

            def at_seam():
                if "_prepare_next" not in llm.__dict__:
                    held()
                self.act(now.get("seam", ()))
                if self.clock == "host":
                    time.sleep(0.005)

            def collect(handle):
                self.act(now.get("collect", ()))
                if self.clock == "device":
                    time.sleep(0.03)
                return real_collect(handle)

            llm.runner.collect = collect
            outs = llm.step(
                after_dispatch=at_seam,
                hold_launch=lambda: "arrival" if self.queue else None)
            for out in outs:
                key = self.key_of(out.seq)
                if out.new_token_id is not None:
                    self.tokens[key].append(out.new_token_id)
                if out.finish_reason is not None:
                    self.reasons[key] = out.finish_reason
            self.passes += 1
        assert not llm.has_unfinished and not llm._in_flight
        assert mm.num_free_pages == mm.allocator.num_total
        return {k: (self.tokens[k], self.reasons.get(k),
                    self.seqs[k].output_logprobs) for k in self.seqs}


def request(key, prompt, max_tokens, seeded, seed):
    """A script's ``add`` event: greedy, or seeded draws; top-2 logprobs
    either way (one ``k``, so one program a kind of step)."""
    kw = dict(max_tokens=max_tokens, ignore_eos=True, logprobs=2)
    sp = (SamplingParams(temperature=0.8, top_p=0.9, seed=seed, **kw)
          if seeded else SamplingParams(temperature=0.0, **kw))
    return ("add", key, [int(t) for t in prompt], sp)


def churn_script(seeded):
    """Six requests over forty passes: arrivals under a running step and
    inside a collect; a prompt of two chunks; lengths that end rows at
    different steps; two aborts (one seen before the next step is
    prepared, one after)."""
    rng = np.random.default_rng(11)

    def req(key, n_prompt, max_tokens):
        return request(key, rng.integers(2, 500, size=n_prompt), max_tokens,
                       seeded, 70 + n_prompt)

    return {
        0: {"before": [req("a", 9, 30), req("b", 5, 14)]},
        6: {"seam": [req("c", 40, 12)]},          # two chunks of 32
        12: {"collect": [req("d", 7, 20)]},
        15: {"seam": [("abort", "a")]},
        19: {"seam": [req("e", 11, 9)]},
        23: {"collect": [("abort", "d")]},
        26: {"seam": [req("f", 6, 8)]},
    }


def pressure_script(seeded):
    """Five requests that grow to 47 pages between them in a pool of 32:
    rows are preempted in mid-run, and taken up again from their first
    token."""
    rng = np.random.default_rng(23)

    def req(key, n_prompt, max_tokens):
        return request(key, rng.integers(2, 500, size=n_prompt), max_tokens,
                       seeded, 40 + n_prompt)

    return {
        0: {"before": [req("a", 9, 30), req("b", 6, 28), req("c", 7, 26)]},
        4: {"seam": [req("d", 5, 30)]},
        9: {"collect": [req("e", 8, 24)]},
    }


def prefix_script():
    """Two requests that share eight whole pages: the second comes when
    the first one's prompt is computed, and hits them."""
    rng = np.random.default_rng(31)
    doc = list(rng.integers(2, 500, size=40))
    return {
        0: {"before": [request("a", doc, 16, False, 0)]},
        8: {"seam": [request("b", doc[:32] + list(rng.integers(2, 500,
                                                               size=6)),
                             12, False, 0)]},
    }


FAMILIES = ["dense", "hybrid", "latent", "windowed", "mamba", "dense_latent",
            "qwen3next"]


@pytest.mark.parametrize("seeded", [False, True], ids=["greedy", "seeded"])
@pytest.mark.parametrize("family", FAMILIES)
def test_streams_and_bookkeeping_match_the_plain_loop(engines, family,
                                                      seeded):
    """Pass for pass the two loops collect the same step, so at every
    seam the allocator's free pages, each row's in-flight count and its
    committed length are the plain loop's, drops included, and every
    request joins the program it joins there; streams, finish reasons
    and logprobs are equal; no step program is seen that the plain loop
    does not see."""
    plain, llm = both_arms(engines, family)
    sigs = llm.runner._seen_sigs
    with plain_order(plain):
        plain.runner._seen_sigs.clear()
        before = prepared_counts()
        want = Driver(plain)
        base = want.run(churn_script(seeded))
        assert prepared_counts() == before      # the plain arm prepares none
        plain_sigs = set(plain.runner._seen_sigs)
    sigs.clear()
    got = Driver(llm)
    outs = got.run(churn_script(seeded))
    assert outs == base
    assert all(len(lp) >= len(toks) > 0 for toks, _, lp in outs.values())
    assert got.seams == want.seams
    assert ([keys for keys, _ in got.programs]
            == [keys for keys, _ in want.programs])
    grew = growth(before)
    assert grew["fired"] >= 10
    assert grew["dropped_arrival"] >= 3          # c, d, e, f; one of them
    #                                              met no prepared step
    assert grew["dropped_other"] == 2            # both aborts come after the
    #                                              next step was prepared
    assert set(sigs) <= plain_sigs
    if family == "dense" and not seeded:
        eager = Driver(make_llm("dense", eager=True))
        assert eager.run(churn_script(seeded)) == base
        assert eager.seams == want.seams


@pytest.mark.parametrize("seeded", [False, True], ids=["greedy", "seeded"])
@pytest.mark.parametrize("family", ["dense", "mamba"])
def test_a_pool_too_small_preempts_in_the_plain_order(engines, family,
                                                      seeded):
    """``schedule_chain`` refuses where there is no page without a
    preemption, so nothing is prepared behind such a step: the pass after
    it preempts in the plain order, as it always has, and steps are
    prepared again once the pool has room. The streams are the plain
    loop's. The ROW preempted need not be: a pass that launches a
    prepared step does not turn the decode rows' rotation
    (``Scheduler._decode_offset``), so the plain pass that finds the
    pool full protects the rows in another order. The logprobs then come
    from steps of other rows, equal to float32's last digits. On
    ``mamba`` a preempted row gives its slot of recurrent state back and
    computes it again from its first token."""
    plain, llm = both_arms(engines, family, twins=True, num_pages=32)
    with plain_order(plain):
        n0 = plain.scheduler.num_preemptions
        base = Driver(plain).run(pressure_script(seeded))
        assert plain.scheduler.num_preemptions > n0
    before, n0 = prepared_counts(), llm.scheduler.num_preemptions
    outs = Driver(llm).run(pressure_script(seeded))
    assert llm.scheduler.num_preemptions > n0
    assert growth(before)["fired"] >= 20
    assert growth(before)["dropped_arrival"] == 2
    for key, (tokens, reason, lps) in outs.items():
        assert (tokens, reason) == base[key][:2] == (tokens, "length")
        for (lp, top, top_lp), (lp0, top0, top_lp0) in zip(lps,
                                                           base[key][2]):
            assert top == top0
            np.testing.assert_allclose([lp, *top_lp], [lp0, *top_lp0],
                                       rtol=0, atol=1e-4)


@pytest.mark.parametrize("family", ["dense", "windowed", "dense_latent"])
def test_a_prefix_hit_starts_a_row_the_plain_loop_starts(engines, family):
    """A request whose prompt begins with whole cached pages joins the
    running step with those pages claimed, in the program it joins in
    the plain loop, and the steps around it are prepared."""
    plain, llm = both_arms(engines, family, prefix_cache=True)

    def run(engine):
        """(the driver, its streams, the tokens hit in the cache)"""
        hit = engine.memory_manager.hit_tokens
        driver = Driver(engine)
        outs = driver.run(prefix_script())
        return driver, outs, engine.memory_manager.hit_tokens - hit

    with plain_order(plain):
        want, base, hit = run(plain)
    before = prepared_counts()
    got, outs, hit_prepared = run(llm)
    assert outs == base
    assert hit == hit_prepared == 32
    assert got.seams == want.seams
    assert got.first_program("b") == want.first_program("b")
    grew = growth(before)
    assert grew["fired"] >= 15 and grew["dropped_arrival"] == 1


@pytest.mark.parametrize("how", ["eos", "stop_id"])
def test_a_token_that_ends_a_row_drops_the_prepared_step(engines, how):
    """The stream ends on that token, as in the plain loop, and the step
    prepared behind it is dropped: nothing ran past the end."""
    llm = engines("dense")
    prompt = [int(t) for t in np.random.default_rng(5).integers(2, 60, 8)]

    def run(**kw):
        return Driver(llm).run({0: {"before": [("add", "x", prompt,
                                                SamplingParams(
            temperature=0.0, max_tokens=40, logprobs=2, **kw))]}})["x"]

    with plain_order(llm):
        probe = run(ignore_eos=True)[0]
    end = probe[9]                      # a token of the greedy stream
    cut = probe[:probe.index(end) + 1]
    kw = {} if how == "eos" else dict(stop_token_ids=[end], ignore_eos=True)
    llm.eos_token_ids = frozenset([end] if how == "eos" else ())
    try:
        with plain_order(llm):
            base = run(**kw)
        before = prepared_counts()
        got = run(**kw)
    finally:
        llm.eos_token_ids = frozenset()
    assert got == base
    assert got[0] == cut and got[1] == "stop"
    # every decode step was prepared (the first one under the prompt's
    # step, whose one row samples); the one behind the last token was
    # dropped
    assert growth(before) == {"fired": len(cut) - 1, "dropped_finish": 1,
                              "dropped_arrival": 0, "dropped_other": 0}


class _Letters:
    """A token is a letter: what the stop-string scan needs of one."""
    eos_token_id = None

    def decode(self, ids, skip_special_tokens=False):
        return "".join(chr(97 + i % 26) for i in ids)


def test_no_step_is_prepared_behind_a_row_under_a_stop_string(engines):
    """The scan for a stop string runs on the host, over text that is
    only there after the collect: while such a row runs nothing is
    prepared, and the stream ends where the plain loop's ends."""
    llm = engines("dense")
    prompt = [int(t) for t in np.random.default_rng(6).integers(2, 60, 8)]

    def run(**kw):
        return Driver(llm).run({0: {"before": [("add", "x", prompt,
                                                SamplingParams(
            temperature=0.0, max_tokens=40, ignore_eos=True, **kw))]}})["x"]

    llm.tokenizer = _Letters()
    try:
        with plain_order(llm):
            text = llm.tokenizer.decode(run()[0])
            stop = text[9:12]
            base = run(stop=[stop])
        before = prepared_counts()
        got = run(stop=[stop])
    finally:
        llm.tokenizer = None
    assert got == base and got[1] == "stop"
    assert len(got[0]) == text.index(stop) + len(stop) <= 12
    assert growth(before) == dict.fromkeys(OUTCOMES, 0)


def test_no_step_is_prepared_past_a_rows_length(engines):
    """``schedule_chain`` refuses a row at its ``max_tokens``: the step
    that samples its last token has nothing prepared behind it, so there
    is nothing to drop."""
    llm = engines("dense")
    script = {0: {"before": [("add", "x", [int(t) for t in
                                           np.random.default_rng(8)
                                           .integers(2, 500, 7)],
                              SamplingParams(temperature=0.0, max_tokens=12,
                                             ignore_eos=True, logprobs=2))]}}
    with plain_order(llm):
        base = Driver(llm).run(script)
    before = prepared_counts()
    assert Driver(llm).run(script) == base
    assert base["x"][1] == "length" and len(base["x"][0]) == 12
    # the prompt's step and eleven decode steps, each of them prepared
    assert growth(before) == {"fired": 11, "dropped_finish": 0,
                              "dropped_arrival": 0, "dropped_other": 0}


def test_an_arrival_rides_the_next_program_and_a_fired_step_places_one_array(
        engines):
    """A request put on the intake queue while a step is prepared (inside
    the collect) is in the very next dispatch, as in the plain loop, and
    waited no pass; the steps before it were launched prepared: one host
    array each (the packed buffer; the tokens stay on the device) against
    two for a step built in the gap; their events say ``prepared``."""
    llm = engines("dense")
    rng = np.random.default_rng(2)
    sp = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True,
                        logprobs=2)
    script = {0: {"before": [("add", "a", [int(t) for t in
                                           rng.integers(2, 500, 6)], sp)]},
              5: {"collect": [("add", "b", [int(t) for t in
                                            rng.integers(2, 500, 7)], sp)]}}
    with plain_order(llm):
        want = Driver(llm)
        base = want.run(script)
    before = prepared_counts()
    mark = TRACE.mark()
    got = Driver(llm)
    assert got.run(script) == base
    # pass 5 collects program 5; b is in program 6 in both loops
    assert got.first_program("b") == want.first_program("b") == 6
    assert got.seqs["b"].passes_waited == want.seqs["b"].passes_waited == 0
    assert growth(before)["dropped_arrival"] == 1
    # arrays placed since the launch before: the plain loop's two a
    # program; a fired step's one (programs 1-5: each follows a step whose
    # every row samples, the prompt's own step too); the dropped step's
    # one and the two of the joining step, which is built in the gap
    assert [n for _, n in want.programs[1:7]] == [2] * 6
    assert [n for _, n in got.programs[1:7]] == [1, 1, 1, 1, 1, 3]
    events = [e for e in TRACE.events(since=mark) if e["kind"] == "decode"]
    assert [bool(e.get("prepared")) for e in events[:5]] == [True] * 5
    assert growth(before)["fired"] == sum(
        bool(e.get("prepared")) for e in events)


def test_the_loops_that_run_ahead_by_other_means_prepare_nothing():
    """``overlap_scheduling`` keeps its chain, ``enforce_eager`` the
    plain order, pp and dp their loops: the gate is the engine's own
    configuration, there is no option for it, and it is all that
    ``enforce_eager`` changes of a default configuration."""
    def cfg(**kw):
        c = EngineConfig(load_format="dummy", max_model_len=64, **kw)
        c.validate()
        return c
    assert prepares_next_step(cfg())
    assert not prepares_next_step(cfg(enforce_eager=True))
    assert not prepares_next_step(cfg(overlap_scheduling=True))
    assert not prepares_next_step(cfg(pp_pipeline_depth=2))
    assert not prepares_next_step(cfg(parallel=ParallelConfig(dp=2)))
    assert not prepares_next_step(cfg(parallel=ParallelConfig(pp=2)))
    assert dataclasses.replace(cfg(enforce_eager=True),
                               enforce_eager=False) == cfg()


def test_a_hybrid_row_still_snapshots_its_state_at_every_page_boundary():
    """Under the prefix cache a hybrid model's commit snapshots the
    recurrent state where a row's computed range ends on a page boundary,
    and only with nothing of the row in flight
    (``register_computed_pages``): a step is not prepared behind such a
    step, so as many snapshots are taken as in the plain loop, the
    second turn of a conversation hits as many cached tokens, and the
    steps between the boundaries are still launched prepared."""
    rng = np.random.default_rng(3)
    sp = SamplingParams(temperature=0.0, max_tokens=21, ignore_eos=True)
    first = [[int(t) for t in rng.integers(2, 500, n)] for n in (8, 6)]

    def turns(plain):
        """Two prompts (one ends on a page boundary), then each answer
        sent back with four more tokens: what the cache holds of it. An
        engine an arm, so that both start cold."""
        llm = make_llm("hybrid", prefix_cache=True)
        llm._prepares = not plain
        mm = llm.memory_manager
        assert mm.ssm_snap_alloc is not None
        snaps = counter("gllm_ssm_intents_total", kind="snapshot")
        d = Driver(llm)
        outs = d.run({0: {"before": [("add", k, p, sp)
                                     for k, p in zip("ab", first)]}})
        hit = mm.hit_tokens
        again = Driver(llm)
        outs2 = again.run({0: {"before": [
            ("add", k, p + outs[k][0] + [7, 8, 9, 10], sp)
            for k, p in zip("ab", first)]}})
        return (outs, outs2, mm.hit_tokens - hit,
                counter("gllm_ssm_intents_total", kind="snapshot") - snaps)

    want = turns(plain=True)
    before = prepared_counts()
    got = turns(plain=False)
    assert got == want
    assert want[3] >= 10 and want[2] >= 40
    assert growth(before)["fired"] >= 20


def test_a_step_is_prepared_only_while_the_device_outlasts_the_host():
    """An engine in a process of its own reads off its clock whether
    there is room to prepare in: the collect of a decode step blocked for
    twice as long as the loop's host work took since the collect before.
    With a device that outlasts the host every decode step but the first
    two is prepared (the first is formed before anything was measured,
    the second while the first is measured); where the host is the slower
    side none is, and the loop is the plain one. The streams are the
    same."""
    llm = make_llm("dense")
    assert llm._own_clock and not llm._room_to_prepare
    prompt = [int(t) for t in np.random.default_rng(9).integers(2, 500, 7)]
    script = {0: {"before": [("add", "x", prompt, SamplingParams(
        temperature=0.0, max_tokens=12, ignore_eos=True))]}}
    before = prepared_counts()
    slow_host = Driver(llm, clock="host").run(script)
    assert growth(before) == dict.fromkeys(OUTCOMES, 0)
    fast_host = Driver(llm, clock="device").run(script)
    assert fast_host == slow_host
    # twelve tokens: the prompt's step and eleven decode steps
    assert growth(before)["fired"] == 9
