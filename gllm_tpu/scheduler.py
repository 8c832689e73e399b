"""Continuous-batching scheduler.

TPU-native re-design of the reference scheduler
(/root/reference/gllm/scheduler.py:16-783). Semantics preserved:

- unified token accounting: each step computes tokens
  ``[computed, computed+n)`` for every scheduled sequence; a sequence whose
  chunk reaches the end of its known tokens samples a next token. Prefill and
  decode are the same code path (chunked prefill, reference :386-520).
- three policies: ``chunked_prefill`` (default), ``token_throttling`` (the
  SC'25 contribution — prefill budget ramps with KV free ratio + waiting-token
  smoothing, decode budget split across pipeline microbatches, reference
  :613-696), ``split_pd`` (pure-prefill else pure-decode batches).
- SGLang-style adaptive admission: a waiting sequence is admitted only if the
  cache can hold its chunk plus ``new_token_ratio`` of its expected output;
  the ratio decays from init to min over steps and resets on preemption
  (reference :28-45,109-163).
- largest-first preemption under memory pressure (reference :254-314);
  preempted sequences return to the head of the waiting queue.
- abort handling (reference :316-337).

What deliberately does NOT carry over: the reference replicates this scheduler
deterministically on every TP rank ("column driver") because each GPU is its
own process. On TPU a single host process drives all local chips through one
jit'd program, so exactly one scheduler instance exists per DP replica and the
deterministic-jitter / lockstep machinery is unnecessary.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from collections import deque
from typing import Deque, List, Optional, Tuple

from gllm_tpu.config import EngineConfig
from gllm_tpu.memory_manager import MemoryManager
from gllm_tpu.obs import metrics as obs
from gllm_tpu.obs.spans import SPANS, first_token_stamps
from gllm_tpu.sequence import (HOLE_SEQ_ID, Sequence, SequenceStatus,
                               make_hole_seq)
from gllm_tpu.utils import bucket_size, cdiv

logger = logging.getLogger(__name__)

# Scheduler metrics (docs/observability.md): pure-host gauges/counters —
# set from numbers the scheduler already computes, nothing extra touches
# the device or the jit cache keys. Gauges are labeled by DP replica
# (``dp``): with dp>1 each replica owns a scheduler and unlabeled gauges
# would flap between replicas; counters sum meaningfully and stay bare.
_M_WAITING = obs.gauge("gllm_sched_waiting_seqs",
                       "sequences queued waiting for admission", ("dp",))
_M_RUNNING = obs.gauge("gllm_sched_running_seqs",
                       "sequences admitted and holding KV pages", ("dp",))
_M_DECODE = obs.gauge("gllm_sched_decode_seqs",
                      "running sequences in the decode phase", ("dp",))
_M_KV_UTIL = obs.gauge("gllm_sched_kv_util",
                       "fraction of KV pages in use (0..1)", ("dp",))
_M_CACHE_HIT = obs.gauge("gllm_prefix_cache_hit_rate",
                         "lifetime prefix-cache hit rate in tokens (0..1)",
                         ("dp",))
_M_PREEMPT = obs.counter("gllm_sched_preemptions_total",
                         "sequences preempted under memory pressure")
_M_ADMIT = obs.counter("gllm_sched_admitted_total",
                       "sequences admitted from the waiting queue")
_M_BUDGET = obs.gauge("gllm_sched_prefill_token_budget",
                      "prefill token budget of the latest schedule pass",
                      ("dp",))
_M_THROTTLE = obs.counter(
    "gllm_sched_throttle_clips_total",
    "token_throttling passes whose prefill budget was clipped below "
    "max_prefill_tokens by the KV ramp / waiting-token smoothing")


@dataclasses.dataclass
class ScheduledSeq:
    seq: Sequence
    num_new_tokens: int          # tokens computed this step
    computed_before: int         # seq.num_computed_tokens when scheduled
    # Speculative decode: draft tokens appended after the committed rows
    # (prompt-lookup proposals, verified on-device in the same step).
    # Not counted in num_new_tokens — the batch builder adds their rows.
    draft_tokens: tuple = ()

    @property
    def samples(self) -> bool:
        """True when this chunk reaches the end of known tokens → the step
        produces logits for this sequence and samples a token."""
        return (self.computed_before + self.num_new_tokens
                == self.seq.num_tokens)


def propose_ngram_drafts(token_ids, n: int, k: int,
                         window: int = 4096) -> tuple:
    """Prompt-lookup proposal (beyond the reference): the continuation of
    the most recent earlier occurrence of the last-``n``-token pattern,
    up to ``k`` tokens. One vectorized sliding-window compare (numpy) —
    a Python scan here would cost O(window) list slices per decode seq
    per step and could eat the speculative win on the host side."""
    import numpy as np
    L = len(token_ids)
    if L <= n or k <= 0:
        return ()
    lo = max(0, L - window)
    arr = np.asarray(token_ids[lo:], dtype=np.int64)
    M = len(arr)
    if M <= n:
        return ()
    pattern = arr[-n:]
    m = M - n + 1                     # number of window start positions
    match = np.ones(m, dtype=bool)
    for d in range(n):
        match &= arr[d:d + m] == pattern[d]
    idx = np.flatnonzero(match[:m - 1])   # exclude the pattern itself
    if idx.size == 0:
        return ()
    j = int(idx[-1])                  # most recent occurrence
    cont = arr[j + n:j + n + k]
    return tuple(int(t) for t in cont)


@dataclasses.dataclass
class ScheduledBatch:
    items: List[ScheduledSeq]
    # Fused multi-step blocks (schedule_chain): per-item count of chain
    # links in which the item is still ALIVE. A seq that reaches its
    # length limit mid-block goes inactive — the device program freezes
    # its position and redirects its KV writes to the dummy page; the
    # host discards its later sampled tokens. None = every item alive
    # for the whole block. Set on the FIRST batch of a chain only.
    # Persistent-slot mode extends this across block boundaries: a HOLE
    # row (finished seq's slot, sequence.HOLE_SEQ_ID sentinel) carries
    # active_until 0 — dead for the whole block.
    # Under ON-DEVICE finish (config.ondevice_finish) this is a
    # conservative UPPER bound, not the only death mechanism: length
    # deaths it encodes exactly, while EOS/stop-token deaths — which
    # the host cannot know at schedule time — lower the device's
    # carried alive count in-loop (runner step_multi), and the block
    # early-exits once every row is dead.
    active_until: Optional[List[int]] = None
    # Persistent-slot mode: row indices whose link-0 input token must be
    # taken from the HOST-built batch instead of the previous step's
    # on-device sampled tokens — sequences JOINING the chain through a
    # vacant slot this boundary (the chain's device tokens carry no row
    # for them). Set on the FIRST batch of a chain only; None = every
    # row chains off the device tokens.
    host_rows: Optional[List[int]] = None
    # Pipelined loop (schedule_reform): per-row index into the PREVIOUS
    # decode entry's sampled-token array — the device-side splice map
    # across a membership change (row buckets may differ on the two
    # sides). -1 = the row's input token is host-known (a joining
    # decode-ready seq). None = not a re-formed batch (chains use the
    # identity mapping + host_rows instead).
    src_rows: Optional[List[int]] = None
    # Fused on-device speculation (config.spec_fused): this chain link
    # belongs to a spec block — the runner runs the draft+verify block
    # driver, ``active_until`` is a per-row TOKEN budget (not a link
    # count), and per-link ``computed_before`` values are worst-case
    # UPPER bounds (each sub-step may emit up to spec_k+1 tokens) that
    # the collect fixes up from the actual accepted counts
    # (FutureMap.trim_overpromise trims in-flight descendants).
    spec_block: bool = False

    @property
    def num_seqs(self) -> int:
        return len(self.items)

    @property
    def has_drafts(self) -> bool:
        return any(it.draft_tokens for it in self.items)

    @property
    def total_tokens(self) -> int:
        return sum(it.num_new_tokens for it in self.items)

    @property
    def num_decode(self) -> int:
        return sum(1 for it in self.items if it.num_new_tokens == 1
                   and not it.seq.is_prefilling)

    @property
    def mixed_step_rows(self) -> Optional[Tuple[int, int]]:
        """(rows the decode kernel serves, rows the ragged kernel serves)
        of a step that holds a row of more than one token; None for a step
        of one-token rows, which is the decode kernel's whole. The host's
        reading of the rule ``ops/attention._mixed_step_attention`` reads
        off ``cu_q_lens``: the leading items of one new token and no
        drafts ride; a one-token item behind a longer one does not."""
        rows = [it.num_new_tokens + len(it.draft_tokens)
                for it in self.items]
        if max(rows) == 1:
            return None
        riding = next(i for i, n in enumerate(rows) if n > 1)
        return riding, len(rows) - riding


@dataclasses.dataclass
class SeqOutput:
    """One step's result for one sequence (engine-facing)."""
    seq: Sequence
    new_token_id: Optional[int]
    finish_reason: Optional[str]


class Scheduler:
    def __init__(self, config: EngineConfig, memory_manager: MemoryManager,
                 pp_size: int = 1):
        self.config = config
        self.sched_cfg = config.scheduler
        self.mm = memory_manager
        self.pp_size = max(1, pp_size)
        # DP replica rank for metric labels (set by the engine; replica
        # gauges must not overwrite each other under dp>1)
        self.dp_rank = 0

        self.waiting: Deque[Sequence] = deque()
        self.running: List[Sequence] = []
        self._aborted_ids: set[int] = set()
        self._deferred_free: set = set()

        self.new_token_ratio = self.sched_cfg.init_new_token_ratio
        self._ratio_decay = (
            (self.sched_cfg.init_new_token_ratio
             - self.sched_cfg.min_new_token_ratio)
            / max(1, self.sched_cfg.new_token_ratio_decay_steps))
        # Rotating offset so decode seqs beyond the per-batch cap are served
        # round-robin (single-controller analogue of the reference's
        # deterministic rotating jitter, scheduler.py:368-384).
        self._decode_offset = 0
        self._last_stats_time = 0.0
        self.num_preemptions = 0
        # (ngram_n, k) when the ENGINE enabled speculative decoding —
        # set after construction for every topology (incl. overlap, where
        # spec owns decode dispatch and schedule_chain defers, and
        # hybrid GDN via SSM snapshot-rollback); None disables proposals
        self.spec_cfg = None
        self.spec_stats = {"proposed": 0, "accepted": 0}
        # Fused on-device speculation (config.spec_fused; set by the
        # engine after gating inert topologies): host-side drafting is
        # disabled — the runner drafts from an on-device recent-token
        # ring inside fused blocks — and schedule_chain accepts
        # spec-eligible rows instead of refusing with reason="spec"
        # (that break class is retired under the flag).
        self.spec_fused = False
        # Persistent-slot decode batching (config.decode_slot_batching):
        # shared dead-row sentinel for holes, the seq-bucket cap the
        # compaction check shares with BatchBuilder.max_seqs, and the
        # reason ("waiting"/"pages"/"shape"/"spec"/"finish") set whenever
        # schedule_chain returns [] (read by the engine's chain_break
        # event + gllm_chain_breaks_total counter).
        self._hole_seq = make_hole_seq()
        self._seq_bucket_cap = min(config.max_num_seqs,
                                   self.sched_cfg.max_decode_seqs
                                   + self.sched_cfg.max_prefill_tokens)
        # Hybrid models whose slot state has a chunked rule
        # (ModelConfig.ssm_chunked_rule; ``ssm_chunk`` 0 says it has
        # none): rows with more than one new token (prefill
        # chunks, decode rows with drafts) run the GDN layers' chunked
        # rule in a packed layout of whole chunks; a step holds as many
        # as the largest token bucket's layout takes
        # (ops/gdn.gdn_chunk_rows_cap, BatchBuilder.shape_signature)
        self._chunk_rows_cap: Optional[int] = None
        if self.mm.use_ssm and self.mm.ssm_chunk:
            from gllm_tpu.ops.gdn import gdn_chunk_rows_cap
            spec_rows = config.spec_k if config.spec_decode else 0
            self._chunk_rows_cap = gdn_chunk_rows_cap(
                self.sched_cfg.max_prefill_tokens
                + self.sched_cfg.max_decode_seqs * (1 + spec_rows),
                self.mm.ssm_chunk)
        self.chain_break_reason: Optional[str] = None
        # Why the last schedule_reform refused (pipelined loop — feeds
        # the engine's loop_stall reason classification): spec / shape /
        # pages / pp_budget, or None after a successful re-form.
        self.reform_fail_reason: Optional[str] = None
        # Request-span ring (obs/spans.py): the owning LLM overwrites
        # this with its per-engine instance (seq_ids restart per engine
        # — a shared ring would merge co-resident engines' trees); the
        # global is the standalone-scheduler fallback.
        self.spans = SPANS
        # Admission passes so far (_schedule_prefill calls): read at
        # add_seq and at a sequence's first schedule, the difference is
        # the passes that went by without admitting it (the first_token
        # event's ``passes_waited``).
        self.passes = 0

    # ---- intake -----------------------------------------------------------

    def add_seq(self, seq: Sequence) -> None:
        if seq.num_tokens == 0:
            raise ValueError("empty prompt")
        if seq.num_tokens + 1 > self.config.max_model_len:
            raise ValueError(
                f"prompt of {seq.num_tokens} tokens exceeds max_model_len "
                f"{self.config.max_model_len}")
        # Reject work that can never fit the KV pool even running alone —
        # otherwise the engine loop would spin on None batches forever.
        max_len = min(seq.num_tokens + seq.sampling_params.max_tokens,
                      self.config.max_model_len)
        need = cdiv(max_len, self.mm.page_size)
        if need > self.mm.allocator.num_total:
            raise ValueError(
                f"request needs {need} KV pages but the pool has only "
                f"{self.mm.allocator.num_total}")
        seq.status = SequenceStatus.WAITING
        seq.passes_waited = self.passes     # the counter, until admitted
        self.waiting.append(seq)

    def abort_seq(self, seq_id: int) -> None:
        self._aborted_ids.add(seq_id)

    def holds_abort(self, batch: ScheduledBatch) -> bool:
        """A row of ``batch`` has an abort pending (``_process_aborts``
        reaps it on the next ``schedule_once``)."""
        return bool(self._aborted_ids) and any(
            it.seq.seq_id in self._aborted_ids for it in batch.items)

    def count_pass(self) -> None:
        """A step launched without ``schedule_once`` (the engine's
        prepared launch) is a scheduling pass all the same: the admission
        ratio decays and the stats line is due as they would have been."""
        self._decay_ratio()
        self._maybe_log_stats()

    @property
    def has_unfinished(self) -> bool:
        return bool(self.waiting or self.running)

    @property
    def num_unfinished(self) -> int:
        return len(self.waiting) + len(self.running)

    # ---- policy budgets ---------------------------------------------------

    def _prefill_token_budget(self) -> int:
        cfg = self.sched_cfg
        if cfg.schedule_method != "token_throttling":
            return cfg.max_prefill_tokens
        # Token throttling (reference scheduler.py:613-696): ramp the prefill
        # budget with the KV free ratio so prefill backs off as the cache
        # fills, and smooth it against the amount of waiting prefill work so
        # pipeline microbatches carry comparable token counts.
        reserve = cfg.throttle_reserve
        ramp = (self.mm.free_ratio - reserve) / max(1e-6, 1.0 - reserve)
        ramp = min(1.0, max(0.0, ramp))
        budget = int(cfg.max_prefill_tokens * ramp)
        wait_tokens = sum(s.num_remaining_tokens for s in self.waiting)
        wait_tokens += sum(s.num_remaining_tokens for s in self.running
                           if s.num_remaining_tokens > 1)
        smooth = wait_tokens // max(1, cfg.iter_smooth)
        budget = min(budget, max(smooth, cfg.min_prefill_tokens))
        budget = max(cfg.min_prefill_tokens,
                     min(budget, cfg.max_prefill_tokens))
        _M_BUDGET.set(budget, dp=self.dp_rank)
        if budget < cfg.max_prefill_tokens and wait_tokens > 0:
            # only count a clip when there was prefill work to throttle —
            # an idle/decode-only pass trivially floors the budget and
            # must not read as continuous throttling
            _M_THROTTLE.inc()
        return budget

    def _decode_budget(self) -> int:
        cfg = self.sched_cfg
        if cfg.schedule_method == "token_throttling" and self.pp_size > 1:
            # Split decode work evenly over the pp_size microbatches in
            # flight (reference scheduler.py:368-384).
            n_decode = sum(1 for s in self.running
                           if s.num_remaining_tokens == 1)
            return min(cfg.max_decode_seqs,
                       max(1, cdiv(n_decode, self.pp_size)))
        return cfg.max_decode_seqs

    # ---- preemption -------------------------------------------------------

    def _do_preempt(self, victim: Sequence) -> None:
        """Evict ``victim`` (already removed from running) to the head of
        the waiting queue. With a host KV tier attached, the victim's
        computed pages swap out instead of being discarded — re-admission
        swaps them back in with zero re-prefill; the recompute path is
        the fallback (no tier configured, or its pool is full)."""
        swap = getattr(self.mm, "swap", None)
        if swap is not None and swap.try_swap_out(victim, self.mm):
            logger.debug("swapped out seq %d (%d tokens)", victim.seq_id,
                         victim.num_tokens)
        else:
            self.mm.free_seq(victim)
            victim.preempt()
            logger.debug("preempted seq %d (%d tokens)", victim.seq_id,
                         victim.num_tokens)
        self.waiting.appendleft(victim)
        self.num_preemptions += 1
        _M_PREEMPT.inc()
        self.new_token_ratio = self.sched_cfg.init_new_token_ratio

    def _preempt_one(self, protect: set[int]) -> bool:
        """Free memory by preempting the largest unprotected running seq.

        In-flight seqs are immune: their pipeline step is still writing KV
        into the pages we would free."""
        victims = [s for s in self.running
                   if s.seq_id not in protect and not s.num_in_flight]
        if not victims:
            return False
        victim = max(victims, key=lambda s: s.num_tokens)
        self.running.remove(victim)
        self._do_preempt(victim)
        return True

    def _allocate_with_preemption(self, seq: Sequence, n_tokens: int,
                                  protect: set[int]) -> bool:
        need = self.mm.pages_needed(seq, n_tokens)
        while not self.mm.can_allocate(need):
            if not self._preempt_one(protect):
                return False
            if seq.status is not SequenceStatus.RUNNING:
                return False  # preempted ourselves — nothing left to take
        self.mm.allocate_seq_pages(seq, n_tokens)
        return True

    # ---- main entry -------------------------------------------------------

    def schedule_once(self) -> Optional[ScheduledBatch]:
        self._process_aborts()
        self._decay_ratio()

        decode_ready = [s for s in self.running
                        if s.num_remaining_tokens == 1 and not s.num_in_flight]
        prefill_mid = [s for s in self.running
                       if s.num_remaining_tokens > 1 and not s.num_in_flight]
        has_prefill_work = bool(prefill_mid or self.waiting)

        items: List[ScheduledSeq] = []
        if self.sched_cfg.schedule_method == "split_pd" and has_prefill_work:
            self._schedule_prefill(items, self._prefill_token_budget())
            if not items:  # could not admit anything → fall back to decode
                self._schedule_decode(items, decode_ready)
        elif self.sched_cfg.schedule_method == "split_pd":
            self._schedule_decode(items, decode_ready)
        else:
            self._schedule_decode(items, decode_ready)
            self._schedule_prefill(items, self._prefill_token_budget())

        self._maybe_log_stats()
        if not items:
            return None
        for it in items:
            it.seq.num_in_flight += 1
        return ScheduledBatch(items)

    def _chunk_rows_full(self, items: List[ScheduledSeq]) -> bool:
        """Hybrid models: the step already holds as many rows with more
        than one new token as the chunked rule's layout takes."""
        return (self._chunk_rows_cap is not None
                and sum(it.num_new_tokens + len(it.draft_tokens) > 1
                        for it in items) >= self._chunk_rows_cap)

    def _schedule_decode(self, items: List[ScheduledSeq],
                         decode_ready: List[Sequence]) -> None:
        budget = self._decode_budget()
        if not decode_ready:
            return
        # Rotate so capped decode scheduling is fair across iterations.
        off = self._decode_offset % len(decode_ready)
        orderd = decode_ready[off:] + decode_ready[:off]
        self._decode_offset += budget
        protect = {it.seq.seq_id for it in items}
        for seq in orderd[:budget]:
            if seq.status is not SequenceStatus.RUNNING:
                # Preempted as a victim by an earlier seq in this same pass
                # (already reset and pushed to waiting) — scheduling it now
                # would double-schedule it against _schedule_prefill.
                continue
            protect.add(seq.seq_id)
            drafts = self._propose_drafts(seq)
            if drafts and not self.mm.can_allocate(
                    self.mm.pages_needed(seq, 1 + len(drafts))):
                # under memory pressure speculation must never COST a seq
                # its KV: drop the drafts before reaching for preemption
                drafts = ()
            if drafts and self.mm.use_ssm and (
                    self.mm.ssm_snap_alloc is None
                    or self.mm.ssm_snap_alloc.num_free == 0):
                # hybrid needs a free snapshot slot to checkpoint the
                # pre-draft recurrent state; without one, don't speculate
                drafts = ()
            if drafts and self._chunk_rows_full(items):
                drafts = ()     # the row decodes its one token
            if not self._allocate_with_preemption(seq, 1 + len(drafts),
                                                  protect):
                protect.discard(seq.seq_id)
                if seq.status == SequenceStatus.RUNNING:
                    # No victim available — preempt this seq itself so the
                    # system always makes progress (last-resort
                    # self-preemption, reference scheduler.py:254-314).
                    self.running.remove(seq)
                    self._do_preempt(seq)
                continue
            if drafts and self.mm.use_ssm:
                # checkpoint the pre-draft SSM state (the snapshot intent
                # drains before this step's forward runs); restored +
                # re-fed on a partial acceptance (process_output_multi)
                snap = self.mm.ssm_snap_alloc.allocate()
                self.mm.ssm_intents.append(("snapshot", seq.ssm_slot,
                                            snap))
                seq._spec_ssm_snap = snap
            items.append(ScheduledSeq(seq, 1, seq.num_computed_tokens,
                                      draft_tokens=drafts))

    def _propose_drafts(self, seq: Sequence) -> tuple:
        """Per-seq speculative drafts: n-gram prompt-lookup. Greedy
        requests verify by argmax equality (byte-identical); sampled
        requests (temperature > 0) verify by rejection sampling against
        the one-hot proposal (ops/sampling.py spec_verify) — the
        distribution is preserved exactly. Penalties / logit_bias ride
        the verify rows via on-device draft-prefix counts
        (ops/sampling.py spec_adjust_logits); logprobs for the committed
        run come from the verify distributions (aux spec_lp). Stop
        STRINGS stay eligible with a capped draft length: the engine's
        stop scan truncates the streamed text exactly at the match and
        trims over-committed tokens, so a draft run can overshoot by at
        most the (small) cap without the client ever seeing past the
        match."""
        if self.spec_cfg is None or self.spec_fused:
            # fused mode moves drafting ON DEVICE (the block driver's
            # n-gram ring) — sync decode steps run plain and root chains
            return ()
        sp = seq.sampling_params
        n, k = self.spec_cfg
        if sp.stop:
            # bound wasted verify rows past a potential match; AIMD below
            # shrinks it further on rejection streaks
            k = min(k, 2)
        # acceptance-adaptive draft length (VERDICT r03 weak #4): each
        # seq's k follows its own acceptance history — grow by one on a
        # fully-accepted run, drop to the accepted length otherwise, so
        # rejection streaks stop paying K wasted verify rows per step
        k = min(k, getattr(seq, "spec_k_cur", k))
        # positions fed run to num_tokens-1+len(drafts); keep every row
        # inside max_model_len (page table + rope table sizing)
        k = min(k, self.config.max_model_len - seq.num_tokens)
        drafts = propose_ngram_drafts(seq.token_ids, n, k)
        self.spec_stats["proposed"] += len(drafts)
        return drafts

    def _ssm_align_chunk(self, seq: Sequence, n: int) -> int:
        """Hybrid models: end non-final prefill chunks at page boundaries
        so the GDN state at chunk end can be snapshotted for that page
        (prefix caching restores state only at boundaries it has — see
        PrefixMemoryManager.register_computed_pages)."""
        if (getattr(self.mm, "ssm_snap_alloc", None) is None
                or getattr(self.mm, "page2snap", None) is None):
            # no snapshot pool, or no PREFIX-CACHE page snapshots (the
            # pool may exist only for spec-decode rollback checkpoints) →
            # aligning chunks at page boundaries would only waste steps
            return n
        page = self.mm.page_size
        end = seq.num_computed_tokens + n
        if end >= seq.prompt_len:
            # final chunk: stop at the last full-page boundary first so its
            # state gets a snapshot; the (mid-page) remainder follows.
            aligned = (seq.prompt_len // page) * page
        else:
            aligned = (end // page) * page
        if seq.num_computed_tokens < aligned < end:
            return aligned - seq.num_computed_tokens
        return n

    def _schedule_prefill(self, items: List[ScheduledSeq],
                          token_budget: int) -> None:
        protect = {it.seq.seq_id for it in items}
        max_seqs = self.config.max_num_seqs
        self.passes += 1

        # 1) continue partially prefilled running seqs (already admitted).
        for seq in [s for s in self.running
                    if s.num_remaining_tokens > 1 and not s.num_in_flight]:
            if (token_budget <= 0 or len(items) >= max_seqs
                    or self._chunk_rows_full(items)):
                break
            avail = seq.num_remaining_tokens
            # Encoder-disagg gate B (reference scheduler.py:444-458): only
            # prefill up to the first visual span whose embedding hasn't
            # landed.
            limit = seq.disagg_prefill_limit
            if limit is not None:
                if limit <= seq.num_computed_tokens:
                    continue        # nothing prefillable yet; stay parked
                avail = min(avail, limit - seq.num_computed_tokens)
            n = self._ssm_align_chunk(seq, min(avail, token_budget))
            protect.add(seq.seq_id)
            if not self._allocate_with_preemption(seq, n, protect):
                protect.discard(seq.seq_id)
                continue
            items.append(ScheduledSeq(seq, n, seq.num_computed_tokens))
            token_budget -= n

        # 2) admit from the waiting queue, FIFO with head-of-line blocking
        #    (matches the reference; no starvation of long prompts). Gate-B
        #    blocked disagg seqs are deferred and re-queued in order
        #    (reference scheduler.py:503) instead of blocking the line.
        deferred_disagg = []
        while (self.waiting and token_budget > 0
               and len(self.running) < self.config.max_num_seqs
               and len(items) < max_seqs
               and not self._chunk_rows_full(items)):
            seq = self.waiting[0]
            if seq.seq_id in self._aborted_ids:
                if seq.num_in_flight:
                    break  # let the in-flight step land before freeing
                self.waiting.popleft()
                self._finish_abort(seq)
                continue
            if seq.num_computed_tokens == 0 and not seq.page_table:
                self.mm.match_prefix(seq)
            avail = seq.num_remaining_tokens
            limit = seq.disagg_prefill_limit
            if limit is not None:
                if limit <= seq.num_computed_tokens:
                    self.waiting.popleft()
                    deferred_disagg.append(seq)
                    continue
                avail = min(avail, limit - seq.num_computed_tokens)
            n = self._ssm_align_chunk(seq, min(avail, token_budget))
            # Adaptive admission: reserve room for the chunk plus
            # new_token_ratio of the expected decode output. When nothing is
            # running and nothing else got scheduled, drop the reservation —
            # admitting the head seq is the only way to make progress.
            est_extra = int(seq.sampling_params.max_tokens
                            * self.new_token_ratio)
            if not self.running and not items:
                est_extra = 0
            need = self.mm.pages_needed(seq, n) + cdiv(
                est_extra, self.mm.page_size)
            if not self.mm.can_allocate(need):
                break
            if not self.mm.can_admit_seq():
                break  # hybrid: no free SSM working slot
            self.mm.allocate_seq_pages(seq, n)
            self.mm.prepare_seq(seq)
            self.waiting.popleft()
            if seq.status is SequenceStatus.SWAPPED:
                # Resume via swap-in: the fresh pages covering the
                # swapped-out KV are restored from the host tier (the
                # runner drains the copy before this batch's forward),
                # so the chunk continues exactly where preemption hit —
                # zero re-prefill.
                self.mm.swap.record_swap_in(seq)
            seq.status = SequenceStatus.RUNNING
            if not seq.first_sched_time:
                # queue-time anchor (request histograms, engine/llm.py);
                # a preempted seq keeps its original admission time
                seq.first_sched_time = time.monotonic()
                seq.passes_waited = self.passes - seq.passes_waited - 1
                if getattr(self.config, "tracing", True):
                    # open the request's span tree (obs/spans.py) with
                    # the stages it has been through: parse, intake and
                    # queued, from the stamps it carries
                    self.spans.begin(seq.seq_id, first_token_stamps(seq),
                                     prompt_tokens=seq.prompt_len)
            _M_ADMIT.inc()
            self.running.append(seq)
            items.append(ScheduledSeq(seq, n, seq.num_computed_tokens))
            token_budget -= n
        # re-queue gate-B-blocked seqs at the front, preserving order
        for seq in reversed(deferred_disagg):
            self.waiting.appendleft(seq)

    def schedule_chain(self, prev: ScheduledBatch, k_max: int,
                       include_prev: bool = False,
                       spec_mult: int = 1) -> List[ScheduledBatch]:
        """Atomically schedule up to ``k_max`` chained decode steps off
        ``prev``, before ``prev``'s sampled tokens have reached the host.

        This is the overlap-scheduling trick (reference OverlapScheduler's
        deferred placeholder finalize, scheduler.py:702-783 + FutureMap):
        the next steps' input token values live only on the device, but
        page allocation, positions, and slots depend solely on token
        *counts*, which the host already knows. The runner feeds each
        step's on-device sampled tokens straight into the next — no
        host↔device round trip between decode iterations.

        Feasibility of every link is checked READ-ONLY first, the chain
        length is then quantized to a power of two, and only the chosen
        links touch the allocator — so the fused multi-step program
        (jit-static per K) compiles for K ∈ {2,4,8,...} per bucket
        instead of every length the workload's nearest-finish distance
        happens to produce, without any allocator-unwind bookkeeping.
        Returns [] (caller falls back to the synchronous path; the reason
        is left in ``chain_break_reason``) unless every prev item samples
        from a live slot and pages are available without preemption.

        With ``config.decode_slot_batching`` membership is SLOT-based: a
        FINISHED row becomes a HOLE (kept in the batch, masked dead via
        active_until=0) so the pow2 shape signature survives the finish;
        decode-ready sequences join vacant holes at this boundary (their
        link-0 token comes from the host — ``host_rows``); the chain
        only re-forms when live occupancy drops below the seq bucket
        (compaction) or ready sequences can't fit the current slots.

        FUSED SPECULATION (config.spec_fused; ``spec_mult`` =
        spec_k + 1 > 1): every chain link becomes a draft+verify
        sub-step that may emit up to ``spec_mult`` tokens, so the
        accounting moves to TOKEN units — ``deaths`` (already computed
        in tokens) become per-row budgets carried as ``active_until``,
        page allocation covers the worst-case frontier
        cn0 + min(links·spec_mult, budget), and per-link
        ``computed_before`` values are upper bounds the collect trims to
        actual accepted counts. The device carries the ACTUAL frontier
        across blocks (the spec state in the handle), so the host's
        conservative bounds only steer allocation and break decisions —
        never token content."""
        self.chain_break_reason = None
        if self.spec_cfg is not None and not self.spec_fused:
            # Host-driven speculation and chaining are competing
            # dispatch-hiding mechanisms, and host drafting needs the
            # committed token VALUES (prompt-lookup over token_ids)
            # which a chained step leaves on device — so when spec is on
            # WITHOUT the fused path it owns decode dispatch: every
            # decode schedules synchronously with drafts. Under
            # config.spec_fused drafting happens on device and this
            # break class is retired.
            return self._chain_fail("spec")
        spec = self.spec_fused and spec_mult > 1
        mult = spec_mult if spec else 1
        slots = self.config.decode_slot_batching
        base: List[Tuple[Sequence, int]] = []
        hole_rows: List[int] = []
        for i, it in enumerate(prev.items):
            seq = it.seq
            if slots and (seq.seq_id == HOLE_SEQ_ID
                          or seq.status is SequenceStatus.FINISHED):
                # Slot mode: a finished row keeps its SLOT as a hole —
                # the fused program masks it (active_until 0: frozen
                # position, dummy-page KV writes) and the shape
                # signature survives the finish. The finished seq's own
                # pages drain through the existing deferred-free path;
                # the hole references only the shared sentinel.
                base.append((self._hole_seq, 0))
                hole_rows.append(i)
                continue
            # A non-RUNNING seq (EOS/stop finish committed while later
            # links were in flight, abort, preemption) must force the
            # sync re-form: without this gate a FINISHED seq whose
            # in-flight chunk end ran ahead of its committed num_tokens
            # would be re-chained forever as a zombie row — allocating
            # pages toward its max_tokens frontier and burning a batch
            # slot on discarded tokens. (The pre-run-through code's
            # strict == chunk-end check refused this case as a side
            # effect.) Slot mode turned the FINISHED case into a hole
            # above.
            if seq.status is not SequenceStatus.RUNNING:
                return self._chain_fail("finish")
            if seq.seq_id in self._aborted_ids:
                # client abort: _process_aborts reaps the pages on the
                # sync pass — host work a chain can't carry in either
                # membership mode, so it's a 'shape' break, keeping
                # reason='finish' strictly zero under slot batching
                return self._chain_fail("shape")
            # Mid-prompt prefill chunks don't sample — nothing to chain
            # off. A chunk at-or-past the end of HOST-known tokens does:
            # ``prev`` may itself be a chained step whose sampled token
            # only exists on device, so its chunk end exceeds
            # seq.num_tokens (``it.samples``'s strict == refused those,
            # silently capping every multi-step block at ONE chained
            # step — r5 on-chip: profile=full ran msd=8 as single-token
            # dispatches).
            if it.computed_before + it.num_new_tokens < seq.num_tokens:
                return self._chain_fail("shape")
            sp = seq.sampling_params
            if (sp.repetition_penalty != 1.0 or sp.presence_penalty != 0.0
                    or sp.frequency_penalty != 0.0):
                return self._chain_fail("shape")  # host-built counts
            cn0 = it.computed_before + it.num_new_tokens
            if spec and prev.spec_block:
                # ``prev``'s last sub-step may itself emit up to ``mult``
                # tokens (its computed_before is already the block's
                # upper-bound base) — the new block's base frontier must
                # cover that worst case; the device carries the actual
                # frontier, so this only steers allocation/feasibility
                cn0 += mult - 1
            base.append((seq, cn0))
        host_rows: List[int] = []
        if slots:
            host_rows = self._join_ready_into_holes(base, hole_rows)
            if self.chain_break_reason is not None:
                return []        # unjoined ready seqs: batch must grow
            live = sum(1 for seq, _ in base if seq.seq_id != HOLE_SEQ_ID)
            if live == 0:
                # fully drained batch — nothing left to run; the sync
                # pass re-forms from whatever is schedulable
                return self._chain_fail("shape")
            if (bucket_size(live, 8, self._seq_bucket_cap)
                    < bucket_size(len(base), 8, self._seq_bucket_cap)):
                # occupancy fell below the next bucket boundary: compact
                # (the re-formed batch compiles to an already-warm
                # smaller signature)
                return self._chain_fail("shape")
        # Per-seq DEATH step: link j processes token index cn0 + j and
        # samples index cn0+j+1; seq s can take links j < d_s, where d_s
        # caps at both its max_tokens and the model length. Link 0 needs
        # EVERY seq alive in legacy mode (a batch already carrying
        # finished rows forces the sync path, which re-forms a clean
        # batch) — but a block may RUN THROUGH deaths that happen inside
        # it: the dead row's device writes go to the dummy page and its
        # later sampled tokens are discarded by process_output's
        # not-RUNNING branch, while the other rows keep their fused
        # block (the all-or-nothing refusal collapsed most blocks to 1-2
        # steps on the r5 ShareGPT bench — with ~150 live seqs SOME row
        # is nearly always one step from finishing). Slot mode extends
        # the same masking across block boundaries: holes are rows whose
        # death already passed (active_until 0).
        page = self.mm.page_size
        deaths = [0 if seq.seq_id == HOLE_SEQ_ID else
                  min(seq.sampling_params.max_tokens
                      + seq.prompt_len - cn0 - 1,
                      self.config.max_model_len - cn0)
                  for seq, cn0 in base]
        if not slots and min(deaths) < 1:
            # a row dies the moment prev lands — the sync path re-forms
            return self._chain_fail("finish")
        if slots and max(deaths) < 1:
            return self._chain_fail("shape")  # nothing can take a link
        # Fused speculation: each link may emit up to ``mult`` tokens,
        # so pages must cover the worst-case frontier; with include_prev
        # the sync batch rides as the block's first sub-step and may
        # itself emit mult tokens before link 0 runs (extra headroom).
        extra = (mult - 1) if (spec and include_prev) else 0
        feasible = 0
        while feasible < min(k_max, max(deaths)):
            j = feasible
            # validate the page need of the WHOLE chain so far before
            # touching the allocator: per-link checks would each pass
            # near a full pool yet exhaust it mid-allocation. Dead links
            # allocate nothing.
            need_cum = sum(
                max(0, cdiv(cn0 + min((j + 1) * mult + extra, d), page)
                    - len(seq.page_table))
                for (seq, cn0), d in zip(base, deaths))
            if not self.mm.can_allocate(need_cum):
                break
            feasible += 1
        if not feasible:
            return self._chain_fail("pages")
        # quantize to a power of two so fused-block compiles stay bounded;
        # with ``include_prev`` the caller fuses ``prev`` itself as the
        # block's first step (a freshly re-formed sync decode batch), so
        # it is prev PLUS the links that must total a power of two
        if include_prev:
            k = (1 << ((feasible + 1).bit_length() - 1)) - 1
            if not k:
                return self._chain_fail("pages")
        else:
            k = 1 << (feasible.bit_length() - 1)
        chain: List[ScheduledBatch] = []
        for j in range(k):
            # dead links freeze computed_before at the death position —
            # the NEXT chain attempt off this batch then fails the
            # link-0 gate above, forcing the sync re-form. Spec blocks
            # stride the (upper-bound) frontier by mult per link,
            # clamped under max_model_len: a frozen upper bound at the
            # model-length cap would overflow the page bucket (the
            # shape-signature prices computed_before + 1), and the
            # collect re-anchors on committed state anyway.
            mml1 = self.config.max_model_len - 1
            items = [ScheduledSeq(seq, 1,
                                  min(cn0 + min(j * mult, d), mml1)
                                  if spec else cn0 + min(j, d))
                     for (seq, cn0), d in zip(base, deaths)]
            for it, ((seq, cn0), d) in zip(items, zip(base, deaths)):
                if j * mult < d:
                    # cover tokens [0, worst-case frontier) —
                    # num_computed_tokens hasn't advanced yet (prev is
                    # still in flight); a table longer than the actual
                    # emission needs is legal (spec-decode precedent)
                    cover = (cn0 + min((j + 1) * mult + extra, d)
                             - seq.num_computed_tokens)
                    self.mm.allocate_seq_pages(seq, cover)
                seq.num_in_flight += 1
            chain.append(ScheduledBatch(items, spec_block=spec))
        if spec:
            # active_until carries the per-row TOKEN budget (the device
            # seeds its carried alive count from it at chain root; holes
            # and joins re-seed from it mid-chain) — always attached,
            # and NEVER capped at the block's worst-case emission: the
            # budget is carried ACROSS blocks (the while_loop bounds one
            # block's sub-steps; the budget bounds the sequence)
            chain[0] = dataclasses.replace(
                chain[0],
                active_until=[max(d, 0) for d in deaths],
                host_rows=host_rows or None, spec_block=True)
        elif any(d < k for d in deaths) or host_rows:
            chain[0] = dataclasses.replace(
                chain[0],
                active_until=([min(d, k) for d in deaths]
                              if any(d < k for d in deaths) else None),
                host_rows=host_rows or None)
        return chain

    def _chain_fail(self, reason: str) -> list:
        """Record why this chain attempt failed (the engine labels the
        chain_break steptrace event and gllm_chain_breaks_total with it:
        waiting / pages / shape / spec / finish) and refuse the chain."""
        self.chain_break_reason = reason
        return []

    def _join_ready_into_holes(self, base: List[Tuple[Sequence, int]],
                               hole_rows: List[int]) -> List[int]:
        """Admit decode-ready running seqs into vacant (hole) slots at
        this chain boundary — membership changes without a shape change.

        A joining row's link-0 input token is HOST-known (its last
        sampled token landed before it went decode-ready) while the
        chain's on-device token array has no row for it, so the filled
        row indices are returned for ``ScheduledBatch.host_rows``: the
        runner splices those rows' tokens from the host-built batch.

        Ready seqs that can't join — no vacant slot, or per-seq features
        a fused chain can't carry (penalties, logit_bias, logprobs, stop
        strings) — set ``chain_break_reason='waiting'`` so the caller
        re-forms a grown batch... unless the batch is already at the
        decode budget, where a re-form couldn't seat them either (they
        wait for a natural break, as in legacy rotation)."""
        chain_ids = {seq.seq_id for seq, _ in base
                     if seq.seq_id != HOLE_SEQ_ID}
        ready = [s for s in self.running
                 if s.num_remaining_tokens == 1 and not s.num_in_flight
                 and s.seq_id not in chain_ids
                 and s.seq_id not in self._aborted_ids]
        if not ready:
            return []

        def fusable(s: Sequence) -> bool:
            sp = s.sampling_params
            return (sp.repetition_penalty == 1.0
                    and sp.presence_penalty == 0.0
                    and sp.frequency_penalty == 0.0
                    and not sp.logit_bias and sp.logprobs is None
                    and not sp.stop)

        joins = list(zip(hole_rows, (s for s in ready if fusable(s))))
        if (len(joins) < len(ready)
                and len(base) < self.sched_cfg.max_decode_seqs):
            # ready work the current slots can't seat — the batch must
            # grow past its signature; caller falls back to the sync
            # re-form (this is the ONLY growth path: joins never widen
            # the bucket)
            self.chain_break_reason = "waiting"
            return []
        for row, seq in joins:
            base[row] = (seq, seq.num_computed_tokens)
        return [row for row, _ in joins]

    # ---- pipelined loop (speculative re-form) -----------------------------

    def schedule_reform(self, prev: ScheduledBatch
                        ) -> Optional[ScheduledBatch]:
        """Speculatively RE-FORM the next pure-decode batch off ``prev``'s
        *promised* token counts, before ``prev``'s sampled ids have
        reached the host (the pipelined engine loop,
        docs/overlap_scheduling.md#pipelined-loop).

        Where ``schedule_chain`` extends a batch with UNCHANGED
        membership, this is the membership-change edge the chain refuses
        — a committed finish dropped a row, slot compaction shrank the
        bucket, or decode-ready sequences must be seated. The FutureMap
        contract: every included in-flight row advances to its promised
        frontier (``computed_before + num_new_tokens`` of its ``prev``
        item) and the runner splices its input token from ``prev``'s
        on-device sampled array via ``ScheduledBatch.src_rows``; rows
        whose promised frontier provably dies by LENGTH are dropped here
        (the sync loop would drop them too — no divergence possible),
        while EOS/stop deaths the host cannot know yet are assumed
        alive: the engine invalidates and rebuilds this batch at collect
        time if the assumption breaks.

        Returns None with ``reform_fail_reason`` ∈
        spec/shape/pages/pp_budget when re-forming needs host-committed
        state (the caller falls back to the drain-and-sync path and
        records a loop_stall)."""
        self.reform_fail_reason = None
        if self.spec_cfg is not None:
            # speculation owns decode dispatch (drafting needs committed
            # token VALUES) — same deferral as schedule_chain
            return self._reform_fail("spec")
        base: List[Tuple[Sequence, int, int]] = []   # (seq, cn0, src row)
        for i, it in enumerate(prev.items):
            seq = it.seq
            if (seq.seq_id == HOLE_SEQ_ID
                    or seq.status is SequenceStatus.FINISHED):
                continue       # committed finish / hole: the row drops
            if seq.status is not SequenceStatus.RUNNING:
                return self._reform_fail("shape")   # preempted: sync path
            if seq.seq_id in self._aborted_ids:
                # _process_aborts reaps pages only on the sync pass; a
                # reform that skipped the row forever would leak it
                return self._reform_fail("shape")
            if it.computed_before + it.num_new_tokens < seq.num_tokens:
                return self._reform_fail("shape")   # mid-prefill row
            sp = seq.sampling_params
            if (sp.repetition_penalty != 1.0 or sp.presence_penalty != 0.0
                    or sp.frequency_penalty != 0.0):
                # penalty counts are built host-side from token_ids,
                # which lack the promised token — the adjusted logits
                # would diverge from the sync loop
                return self._reform_fail("shape")
            cn0 = it.computed_before + it.num_new_tokens
            # promised LENGTH death: once prev commits, the seq holds
            # cn0+1 tokens — host-predictable, so the row drops here
            if (cn0 + 1 - seq.prompt_len >= sp.max_tokens
                    or cn0 + 1 >= self.config.max_model_len):
                continue
            base.append((seq, cn0, i))
        # decode-ready running seqs join with HOST-known input tokens
        # (src -1); one unfusable-for-promising candidate (penalties)
        # refuses the whole re-form so the sync pass can seat it —
        # skipping it here would starve it at decode saturation
        in_batch = {seq.seq_id for seq, _, _ in base}
        # Per-stage token throttling: under pp > 1 the decode budget is
        # the per-microbatch share (cdiv(n_decode, pp)), not the global
        # cap, so re-formed stage batches keep the same geometry the
        # sync scheduler feeds the pipeline. The share is recomputed
        # from live counts, so finishes in OTHER microbatches can
        # shrink it below the promised row count of THIS one — honoring
        # the budget would drop promised rows (breaking the FutureMap
        # contract), exceeding it would unbalance the stages, so the
        # re-form refuses with its own reason and the drain-and-sync
        # pass re-balances the stage batches.
        budget = self._decode_budget()
        if len(base) > budget:
            return self._reform_fail("pp_budget")
        for s in self.running:
            if (s.num_remaining_tokens != 1 or s.num_in_flight
                    or s.seq_id in in_batch
                    or s.seq_id in self._aborted_ids):
                continue
            if len(base) >= budget:
                # over budget: waits, as in legacy rotation — and a
                # penalized candidate past the budget must NOT refuse
                # the re-form (the sync path could not seat it either,
                # so the refusal would buy no fairness while degrading
                # the whole loop to drain-and-sync)
                continue
            sp = s.sampling_params
            if (sp.repetition_penalty != 1.0 or sp.presence_penalty != 0.0
                    or sp.frequency_penalty != 0.0):
                return self._reform_fail("shape")
            base.append((s, s.num_computed_tokens, -1))
        if not base:
            return self._reform_fail("shape")   # nothing left to run
        base = base[:budget]
        page = self.mm.page_size
        need = sum(max(0, cdiv(cn0 + 1, page) - len(seq.page_table))
                   for seq, cn0, _ in base)
        if not self.mm.can_allocate(need):
            # never preempt for a speculative batch — a victim's freed
            # pages could not be restored if the speculation invalidates
            return self._reform_fail("pages")
        items: List[ScheduledSeq] = []
        src_rows: List[int] = []
        for seq, cn0, src in base:
            cover = cn0 + 1 - seq.num_computed_tokens
            self.mm.allocate_seq_pages(seq, cover)
            items.append(ScheduledSeq(seq, 1, cn0))
            src_rows.append(src)
        for it in items:
            it.seq.num_in_flight += 1
        return ScheduledBatch(items, src_rows=src_rows)

    def _reform_fail(self, reason: str):
        self.reform_fail_reason = reason
        return None

    def discard_batch(self, batch: ScheduledBatch) -> None:
        """Unwind a speculatively scheduled entry the reconciliation
        invalidated (pipelined loop): per-item in-flight counts drop
        WITHOUT committing tokens or advancing computed counts, so the
        sync rebuild re-schedules the same positions. Pages allocated
        toward the promised frontier stay on the seq's table (tables
        longer than the next step needs are legal — the speculative-
        decode precedent in BatchBuilder.shape_signature); a finished
        seq's deferred free fires once its last in-flight entry drains.
        Accepts a single batch or a fused chain list."""
        for b in (batch if isinstance(batch, list) else [batch]):
            for it in b.items:
                seq = it.seq
                seq.num_in_flight -= 1
                if (seq.status is not SequenceStatus.RUNNING
                        and seq in self._deferred_free
                        and seq.num_in_flight == 0):
                    self._deferred_free.discard(seq)
                    self.mm.free_seq(seq)

    # ---- output path ------------------------------------------------------

    def process_output(self, batch: ScheduledBatch,
                       sampled_tokens: List[int],
                       eos_token_ids) -> List[SeqOutput]:
        """Advance state after a step. ``sampled_tokens[i]`` is the sampled
        token for batch item i (ignored for items that don't sample).
        ``eos_token_ids`` is a collection of terminator ids (or None)."""
        return self.process_output_multi(
            batch, [[t] for t in sampled_tokens], eos_token_ids)

    def process_output_multi(self, batch: ScheduledBatch,
                             token_lists: List[List[int]],
                             eos_token_ids) -> List[SeqOutput]:
        """Like process_output but each item may commit SEVERAL tokens
        (speculative decoding: the verified draft run + the correction
        token). Tokens append in order with per-token finish checks; a
        finish mid-list discards the rest. ``num_computed_tokens``
        advances by the number of rows whose input token proved correct —
        rejected draft rows' KV is overwritten when the real token at
        that position is fed later."""
        outputs: List[SeqOutput] = []
        for it, toks in zip(batch.items, token_lists):
            seq = it.seq
            seq.num_in_flight -= 1
            snap = getattr(seq, "_spec_ssm_snap", None)
            if snap is not None:
                seq._spec_ssm_snap = None
                if (seq.status is not SequenceStatus.RUNNING
                        or seq.seq_id in self._aborted_ids):
                    # finished/aborted/preempted mid-flight: the state no
                    # longer matters; just return the slot (drain-deferred
                    # — a pending intent may still reference it)
                    self.mm.free_snap_after_drain(snap)
                    snap = None
            if seq.status is not SequenceStatus.RUNNING:
                # finished at an earlier (chained) step while this one was
                # in flight: release its deferred pages once the last
                # in-flight step lands (even if the client also aborted it
                # meanwhile).
                if (seq in self._deferred_free
                        and seq.num_in_flight == 0):
                    self._deferred_free.discard(seq)
                    self.mm.free_seq(seq)
                continue
            if seq.seq_id in self._aborted_ids:
                continue  # handled in _process_aborts
            finish: Optional[str] = None
            if not it.samples:
                seq.prefill_chunks += 1
                seq.num_computed_tokens = (it.computed_before
                                           + it.num_new_tokens)
                self.mm.register_computed_pages(seq)
                outputs.append(SeqOutput(seq, None, None))
                continue
            emitted = 0
            for tok in toks:
                seq.append_token(int(tok))
                emitted += 1
                finish = seq.check_finish(eos_token_ids)
                # Hard cap: the KV layout (page_table width, rope table)
                # is sized for max_model_len; never decode past it.
                if (finish is None
                        and seq.num_tokens >= self.config.max_model_len):
                    finish = "length"
                outputs.append(SeqOutput(seq, int(tok),
                                         finish))
                if finish is not None:
                    break
            ssm_rollback = False
            if self.spec_cfg is not None and it.draft_tokens:
                accepted = emitted - 1
                self.spec_stats["accepted"] += accepted
                # AIMD draft-length adaptation: +1 on a clean sweep (cap
                # spec_k), collapse to the accepted run length otherwise
                cap = self.spec_cfg[1]
                cur = getattr(seq, "spec_k_cur", cap)
                if accepted >= len(it.draft_tokens):
                    seq.spec_k_cur = min(cap, cur + 1)
                else:
                    seq.spec_k_cur = max(1, accepted)
                if snap is not None:
                    if (accepted < len(it.draft_tokens)
                            and finish is None):
                        # hybrid partial acceptance: the recurrent state
                        # advanced over rejected draft rows too — restore
                        # the pre-draft snapshot and re-feed the committed
                        # run (the rolled-back num_computed below routes
                        # the seq through the chunked re-feed path)
                        self.mm.ssm_intents.append(
                            ("restore", snap, seq.ssm_slot))
                        ssm_rollback = True
                    self.mm.free_snap_after_drain(snap)
            # rows fed were num_new_tokens committed tokens (+ drafts);
            # valid KV covers the rows whose inputs were correct: the
            # chunk plus the accepted drafts = num_new-1 + emitted rows
            seq.num_computed_tokens = (
                it.computed_before + it.num_new_tokens - 1
                + (0 if ssm_rollback else emitted))
            self.mm.register_computed_pages(seq)
            if finish is not None:
                seq.status = SequenceStatus.FINISHED
                seq.finish_reason = finish
                self.running.remove(seq)
                if seq.num_in_flight > 0:
                    # a chained step for this seq is still writing KV into
                    # its pages — free when it lands
                    self._deferred_free.add(seq)
                else:
                    self.mm.free_seq(seq)
        return outputs

    def finish_seq(self, seq: Sequence, reason: str = "stop") -> None:
        """Finish a RUNNING seq from outside the output path (host-side
        stop-string match — the reference finishes these in the frontend).
        Same page bookkeeping as an EOS finish."""
        if seq.status is not SequenceStatus.RUNNING:
            return
        seq.status = SequenceStatus.FINISHED
        seq.finish_reason = reason
        self.running.remove(seq)
        if seq.num_in_flight > 0:
            self._deferred_free.add(seq)
        else:
            self.mm.free_seq(seq)

    # ---- aborts / stats ---------------------------------------------------

    def quarantine(self, seq_ids) -> List[Sequence]:
        """Fault-isolation rollback after a step exception (serving
        engine → ``LLM.quarantine_step_failure``): the given seqs'
        device state is unknown — drop them wholesale. Pages free
        immediately (the engine already cleared its dispatch queue, so
        nothing is writing into them), in-flight counts reset, deferred
        frees flush, and the seqs leave both queues so ``has_unfinished``
        can reach False again — no hot-retry of a poisoned batch."""
        ids = set(seq_ids)
        dropped: List[Sequence] = []
        for seq in [s for s in self.running if s.seq_id in ids]:
            self.running.remove(seq)
            self._quarantine_one(seq, dropped)
        for seq in [s for s in self.waiting if s.seq_id in ids]:
            self.waiting.remove(seq)
            self._quarantine_one(seq, dropped)
        for seq in [s for s in self._deferred_free
                    if s.seq_id in ids]:
            # already FINISHED; its pages waited on an in-flight step
            # that will never land now
            self._deferred_free.discard(seq)
            seq.num_in_flight = 0
            self.mm.free_seq(seq)
        self._aborted_ids -= ids
        # the shared hole sentinel's in-flight bumps from dropped fused
        # chains will never see their process_output decrements
        self._hole_seq.num_in_flight = 0
        return dropped

    def _quarantine_one(self, seq: Sequence,
                        dropped: List[Sequence]) -> None:
        seq.num_in_flight = 0
        seq.status = SequenceStatus.ABORTED
        seq.finish_reason = "error"
        self.mm.free_seq(seq)
        dropped.append(seq)

    def _finish_abort(self, seq: Sequence) -> None:
        seq.status = SequenceStatus.ABORTED
        seq.finish_reason = "abort"
        self.mm.free_seq(seq)
        self._aborted_ids.discard(seq.seq_id)
        if getattr(self.config, "tracing", True):
            # aborted seqs never emit a finishing SeqOutput — close the
            # span tree here (first close wins: the serving engine may
            # already have recorded a more specific reason, e.g.
            # "deadline")
            self.spans.close(seq, "abort", time.monotonic())

    def _process_aborts(self) -> None:
        if not self._aborted_ids:
            return
        # In-flight seqs keep their pages until the step lands; they are
        # reaped on a later schedule_once after process_output cleared the
        # flag.
        for seq in [s for s in self.running
                    if s.seq_id in self._aborted_ids
                    and not s.num_in_flight]:
            self.running.remove(seq)
            self._finish_abort(seq)
        for seq in [s for s in self.waiting
                    if s.seq_id in self._aborted_ids
                    and not s.num_in_flight]:
            self.waiting.remove(seq)
            self._finish_abort(seq)

    def _decay_ratio(self) -> None:
        self.new_token_ratio = max(self.sched_cfg.min_new_token_ratio,
                                   self.new_token_ratio - self._ratio_decay)

    def _maybe_log_stats(self) -> None:
        # 1 Hz stats line (reference scheduler.py:576-603).
        now = time.monotonic()
        if now - self._last_stats_time < 1.0:
            return
        self._last_stats_time = now
        n_decode = sum(1 for s in self.running if s.num_remaining_tokens == 1)
        n_prefill = len(self.running) - n_decode
        util = 1.0 - self.mm.free_ratio
        hit = getattr(self.mm, "cache_hit_rate", None)
        _M_WAITING.set(len(self.waiting), dp=self.dp_rank)
        _M_RUNNING.set(len(self.running), dp=self.dp_rank)
        _M_DECODE.set(n_decode, dp=self.dp_rank)
        _M_KV_UTIL.set(util, dp=self.dp_rank)
        if hit is not None:
            _M_CACHE_HIT.set(hit, dp=self.dp_rank)
        spec = ""
        if self.spec_cfg is not None and self.spec_stats["proposed"]:
            spec = (" spec_accept={:.1f}%".format(
                100.0 * self.spec_stats["accepted"]
                / self.spec_stats["proposed"]))
        # host KV tier occupancy (+ disk tier when attached) — the
        # lower-tier health reads off the same 1 Hz line as kv_util
        host = ""
        swap = getattr(self.mm, "swap", None)
        if swap is not None:
            host = f" host_pool={swap.pool.num_used}/{swap.pool.num_pages}"
            tiers = getattr(swap, "tiers", None)
            if tiers is not None and tiers.disk is not None:
                host += (f" disk={len(tiers.disk)}pg/"
                         f"{tiers.disk.bytes_used / (1 << 20):.0f}MiB")
        logger.info(
            "sched: wait=%d run=%d prefill=%d decode=%d kv_util=%.1f%%%s%s%s",
            len(self.waiting), len(self.running), n_prefill, n_decode,
            util * 100.0,
            f" cache_hit={hit*100.0:.1f}%" if hit is not None else "",
            spec, host)
