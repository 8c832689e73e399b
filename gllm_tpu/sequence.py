"""Per-request sequence state.

TPU-native analogue of the reference Sequence
(/root/reference/gllm/sequence.py:8-177): all known token ids (prompt +
generated), the count of tokens whose KV is resident (``num_computed_tokens``),
the page table, sampling params, and lifecycle status. Prefill and decode are
unified: every schedule step computes tokens [computed, computed+n); a step
whose chunk reaches the end of the known tokens produces logits and samples.
"""

from __future__ import annotations

import enum
from typing import List, Optional

from gllm_tpu.sampling_params import SamplingParams


class SequenceStatus(enum.Enum):
    WAITING = enum.auto()
    RUNNING = enum.auto()
    PREEMPTED = enum.auto()
    # Preempted with KV intact in the host tier (gllm_tpu/kvswap): sits
    # in the waiting queue like PREEMPTED, but re-admission swaps the
    # pages back in instead of re-prefilling.
    SWAPPED = enum.auto()
    FINISHED = enum.auto()
    ABORTED = enum.auto()


# Sentinel seq_id for HOLE rows in persistent-slot decode batches
# (scheduler.schedule_chain): a finished sequence's slot keeps its row in
# the fused chain so the shape signature survives the finish, but the row
# is dead — the device program freezes its position and redirects its KV
# writes to the dummy page, and the host discards its sampled tokens.
HOLE_SEQ_ID = -1


def make_hole_seq() -> "Sequence":
    """A dead placeholder Sequence backing hole rows. One instance can be
    shared by every hole row of every batch: the batch builder only reads
    per-row constants from it (token [0], position 0, page table [0] → the
    dummy page, greedy sampling), ``num_in_flight`` bumps stay symmetric
    with ``process_output``'s decrements, and nothing else ever reads it —
    it is never in ``running``/``waiting`` and owns no allocator pages."""
    from gllm_tpu.sampling_params import SamplingParams as _SP
    # ignore_eos: a hole can never finish (it is already dead), so it
    # must contribute NOTHING to on-device stop sets — otherwise the
    # first hole in an all-ignore_eos workload would flip the fused
    # block's stop-set compile signature mid-run
    seq = Sequence(HOLE_SEQ_ID, [0], _SP(temperature=0.0, max_tokens=1,
                                         ignore_eos=True))
    seq.status = SequenceStatus.FINISHED
    # looks post-prefill so hole rows count as decode (step-kind metrics)
    seq.num_computed_tokens = 1
    seq.page_table = [0]          # dummy page: dead KV writes land there
    return seq


class Sequence:
    def __init__(
        self,
        seq_id: int,
        prompt_token_ids: List[int],
        sampling_params: Optional[SamplingParams] = None,
        arrival_time: float = 0.0,
    ):
        self.seq_id = seq_id
        self.token_ids: List[int] = list(prompt_token_ids)
        # raw vs dynamic prompt length: multimodal models splice placeholder
        # spans, growing the effective prompt (reference sequence.py raw_prompt_len).
        self.raw_prompt_len = len(prompt_token_ids)
        self.prompt_len = len(prompt_token_ids)
        self.sampling_params = sampling_params or SamplingParams()
        self.arrival_time = arrival_time
        # Request-latency anchors (gllm_tpu/obs request histograms —
        # TTFT/TPOT/ITL/queue-time/e2e): set by the scheduler on first
        # admission and by the engine as sampled tokens commit. 0.0 =
        # not yet reached. Preemption keeps them (re-admission must not
        # reset a request's clock).
        self.first_sched_time = 0.0
        self.first_token_time = 0.0
        self.last_token_time = 0.0
        # The other stamps of a request's way to its first token
        # (obs/spans.py first_token_stamps; all time.monotonic()): the
        # front end's body read (None where no front end read one), the
        # handler's put onto the intake queue, the engine thread's
        # add_seq in the intake drain. A request that no serving engine
        # submitted (LLM.generate) keeps the latter two at 0.0.
        self.received_t: Optional[float] = None
        self.submitted_t = 0.0
        self.admitted_t = 0.0
        # Steps that carried a chunk of the prompt and sampled nothing
        # (the step that brings the first token is one more), and the
        # scheduler's admission passes that went by without admitting it
        # (Scheduler.passes at add_seq, then the difference at the first
        # schedule).
        self.prefill_chunks = 0
        self.passes_waited = 0
        # its first_token event is written, or rides its first chunk to
        # the thread that will write it: exactly one a request
        self.first_token_out = False

        self.status = SequenceStatus.WAITING
        self.num_computed_tokens = 0
        # Number of scheduled chunks for this seq currently in flight
        # (pipeline microbatches + chained overlap decode; reference keeps
        # <= pp_size batches running, scheduler.py:358-364, and overlaps
        # decode with placeholder tokens, scheduler.py:702-783). An
        # in-flight seq must not be rescheduled (except by chaining),
        # preempted, or have its pages freed until its steps land.
        self.num_in_flight = 0
        self.page_table: List[int] = []
        self._pt_np = None   # np cache of page_table (builder fast path)
        # Host-tier page ids holding this seq's KV while SWAPPED
        # (gllm_tpu/kvswap); num_computed_tokens keeps counting that KV.
        self.swap_host_pages: Optional[List[int]] = None
        # Pages whose contents came from the prefix cache (KV already valid).
        self.num_cached_tokens = 0
        self.finish_reason: Optional[str] = None
        # Incremental detokenization state (reference sequence.py
        # detokenize_inc): window start / first-unemitted-token offsets.
        # The window starts a few tokens INSIDE the prompt so sentencepiece
        # word-boundary markers render as the leading space of the first
        # output token (the reference re-adds this space explicitly).
        self.detok_prefix_offset = max(0, len(prompt_token_ids) - 6)
        self.detok_read_offset = len(prompt_token_ids)
        self.output_text = ""
        # Multimodal state (gllm_tpu/engine/mm.py MMState) or None for
        # text-only requests.
        self.mm = None
        # Encoder-disaggregation gate state (gllm_tpu/disagg/lm_manager.py
        # DisaggSeqState) or None for monolith seqs.
        self.disagg = None
        # Logprob accumulators (filled by the engine when requested):
        # output_logprobs[i] = (chosen, top_ids, top_lps) for output token
        # i; prompt_logprobs[p] likewise per prompt position (0 → None).
        self.output_logprobs = None
        self.prompt_logprobs = None

    @property
    def cache_token_ids(self) -> List[int]:
        """Token ids used for prefix-cache page hashing: visual placeholder
        spans carry content-hash pad ids so two different images never
        share pages (reference model_runner.py:100-158)."""
        return self.mm.hash_token_ids if self.mm is not None \
            else self.token_ids

    # ---- token accounting -------------------------------------------------

    @property
    def num_tokens(self) -> int:
        return len(self.token_ids)

    @property
    def num_output_tokens(self) -> int:
        return len(self.token_ids) - self.prompt_len

    @property
    def output_token_ids(self) -> List[int]:
        return self.token_ids[self.prompt_len:]

    @property
    def num_remaining_tokens(self) -> int:
        """Tokens not yet computed into the KV cache."""
        return len(self.token_ids) - self.num_computed_tokens

    @property
    def is_prefilling(self) -> bool:
        return self.num_computed_tokens < self.prompt_len

    @property
    def disagg_prefill_limit(self) -> Optional[int]:
        """Gate B (reference scheduler.py:444-458): a disagg seq may only
        prefill up to the first visual span whose embedding hasn't landed.
        None → no cap (monolith seq or all embeddings ready)."""
        if self.disagg is None:
            return None
        return self.disagg.prefill_limit()

    def append_token(self, token_id: int) -> None:
        self.token_ids.append(token_id)
        if self.mm is not None:
            self.mm.hash_token_ids.append(token_id)

    # ---- lifecycle --------------------------------------------------------

    def preempt(self) -> None:
        """Return to waiting state; KV pages are released by the caller
        (reference sequence.py preempt + scheduler.py:254-314)."""
        self.status = SequenceStatus.PREEMPTED
        self.num_computed_tokens = 0
        self.num_cached_tokens = 0
        self.page_table = []
        # the batch builder caches the np form of the page table with
        # length-only invalidation (append-only growth); every shrink
        # site must drop it or a same-length regrow serves stale page ids
        self._pt_np = None

    def swap_out(self, host_pages: List[int]) -> None:
        """Preempt WITHOUT discarding KV: the pages covering
        ``num_computed_tokens`` now live in the host tier (caller already
        released the device pages). The computed count is kept — on
        re-admission the scheduler allocates fresh device pages and the
        swap manager restores into them, so no token is recomputed."""
        self.status = SequenceStatus.SWAPPED
        self.swap_host_pages = list(host_pages)
        self.page_table = []
        self._pt_np = None

    def device_stop_ids(self, eos_token_ids) -> List[int]:
        """The token ids whose sampling finishes this sequence, as seen
        by ON-DEVICE finish detection (fused multi-step blocks): the
        engine's EOS set (unless ignore_eos) plus the request's
        stop_token_ids — exactly the membership tests check_finish runs
        host-side. Sorted so the padded device rows are deterministic.
        The min_tokens gate is positional, not id-based; the batch
        builder arms it separately (SamplingMetadata.stop_from)."""
        sp = self.sampling_params
        ids = set(sp.stop_token_ids)
        if not sp.ignore_eos and eos_token_ids:
            ids.update(int(t) for t in eos_token_ids)
        return sorted(ids)

    def check_finish(self, eos_token_ids) -> Optional[str]:
        """EOS / stop-token / length check after a token was appended.

        ``eos_token_ids`` is a collection — checkpoints declare several
        terminators (reference llm_engine.py finish_tokens membership
        check; GLM4 has three eos ids, Llama-3 two).
        """
        return self._finish_at(self.token_ids[-1], self.num_output_tokens,
                               eos_token_ids)

    def would_finish(self, token: int, eos_token_ids) -> Optional[str]:
        """What :meth:`check_finish` will say once ``token`` is appended:
        the same rule, asked before the token is committed."""
        return self._finish_at(token, self.num_output_tokens + 1,
                               eos_token_ids)

    def _finish_at(self, last: int, num_output: int,
                   eos_token_ids) -> Optional[str]:
        sp = self.sampling_params
        if isinstance(eos_token_ids, int):
            eos_token_ids = (eos_token_ids,)
        if num_output >= sp.min_tokens:
            if not sp.ignore_eos and eos_token_ids and last in eos_token_ids:
                return "stop"
            if last in sp.stop_token_ids:
                return "stop"
        if num_output >= sp.max_tokens:
            return "length"
        return None

    @property
    def is_finished(self) -> bool:
        return self.status in (SequenceStatus.FINISHED, SequenceStatus.ABORTED)

    def __repr__(self) -> str:  # pragma: no cover
        return (f"Sequence(id={self.seq_id}, tokens={self.num_tokens}, "
                f"computed={self.num_computed_tokens}, status={self.status.name})")
