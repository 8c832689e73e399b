"""Pipelined engine loop: in-flight entries and the FutureMap.

TPU-native analogue of the reference OverlapWorker/FutureMap pair
(PAPER.md §4-5): the reference resolves negative placeholder token ids
against a future table when the GPU step lands; here the placeholder IS
the device array — a re-formed batch's input tokens are spliced from the
previous entry's on-device sampled tokens (runner._splice_mapped_tokens)
and the host only tracks *which sequences were promised alive*.

The promise contract (docs/overlap_scheduling.md#pipelined-loop):

- Scheduling needs token COUNTS, not values: page allocation, positions,
  slots, and the sampling out_step all derive from the promised frontier
  ``computed_before + num_new_tokens`` of a sequence's newest in-flight
  row (scheduler.schedule_reform).
- Deaths the host can predict (LENGTH: max_tokens / max_model_len) are
  applied at promise time — those rows simply drop, and no divergence is
  possible. Deaths the host cannot predict (EOS / stop tokens / stop
  strings) are assumed NOT to happen.
- When a finish commits for a sequence some later in-flight entry
  promised alive, that entry — and every entry chained off it — is
  INVALIDATED: its sampled tokens never commit, its in-flight counts
  unwind (scheduler.discard_batch), and the sync path rebuilds from
  committed state. Greedy and seeded sampling draw identically on the
  rebuild (context- resp. (seed, out_step)-determined), so token streams
  stay byte-identical to the sync loop.

No jax imports: this module is host bookkeeping only.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class InFlight:
    """One dispatched-but-uncollected engine entry.

    ``batch`` is a ScheduledBatch or a fused-chain list of them;
    ``handle`` is the runner's opaque async handle; ``t_dispatch`` and
    ``phases`` feed the attribution layer (obs/spans.py). The pipelined
    fields: ``chained`` marks entries whose input tokens came off the
    previous decode entry's device array (chain extensions, fused
    blocks, re-forms) — an invalidation cascades through them;
    ``roots`` marks a sync-scheduled entry that ROOTS a fresh chain
    from host-committed state (a pure-decode sync batch or a fresh
    fused block) — the cascade stops there, later chained entries
    descend from it, not from anything older; ``promises`` is the set
    of seq ids a speculative re-form assumed alive; ``invalid`` marks
    an entry reconciliation dropped (collected as a discard, never
    committed). ``prepared``: the entry was built under the step before
    it and launched from that step's collect (the default loop's
    prepared launch; it rides its step event)."""

    batch: object
    handle: object
    t_dispatch: float
    phases: Optional[dict]
    chained: bool = False
    roots: bool = False
    promises: frozenset = frozenset()
    invalid: bool = False
    prepared: bool = False

    @property
    def tip(self):
        """(batch, handle) — the chain-tip view the fill loop extends."""
        return self.batch, self.handle


@dataclasses.dataclass
class DPBatches:
    """Per-replica batch list for one dp SUPER-STEP entry (the dp
    pipelined loop, docs/overlap_scheduling.md#topology-matrix):
    ``batches[r]`` is replica r's ScheduledBatch or None (idle dummy).
    A dedicated holder — NOT a plain list — so the fused-chain
    ``isinstance(batch, list)`` checks elsewhere never mistake a
    dp-wide entry for a multi-step chain."""

    batches: list


class FutureMap:
    """Promise registry + reconciliation for the pipelined loop.

    State lives IN the in-flight entries (promises travel with the work
    they gate); this object owns the reconciliation scan and the
    divergence counters the loop_stall observability reads."""

    def __init__(self):
        self.rebuilds = 0          # invalidated entries, lifetime
        self.divergences = 0       # reconcile() calls that invalidated

    @staticmethod
    def promised_ids(batch) -> frozenset:
        """Seq ids a re-formed batch assumed alive: rows whose input
        token is a promise (src_rows >= 0). Joining rows (src -1) carry
        committed state — nothing is assumed for them."""
        if batch.src_rows is None:
            return frozenset()
        return frozenset(it.seq.seq_id
                         for it, src in zip(batch.items, batch.src_rows)
                         if src >= 0)

    def reconcile(self, in_flight, finished_ids) -> int:
        """Invalidate every in-flight entry whose promises intersect
        ``finished_ids`` — and, transitively, every later entry chained
        off an invalidated one (its input tokens came from a batch that
        never commits). Entries scheduled synchronously from committed
        state stay valid — interleaved prefill dispatches because their
        sequences were not in flight when formed, and a later
        chain-ROOTING entry (``roots``) additionally STOPS the cascade:
        chained entries after it descend from that valid root, not from
        the invalidated speculation, and discarding them would re-run
        real committed-parent work for nothing. Returns the number of
        entries newly invalidated."""
        if not finished_ids:
            return 0
        hit = 0
        cascading = False
        for e in in_flight:
            if e.invalid:
                cascading = True
                continue
            if (e.promises & finished_ids) or (cascading and e.chained):
                e.invalid = True
                cascading = True
                hit += 1
                continue
            if e.roots:
                # a valid sync-rooted decode batch: later chained
                # entries extend IT — the invalidation stops here
                cascading = False
        self.rebuilds += hit
        if hit:
            self.divergences += 1
        return hit

    @staticmethod
    def trim_overpromise(in_flight, frontiers) -> int:
        """Fused speculation (config.spec_fused): a spec block's chained
        descendants were scheduled off worst-case token-count UPPER
        bounds (every sub-step may emit spec_k+1 tokens); when the block
        collects, the committed counts are known and any over-promise is
        trimmed — each still-in-flight spec entry's per-link
        ``computed_before`` values rebase onto the committed frontier.

        This is pure host bookkeeping: the device already carries the
        ACTUAL frontier across blocks (the spec state in the handle), so
        the trim never touches token content — it tightens the
        allocation/feasibility arithmetic later ``schedule_chain``
        extensions run off these entries' items, exactly the
        invalidate-and-rebuild discipline's bookkeeping half.

        ``frontiers`` maps seq_id → committed ``num_computed_tokens``.
        Returns the total number of over-promised tokens trimmed.

        Descendant entries rebase by the SAME per-seq delta as the
        oldest in-flight entry: the over-promise accrued exactly once at
        the collected block's boundary, and the later entries' strides
        (scheduled relative to their parent) remain upper bounds of
        whatever the parent actually emits — collapsing them all onto
        the committed frontier would UNDER-bound page needs."""
        trimmed = 0
        applied = {}        # seq_id -> delta fixed at the oldest entry
        for e in in_flight:
            if e.invalid or not e.chained:
                continue
            chain = e.batch if isinstance(e.batch, list) else [e.batch]
            if not getattr(chain[0], "spec_block", False):
                continue
            deltas = {}
            for it in chain[0].items:
                sid = it.seq.seq_id
                if sid not in applied:
                    f = frontiers.get(sid)
                    if f is None:
                        continue
                    # anchor at the OLDEST entry even when the delta is
                    # zero — descendants must never re-derive their own
                    # (their elevation over the committed frontier is
                    # their parent's still-unknown emission, not an
                    # over-promise)
                    applied[sid] = max(0, it.computed_before - f)
                    trimmed += applied[sid]
                if applied[sid]:
                    deltas[sid] = applied[sid]
            if not deltas:
                continue
            for b in chain:
                for it in b.items:
                    d = deltas.get(it.seq.seq_id)
                    if d:
                        it.computed_before -= d
        return trimmed
