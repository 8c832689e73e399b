"""Paged KV-cache bookkeeping + hash-chain prefix cache.

Host-side page accounting for the HBM KV arrays owned by the ModelRunner. This
is the TPU-native analogue of the reference MemoryManager / PrefixMemoryManager
(/root/reference/gllm/memory_manager.py):

- pages are fixed-size slabs of KV slots; a sequence's ``page_table`` lists its
  page ids in order; flat KV slot = page_id * page_size + offset.
- page id 0 is reserved as the *dummy page*: padded batch rows and padded
  tokens write there (reference memory_manager.py:522 uses a dummy page the
  same way for CUDA-graph padding).
- prefix cache (reference memory_manager.py:858-1272): a chained per-page hash
  (O(page) to extend, :898-917) keys full pages for reuse; pages are
  ref-counted (:1250-1262); a cached page *survives refcount 0* and remains
  reusable until the allocator re-mints it for other content (:1254-1262); an
  8-token canary guards against hash collisions (:920-935).
- registration of freshly computed pages is decoupled from allocation and
  driven by the scheduler after outputs land (:1055-1079) so in-flight
  (placeholder) tokens never poison cache keys.

Differences from the reference are deliberate: there is no per-GPU process, so
one manager serves all local devices of a replica; KV sizing from live HBM
telemetry happens in the runner, which passes ``num_pages`` here.
"""

from __future__ import annotations

import hashlib
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from gllm_tpu.id_allocator import IDAllocator
from gllm_tpu.obs import metrics as obs
from gllm_tpu.obs.steptrace import TRACE
from gllm_tpu.sequence import Sequence
from gllm_tpu.utils import cdiv

# The hybrid models' second pool (docs/observability.md): working slots of
# recurrent state held by running sequences, and the slot maintenance the
# runner is asked for (snapshot / zero / restore, applied before a step).
_M_SSM_SLOTS = obs.gauge(
    "gllm_ssm_slots_in_use",
    "Working slots of recurrent state (GDN or Mamba-2 layers) held by "
    "running sequences (of max_num_seqs)")
_M_SWA_SLOTS = obs.gauge(
    "gllm_swa_ring_slots_in_use",
    "Window rings held by running sequences (of max_num_seqs): one a "
    "sequence in every windowed layer, each of swa_ring_len rows")
_M_SSM_INTENTS = obs.counter(
    "gllm_ssm_intents_total",
    "Recurrent-state slot maintenance handed to the runner, by kind",
    ("kind",))

# Prefix-cache metrics (docs/observability.md): lifetime token counters —
# rate(hit)/rate(query) gives the windowed hit rate in any scraper; the
# scheduler's gllm_prefix_cache_hit_rate gauge mirrors the lifetime ratio.
_M_PFX_QUERY = obs.counter("gllm_prefix_cache_query_tokens_total",
                           "prompt tokens probed against the prefix cache")
_M_PFX_HIT = obs.counter("gllm_prefix_cache_hit_tokens_total",
                         "prompt tokens served from cached KV pages")

# Tokens stored per cached page to verify against hash collisions
# (reference memory_manager.py:920-935).
_CANARY_TOKENS = 8

# Chain-parent map bound (digest -> predecessor digest, LRU): the lower
# prefix tiers (gllm_tpu/kvstore) use the edge for read-ahead; a capped
# map loses only the oldest edges (a lost edge costs a prefetch, never
# correctness).
_PARENT_CAP = 1 << 16


def _chain_hash(prev: bytes, token_ids: List[int], extra_key: bytes = b"") -> bytes:
    h = hashlib.blake2b(digest_size=16)
    h.update(prev)
    h.update(extra_key)
    h.update(b"".join(t.to_bytes(4, "little", signed=True) for t in token_ids))
    return h.digest()


def prefix_digests(cache_token_ids, prompt_len: int, page_size: int,
                   extra_key: bytes = b"") -> List[Tuple[bytes, list]]:
    """Chained page digests over the cacheable prompt prefix — only whole
    pages, leaving >= 1 token to compute (the match_prefix guarantee).
    Replica-independent: cache-aware DP routing computes this ONCE and
    probes every replica's maps with it."""
    out: List[Tuple[bytes, list]] = []
    digest = b"root"
    for i in range((prompt_len - 1) // page_size):
        s = i * page_size
        tokens = cache_token_ids[s:s + page_size]
        digest = _chain_hash(digest, tokens, extra_key)
        out.append((digest, tokens))
    return out


class MemoryManager:
    """Plain paged allocator (no prefix reuse).

    For hybrid (GDN) models it additionally owns the SSM slot allocators
    (reference SSMSegment, memory_manager.py:87-255): one *working* slot
    per live request plus an optional *snapshot* range for cached-prefix
    state. The device arrays live with the runner; this class only hands
    out slot ids and records copy/zero intents the runner applies before
    its next step (single-controller, so FIFO intent order is exact).
    Slot 0 is the padding dummy in both ranges.
    """

    def __init__(self, num_pages: int, page_size: int,
                 ssm_working_slots: int = 0, ssm_snapshot_slots: int = 0,
                 ssm_chunk: int = 64):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (one is the dummy page)")
        self.page_size = page_size
        self.num_pages = num_pages
        self.dummy_page = 0
        # Page 0 reserved for padding writes.
        self.allocator = IDAllocator(num_pages - 1, start=1)
        self.ref_count: Dict[int, int] = {}
        # Host-RAM KV tier (gllm_tpu/kvswap.KVSwapManager) — attached by
        # the engine when a host pool is configured; None keeps every
        # code path byte-for-byte the pre-offload behavior.
        self.swap = None
        # int8 KV cache (kv_cache_dtype=int8): minted pages queue a
        # device-side SCALE RESET (drained by the runner before the next
        # step, ordered between the host tier's gathers and scatters) so
        # a recycled page quantizes like a fresh one — quantization
        # never depends on page-reuse history, and the running absmax
        # cannot ratchet across tenants. Off (flag False) this list
        # stays empty and no reset program ever dispatches.
        self.track_scale_resets = False
        self.scale_resets: List[int] = []

        self.ssm_working_slots = ssm_working_slots
        self.ssm_snapshot_slots = ssm_snapshot_slots
        # tokens in a chunk of the recurrent layers' chunked rule
        # (ModelConfig.ssm_chunk; 0 where the slot state has none): the
        # scheduler's cap on multi-token rows
        self.ssm_chunk = ssm_chunk
        # the gauge the working slots are counted in (the engine names
        # _M_SWA_SLOTS where the slots are window rings)
        self.slot_gauge = _M_SSM_SLOTS
        if ssm_working_slots:
            self.ssm_alloc: Optional[IDAllocator] = IDAllocator(
                ssm_working_slots, start=1)
            self.ssm_snap_alloc: Optional[IDAllocator] = (
                IDAllocator(ssm_snapshot_slots,
                            start=1 + ssm_working_slots)
                if ssm_snapshot_slots else None)
        else:
            self.ssm_alloc = None
            self.ssm_snap_alloc = None
        # ("snapshot", work, snap) | ("zero", slot, 0) | ("restore", snap,
        # work) — drained by the runner, applied snapshot→zero→restore.
        self.ssm_intents: List[Tuple[str, int, int]] = []
        self._snap_free_pending: List[int] = []

    # ---- SSM slots (hybrid models) ----------------------------------------

    @property
    def use_ssm(self) -> bool:
        return self.ssm_alloc is not None

    def can_admit_seq(self) -> bool:
        return self.ssm_alloc is None or self.ssm_alloc.num_free > 0

    def prepare_seq(self, seq: Sequence) -> None:
        """Allocate per-seq auxiliary state at admission (waiting→running):
        a fresh (zeroed-on-free) SSM working slot, plus the prefix-cache
        state restore recorded by match_prefix."""
        if self.ssm_alloc is None:
            return
        if getattr(seq, "ssm_slot", None) is None:
            seq.ssm_slot = self.ssm_alloc.allocate()
            self.slot_gauge.set(self.ssm_working_slots
                                - self.ssm_alloc.num_free)
        snap = getattr(seq, "_ssm_restore_snap", None)
        if snap is not None:
            self.ssm_intents.append(("restore", snap, seq.ssm_slot))
            seq._ssm_restore_snap = None

    def _free_ssm(self, seq: Sequence) -> None:
        slot = getattr(seq, "ssm_slot", None)
        if slot is not None:
            # Drop pending restores INTO this slot (e.g. a spec-decode
            # rollback for a seq preempted before the drain): the slot may
            # be reallocated before the intents apply, and restores run
            # AFTER zeros — a stale one would clobber the new tenant.
            self.ssm_intents = [t for t in self.ssm_intents
                                if not (t[0] == "restore"
                                        and t[2] == slot)]
            self.ssm_intents.append(("zero", slot, 0))
            self.ssm_alloc.free(slot)
            seq.ssm_slot = None
            self.slot_gauge.set(self.ssm_working_slots
                                - self.ssm_alloc.num_free)

    def free_snap_after_drain(self, snap: int) -> None:
        """Return a snapshot slot to the pool only once the currently
        pending intents have been drained. A pending ``restore`` may still
        read the slot; an immediate free could let a NEW ``snapshot``
        claim it in the same drain batch — and snapshots apply BEFORE
        restores, so the restore would read the new tenant's state."""
        self._snap_free_pending.append(snap)

    def drain_ssm_intents(self) -> List[Tuple[str, int, int]]:
        out, self.ssm_intents = self.ssm_intents, []
        for kind, _, _ in out:
            _M_SSM_INTENTS.inc(kind=kind)
        pend, self._snap_free_pending = self._snap_free_pending, []
        for snap in pend:
            self.ssm_snap_alloc.free(snap)
        return out

    # ---- stats ------------------------------------------------------------

    @property
    def num_free_pages(self) -> int:
        return self.allocator.num_free

    @property
    def free_ratio(self) -> float:
        return self.allocator.num_free / self.allocator.num_total

    # ---- allocation -------------------------------------------------------

    def pages_needed(self, seq: Sequence, num_new_tokens: int) -> int:
        return cdiv(seq.num_computed_tokens + num_new_tokens,
                    self.page_size) - len(seq.page_table)

    def can_allocate(self, num_pages: int) -> bool:
        return self.num_free_pages >= num_pages

    def _mint_page(self) -> int:
        page = self.allocator.allocate()
        if self.track_scale_resets:
            self.scale_resets.append(page)
        return page

    def drain_scale_resets(self) -> List[int]:
        out, self.scale_resets = self.scale_resets, []
        return out

    def allocate_seq_pages(self, seq: Sequence, num_new_tokens: int) -> None:
        """Extend ``seq.page_table`` to cover computed+num_new_tokens tokens.

        Caller must have checked ``can_allocate(pages_needed(...))``.
        """
        for _ in range(self.pages_needed(seq, num_new_tokens)):
            page = self._mint_page()
            self.ref_count[page] = 1
            seq.page_table.append(page)

    def match_prefix(self, seq: Sequence) -> int:
        """Prefix-cache hook; no-op without prefix caching."""
        return 0

    def peek_prefix(self, cache_token_ids, prompt_len: int) -> int:
        """Read-only prefix-match estimate; 0 without prefix caching."""
        return 0

    def peek_digests(self, digests) -> int:
        """Read-only prefix-match estimate; 0 without prefix caching."""
        return 0

    def register_computed_pages(self, seq: Sequence) -> None:
        """Prefix-cache hook; no-op without prefix caching."""

    def free_seq(self, seq: Sequence) -> None:
        for page in seq.page_table:
            self._release_page(page)
        seq.page_table = []
        seq._pt_np = None      # see Sequence.preempt: shrink ⇒ drop cache
        if self.swap is not None and seq.swap_host_pages:
            # SWAPPED seq freed without resuming (abort / shutdown):
            # return its host-tier pages too
            self.swap.release_seq(seq)
        self._free_ssm(seq)

    def _release_page(self, page: int) -> None:
        self.ref_count[page] -= 1
        if self.ref_count[page] == 0:
            del self.ref_count[page]
            self.allocator.free(page)


class PrefixMemoryManager(MemoryManager):
    """Paged allocator with page-granular hash-keyed KV reuse."""

    def __init__(self, num_pages: int, page_size: int, **ssm_kwargs):
        super().__init__(num_pages, page_size, **ssm_kwargs)
        # hash digest -> page id (only fully computed pages).
        self.hash_to_page: Dict[bytes, int] = {}
        # page id -> (hash digest, canary token ids)
        self.page_meta: Dict[int, Tuple[bytes, Tuple[int, ...]]] = {}
        # per-seq chained hash of the last registered page, for O(page)
        # extension (reference memory_manager.py:898-917 caches the chain on
        # the sequence; we key it by seq id here).
        self._seq_chain: Dict[int, Tuple[int, bytes]] = {}  # seq_id -> (num_pages_hashed, digest)
        # hybrid: page id → SSM snapshot slot holding the state at that
        # page's boundary (reference page2ssm_snapshot; entries here are
        # always valid — slots are allocated at capture time, not
        # pre-reserved).
        self.page2snap: Dict[int, int] = {}
        self.hit_tokens = 0
        self.query_tokens = 0
        # digest -> chain-predecessor digest (None for a chain head),
        # LRU-capped; consumed by the host spill so demoted pages carry
        # their read-ahead edge down the tier stack.
        self._digest_parent: "OrderedDict[bytes, Optional[bytes]]" = \
            OrderedDict()

    def _note_parent(self, digest: bytes,
                     parent: Optional[bytes]) -> None:
        self._digest_parent[digest] = parent
        self._digest_parent.move_to_end(digest)
        while len(self._digest_parent) > _PARENT_CAP:
            self._digest_parent.popitem(last=False)

    # A page in the free list may still carry cache metadata; minting it for
    # new content must drop the stale key (reference :1254-1262).
    def _mint_page(self) -> int:
        page = super()._mint_page()   # keeps the int8 scale-reset queue
        meta = self.page_meta.pop(page, None)
        if meta is not None:
            digest, canary = meta
            if self.hash_to_page.get(digest) == page:
                del self.hash_to_page[digest]
                if self.swap is not None:
                    # this was the canonical copy of its content — spill
                    # it to the host tier instead of losing it (eviction
                    # becomes a transfer, not a future re-prefill)
                    self.swap.spill_prefix(
                        page, digest, canary,
                        parent=self._digest_parent.get(digest))
        self._release_snapshot_for(page)
        return page

    def _restore_from_host(self, digest: bytes, tokens) -> Optional[int]:
        """Host-tier prefix probe for match_prefix: on a (canary-verified)
        hit, mint a fresh device page, queue the host->device restore,
        and re-register the digest device-side. None = miss / no device
        page to restore into."""
        if self.swap is None:
            return None
        host_page = self.swap.match_host_prefix(digest, tokens)
        if host_page is None:
            return None
        if not self.can_allocate(1):
            self.swap.release_probe_pin(host_page)
            return None
        # the probe pin guards host_page across this mint: the mint's
        # own spill may allocate (and evict) in the host pool, and the
        # hit must not be its victim
        page = self._mint_page()
        self.swap.restore_prefix(host_page, page)   # takes its own pin
        self.swap.release_probe_pin(host_page)
        self.hash_to_page[digest] = page
        self.page_meta[page] = (digest, tuple(tokens[:_CANARY_TOKENS]))
        return page

    def _release_snapshot_for(self, page: int) -> None:
        """Drop the SSM snapshot of a page's previous tenant (reference
        memory_manager.py _release_snapshot_for)."""
        snap = self.page2snap.pop(page, None)
        if snap is not None:
            self.ssm_snap_alloc.free(snap)

    def _page_tokens(self, seq: Sequence, page_idx: int) -> List[int]:
        s = page_idx * self.page_size
        # cache_token_ids splices multimodal content-hash pad ids over
        # visual spans (Sequence.cache_token_ids).
        return seq.cache_token_ids[s:s + self.page_size]

    def _probe_page(self, digest: bytes, tokens) -> Optional[int]:
        """Cached page id for this chained digest, or None (missing /
        canary mismatch = hash collision). Shared by the claiming walk
        (match_prefix) and the read-only routing peek so the two can
        never disagree on what counts as a hit."""
        page = self.hash_to_page.get(digest)
        if page is None:
            return None
        _, canary = self.page_meta[page]
        if tuple(tokens[:_CANARY_TOKENS]) != canary:
            return None
        return page

    def peek_digests(self, digests) -> int:
        """Read-only estimate of the tokens ``match_prefix`` would claim,
        given ``prefix_digests(...)`` output — no refcounts/claims. Used
        by cache-aware DP routing (the frontend hashes the prompt ONCE
        and probes every replica); the hybrid SSM-snapshot rollback
        refinement is deliberately skipped (this is a routing heuristic,
        not a reservation)."""
        matched = 0
        for digest, tokens in digests:
            if self._probe_page(digest, tokens) is None:
                break
            matched += 1
        return matched * self.page_size

    def peek_prefix(self, cache_token_ids, prompt_len: int,
                    extra_key: bytes = b"") -> int:
        return self.peek_digests(prefix_digests(
            cache_token_ids, prompt_len, self.page_size, extra_key))

    def match_prefix(self, seq: Sequence, extra_key: bytes = b"") -> int:
        """Claim cached pages covering the longest matching prompt prefix.

        Returns the number of cached tokens (always < prompt_len so at least
        one token is computed to produce logits — same guarantee the reference
        keeps). Claimed pages get ref_count++ and enter seq.page_table.
        """
        assert seq.num_computed_tokens == 0 and not seq.page_table
        t_probe = time.monotonic()
        self.query_tokens += seq.prompt_len
        _M_PFX_QUERY.inc(seq.prompt_len)
        matched_digest = b"root"
        matched = 0
        digests: List[bytes] = []
        page_tiers: List[str] = []   # which tier served each claimed page
        for digest, tokens in prefix_digests(
                seq.cache_token_ids, seq.prompt_len, self.page_size,
                extra_key):
            self._note_parent(digest,
                              matched_digest if digests else None)
            page = self._probe_page(digest, tokens)
            tier = "hbm" if page is not None else None
            if page is None:
                # HBM miss → lower tiers (gllm_tpu/kvswap + kvstore,
                # probe order host → disk → peer): a hit mints a fresh
                # device page and queues the restore copy, which the
                # runner drains before the step that reads it.
                page = self._restore_from_host(digest, tokens)
                if page is not None:
                    tier = getattr(self.swap, "last_hit_tier",
                                   None) or "host"
            if page is None:
                break
            if self.allocator.is_free(page):
                self.allocator.allocate_id(page)
            self.ref_count[page] = self.ref_count.get(page, 0) + 1
            seq.page_table.append(page)
            matched += 1
            matched_digest = digest
            digests.append(digest)
            page_tiers.append(tier)
        if self.use_ssm and matched:
            # Hybrid: a KV hit is only usable up to the last page whose SSM
            # snapshot exists — roll the claim back to that boundary
            # (reference _rollback_to_last_ssm_hit). Without any snapshot,
            # the whole hit is dropped: replaying from token 0 with a
            # claimed-but-stateless prefix would corrupt the recurrence.
            keep = matched
            while keep > 0 and seq.page_table[keep - 1] not in self.page2snap:
                keep -= 1
            for page in seq.page_table[keep:]:
                self._release_page(page)
            del seq.page_table[keep:]
            seq._pt_np = None  # see Sequence.preempt: shrink ⇒ drop cache
            if keep:
                matched_digest = digests[keep - 1]
                seq._ssm_restore_snap = self.page2snap[
                    seq.page_table[keep - 1]]
            matched = keep
        seq.num_computed_tokens = matched * self.page_size
        seq.num_cached_tokens = seq.num_computed_tokens
        if matched:
            self._seq_chain[seq.seq_id] = (matched, matched_digest)
        self.hit_tokens += seq.num_computed_tokens
        _M_PFX_HIT.inc(seq.num_computed_tokens)
        # Per-tier attribution on the steptrace ring: one event per
        # admission probe; steptrace.summarize() reduces a window to a
        # per-tier prefix hit rate (docs/observability.md). The SSM
        # rollback above trimmed the claim, so count only kept pages.
        pages: Dict[str, int] = {}
        for t in page_tiers[:matched]:
            pages[t] = pages.get(t, 0) + 1
        TRACE.record("prefix", query_tokens=seq.prompt_len,
                     hit_tokens=seq.num_computed_tokens, pages=pages,
                     ms=round((time.monotonic() - t_probe) * 1e3, 3))
        return seq.num_computed_tokens

    def register_computed_pages(self, seq: Sequence, extra_key: bytes = b"") -> None:
        """Register hashes for fully computed pages of ``seq``.

        Called by the scheduler *after* outputs for a step landed, so only real
        (non-placeholder) tokens are ever hashed (reference :1055-1079).

        Hybrid: when the just-computed range ends exactly at a page
        boundary (and the seq has no chained step in flight that would have
        advanced the device state past it), the working SSM state IS the
        state at that boundary — capture it into a snapshot slot tied to
        the page (reference _maybe_snapshot_state, qwen3_5.py:307-360).
        """
        full_pages = seq.num_computed_tokens // self.page_size
        n_hashed, digest = self._seq_chain.get(seq.seq_id, (0, b"root"))
        for i in range(n_hashed, min(full_pages, len(seq.page_table))):
            tokens = self._page_tokens(seq, i)
            parent = digest if digest != b"root" else None
            digest = _chain_hash(digest, tokens, extra_key)
            self._note_parent(digest, parent)
            page = seq.page_table[i]
            existing = self.hash_to_page.get(digest)
            if existing is None:
                self.hash_to_page[digest] = page
                self.page_meta[page] = (digest, tuple(tokens[:_CANARY_TOKENS]))
                if (self.ssm_snap_alloc is not None
                        and (i + 1) * self.page_size
                        == seq.num_computed_tokens
                        and not seq.num_in_flight
                        and getattr(seq, "ssm_slot", None) is not None
                        and page not in self.page2snap
                        and self.ssm_snap_alloc.num_free > 0):
                    snap = self.ssm_snap_alloc.allocate()
                    self.page2snap[page] = snap
                    self.ssm_intents.append(("snapshot", seq.ssm_slot,
                                             snap))
            n_hashed = i + 1
        self._seq_chain[seq.seq_id] = (n_hashed, digest)

    def free_seq(self, seq: Sequence) -> None:
        super().free_seq(seq)
        self._seq_chain.pop(seq.seq_id, None)

    @property
    def cache_hit_rate(self) -> float:
        return self.hit_tokens / self.query_tokens if self.query_tokens else 0.0


def make_memory_manager(num_pages: int, page_size: int,
                        enable_prefix_caching: bool,
                        ssm_working_slots: int = 0,
                        ssm_snapshot_slots: int = 0,
                        ssm_chunk: int = 64) -> MemoryManager:
    cls = PrefixMemoryManager if enable_prefix_caching else MemoryManager
    return cls(num_pages, page_size, ssm_working_slots=ssm_working_slots,
               ssm_snapshot_slots=ssm_snapshot_slots, ssm_chunk=ssm_chunk)
