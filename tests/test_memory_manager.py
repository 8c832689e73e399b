"""Unit tests: IDAllocator, paged MemoryManager, prefix cache."""

import pytest

from gllm_tpu.id_allocator import IDAllocator
from gllm_tpu.memory_manager import MemoryManager, PrefixMemoryManager
from gllm_tpu.sampling_params import SamplingParams
from gllm_tpu.sequence import Sequence


def make_seq(seq_id, n_tokens, start=0):
    return Sequence(seq_id, list(range(start, start + n_tokens)),
                    SamplingParams(max_tokens=8))


class TestIDAllocator:
    def test_fifo(self):
        a = IDAllocator(4)
        assert [a.allocate() for _ in range(4)] == [0, 1, 2, 3]
        with pytest.raises(RuntimeError):
            a.allocate()
        a.free(2)
        a.free(0)
        assert a.allocate() == 2  # FIFO: freed first, reused first
        assert a.allocate() == 0

    def test_targeted(self):
        a = IDAllocator(4, start=10)
        a.allocate_id(12)
        assert not a.is_free(12)
        assert a.num_free == 3
        a.free(12)
        with pytest.raises(RuntimeError):
            a.free(12)


class TestMemoryManager:
    def test_alloc_free(self):
        mm = MemoryManager(num_pages=9, page_size=4)  # 8 usable
        seq = make_seq(0, 10)
        assert mm.pages_needed(seq, 10) == 3
        mm.allocate_seq_pages(seq, 10)
        assert len(seq.page_table) == 3
        assert mm.num_free_pages == 5
        assert mm.dummy_page not in seq.page_table
        # decode growth: token 11,12 fit page 3; token 13 needs a new page
        seq.num_computed_tokens = 10
        assert mm.pages_needed(seq, 2) == 0
        assert mm.pages_needed(seq, 3) == 1
        mm.free_seq(seq)
        assert mm.num_free_pages == 8

    def test_exhaustion(self):
        mm = MemoryManager(num_pages=3, page_size=4)
        seq = make_seq(0, 8)
        assert not mm.can_allocate(mm.pages_needed(seq, 9))
        assert mm.can_allocate(mm.pages_needed(seq, 8))


class TestPrefixCache:
    def test_hit_after_registration(self):
        mm = PrefixMemoryManager(num_pages=32, page_size=4)
        a = make_seq(0, 14)
        assert mm.match_prefix(a) == 0
        mm.allocate_seq_pages(a, 14)
        a.num_computed_tokens = 14
        mm.register_computed_pages(a)  # pages 0..2 full (12 tokens)

        b = make_seq(1, 14)  # identical prompt
        hit = mm.match_prefix(b)
        assert hit == 12  # 3 full pages; page 4 partial not cacheable
        assert b.page_table == a.page_table[:3]
        assert b.num_computed_tokens == 12
        # shared pages ref-counted
        assert mm.ref_count[a.page_table[0]] == 2

    def test_whole_prompt_cached_leaves_one_token(self):
        mm = PrefixMemoryManager(num_pages=32, page_size=4)
        a = make_seq(0, 8)
        mm.allocate_seq_pages(a, 8)
        a.num_computed_tokens = 8
        mm.register_computed_pages(a)
        b = make_seq(1, 8)
        # prompt is exactly 2 pages but only page 0 may be reused: at least
        # one token must be computed to produce logits.
        assert mm.match_prefix(b) == 4

    def test_cache_survives_refcount_zero_until_remint(self):
        mm = PrefixMemoryManager(num_pages=8, page_size=4)  # 7 usable
        a = make_seq(0, 9)
        mm.allocate_seq_pages(a, 9)
        a.num_computed_tokens = 9
        mm.register_computed_pages(a)
        pages_a = list(a.page_table)
        mm.free_seq(a)
        assert mm.num_free_pages == 7
        # Still hits: freed pages keep their cache identity.
        b = make_seq(1, 9)
        assert mm.match_prefix(b) == 8
        assert b.page_table == pages_a[:2]
        mm.free_seq(b)

        # Exhaust the allocator with unrelated content → pages re-minted,
        # stale keys dropped.
        c = make_seq(2, 28, start=1000)
        mm.allocate_seq_pages(c, 28)
        d = make_seq(3, 9)
        assert mm.match_prefix(d) == 0

    def test_divergent_prompt_partial_hit(self):
        mm = PrefixMemoryManager(num_pages=32, page_size=4)
        a = make_seq(0, 12)
        mm.allocate_seq_pages(a, 12)
        a.num_computed_tokens = 12
        mm.register_computed_pages(a)
        b = Sequence(1, list(range(8)) + [99, 98, 97, 96, 95],
                     SamplingParams())
        assert mm.match_prefix(b) == 8  # first two pages match, third diverges

    def test_decode_pages_registered_incrementally(self):
        mm = PrefixMemoryManager(num_pages=32, page_size=4)
        a = make_seq(0, 6)
        mm.allocate_seq_pages(a, 6)
        a.num_computed_tokens = 6
        mm.register_computed_pages(a)
        # decode 3 tokens → 9 total, page 1 (tokens 4..7) becomes full
        for t in (100, 101, 102):
            a.append_token(t)
        mm.allocate_seq_pages(a, 3)
        a.num_computed_tokens = 9
        mm.register_computed_pages(a)
        b = Sequence(1, list(range(6)) + [100, 101, 102], SamplingParams())
        assert mm.match_prefix(b) == 8


def test_pt_cache_invalidated_on_preempt_and_rollback():
    """The builder's cached np page-table row must never survive a shrink:
    a same-length regrow with different page ids (preempt → re-admit)
    would otherwise write KV into pages owned by other sequences."""
    import jax
    import numpy as np

    from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
    from gllm_tpu.runner.prepare import BatchBuilder
    from gllm_tpu.sampling_params import SamplingParams
    from gllm_tpu.scheduler import ScheduledBatch, ScheduledSeq
    from gllm_tpu.sequence import Sequence

    cfg = EngineConfig(max_model_len=64, max_num_seqs=8,
                       scheduler=SchedulerConfig(max_prefill_tokens=32,
                                                 max_decode_seqs=8),
                       cache=CacheConfig(page_size=4, num_pages=32))
    b = BatchBuilder(cfg, 4, vocab_size=128)
    seq = Sequence(0, [1, 2, 3, 4, 5, 6, 7], SamplingParams(max_tokens=4))
    seq.page_table = [3, 4]
    seq.num_computed_tokens = 0
    sb = ScheduledBatch([ScheduledSeq(seq, 7, 0)])
    batch, _, _ = b.build(sb)
    assert list(np.asarray(batch.attn.page_table)[0][:2]) == [3, 4]

    seq.preempt()
    seq.page_table = [9, 10]          # same length, different pages
    seq.num_computed_tokens = 0
    batch, _, _ = b.build(ScheduledBatch([ScheduledSeq(seq, 7, 0)]))
    assert list(np.asarray(batch.attn.page_table)[0][:2]) == [9, 10]
