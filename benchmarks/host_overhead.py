"""Control-plane microbenchmark: host-side cost per engine step.

The reference documents its host-path micro-optimizations with measured
numbers (SURVEY §6: `_cal_block_table` 3.2 ms → <1 ms, zmq sender 205 µs
→ 1 µs; reference input_data.py:436-533 commented perf history). This is
the counterpart for our control plane — it measures, WITHOUT any device
dispatch, the per-step host cost of:

- ``schedule``:   Scheduler.schedule_once + process_output over a steady
                  decode batch (paged bookkeeping, finish checks)
- ``prepare``:    BatchBuilder build (padding, buckets, numpy fills) for
                  that batch — the jit program's host-side input path
- ``prefix``:     PrefixMemoryManager.match_prefix + free on a warm
                  cache (chained hashing + page claim/release; the
                  register write path is excluded)
- ``route``:      cache-aware DP routing probe (prefix_digests +
                  peek_digests over 2 replicas)

On TPU the step loop overlaps host work with device compute (async
dispatch / chained decode), so these costs matter when they exceed the
device step time — the numbers here say how far away that is. Prints one
JSON line: microseconds per operation.

Usage: python benchmarks/host_overhead.py [--seqs 64] [--iters 50]
(CPU-only: pure host code, no jax device work.)
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _time_us(fn, iters):
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return 1e6 * (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=int, default=64)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--prompt-len", type=int, default=256)
    args = ap.parse_args()

    from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
    from gllm_tpu.memory_manager import (make_memory_manager,
                                         prefix_digests)
    from gllm_tpu.sampling_params import SamplingParams
    from gllm_tpu.scheduler import Scheduler
    from gllm_tpu.sequence import Sequence

    S, P = args.seqs, args.prompt_len
    cfg = EngineConfig(
        max_model_len=P + 512, max_num_seqs=S,
        scheduler=SchedulerConfig(max_decode_seqs=S,
                                  max_prefill_tokens=2048),
        cache=CacheConfig(page_size=16, num_pages=S * (P + 512) // 16
                          + S))

    def make_engine():
        mm = make_memory_manager(cfg.cache.num_pages, cfg.cache.page_size,
                                 False)
        sched = Scheduler(cfg, mm)
        for i in range(S):
            # max_tokens must FIT max_model_len: adaptive admission
            # reserves est_extra = max_tokens * new_token_ratio pages per
            # seq, and an absurd cap starves every admission after the
            # first (the batch silently degenerates to 1 seq)
            seq = Sequence(i, list(range(1, P + 1)),
                           SamplingParams(temperature=0.0,
                                          max_tokens=400,
                                          ignore_eos=True))
            sched.add_seq(seq)
        # run prefill to steady decode state: EVERY seq admitted and at
        # its decode boundary (running alone isn't enough — chunked
        # admission can leave seqs waiting)
        while True:
            b = sched.schedule_once()
            assert b is not None
            sched.process_output(b, [7] * len(b.items), None)
            if (not sched.waiting and len(sched.running) == S
                    and all(s.num_remaining_tokens == 1
                            for s in sched.running)):
                return sched

    sched = make_engine()

    # ---- schedule: one decode step of bookkeeping ------------------------
    def one_step():
        b = sched.schedule_once()
        assert b is not None and len(b.items) == S, \
            "decode batch degenerated — raise max_tokens headroom"
        sched.process_output(b, [7] * len(b.items), None)

    one_step()                                     # warm
    schedule_us = _time_us(one_step, args.iters)

    # ---- prepare: batch build for the same decode batch ------------------
    from gllm_tpu.runner.prepare import BatchBuilder
    bb = BatchBuilder(cfg, cfg.cache.page_size, vocab_size=32000,
                      hidden_size=1024)
    batch = sched.schedule_once()
    from gllm_tpu.batching import pack

    def build():
        pack(bb.build(batch)[0], (1,))

    build()
    prepare_us = _time_us(build, args.iters)
    sched.process_output(batch, [7] * len(batch.items), None)

    # ---- prefix: warm-cache match + register -----------------------------
    pmm = make_memory_manager(cfg.cache.num_pages, cfg.cache.page_size,
                              True)
    warm = Sequence(10_000, list(range(1, P + 1)),
                    SamplingParams(temperature=0.0, max_tokens=4))
    pmm.allocate_seq_pages(warm, P)
    warm.num_computed_tokens = P
    pmm.register_computed_pages(warm)

    probe_ids = list(range(1, P + 1))
    probes = iter([Sequence(10_001 + i, list(probe_ids),
                            SamplingParams(temperature=0.0, max_tokens=4))
                   for i in range(args.iters + 1)])

    def match():
        probe = next(probes)
        pmm.match_prefix(probe)
        pmm.free_seq(probe)

    match()
    prefix_us = _time_us(match, args.iters)

    # ---- route: cache-aware DP probe over 2 replicas ---------------------
    ids = list(range(1, P + 1))

    def route():
        digests = prefix_digests(ids, P, cfg.cache.page_size)
        pmm.peek_digests(digests)
        pmm.peek_digests(digests)

    route()
    route_us = _time_us(route, args.iters)

    print(json.dumps({
        "metric": "host_step_overhead_us",
        "value": round(schedule_us + prepare_us, 1),
        "unit": "us/step",
        "detail": {
            "seqs": S,
            "schedule_us": round(schedule_us, 1),
            "prepare_us": round(prepare_us, 1),
            "prefix_match_us": round(prefix_us, 1),
            "dp_route_probe_us": round(route_us, 1),
            "per_seq_us": round((schedule_us + prepare_us) / S, 2),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
