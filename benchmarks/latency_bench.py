"""Single-process serving-latency benchmark: TTFT / TPOT / ITL on chip.

Counterpart of the reference's online-serving latency measurement
(reference docs/encoder_disaggregation_usage.md:285-315 methodology:
streaming requests against a live endpoint, percentile TTFT/TPOT): boots
the SAME flagship dummy model bench.py uses, serves it over the stdlib
HTTP server IN THIS PROCESS (a chip belongs to one process at a time),
and drives Poisson-arrival streaming
completions from client threads. Prints ONE JSON line:

  {"metric": "ttft_p50_ms", "value": ..., "unit": "ms",
   "vs_baseline": ..., "detail": {summarize(...) fields}}

vs_baseline compares the TTFT p50 against BASELINE.md's <500 ms serving
target (value > 0 means faster than target).

Usage (on chip):   python benchmarks/latency_bench.py
CPU smoke:         python benchmarks/latency_bench.py --tiny
"""

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TTFT_TARGET_MS = 500.0     # BASELINE.md: p50 TTFT < 500 ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CPU smoke test (small model/workload)")
    ap.add_argument("--num-prompts", type=int, default=None)
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=256)
    ap.add_argument("--output-len", type=int, default=64)
    ap.add_argument("--request-rate", type=float, default=8.0,
                    help="Poisson arrival rate (req/s); inf = closed loop")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    import jax
    if args.tiny:
        jax.config.update("jax_platforms", "cpu")

    import bench
    from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
    from gllm_tpu.engine.llm import LLM
    from gllm_tpu.entrypoints.api_server import serve
    from gllm_tpu.models.config import ModelConfig

    if args.tiny:
        model_cfg = ModelConfig(
            architecture="LlamaForCausalLM", vocab_size=2048,
            hidden_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
            head_dim=32, intermediate_size=256, max_position=512)
        engine_cfg = EngineConfig(
            load_format="dummy", dtype="float32", max_model_len=512,
            max_num_seqs=32,
            scheduler=SchedulerConfig(max_prefill_tokens=128,
                                      max_decode_seqs=16),
            cache=CacheConfig(page_size=4, num_pages=512))
        n_prompts = args.num_prompts or 8
        plen, olen = 32, 8
    else:
        model_cfg = bench.flagship_model_cfg()
        # conservative serving loop (the ladder's proven-first rung):
        # no overlap chaining so TTFT reflects plain admission latency
        engine_cfg = EngineConfig(
            load_format="dummy", dtype="bfloat16", max_model_len=2048,
            max_num_seqs=128,
            scheduler=SchedulerConfig(max_prefill_tokens=1024,
                                      max_decode_seqs=128),
            cache=CacheConfig(page_size=16, num_pages=8192))
        n_prompts = args.num_prompts or 48
        plen, olen = args.prompt_len, args.output_len

    t0 = time.monotonic()
    llm = LLM(config=engine_cfg, model_cfg=model_cfg)
    print(f"[latency_bench] engine up in {time.monotonic() - t0:.1f}s",
          file=sys.stderr, flush=True)
    httpd = serve(llm, "127.0.0.1", 0)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    from benchmarks.backend_request_func import run_requests, summarize
    rng = np.random.default_rng(args.seed)
    vocab = model_cfg.vocab_size
    # payloads materialized up front (thread-safety + seeded reproduction)
    payloads = [{"prompt": rng.integers(1, vocab, plen).tolist(),
                 "max_tokens": olen, "temperature": 0,
                 "ignore_eos": True} for _ in range(n_prompts)]

    # warmup pass: the SAME workload at the same concurrency, so every
    # (token-bucket, seq-bucket) program the measured pass hits is
    # compiled before timing starts (bench.py warms the same way)
    t0 = time.monotonic()
    warm, _ = run_requests("127.0.0.1", port, payloads, args.concurrency,
                           args.request_rate, seed=args.seed)
    n_ok = sum(1 for r in warm if r is not None and r.success)
    print(f"[latency_bench] warmup pass: {n_ok}/{n_prompts} ok in "
          f"{time.monotonic() - t0:.1f}s", file=sys.stderr, flush=True)
    assert n_ok == n_prompts, [r.error for r in warm if not r.success][:2]

    results, wall = run_requests("127.0.0.1", port, payloads,
                                 args.concurrency, args.request_rate,
                                 seed=args.seed)

    summary = summarize([r for r in results if r is not None], wall)
    if summary["failed"] or summary["completed"] != n_prompts:
        # a post-warmup wedge must FAIL the step, not report 0.0 ms
        errs = sorted({r.error for r in results
                       if r is not None and not r.success})[:3]
        print(f"[latency_bench] measured pass failed: {summary['failed']}"
              f" errors, e.g. {errs}", file=sys.stderr, flush=True)
        sys.exit(1)
    ttft_p50 = summary["ttft_ms"].get("p50", 0.0)
    httpd.shutdown()
    llm_engine = httpd.state.engine
    llm_engine.shutdown()
    print(json.dumps({
        "metric": "ttft_p50_ms",
        "value": ttft_p50,
        "unit": "ms",
        # >0 ⇔ faster than the BASELINE 500 ms serving target
        "vs_baseline": round((TTFT_TARGET_MS - ttft_p50)
                             / TTFT_TARGET_MS, 4) if ttft_p50 else None,
        "detail": summary,
    }), flush=True)


if __name__ == "__main__":
    main()
