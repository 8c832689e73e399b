#!/usr/bin/env python
"""Headline benchmark: synthetic-ShareGPT offline throughput.

Mirrors the reference's measurement harness
(/root/reference/examples/batch_inference.py:56-74 — offline ShareGPT
reqs/s + output tok/s) with a synthetic, zero-egress workload: a
Llama-3.2-1B-shaped dummy-weight model served by the full engine
(continuous batching + chunked prefill + paged KV) on one chip.

Prints exactly ONE JSON line to stdout:
  {"metric": "sharegpt_output_tok_s_per_chip", "value": N, "unit": "tok/s",
   "vs_baseline": N / 2000.0}

vs_baseline denominator: BASELINE.json's flagship target (2000 output tok/s
for Llama-3-70B PP=8 on v5e-8 — i.e. ~250 tok/s/chip × 8; a 1B model on one
chip should beat it by a wide margin; it is the round-over-round yardstick).

Robustness:
 - the default invocation is a supervisor that never touches jax (a chip
   belongs to one process at a time); the measurement runs in a child
   process, one at a time, under a hard deadline;
 - attempts run a DEGRADE LADDER: the first profile is the simplest serving
   loop (multi_step_decode=1, no overlap) to get ANY number; only if that
   succeeds and budget remains is the full-featured profile tried, and the
   best successful number wins;
 - the inner process emits ``[bench phase] <name>`` markers so a timeout's
   error JSON says *where* it died, and faulthandler dumps stacks every
   300 s for device-side stalls.

Usage: python bench.py            # the attached TPU (refuses any other backend)
       python bench.py --tiny     # CPU smoke (small model, small workload)
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import subprocess
import sys
import time

METRIC = "sharegpt_output_tok_s_per_chip"
PHASE_TAG = "[bench phase] "
# vs_baseline denominator: BASELINE.json's flagship target (see module
# docstring) — one constant so the salvage and report paths can't drift
BASELINE_TOK_S = 2000.0
# inner exit code for "backend is not a TPU": no rung can measure, so the
# supervisor stops instead of walking the ladder
NO_TPU_RC = 3

# Degrade ladder: ``minimal`` first to get ANY number (its bucket surface — decode seqs ≤64, model_len 1024,
# prefill chunk 512 — compiles in minutes, and every compile lands in the
# persistent XLA cache so later rungs start warm), then ``full`` (the
# headline rung: fused multi-step blocks + overlap) BEFORE conservative —
# the budget must reach the rung that matters even if the middle rung's
# compiles would not fit (r5: conservative cold-compiles ran past the
# supervisor deadline while full was already cache-warm).
PROFILES = ("minimal", "full", "conservative")

# Regression gate (ISSUE 20, GLLM_BENCH_BASELINE=<committed BENCH JSON>):
# the efficiency metrics a perf PR must not silently give back, with the
# direction that counts as better. Gated with tolerance — these are
# measured quantities, not counters.
GATE_METRICS = (
    ("bubble_frac", "lower"),
    ("mfu", "higher"),
    ("tokens_per_dispatch", "higher"),
)


def check_bench_regression(result, baseline, rel_tol=0.10, abs_tol=0.02):
    """Compare a measured result dict against a committed baseline BENCH
    JSON. Returns a list of human-readable offender strings, each naming
    the regressed metric — empty when the run holds the line. A metric
    absent from either side is skipped (profiles differ in what they
    measure), never failed: the gate flags regressions, not coverage."""
    failures = []
    for name, direction in GATE_METRICS:
        base, got = baseline.get(name), result.get(name)
        if base is None or got is None:
            continue
        slack = max(abs(base) * rel_tol, abs_tol)
        if direction == "lower" and got > base + slack:
            failures.append(
                f"{name} regressed: {got} vs baseline {base} "
                f"(lower is better, tolerance {slack:.4f})")
        elif direction == "higher" and got < base - slack:
            failures.append(
                f"{name} regressed: {got} vs baseline {base} "
                f"(higher is better, tolerance {slack:.4f})")
    return failures


def run_bench_gate(result, baseline_path):
    """GLLM_BENCH_BASELINE gate: compare the measured pass against the
    committed baseline, record the verdict in the result JSON, and
    return the process exit code (nonzero on regression, with every
    offending metric named on stderr)."""
    with open(baseline_path) as f:
        baseline = json.load(f)
    failures = check_bench_regression(result, baseline)
    result["baseline_gate"] = {
        "baseline": os.path.abspath(baseline_path),
        "failures": failures,
    }
    for m in failures:
        log(f"[bench] REGRESSION {m}")
    return 1 if failures else 0


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def last_phase(text):
    ph = "start"
    for line in text.splitlines():
        if line.startswith(PHASE_TAG):
            ph = line[len(PHASE_TAG):].strip()
    return ph


def salvage_result(text):
    """tok/s from a ``RESULT <value>`` line the inner process prints the
    moment the measured pass ends (benchmarks/kernel_tune.py run_inner's
    salvage pattern): a child that measured but then wedged or died in
    the sampled pass / report / teardown still yields its number instead
    of reading as a silent 0.0 regression. None when no RESULT landed."""
    for line in reversed((text or "").strip().splitlines()):
        if line.startswith("RESULT "):
            try:
                return float(line.split()[1])
            except (IndexError, ValueError):
                continue   # truncated by the kill mid-write; scan on
    return None


def salvage_attribution(text):
    """The ``ATTRIBUTION <json>`` line the inner process prints right
    after RESULT (measured-pass phase/device/overlap/MFU attribution,
    ISSUE 10): a salvaged run keeps its attribution instead of going
    blind — the r02-r04 trajectory had numbers with no *why*. None when
    no parseable line landed."""
    for line in reversed((text or "").strip().splitlines()):
        if line.startswith("ATTRIBUTION "):
            try:
                return json.loads(line[len("ATTRIBUTION "):])
            except json.JSONDecodeError:
                continue   # truncated mid-write; scan on
    return None


def supervise(args, argv):
    """Degrade-ladder supervisor; always prints one JSON line.

    Each attempt's jit compiles land in the persistent XLA cache
    (``.jax_cache/``) even when the attempt itself is killed, so a
    timed-out profile is retried once: the retry replays every compile
    the first attempt finished and spends its budget measuring. The
    ladder therefore makes forward progress across failed attempts
    instead of starting from scratch.
    """
    deadline = time.monotonic() + (1020 if not args.tiny else 420)
    best = None          # best successful (rank, profile, parsed)
    last_tail, phase = "", "start"
    last_rc = None       # rc of the last failed attempt ("timeout" for
                         # a deadline kill) — carried into failure JSON
    ladder = [[p, 0] for p in PROFILES]   # [profile, attempts_so_far]

    def consider(rank, profile, parsed):
        nonlocal best
        if best is None or rank > best[0]:
            best = (rank, profile, parsed)

    def consider_salvage(out_text, profile, how):
        """A measured-pass RESULT that outlived its process: rank below
        any COMPLETE json of the same rung class (no metrics snapshot),
        above nothing."""
        v = salvage_result(out_text)
        if v is None:
            return False
        log(f"[bench supervisor] salvaged RESULT {v:.1f} tok/s from "
            f"{how} {profile} attempt")
        parsed = {"metric": METRIC, "value": round(v, 2), "unit": "tok/s",
                  "vs_baseline": round(v / BASELINE_TOK_S, 4),
                  "salvaged": True,
                  "salvaged_from": how}
        attr = salvage_attribution(out_text)
        if attr:
            # attribution survives the salvage: the measured pass's
            # phase/overlap/MFU fields ride the ATTRIBUTION line
            parsed.update(attr)
        consider((0 if profile == "minimal" else 1, 0, v), profile,
                 parsed)
        return True

    while ladder:
        profile, tried = ladder[0]
        remaining = deadline - time.monotonic()
        if remaining < 120:
            break
        if best is not None and remaining < 360:
            # don't chase a bigger profile on a thin budget
            break
        budget = max(60, min(deadline - time.monotonic(), 640))
        log(f"[bench supervisor] profile={profile} attempt {tried + 1}, "
            f"budget {budget:.0f}s")
        ladder[0][1] += 1
        timed_out = crashed = False
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--inner",
                 "--profile", profile] + argv,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, timeout=budget)
            tail = proc.stdout[-8000:]
            sys.stderr.write(tail)
            sys.stderr.flush()
            phase = last_phase(proc.stdout)
            if proc.returncode == 0:
                for line in reversed(proc.stdout.strip().splitlines()):
                    line = line.strip()
                    if line.startswith("{"):
                        try:
                            parsed = json.loads(line)
                        except json.JSONDecodeError:
                            continue
                        if parsed.get("metric") == METRIC:
                            # minimal's shorter-context workload is not
                            # comparable to the other rungs: any
                            # conservative/full number outranks it; a
                            # complete JSON outranks a same-class salvage
                            consider((0 if profile == "minimal" else 1,
                                      1, parsed["value"]), profile, parsed)
                            break
                if best is None:
                    last_tail = tail[-1500:]
            else:
                # a baseline-gate failure is a COMPLETED measurement with
                # a regression verdict, not a crash: the child printed its
                # full result JSON (baseline_gate.failures non-empty) and
                # then exited nonzero.  Forward both verbatim — no salvage,
                # no retry (a retry would re-measure and could mask the
                # regression behind run-to-run noise).
                for line in reversed(proc.stdout.strip().splitlines()):
                    line = line.strip()
                    if line.startswith("{"):
                        try:
                            parsed = json.loads(line)
                        except json.JSONDecodeError:
                            continue
                        if (parsed.get("metric") == METRIC
                                and parsed.get("baseline_gate", {})
                                          .get("failures")):
                            parsed["profile"] = profile
                            log("[bench supervisor] baseline gate failed; "
                                "propagating nonzero exit")
                            print(json.dumps(parsed))
                            return proc.returncode
                        break
                crashed = True
                last_rc = proc.returncode
                last_tail = tail[-1500:]
                if proc.returncode == NO_TPU_RC:
                    break
                log(f"[bench supervisor] profile={profile} exited "
                    f"rc={proc.returncode} in phase '{phase}'")
                consider_salvage(proc.stdout, profile,
                                 f"rc={proc.returncode}")
        except subprocess.TimeoutExpired as e:
            out = (e.stdout or b"")
            if isinstance(out, bytes):
                out = out.decode(errors="replace")
            phase = last_phase(out)
            last_rc = "timeout"
            last_tail = (out[-1500:]
                         + f"\n[timeout after {budget:.0f}s in phase "
                           f"'{phase}' profile={profile}]")
            log(f"[bench supervisor] profile={profile} timed out in "
                f"phase '{phase}'")
            timed_out = True
            consider_salvage(out, profile, "timeout")
        if (timed_out or crashed) and ladder[0][1] < 2:
            if crashed:
                # bounded backoff before the retry: a crash right after
                # device init (a transient backend error) usually clears
                # in seconds, and the retry replays
                # every finished compile from the persistent cache
                back = min(30.0, max(0.0, deadline - time.monotonic()
                                     - 120))
                if back > 0:
                    log(f"[bench supervisor] backing off {back:.0f}s "
                        "before retry")
                    time.sleep(back)
            continue          # retry same profile, now cache-warm
        ladder.pop(0)
    if best is not None:
        _, profile, parsed = best
        parsed["profile"] = profile
        if profile == "minimal":
            # shorter-context fallback workload; don't read this as the
            # round-over-round headline (see PROFILES docstring)
            parsed["comparable"] = False
        print(json.dumps(parsed))
        return 0
    # No number at all: NEVER a bare 0.0 and never exit code 0 — the JSON
    # carries failed=true, the child's rc (or "timeout"), the last phase
    # marker, and the output tail so a harness failure is distinguishable
    # from a real regression.
    print(json.dumps({
        "metric": METRIC, "value": 0.0, "unit": "tok/s",
        "vs_baseline": 0.0, "failed": True, "rc": last_rc,
        "phase": phase,
        "error": f"no profile produced a number; last phase '{phase}': "
                 + last_tail[-900:],
    }))
    return 1


def build_workload(rng, n_requests, max_model_len, tiny=False):
    """Synthetic ShareGPT-like length distribution."""
    from gllm_tpu.sampling_params import SamplingParams
    prompts, params = [], []
    for _ in range(n_requests):
        if tiny:
            p_len = int(rng.integers(8, 64))
            o_len = int(rng.integers(8, 32))
        else:
            p_len = int(min(max(rng.lognormal(5.2, 0.8), 16), 1024))
            o_len = int(min(max(rng.lognormal(4.8, 0.7), 16), 512))
        p_len = min(p_len, max_model_len - o_len - 1)
        prompts.append(rng.integers(1, 30000, size=p_len).tolist())
        params.append(SamplingParams(temperature=0.0, max_tokens=o_len,
                                     ignore_eos=True))
    return prompts, params


# The dense-peak bf16 TFLOP/s table moved to gllm_tpu/obs/spans.py
# (PEAK_TFLOPS) — the per-step MFU gauge needs it too, and two copies
# would drift. It turns measured tok/s into an MFU so rounds compare
# efficiency, not just absolute rate (VERDICT r03 next #3).
def chip_peak_flops() -> float:
    """Peak bf16 FLOP/s of device 0 (0.0 on CPU; an unlisted TPU raises).
    Thin wrapper over the obs-layer table (obs/spans.py peak_flops)
    so bench and the per-step MFU gauge can never disagree; the
    GLLM_TPU_PEAK_TFLOPS override lives there too."""
    from gllm_tpu.obs.spans import peak_flops
    import jax
    return peak_flops(jax.devices()[0])


def model_flops(mc, prompts, params, prefill_chunk: int) -> float:
    """Total forward matmul FLOPs for the workload on the dense
    Llama-family bench model.

    Per processed token: 2·(weight params on the matmul path); embedding
    gather excluded. The lm_head projection runs once per engine step per
    sequence (the runner gathers last-token rows before the vocab GEMM,
    models/dense.py compute_logits), i.e. ~once per output token plus once
    per prefill chunk — NOT once per prompt token. Attention is
    token-weighted causally — a prefill token at position i attends i keys
    (Σ over the prompt = p²/2), a decode token at output position j attends
    p+j keys (Σ = o·p + o²/2) — at 2·2·ctx·Hq·D FLOPs per token (QKᵀ+PV).
    """
    import math
    qkv = mc.hidden_size * (mc.num_heads + 2 * mc.num_kv_heads) * mc.head_dim
    o_proj = mc.num_heads * mc.head_dim * mc.hidden_size
    mlp = 3 * mc.hidden_size * mc.intermediate_size
    body_tok = 2 * mc.num_layers * (qkv + o_proj + mlp)
    lm_head = 2 * mc.vocab_size * mc.hidden_size
    n_tok = sum(len(p) + s.max_tokens for p, s in zip(prompts, params))
    n_head_rows = sum(s.max_tokens + math.ceil(len(p) / prefill_chunk)
                      for p, s in zip(prompts, params))
    ctx_sum = sum(len(p) ** 2 / 2
                  + s.max_tokens * len(p) + s.max_tokens ** 2 / 2
                  for p, s in zip(prompts, params))
    attn = mc.num_layers * 4 * mc.num_heads * mc.head_dim * ctx_sum
    return n_tok * body_tok + n_head_rows * lm_head + attn


def flagship_model_cfg():
    """Llama-3.2-1B shape (BASELINE config 1), dummy weights — shared by
    every on-chip ladder rung so all rungs benchmark the same model."""
    from gllm_tpu.models.config import ModelConfig
    return ModelConfig(
        architecture="LlamaForCausalLM", vocab_size=128256,
        hidden_size=2048, num_layers=16, num_heads=32, num_kv_heads=8,
        head_dim=64, intermediate_size=8192, max_position=4096,
        rope_theta=500000.0, tie_word_embeddings=True)


def phase(name):
    print(PHASE_TAG + name, flush=True)


def kv_bytes_per_step(kv_read: float, summary: dict):
    """Effective KV bytes streamed per engine step over a measured
    window: the runner's gllm_kv_bytes_read_total delta divided by the
    window's step count (fused blocks count their sub-steps — each
    sub-step re-reads the context). This is the decode bandwidth-floor
    numerator the int8 cache halves; per-device estimate."""
    steps = sum(r["steps"] for k, r in summary.get("by_kind", {}).items()
                if k != "fused_block")
    steps += summary.get("decode_substeps_fused", 0)
    return round(kv_read / steps) if steps else None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CPU smoke test (small model/workload)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile", choices=PROFILES, default="full",
                    help="serving-loop feature level (degrade ladder)")
    ap.add_argument("--attn", choices=("auto", "pallas", "xla"),
                    default="auto",
                    help="attention_impl override (A/B the decode paths "
                         "on chip without editing profiles)")
    ap.add_argument("--inner", action="store_true",
                    help="(internal) run the measurement directly; without"
                         " this flag a supervisor child-process wrapper"
                         " with deadline + degrade ladder is used")
    args = ap.parse_args()

    if not args.inner:
        # forward argv minus --inner and any user --profile: the degrade
        # ladder owns the child's profile flag (last-wins in argparse)
        argv, skip = [], False
        for a in sys.argv[1:]:
            if skip:
                skip = False
                continue
            if a == "--inner" or a.startswith("--profile="):
                continue
            if a == "--profile":
                skip = True
                continue
            argv.append(a)
        sys.exit(supervise(args, argv))

    # Stall forensics: dump all thread stacks to stderr every 5 minutes so
    # a hung run (device stall, compile hang, deadlock) leaves evidence.
    import faulthandler
    faulthandler.dump_traceback_later(300, repeat=True, file=sys.stderr)

    if args.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # CPU has no spec-sheet peak, which would null every MFU field
        # and leave the attribution smoke blind — assume a declared
        # 1 TFLOP/s nominal peak so the --tiny MFU numbers exercise the
        # full plumbing (they are relative to this declared peak, not a
        # real chip; the on-chip rungs use the real table).
        os.environ.setdefault("GLLM_TPU_PEAK_TFLOPS", "1")

    phase("import_jax")
    import numpy as np
    import jax
    if args.tiny:
        jax.config.update("jax_platforms", "cpu")
    if not args.tiny and jax.default_backend() != "tpu":
        # a measurement is a chip run or it is nothing: never a CPU
        # number under a device metric's name
        log(f"bench.py measures on a TPU; backend is "
            f"{jax.default_backend()!r} (use --tiny for the CPU smoke)")
        sys.exit(NO_TPU_RC)
    logging.basicConfig(level=logging.INFO, stream=sys.stderr)

    from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
    from gllm_tpu.engine.llm import LLM
    from gllm_tpu.models.config import ModelConfig

    full = args.profile == "full"
    minimal = args.profile == "minimal"
    # KV-cache dtype A/B lever (same discipline as GLLM_BENCH_SLOTS):
    # GLLM_BENCH_KV_DTYPE=int8 stores quantized KV with in-kernel dequant
    # on every rung; the default arm stays byte-identical legacy.
    kv_dtype = os.environ.get("GLLM_BENCH_KV_DTYPE", "auto") or "auto"
    # Tiered-prefix-store lever (GLLM_BENCH_PREFIX=1): configure prefix
    # caching + host pool + disk tier and run a repeated-system-prompt
    # pass reporting per-tier hit rate and TTFT with/without the disk
    # tier (docs/kv_offload.md). Off by default: the headline engine
    # stays byte-identical (random ShareGPT prompts share no prefixes,
    # but the A/B discipline is the same as the other levers).
    prefix_bench = os.environ.get("GLLM_BENCH_PREFIX", "0") not in ("", "0")
    if args.tiny:
        model_cfg = ModelConfig(
            architecture="LlamaForCausalLM", vocab_size=2048,
            hidden_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
            head_dim=32, intermediate_size=256, max_position=512)
        # same A/B levers as the on-chip full profile: GLLM_BENCH_SLOTS=0
        # reverts to legacy chain membership, GLLM_BENCH_ODF=0 to
        # host-side finish detection, GLLM_BENCH_PIPELINED=0 to the
        # drain-on-break engine loop, on the CPU pass
        slots = os.environ.get("GLLM_BENCH_SLOTS", "1") not in ("", "0")
        odf = os.environ.get("GLLM_BENCH_ODF", "1") not in ("", "0")
        pipelined = os.environ.get("GLLM_BENCH_PIPELINED",
                                   "1") not in ("", "0")
        # Unified-step A/B (GLLM_BENCH_UNIFIED=0 reverts to the split
        # prefill/decode dispatch + per-kind shape families; the
        # unfused_frac / mixed_step_frac / warmed_buckets fields below
        # are the comparison axes)
        unified = os.environ.get("GLLM_BENCH_UNIFIED",
                                 "1") not in ("", "0")
        # Fused-speculation A/B (GLLM_BENCH_SPEC_FUSED=0 reverts to the
        # no-speculation engine; greedy streams are byte-identical
        # either way, so the workload tokens match across arms — the
        # spec_accept_rate / tokens_per_dispatch fields below are the
        # comparison axes)
        spec_fused = os.environ.get("GLLM_BENCH_SPEC_FUSED",
                                    "1") not in ("", "0")
        engine_cfg = EngineConfig(
            load_format="dummy", dtype="float32", max_model_len=512,
            max_num_seqs=32,
            overlap_scheduling=full, multi_step_decode=8 if full else 1,
            pipelined_loop=full and pipelined,
            unified_step=full and unified,
            spec_decode="ngram" if full and spec_fused else None,
            spec_fused=full and spec_fused,
            ondevice_finish=full and odf,
            decode_slot_batching=full and slots,
            chain_under_prefill=(8 if full and slots and not unified
                                 else 0),
            scheduler=SchedulerConfig(max_prefill_tokens=128,
                                      max_decode_seqs=16),
            cache=CacheConfig(page_size=4, num_pages=512,
                              kv_cache_dtype=kv_dtype))
        n_requests = args.requests or 8
    elif minimal:
        # Same Llama-3.2-1B model, smallest serviceable bucket surface:
        # decode buckets {8..64}, page buckets {4..64}, one 512-token
        # prefill chunk bucket — roughly half the conservative profile's
        # compile count, for a first number. NOTE: the
        # shorter-context workload is NOT comparable to the conservative/
        # full rungs; the supervisor only reports it when no comparable
        # rung produced a number, and tags the JSON.
        model_cfg = flagship_model_cfg()
        engine_cfg = EngineConfig(
            load_format="dummy", dtype="bfloat16", max_model_len=1024,
            max_num_seqs=64, overlap_scheduling=False, multi_step_decode=1,
            scheduler=SchedulerConfig(max_prefill_tokens=512,
                                      max_decode_seqs=64),
            cache=CacheConfig(page_size=16, num_pages=4096,
                              kv_cache_dtype=kv_dtype))
        n_requests = args.requests or 64
    else:
        model_cfg = flagship_model_cfg()
        # experiment overrides for on-chip A/B tuning of the full profile
        # (committed defaults are the measured winners)
        msd = int(os.environ.get("GLLM_BENCH_MSD", "32"))
        depth = int(os.environ.get("GLLM_BENCH_DEPTH", "4"))
        chunk = int(os.environ.get("GLLM_BENCH_PREFILL", "2048"))
        # persistent-slot decode chains + on-device finish (A/B levers:
        # GLLM_BENCH_SLOTS=0 reverts the full profile to legacy chain
        # membership, GLLM_BENCH_ODF=0 to host-side finish detection)
        slots = os.environ.get("GLLM_BENCH_SLOTS", "1") not in ("", "0")
        odf = os.environ.get("GLLM_BENCH_ODF", "1") not in ("", "0")
        # Pipelined-loop A/B (GLLM_BENCH_PIPELINED=0 reverts the full
        # profile to the drain-on-break loop; the bubble_frac /
        # mean_inflight_depth fields below are the comparison axes)
        pipelined = os.environ.get("GLLM_BENCH_PIPELINED",
                                   "1") not in ("", "0")
        # Unified-step A/B lever, same discipline as the tiny profile
        unified = os.environ.get("GLLM_BENCH_UNIFIED",
                                 "1") not in ("", "0")
        # Fused-speculation A/B lever (GLLM_BENCH_SPEC_FUSED=0): the
        # ShareGPT-shaped random workload is draft-hostile, so the
        # headline mostly measures that the drafting machinery never
        # slows the chain down; the draft-friendly win shows in the
        # --tiny in-process A/B below.
        spec_fused = os.environ.get("GLLM_BENCH_SPEC_FUSED",
                                    "1") not in ("", "0")
        cup = int(os.environ.get("GLLM_BENCH_CUP", str(msd)))
        engine_cfg = EngineConfig(
            load_format="dummy", dtype="bfloat16", max_model_len=2048,
            # conservative halves the decode width: fewer/smaller decode
            # buckets to compile, so the first (budget-bounded) attempt
            # spends its time measuring, not compiling
            max_num_seqs=256 if full else 128,
            overlap_scheduling=full,
            pipelined_loop=full and pipelined,
            unified_step=full and unified,
            spec_decode="ngram" if full and spec_fused else None,
            spec_fused=full and spec_fused,
            overlap_depth=depth if full else 1,
            multi_step_decode=msd if full else 1,
            ondevice_finish=full and odf,
            decode_slot_batching=full and slots,
            # gated on slots too: the GLLM_BENCH_SLOTS=0 arm must be the
            # byte-identical legacy baseline, not legacy-with-ramp-policy
            # (and the unified step retires the ramp policy entirely)
            chain_under_prefill=(cup if full and slots and not unified
                                 else 0),
            scheduler=SchedulerConfig(max_prefill_tokens=chunk,
                                      max_decode_seqs=256 if full
                                      else 128),
            # explicit pool (4 GB KV bf16; int8 halves the bytes at the
            # same page count)
            cache=CacheConfig(page_size=16, num_pages=8192,
                              kv_cache_dtype=kv_dtype))
        n_requests = args.requests or 160

    if prefix_bench:
        import tempfile
        c = engine_cfg.cache
        c.enable_prefix_caching = True
        if not c.kv_host_pool_pages and c.kv_host_pool_gb <= 0:
            c.kv_host_pool_pages = 256 if args.tiny else 2048
        c.kv_disk_path = tempfile.mkdtemp(prefix="gllm_bench_kvdisk_")
        c.kv_disk_gb = 2.0

    # Tracing A/B lever (ISSUE 10 acceptance gate: default-on tracing
    # must cost <2% --tiny throughput and keep token streams
    # byte-identical): GLLM_BENCH_TRACING=0 runs the flag-off arm.
    engine_cfg.tracing = (os.environ.get("GLLM_BENCH_TRACING", "1")
                          not in ("", "0"))

    # pp topology lever (ISSUE 20, GLLM_BENCH_PP=2): run the measured
    # pass over a pp-stage pipeline — the fast-path flags (pipelined +
    # unified) now ride per-stage dispatch. Fused speculation and the
    # slot/fused-block machinery are single-program features the config
    # rejects / the engine ignores under pp, so the pp arm switches them
    # off EXPLICITLY here (the bench choosing its config, loudly — never
    # the engine dropping a flag).
    bench_pp = int(os.environ.get("GLLM_BENCH_PP", "1") or "1")
    if bench_pp > 1:
        engine_cfg.parallel.pp = bench_pp
        engine_cfg.spec_fused = False
        engine_cfg.spec_decode = None
        engine_cfg.multi_step_decode = 1
        engine_cfg.decode_slot_batching = False
        engine_cfg.ondevice_finish = False
        engine_cfg.chain_under_prefill = 0
        log(f"[bench] GLLM_BENCH_PP={bench_pp}: pp pipeline arm "
            f"(spec_fused / fused-block / slot levers off — "
            f"single-program features)")

    phase("backend_init")
    log(f"backend={jax.default_backend()} devices={jax.devices()} "
        f"profile={args.profile}")
    if args.attn != "auto":
        engine_cfg.attention_impl = args.attn
    phase("engine_build")
    t0 = time.monotonic()
    llm = LLM(config=engine_cfg, model_cfg=model_cfg)
    log(f"engine up in {time.monotonic() - t0:.1f}s "
        f"({llm.runner.num_pages} KV pages)")

    rng = np.random.default_rng(args.seed)
    prompts, params = build_workload(rng, n_requests,
                                     engine_cfg.max_model_len,
                                     tiny=args.tiny)
    total_out = sum(p.max_tokens for p in params)
    total_in = sum(len(p) for p in prompts)
    log(f"workload: {n_requests} reqs, {total_in} prompt tokens, "
        f"{total_out} output tokens")

    # Warmup pass: same workload → compiles every bucket the measured pass
    # will hit (the reference warms its CUDA graphs the same way).
    phase("warmup_pass")
    t0 = time.monotonic()
    llm.generate(prompt_token_ids=prompts, sampling_params=params)
    log(f"warmup pass: {time.monotonic() - t0:.1f}s")

    # Bracket the measured pass in the obs layer: steptrace mark +
    # request-histogram snapshots so the summary excludes warmup.
    from gllm_tpu.obs import metrics as obs_metrics
    from gllm_tpu.obs.steptrace import TRACE, summarize
    trace_mark = TRACE.mark()
    hist_names = ("gllm_request_ttft_seconds", "gllm_request_itl_seconds",
                  "gllm_request_e2e_seconds", "gllm_request_tpot_seconds")
    hist_before = {n: obs_metrics.REGISTRY.get(n).snapshot()
                   for n in hist_names}
    kv_read_metric = obs_metrics.REGISTRY.get("gllm_kv_bytes_read_total")
    kv_read0 = kv_read_metric.get() if kv_read_metric else 0.0

    phase("measured_pass")
    t0 = time.monotonic()
    outs = llm.generate(prompt_token_ids=prompts, sampling_params=params)
    dt = time.monotonic() - t0

    # Salvageable headline the moment it exists (the supervisor's
    # salvage_result pattern): the sampled pass / report / teardown can
    # still wedge or crash without losing the measured number.
    out_tokens = sum(o.num_output_tokens for o in outs)
    assert out_tokens == total_out, (out_tokens, total_out)
    value = out_tokens / dt
    print(f"RESULT {value:.3f}", flush=True)

    # Machine-readable measured-pass attribution (step-kind wall time,
    # fused/unfused decode split, compile events, request latency
    # percentiles) — the "18/59 unfused steps" class of finding reads
    # straight out of BENCH_r*.json now instead of log archaeology.
    events = TRACE.events(since=trace_mark)
    step_summary = summarize(events)
    # Unified-step acceptance (ISSUE 12): with the flag on, prefill
    # arrivals are absorbed into mixed re-formed batches — the 'waiting'
    # break class is retired and MUST stay at zero, on every profile
    # (the flag is inert for hybrid models, where legacy yields remain).
    if engine_cfg.unified_step and not model_cfg.use_hybrid:
        waiting = (step_summary.get("chain_breaks_by_reason")
                   or {}).get("waiting", 0)
        assert not waiting, (
            f"--unified-step run recorded {waiting} chain_breaks with "
            f"reason='waiting' — the retired break class fired")
    # Salvageable attribution right behind RESULT (ISSUE 10): a run the
    # supervisor kills in the sampled pass / report / teardown keeps its
    # WHY, not just its number — the supervisor merges this line into
    # the salvaged JSON.
    # (the result JSON's mfu is the workload-level model_flops/dt/peak;
    # the steptrace-window estimator that rode here as window_mfu went
    # with the engine loop's per-step FLOPs walk, PR 24)
    print("ATTRIBUTION " + json.dumps({
        "host_ms_by_phase": step_summary.get("host_ms_by_phase"),
        "device_ms_by_kind": step_summary.get("device_ms_by_kind"),
        "overlap_efficiency": step_summary.get("overlap_efficiency"),
        "bubble_frac": step_summary.get("bubble_frac"),
        # pipelined loop (ISSUE 11): the sustained run-ahead depth and
        # why the loop failed to run further ahead — a salvaged run
        # keeps the bubble story, not just the bubble number
        "mean_inflight_depth": step_summary.get("mean_inflight_depth"),
        "loop_stalls": step_summary.get("loop_stalls_by_reason"),
        "pipelined_loop": bool(engine_cfg.pipelined_loop),
        # unified step (ISSUE 12): the dispatch-shape story — share of
        # steps that were mixed unified batches and the shape-bucket
        # population the runner compiled/warmed over the whole run
        "unified_step": bool(engine_cfg.unified_step),
        "mixed_step_frac": step_summary.get("mixed_step_frac"),
        "warmed_buckets": getattr(llm.runner, "num_shape_signatures",
                                  None),
        # fused speculation (ISSUE 13): the dispatch-amortization story
        "spec_fused": bool(engine_cfg.spec_fused),
        "spec_accept_rate": step_summary.get("spec_accept_rate"),
        "tokens_per_dispatch": step_summary.get("tokens_per_dispatch"),
    }), flush=True)


    # On-demand Chrome trace artifact of the measured pass
    # (GLLM_BENCH_TRACE=1): engine-phase tracks + per-request span
    # tracks, loadable in Perfetto (docs/observability.md#tracing).
    trace_path = None
    if os.environ.get("GLLM_BENCH_TRACE", "0") not in ("", "0"):
        from gllm_tpu.obs.spans import chrome_trace
        trace_path = os.path.abspath(f"bench_trace_{args.profile}.json")
        with open(trace_path, "w") as f:
            json.dump(chrome_trace(events, llm.spans.spans(),
                                   span_t0=TRACE.t0), f)
        log(f"[bench] chrome trace written to {trace_path}")
    kv_read = (kv_read_metric.get() - kv_read0) if kv_read_metric else 0.0
    # no silent caps: the ring holds GLLM_OBS_TRACE_CAP events — report
    # how many measured-pass iterations rolled off before the dump
    lost = max(0, TRACE.mark() - TRACE.capacity - trace_mark)
    if lost:
        step_summary["trace_dropped"] = lost
        log(f"[bench] steptrace ring dropped {lost} measured-pass "
            f"events (raise GLLM_OBS_TRACE_CAP for full attribution)")
    lat = {}
    for name in hist_names:
        h = obs_metrics.REGISTRY.get(name)
        short = name[len("gllm_request_"):-len("_seconds")]
        pcts = {q: obs_metrics.percentile(h, q / 100.0,
                                          before=hist_before[name])
                for q in (50, 90, 99)}
        if any(v is not None for v in pcts.values()):
            lat[short] = {f"p{q}": (round(v, 4) if v is not None else None)
                          for q, v in pcts.items()}
    metrics_snapshot = {"steps": step_summary, "request_latency_s": lat}

    # Tiny-mode pipelined A/B control (ISSUE 11): re-run the same
    # measured workload on a flag-off engine in the same process so the
    # result JSON carries the bubble_frac DELTA directly — the on-chip
    # rungs A/B across runs via GLLM_BENCH_PIPELINED instead (engine
    # build + recompiles are too expensive to double there). Runs AFTER
    # the headline window's metric deltas (kv_read, latency histograms)
    # were snapshotted so the control never pollutes them.
    bubble_delta = None
    if args.tiny and engine_cfg.pipelined_loop:
        phase("pipelined_control_pass")
        import dataclasses as _dc
        ctrl_cfg = _dc.replace(engine_cfg, pipelined_loop=False)
        ctrl = LLM(config=ctrl_cfg, model_cfg=model_cfg)
        ctrl.generate(prompt_token_ids=prompts,
                      sampling_params=params)          # warm the buckets
        c_mark = TRACE.mark()
        ctrl.generate(prompt_token_ids=prompts, sampling_params=params)
        c_summary = summarize(TRACE.events(since=c_mark))
        b_on = step_summary.get("bubble_frac")
        b_off = c_summary.get("bubble_frac")
        if b_on is not None and b_off is not None:
            bubble_delta = {"bubble_frac_sync": b_off,
                            "bubble_frac_delta": round(b_on - b_off, 4)}
            log(f"pipelined A/B: bubble_frac {b_off} (sync) -> {b_on} "
                f"(pipelined)")
            # re-print the salvageable ATTRIBUTION line carrying BOTH
            # arms (salvage takes the most recent line; if the run dies
            # during the control, the first line already landed)
            print("ATTRIBUTION " + json.dumps({
                "host_ms_by_phase": step_summary.get("host_ms_by_phase"),
                "device_ms_by_kind":
                    step_summary.get("device_ms_by_kind"),
                "overlap_efficiency":
                    step_summary.get("overlap_efficiency"),
                "bubble_frac": b_on,
                "mean_inflight_depth":
                    step_summary.get("mean_inflight_depth"),
                "loop_stalls": step_summary.get("loop_stalls_by_reason"),
                "pipelined_loop": True,
                **bubble_delta,
            }), flush=True)

    # Tiny-mode pp A/B (ISSUE 20, GLLM_BENCH_PP=2): the same measured
    # workload on a LEGACY pp engine (sync drain-per-pass loop: no
    # overlap, no pipelined re-forms, split dispatch families) in the
    # same process — the pipelined+unified pp arm must hold bubble_frac
    # no worse than the legacy pp pipeline (the no-inter-stage-bubble
    # claim, measured, not asserted from structure).
    pp_ab = None
    if args.tiny and bench_pp > 1 and engine_cfg.pipelined_loop:
        phase("pp_ab_pass")
        import dataclasses as _dc
        leg_cfg = _dc.replace(engine_cfg, overlap_scheduling=False,
                              pipelined_loop=False, unified_step=False)
        leg = LLM(config=leg_cfg, model_cfg=model_cfg)
        leg.generate(prompt_token_ids=prompts,
                     sampling_params=params)           # warm the buckets
        l_mark = TRACE.mark()
        leg.generate(prompt_token_ids=prompts, sampling_params=params)
        l_summary = summarize(TRACE.events(since=l_mark))
        b_on = step_summary.get("bubble_frac")
        b_off = l_summary.get("bubble_frac")
        pp_ab = {"pp": bench_pp, "bubble_frac": b_on,
                 "bubble_frac_legacy": b_off}
        log(f"pp A/B: bubble_frac {b_off} (legacy pp) -> {b_on} "
            f"(pipelined+unified pp)")
        if b_on is not None and b_off is not None:
            assert b_on <= b_off + 0.05, (
                f"pp fast path worsened bubble_frac vs legacy pp: "
                f"{b_on} vs {b_off}")

    # Tiny-mode unified-step A/B (ISSUE 12): the headline pass submits
    # every request up front, so the prefill/decode phase split barely
    # fires — run a STAGGERED-ARRIVAL churn micro-pass on two fresh
    # engines (flag on / flag off, same workload) and report the
    # dispatch-shape story directly: distinct warmed shape-bucket
    # signatures and the unfused decode share, both of which the
    # unified step must hold strictly lower. On-chip rungs A/B across
    # runs via GLLM_BENCH_UNIFIED instead.
    unified_ab = None
    if args.tiny and engine_cfg.unified_step:
        phase("unified_ab_pass")
        import dataclasses as _dc
        from gllm_tpu.sampling_params import SamplingParams

        def churn_arm(unified_on):
            cfg = _dc.replace(
                engine_cfg, unified_step=unified_on,
                # the flag-off arm runs the legacy ramp policy the
                # unified step retires — but only in the slots
                # configuration the headline gates it on (the SLOTS=0
                # arm must stay byte-identical legacy, not
                # legacy-with-ramp-policy)
                chain_under_prefill=(
                    0 if unified_on
                    else 8 if engine_cfg.decode_slot_batching else 0))
            arm = LLM(config=cfg, model_cfg=model_cfg)
            arng = np.random.default_rng(7)
            arrivals = {0: 4, 3: 3, 7: 3, 12: 2, 18: 2, 25: 2}
            mark, nseq, it = TRACE.mark(), 0, 0
            while nseq < 14 or arm.has_unfinished:
                for _ in range(arrivals.get(it, 0)):
                    if nseq >= 14:
                        break
                    ids = arng.integers(
                        1, model_cfg.vocab_size - 1,
                        size=int(arng.integers(8, 64))).tolist()
                    s = arm._allocate_seq(
                        ids, SamplingParams(
                            temperature=0.0, ignore_eos=True,
                            max_tokens=int(arng.integers(16, 48))))
                    arm.add_seq(s)
                    nseq += 1
                arm.step()
                it += 1
                assert it < 4000, "unified A/B churn arm wedged"
            summ = summarize(TRACE.events(since=mark))
            return {"warmed_buckets": arm.runner.num_shape_signatures,
                    "unfused_frac": summ.get("unfused_frac"),
                    "mixed_step_frac": summ.get("mixed_step_frac"),
                    "chain_breaks": summ.get("chain_breaks_by_reason")}

        on, off = churn_arm(True), churn_arm(False)
        assert not (on["chain_breaks"] or {}).get("waiting"), (
            "unified churn arm recorded retired 'waiting' breaks")
        unified_ab = {
            "warmed_buckets": on["warmed_buckets"],
            "warmed_buckets_split": off["warmed_buckets"],
            "unfused_frac": on["unfused_frac"],
            "unfused_frac_split": off["unfused_frac"],
            "mixed_step_frac": on["mixed_step_frac"],
        }
        log(f"unified A/B (churn): warmed_buckets "
            f"{off['warmed_buckets']} (split) -> {on['warmed_buckets']} "
            f"(unified); unfused_frac {off['unfused_frac']} -> "
            f"{on['unfused_frac']}")

    # Tiny-mode fused-speculation A/B (ISSUE 13): the headline random
    # workload is draft-hostile, so the dispatch-amortization win needs
    # a DRAFT-FRIENDLY (repetitive) micro-pass — two fresh engines run
    # the same workload (greedy byte-identity guarantees equal token
    # output) and the fused arm must take STRICTLY fewer device
    # dispatches. On-chip rungs A/B across runs via
    # GLLM_BENCH_SPEC_FUSED instead.
    spec_fused_ab = None
    if args.tiny and engine_cfg.spec_fused:
        phase("spec_fused_ab_pass")
        import dataclasses as _dc
        from gllm_tpu.sampling_params import SamplingParams

        # dedicated SMALL-VOCAB model for the A/B arms: greedy decode of
        # a random-weight model enters short cycles quickly at vocab 32
        # (measured periods 1-3) — the draft-friendly regime where
        # prompt-lookup actually accepts; the headline model's vocab
        # (2048) random-walks for hundreds of tokens and never drafts
        ab_model = ModelConfig(
            architecture="LlamaForCausalLM", vocab_size=32,
            hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
            head_dim=16, intermediate_size=128, max_position=512)

        def spec_arm(fused_on):
            cfg = _dc.replace(
                engine_cfg, spec_fused=fused_on,
                spec_decode="ngram" if fused_on else None)
            arm = LLM(config=cfg, model_cfg=ab_model)
            arng = np.random.default_rng(13)
            # repetitive prompts seed the n-gram window immediately
            s_prompts = [(arng.integers(
                1, ab_model.vocab_size - 1, size=4).tolist() * 8)[:24]
                for _ in range(6)]
            s_params = [SamplingParams(temperature=0.0, max_tokens=48,
                                       ignore_eos=True)
                        for _ in s_prompts]
            arm.generate(prompt_token_ids=s_prompts,
                         sampling_params=s_params)   # warm the buckets
            mark = TRACE.mark()
            d0 = arm.runner.num_dispatches
            outs = arm.generate(prompt_token_ids=s_prompts,
                                sampling_params=s_params)
            summ = summarize(TRACE.events(since=mark))
            toks = sum(o.num_output_tokens for o in outs)
            return {"dispatches": arm.runner.num_dispatches - d0,
                    "tokens": toks,
                    "out_ids": [o.output_token_ids for o in outs],
                    "spec_accept_rate": summ.get("spec_accept_rate"),
                    "tokens_per_dispatch":
                        summ.get("tokens_per_dispatch")}

        on, off = spec_arm(True), spec_arm(False)
        assert on["out_ids"] == off["out_ids"], (
            "fused speculation changed greedy token content")
        assert on["tokens"] == off["tokens"]
        assert on["dispatches"] < off["dispatches"], (
            "fused speculation must strictly reduce dispatches at equal "
            f"token output ({on['dispatches']} vs {off['dispatches']})")
        spec_fused_ab = {
            "dispatches": on["dispatches"],
            "dispatches_off": off["dispatches"],
            "tokens": on["tokens"],
            "spec_accept_rate": on["spec_accept_rate"],
            "tokens_per_dispatch": on["tokens_per_dispatch"],
            "tokens_per_dispatch_off": off["tokens_per_dispatch"],
        }
        log(f"spec_fused A/B (draft-friendly): dispatches "
            f"{off['dispatches']} -> {on['dispatches']} at "
            f"{on['tokens']} tokens; accept_rate "
            f"{on['spec_accept_rate']}")

    # Sampled-path pass (VERDICT r05: the sampled sampler program never
    # appeared in BENCH JSON, so its ~88 ms full-vocab sort regression was
    # invisible for two rounds): a smaller measured pass with temperature
    # > 0 / top_p < 1 so the sampled program variant gets a number of its
    # own. GLLM_BENCH_SAMPLED=0 skips it (budget-constrained reruns).
    sampled_result = None
    if os.environ.get("GLLM_BENCH_SAMPLED", "1") not in ("", "0"):
        from gllm_tpu.sampling_params import SamplingParams
        n_sampled = min(n_requests, 64)
        s_prompts = prompts[:n_sampled]
        s_params = [SamplingParams(temperature=0.8, top_p=0.95, top_k=64,
                                   max_tokens=p.max_tokens,
                                   ignore_eos=True)
                    for p in params[:n_sampled]]
        phase("sampled_warmup")
        llm.generate(prompt_token_ids=s_prompts, sampling_params=s_params)
        phase("sampled_pass")
        s_mark = TRACE.mark()
        s_kv0 = kv_read_metric.get() if kv_read_metric else 0.0
        t0 = time.monotonic()
        s_outs = llm.generate(prompt_token_ids=s_prompts,
                              sampling_params=s_params)
        s_dt = time.monotonic() - t0
        s_tokens = sum(o.num_output_tokens for o in s_outs)
        s_summary = summarize(TRACE.events(since=s_mark))
        s_kv = (kv_read_metric.get() - s_kv0) if kv_read_metric else 0.0
        s_flops = model_flops(model_cfg, s_prompts, s_params,
                              engine_cfg.scheduler.max_prefill_tokens)
        s_peak = chip_peak_flops()
        sampled_result = {
            "output_tok_s": round(s_tokens / s_dt, 2),
            "wall_s": round(s_dt, 2),
            "requests": n_sampled,
            # rung-comparable efficiency fields (same definitions as the
            # greedy headline): MFU + effective KV bytes per step
            "mfu": (round(s_flops / s_dt / s_peak, 4) if s_peak
                    else None),
            "kv_bytes_per_step": kv_bytes_per_step(s_kv, s_summary),
            "steps": s_summary,
        }
        log(f"sampled pass: {s_dt:.2f}s → {s_tokens / s_dt:.1f} "
            f"output tok/s ({n_sampled} reqs, temp=0.8 top_p=0.95)")

    # Repeated-system-prompt pass (ISSUE 9): the workload real multi-user
    # traffic is made of — N requests sharing one long system prefix with
    # unique tails. Three arms probe the tier stack: "populate" (cold
    # store; requests 2..N hit HBM), "disk" (HBM + host demoted to the
    # disk tier first, so every prefix page restores from disk), and
    # "no_tier" (tiers detached, full recompute — the without-disk
    # control). Hit rate + TTFT p50 per arm land first-class in the
    # result JSON.
    prefix_result = None
    if prefix_bench and getattr(llm, "prefix_tiers", None) is not None:
        from gllm_tpu.sampling_params import SamplingParams
        phase("prefix_pass")
        sys_len = 64 if args.tiny else 512
        n_pref = min(n_requests, 8 if args.tiny else 32)
        shared = rng.integers(1, 30000, size=sys_len).tolist()
        ttft_h = obs_metrics.REGISTRY.get("gllm_request_ttft_seconds")
        q_m = obs_metrics.REGISTRY.get(
            "gllm_prefix_cache_query_tokens_total")
        h_m = obs_metrics.REGISTRY.get(
            "gllm_prefix_cache_hit_tokens_total")
        disk_hits = obs_metrics.REGISTRY.get("gllm_kvstore_hits_total")

        def prefix_arm():
            before, q0, h0 = ttft_h.snapshot(), q_m.get(), h_m.get()
            p_prompts = [shared + rng.integers(
                1, 30000, size=16).tolist() for _ in range(n_pref)]
            p_params = [SamplingParams(temperature=0.0, max_tokens=8,
                                       ignore_eos=True)
                        for _ in range(n_pref)]
            t0 = time.monotonic()
            llm.generate(prompt_token_ids=p_prompts,
                         sampling_params=p_params)
            p50 = obs_metrics.percentile(ttft_h, 0.5, before=before)
            dq, dh = q_m.get() - q0, h_m.get() - h0
            return {"hit_rate": round(dh / dq, 4) if dq else 0.0,
                    "ttft_p50_s": (round(p50, 4) if p50 is not None
                                   else None),
                    "wall_s": round(time.monotonic() - t0, 2)}

        arms = {"populate": prefix_arm()}
        moved = llm.demote_prefix_cache()
        d0 = disk_hits.get(tier="disk")
        arms["disk"] = prefix_arm()
        disk_pages = disk_hits.get(tier="disk") - d0
        # control: detach the tiers AND the eviction demotion hook, and
        # forget every upper level (HBM maps + host-pool entries — the
        # disk arm re-staged pages there), so the same workload
        # recomputes every prefix token with true-legacy eviction costs
        pool = llm.swap_manager.pool
        llm.swap_manager.tiers, pool.on_evict = None, None
        mm = llm.memory_manager
        mm.hash_to_page.clear(); mm.page_meta.clear()
        mm._seq_chain.clear()
        for p in list(pool.page_meta):
            pool.drop_prefix(p)
        arms["no_tier"] = prefix_arm()
        llm.swap_manager.tiers = llm.prefix_tiers
        pool.on_evict = llm.prefix_tiers._on_host_evict
        prefix_result = {"system_prompt_tokens": sys_len,
                         "requests": n_pref,
                         "pages_demoted": moved,
                         "disk_hit_pages": int(disk_pages), **arms}
        log(f"prefix pass: hit_rate populate={arms['populate']['hit_rate']}"
            f" disk={arms['disk']['hit_rate']} "
            f"no_tier={arms['no_tier']['hit_rate']}; ttft_p50 "
            f"disk={arms['disk']['ttft_p50_s']} vs "
            f"no_tier={arms['no_tier']['ttft_p50_s']}")

    # Self-healing chaos lever (ISSUE 14, GLLM_BENCH_CHAOS=1): the
    # recovery acceptance run inside bench — a ServingEngine with
    # --engine-recovery serves the same greedy workload twice (a clean
    # arm, then an arm with an injected engine_hard_crash mid-pass), and
    # throughput degradation + recovery_s land FIRST-CLASS in the result
    # JSON. Greedy + ignore_eos makes every request replay-safe, so the
    # faulted arm must still emit every token (asserted) — the cost of
    # the crash shows up as wall clock, never as lost output.
    chaos_result = None
    if os.environ.get("GLLM_BENCH_CHAOS", "0") not in ("", "0"):
        phase("chaos_pass")
        import dataclasses as _dc
        import threading as _th
        from gllm_tpu.engine.serving_engine import ServingEngine
        from gllm_tpu.faults import FAULTS
        from gllm_tpu.sampling_params import SamplingParams
        ch_cfg = _dc.replace(engine_cfg, engine_recovery=True,
                             max_step_failures=1,
                             rebuild_backoff_s=0.05,
                             rebuild_backoff_max_s=1.0)
        n_chaos = min(n_requests, 8 if args.tiny else 32)
        ch_prompts = [list(p) for p in prompts[:n_chaos]]
        ch_tokens = [min(p.max_tokens, 64) for p in params[:n_chaos]]

        def chaos_arm(fault_delay_s=None):
            llm_c = LLM(config=ch_cfg, model_cfg=model_cfg)
            eng = ServingEngine(llm_c)
            counts = [0] * n_chaos
            timer = None
            try:
                if fault_delay_s is not None:
                    # time-based so the crash lands MID-pass on every
                    # profile (a fused engine drains the workload in
                    # too few loop passes for pass-counting to work)
                    timer = _th.Timer(
                        fault_delay_s,
                        lambda: FAULTS.arm("engine_hard_crash:0:1"))
                    timer.daemon = True
                    timer.start()
                t0 = time.monotonic()
                handles = [eng.submit(p, SamplingParams(
                    temperature=0.0, max_tokens=mt, ignore_eos=True))
                    for p, mt in zip(ch_prompts, ch_tokens)]

                def drain(i, h):
                    for c in h:
                        if c.token_id is not None:
                            counts[i] += 1

                ts = [_th.Thread(target=drain, args=(i, h), daemon=True)
                      for i, h in enumerate(handles)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=600)
                    assert not t.is_alive(), "chaos-arm stream hung"
                dt_arm = time.monotonic() - t0
            finally:
                if timer is not None:
                    timer.cancel()
                FAULTS.reset()
                eng.shutdown()
            sup = eng.supervisor
            return {"tok": sum(counts), "dt": dt_arm,
                    "recoveries": sup.recoveries if sup else 0,
                    "recovery_s": (sup.last_recovery_s
                                   if sup else None)}

        clean = chaos_arm(None)
        # the crash lands ~40% into the measured window (sized off the
        # clean arm), mid-stream on every profile
        faulted = chaos_arm(max(0.02, 0.4 * clean["dt"]))
        assert faulted["tok"] == clean["tok"], (
            "recovery dropped tokens: the greedy replay-safe workload "
            f"must re-emit every token ({faulted['tok']} vs "
            f"{clean['tok']})")
        tps_clean = clean["tok"] / clean["dt"]
        tps_fault = faulted["tok"] / faulted["dt"]
        chaos_result = {
            "requests": n_chaos,
            "output_tok_s": round(tps_fault, 2),
            "output_tok_s_clean": round(tps_clean, 2),
            "degradation_frac": round(1.0 - tps_fault / tps_clean, 4),
            "recoveries": faulted["recoveries"],
            "recovery_s": (round(faulted["recovery_s"], 3)
                           if faulted["recovery_s"] is not None
                           else None),
        }
        log(f"chaos pass: {tps_clean:.1f} tok/s clean -> "
            f"{tps_fault:.1f} tok/s under an injected hard crash "
            f"({faulted['recoveries']} recoveries, recovery_s="
            f"{chaos_result['recovery_s']})")

    # Fleet failover lever (ISSUE 15, GLLM_BENCH_FLEET=1): two
    # in-process replicas — real HTTP api_servers — behind the front
    # router core; a clean pass, then a pass with a time-based mid-pass
    # REPLICA KILL (engine + server torn down). Greedy ignore_eos makes
    # every stream replay-safe, so every stream on the dead replica
    # must MIGRATE and the client-side token count must not drop:
    # lost_tokens is asserted 0 — the cost of losing a replica shows up
    # as wall clock and failover_s, never as lost output.
    fleet_result = None
    if os.environ.get("GLLM_BENCH_FLEET", "0") not in ("", "0"):
        phase("fleet_pass")
        import threading as _th
        from gllm_tpu.entrypoints.api_server import serve as _serve
        from gllm_tpu.router import FrontRouter
        from gllm_tpu.router import core as _rcore
        n_fleet = min(n_requests, 8 if args.tiny else 16)
        fl_prompts = [list(p) for p in prompts[:n_fleet]]
        fl_tokens = [min(p.max_tokens, 64) for p in params[:n_fleet]]

        class _Sink:
            # FrontRouter.stream's downstream surface, minus the HTTP
            # hop — the router core + replica HTTP path is the measured
            # object; one SSE event per token makes counting exact
            def __init__(self):
                self.started = False
                self.tokens = 0
                self.finish = None
                self.error = None

            def start(self):
                self.started = True

            def send(self, ev):
                if "choices" in ev:
                    # one SSE event per generated token; the finish
                    # reason rides the LAST token's chunk, so events
                    # count tokens exactly
                    self.tokens += 1
                    fin = ev["choices"][0].get("finish_reason")
                    if fin:
                        self.finish = fin
                        if fin in ("error", "abort"):
                            self.error = f"finish={fin}"
                elif "error" in ev:
                    self.error = ev["error"].get("message")

            def done(self):
                pass

            def fail_json(self, status, obj, headers):
                self.error = f"{status}: {obj}"

        def fleet_arm(kill_delay_s=None):
            reps = []
            for _ in range(2):
                llm_r = LLM(config=engine_cfg, model_cfg=model_cfg)
                httpd = _serve(llm_r, "127.0.0.1", 0)
                _th.Thread(target=httpd.serve_forever,
                           daemon=True).start()
                reps.append(httpd)
            fr = FrontRouter(
                [f"127.0.0.1:{h.server_address[1]}" for h in reps],
                probe_interval_s=0.1, breaker_base_s=0.5,
                breaker_jitter=0.0, stream_idle_timeout_s=300.0)
            fo_before = _rcore._M_FAILOVERS.get(outcome="ok")
            _, fs_sum0, fs_n0 = _rcore._M_FAILOVER_S.snapshot()
            sinks = [_Sink() for _ in range(n_fleet)]
            timer = None
            try:
                t0 = time.monotonic()
                if kill_delay_s is not None:
                    def kill():
                        reps[0].state.engine.shutdown()
                        reps[0].shutdown()
                        reps[0].server_close()
                    timer = _th.Timer(kill_delay_s, kill)
                    timer.daemon = True
                    timer.start()
                threads = [_th.Thread(
                    target=fr.stream,
                    args=("completion",
                          {"prompt": p, "max_tokens": mt,
                           "temperature": 0, "ignore_eos": True,
                           "stream": True}, s),
                    daemon=True)
                    for p, mt, s in zip(fl_prompts, fl_tokens, sinks)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=600)
                    assert not t.is_alive(), "fleet-arm stream hung"
                dt_arm = time.monotonic() - t0
            finally:
                if timer is not None:
                    timer.cancel()
                fr.close()
                for h in reps:
                    try:
                        h.shutdown()
                        h.state.engine.shutdown()
                    except Exception:
                        pass        # the killed replica is already down
            _, fs_sum1, fs_n1 = _rcore._M_FAILOVER_S.snapshot()
            migrated = _rcore._M_FAILOVERS.get(outcome="ok") - fo_before
            errors = [s.error for s in sinks if s.error]
            assert not errors, f"fleet-arm stream errors: {errors[:3]}"
            return {"tok": sum(s.tokens for s in sinks), "dt": dt_arm,
                    "migrated": int(migrated),
                    "failover_s": (round((fs_sum1 - fs_sum0)
                                         / (fs_n1 - fs_n0), 3)
                                   if fs_n1 > fs_n0 else None)}

        clean = fleet_arm(None)
        assert clean["tok"] == sum(fl_tokens), (
            "clean fleet arm dropped tokens", clean["tok"],
            sum(fl_tokens))
        faulted = fleet_arm(max(0.05, 0.4 * clean["dt"]))
        lost = clean["tok"] - faulted["tok"]
        assert lost == 0, (
            "replica kill lost tokens despite journal-backed failover "
            f"({faulted['tok']} vs {clean['tok']})")
        assert faulted["migrated"] > 0, \
            "the mid-pass kill migrated no stream"
        tps_clean = clean["tok"] / clean["dt"]
        tps_fault = faulted["tok"] / faulted["dt"]
        fleet_result = {
            "requests": n_fleet,
            "replicas": 2,
            "output_tok_s": round(tps_fault, 2),
            "output_tok_s_clean": round(tps_clean, 2),
            "degradation_frac": round(1.0 - tps_fault / tps_clean, 4),
            "streams_migrated": faulted["migrated"],
            "failover_s": faulted["failover_s"],
            "lost_tokens": int(lost),
        }
        log(f"fleet pass: {tps_clean:.1f} tok/s clean -> "
            f"{tps_fault:.1f} tok/s across a mid-pass replica kill "
            f"({faulted['migrated']} streams migrated, failover_s="
            f"{faulted['failover_s']}, lost_tokens=0)")

    # Disaggregated prefill/decode lever (ISSUE 17, GLLM_BENCH_PD=1):
    # one prefill-role + one decode-role in-process replica behind the
    # front router core. Every stream prefills on the prefill pool, the
    # prefix KV chain is PUSHED to the decode replica at first token,
    # and the stream migrates there via the journaled continuation path.
    # Asserted invariants: reprefill_tokens == 0 (every pushed page is
    # claimed as cached tokens by the decode side — the decode pool
    # never recomputes the prompt) and lost_tokens == 0 — including
    # under a drain-triggered scale-down of the decode replica mid-pass.
    pd_result = None
    if os.environ.get("GLLM_BENCH_PD", "0") not in ("", "0"):
        phase("pd_pass")
        import copy as _copy
        import statistics as _stats
        import threading as _th
        from gllm_tpu.entrypoints.api_server import serve as _serve
        from gllm_tpu.kvstore import stats as _kvs
        from gllm_tpu.router import FrontRouter
        n_pd = min(n_requests, 4 if args.tiny else 8)
        pd_prompts = [list(p) for p in prompts[:n_pd]]
        pd_tokens = [min(p.max_tokens, 32) for p in params[:n_pd]]
        page = engine_cfg.cache.page_size
        # full prefix pages per prompt — the zero-re-prefill ledger
        pd_pages = [max(0, (len(p) - 1) // page) for p in pd_prompts]

        def _pd_cfg(role):
            cfg = _copy.deepcopy(engine_cfg)
            cfg.scheduler.pool_role = role
            cfg.cache.enable_prefix_caching = True
            cfg.cache.kv_host_pool_pages = max(
                256, 2 * sum(pd_pages) + 8)
            cfg.cache.prefix_serve_port = 0
            cfg.validate()
            return cfg

        class _PdSink:
            def __init__(self):
                self.started = False
                self.tokens = 0
                self.error = None
                self.t0 = None
                self.ttft = None

            def start(self):
                self.started = True

            def send(self, ev):
                if "choices" in ev:
                    if self.ttft is None and self.t0 is not None:
                        self.ttft = time.monotonic() - self.t0
                    self.tokens += 1
                    fin = ev["choices"][0].get("finish_reason")
                    if fin in ("error", "abort"):
                        self.error = f"finish={fin}"
                elif "error" in ev:
                    self.error = ev["error"].get("message")

            def done(self):
                pass

            def fail_json(self, status, obj, headers):
                self.error = f"{status}: {obj}"

        def pd_arm(drain_decode_frac=None, clean_dt=None):
            reps = []
            for role in ("prefill", "decode"):
                llm_r = LLM(config=_pd_cfg(role), model_cfg=model_cfg)
                httpd = _serve(llm_r, "127.0.0.1", 0)
                _th.Thread(target=httpd.serve_forever,
                           daemon=True).start()
                reps.append(httpd)
            addrs = [f"127.0.0.1:{h.server_address[1]}" for h in reps]
            fr = FrontRouter(addrs, probe_interval_s=0.1,
                             breaker_base_s=0.5, breaker_jitter=0.0,
                             stream_idle_timeout_s=300.0)
            push0 = _kvs.PUSH_PAGES.get()
            hit0 = obs_metrics.REGISTRY.get(
                "gllm_prefix_cache_hit_tokens_total").get()
            sinks = [_PdSink() for _ in range(n_pd)]
            timer = None
            try:
                t0 = time.monotonic()
                if drain_decode_frac is not None:
                    delay = max(0.05, drain_decode_frac * clean_dt)
                    timer = _th.Timer(
                        delay,
                        lambda: fr.drain_replica(addrs[1], migrate=True))
                    timer.daemon = True
                    timer.start()

                def run(p, mt, s):
                    s.t0 = time.monotonic()
                    fr.stream("completion",
                              {"prompt": p, "max_tokens": mt,
                               "temperature": 0, "ignore_eos": True,
                               "stream": True}, s)

                threads = [_th.Thread(target=run, args=(p, mt, s),
                                      daemon=True)
                           for p, mt, s in zip(pd_prompts, pd_tokens,
                                               sinks)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=600)
                    assert not t.is_alive(), "pd-arm stream hung"
                dt_arm = time.monotonic() - t0
            finally:
                if timer is not None:
                    timer.cancel()
                fr.close()
                for h in reps:
                    h.shutdown()
                    h.state.engine.shutdown()
            errors = [s.error for s in sinks if s.error]
            assert not errors, f"pd-arm stream errors: {errors[:3]}"
            pushed = int(_kvs.PUSH_PAGES.get() - push0)
            hit_tok = int(obs_metrics.REGISTRY.get(
                "gllm_prefix_cache_hit_tokens_total").get() - hit0)
            return {"tok": sum(s.tokens for s in sinks), "dt": dt_arm,
                    "pushed": pushed, "hit_tok": hit_tok,
                    "ttft_p50": round(_stats.median(
                        s.ttft for s in sinks if s.ttft is not None), 4)}

        clean = pd_arm()
        want_tok = sum(pd_tokens)
        want_pages = sum(pd_pages)
        assert clean["tok"] == want_tok, (
            "clean pd arm dropped tokens", clean["tok"], want_tok)
        # zero re-prefill: the push moved EVERY full prefix page, and
        # the decode side claimed every pushed token as cached
        assert clean["pushed"] == want_pages, (
            "push moved fewer pages than the prompts' prefix chains",
            clean["pushed"], want_pages)
        reprefill = max(0, want_pages * page - clean["hit_tok"])
        assert reprefill == 0, (
            f"decode pool re-prefilled {reprefill} pushed tokens")
        # drain-triggered scale-down mid-pass: the decode replica is
        # admin-drained with migrate=True while streams run on it —
        # journal-backed migration keeps every client stream whole
        drained = pd_arm(drain_decode_frac=0.4, clean_dt=clean["dt"])
        lost = want_tok - drained["tok"]
        assert lost == 0, (
            "drain-triggered scale-down lost tokens "
            f"({drained['tok']} vs {want_tok})")
        pd_result = {
            "requests": n_pd,
            "ttft_p50": clean["ttft_p50"],
            "pushed_pages": clean["pushed"],
            "reprefill_tokens": int(reprefill),
            "lost_tokens": int(lost),
            "drain_ttft_p50": drained["ttft_p50"],
        }
        log(f"pd pass: ttft_p50={clean['ttft_p50']}s, "
            f"{clean['pushed']} pages pushed, reprefill_tokens=0, "
            f"lost_tokens=0 across a mid-pass decode drain")

    phase("report")
    # MFU: every processed token (prompt + output) makes one forward pass.
    total_proc = total_in + total_out
    flops = model_flops(model_cfg, prompts, params,
                        engine_cfg.scheduler.max_prefill_tokens)
    peak = chip_peak_flops()
    mfu = round(flops / dt / peak, 4) if peak else None
    log(f"measured pass: {dt:.2f}s → {value:.1f} output tok/s "
        f"({n_requests / dt:.2f} req/s, "
        f"{total_proc / dt:.0f} processed tok/s, mfu={mfu})")
    result = {
        "metric": METRIC,
        "value": round(value, 2),
        "unit": "tok/s",
        "vs_baseline": round(value / BASELINE_TOK_S, 4),
        "mfu": mfu,
        # KV-cache efficiency (ISSUE 5): the active storage dtype and
        # the effective KV bytes streamed per step over the measured
        # pass — the int8 A/B (GLLM_BENCH_KV_DTYPE) halves the latter
        # against the decode HBM-bandwidth floor.
        "kv_cache_dtype": kv_dtype,
        "kv_bytes_per_step": kv_bytes_per_step(kv_read, step_summary),
        # First-class regression tracker (ISSUE 4): fraction of
        # measured-pass wall time spent in plain (UNfused) decode
        # iterations — the r5 "18/59 steps at 90.8 ms" class. The
        # trajectory watches this directly instead of digging through
        # metrics.steps.by_kind.
        "unfused_frac": step_summary.get("unfused_frac"),
        # On-device finish (ISSUE 6): wasted (dead-row) share of executed
        # fused-block sub-steps over the measured pass — the post-EOS
        # waste the in-loop alive mask + early exit remove. None when
        # ondevice_finish is off (GLLM_BENCH_ODF=0 A/B arm).
        "dead_substep_frac": step_summary.get("dead_substep_frac"),
        "chain_breaks": step_summary.get("chain_breaks_by_reason") or {},
        # Performance attribution (ISSUE 10): where the measured pass's
        # wall clock went (host phases vs device by kind), how much
        # device wall hid under host work, and the device-idle share —
        # every future BENCH_r*.json says WHY it got its number.
        "host_ms_by_phase": step_summary.get("host_ms_by_phase"),
        "device_ms_by_kind": step_summary.get("device_ms_by_kind"),
        "overlap_efficiency": step_summary.get("overlap_efficiency"),
        "bubble_frac": step_summary.get("bubble_frac"),
        # Pipelined loop (ISSUE 11, GLLM_BENCH_PIPELINED A/B): sustained
        # run-ahead depth + stall taxonomy — the bubble_frac's WHY; the
        # --tiny rung also carries the in-process sync-control delta.
        "pipelined_loop": bool(engine_cfg.pipelined_loop),
        "mean_inflight_depth": step_summary.get("mean_inflight_depth"),
        "loop_stalls": step_summary.get("loop_stalls_by_reason") or {},
        # Unified step (ISSUE 12, GLLM_BENCH_UNIFIED A/B): one dispatch
        # family — share of steps that were mixed unified batches
        # (chains absorbing arrivals) and the distinct shape-bucket
        # signatures the runner compiled/warmed over the whole run (the
        # two-population decode+mixed split this flag collapses).
        "unified_step": bool(engine_cfg.unified_step),
        "mixed_step_frac": step_summary.get("mixed_step_frac"),
        "warmed_buckets": getattr(llm.runner, "num_shape_signatures",
                                  None),
        # Fused speculation (ISSUE 13, GLLM_BENCH_SPEC_FUSED A/B): the
        # window draft-acceptance rate and committed tokens per device
        # dispatch — the per-dispatch multiplier the fused path buys
        # (None accept rate on draft-hostile windows that never drafted)
        "spec_fused": bool(engine_cfg.spec_fused),
        "spec_accept_rate": step_summary.get("spec_accept_rate"),
        "tokens_per_dispatch": step_summary.get("tokens_per_dispatch"),
        "metrics": metrics_snapshot,
    }
    if bench_pp > 1:
        # pp topology arm (ISSUE 20, GLLM_BENCH_PP): tag the JSON so pp
        # and single-runner rungs never get compared as like-for-like
        result["parallel_pp"] = bench_pp
    if pp_ab is not None:
        result["pp_ab"] = pp_ab
    if bubble_delta is not None:
        result.update(bubble_delta)
    if unified_ab is not None:
        result["unified_ab"] = unified_ab
    if spec_fused_ab is not None:
        result["spec_fused_ab"] = spec_fused_ab
    if trace_path is not None:
        result["trace_path"] = trace_path
    if sampled_result is not None:
        result["sampled"] = sampled_result
    if prefix_result is not None:
        # tiered prefix store A/B (ISSUE 9, GLLM_BENCH_PREFIX=1):
        # repeated-system-prompt hit rate + TTFT with the disk tier vs
        # full recompute — first-class so the trajectory tracks it
        result["prefix"] = prefix_result
        result["prefix_tiers"] = True
    if chaos_result is not None:
        # self-healing recovery (ISSUE 14, GLLM_BENCH_CHAOS=1): serving
        # throughput under an injected hard crash vs clean, and the
        # latch-to-ready recovery wall — first-class
        result["chaos"] = chaos_result
    if fleet_result is not None:
        # fleet failover (ISSUE 15, GLLM_BENCH_FLEET=1): two replicas
        # behind the front router, a mid-pass replica kill — throughput
        # degradation, streams migrated, failover wall, and the
        # zero-lost-tokens contract — first-class
        result["fleet"] = fleet_result
    if pd_result is not None:
        # disaggregated prefill/decode (ISSUE 17, GLLM_BENCH_PD=1): one
        # prefill + one decode replica behind the router — TTFT, pages
        # pushed, and the zero-re-prefill / zero-lost-tokens contracts
        # (the latter across a drain-triggered scale-down) — first-class
        result["pd"] = pd_result
    # Regression gate (ISSUE 20, GLLM_BENCH_BASELINE=<path>): compare
    # the measured pass against a committed BENCH JSON — the verdict
    # rides the result JSON either way; a regression exits nonzero AFTER
    # the JSON lands (the number is never lost to the gate).
    gate_rc = 0
    baseline_path = os.environ.get("GLLM_BENCH_BASELINE", "")
    if baseline_path and args.profile == "minimal":
        # the minimal rung's shorter-context workload is not comparable
        # to a committed full/conservative baseline (see PROFILES) — a
        # gate verdict here would be noise, and failing it would stop
        # the supervisor ladder before the rung that matters
        log("[bench] GLLM_BENCH_BASELINE set but profile=minimal is "
            "not comparable; gate deferred to the full rung")
        baseline_path = ""
    if baseline_path:
        gate_rc = run_bench_gate(result, baseline_path)
    print(json.dumps(result))
    if gate_rc:
        sys.exit(gate_rc)


if __name__ == "__main__":
    main()
