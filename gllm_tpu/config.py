"""Engine configuration.

One dataclass is the single config schema for the whole engine — the TPU-native
equivalent of the reference's constructor-kwarg threading
(/root/reference/gllm/llm_engine.py:34-75) and CLI flag surface
(/root/reference/gllm/entrypoints/api_server.py:267-508).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from gllm_tpu.utils import cdiv


@dataclasses.dataclass
class SchedulerConfig:
    """Scheduling policy knobs (reference: scheduler.py:16-163, api_server flags
    --schedule-method/--maxd/--maxp/--minp/--iterp)."""

    schedule_method: str = "chunked_prefill"  # chunked_prefill | token_throttling | split_pd
    max_decode_seqs: int = 256            # --maxd: decode seqs per batch
    max_prefill_tokens: int = 2048        # --maxp: prefill token budget per batch
    min_prefill_tokens: int = 128         # --minp: throttling lower clamp
    # --min-token-bucket: the smallest token bucket of a mixed step (a
    # power of two). Every bucket is a step program to compile; a
    # deployment whose prompts are long raises it and pads its short last
    # chunks instead of building programs for them.
    min_token_bucket: int = 16
    # --min-row-bucket / --min-page-bucket: the same for the other two
    # axes of a step program's shape (rows of sequences, page-table
    # width). A deployment that runs full raises them to its --maxd and
    # to its --max-model-len in pages and builds one program a token
    # bucket; steps under the floor are padded to it.
    min_row_bucket: int = 8
    min_page_bucket: int = 4
    iter_smooth: int = 16                 # --iterp: waiting-token smoothing divisor
    init_new_token_ratio: float = 0.7     # adaptive KV admission ramp start
    min_new_token_ratio: float = 0.1      # ramp floor
    new_token_ratio_decay_steps: int = 600
    # KV free-ratio reserve used by token throttling's prefill budget ramp
    # (reference scheduler.py:613-696).
    throttle_reserve: float = 0.2
    # pd-pool topology role this replica advertises on /server_info
    # (--pool-role, docs/pd_pools.md): the front router places new
    # prompts on the prefill pool and migrates streams to the decode
    # pool at first token. "mixed" (default) keeps the replica eligible
    # for both phases — the single-replica and legacy-fleet behavior.
    pool_role: str = "mixed"              # prefill | decode | mixed


@dataclasses.dataclass
class CacheConfig:
    """Paged KV cache geometry (reference: memory_manager.py, --page-size,
    --gpu-memory-util)."""

    page_size: int = 16
    memory_util: float = 0.9              # fraction of free HBM given to KV
    num_pages: Optional[int] = None       # explicit override (tests/benchmarks)
    # Paged-KV storage dtype (--kv-cache-dtype). "auto" stores the model
    # dtype (byte-identical legacy). "int8" stores quantized K/V with
    # running per-page per-head f32 scales, dequantized inside the
    # attention kernels — halves KV read bandwidth and roughly doubles
    # page capacity from the same HBM budget at a bounded numerics cost
    # (docs/kv_quantization.md; unsupported for MLA/hybrid models).
    kv_cache_dtype: str = "auto"   # auto | bfloat16 | float16 | float32
                                   # | fp8 | int8
    enable_prefix_caching: bool = False
    # Hybrid (GDN) models: cached-prefix SSM state slots (reference
    # --max-snapshot-ssm-slots; 0 disables the SSM half of prefix caching)
    ssm_snapshot_slots: int = 64
    # Host-RAM KV tier size in GiB (gllm_tpu/kvswap, --kv-host-pool-gb):
    # pinned host pages mirroring the device paged layout. Preemption
    # victims swap out instead of recomputing, and evicted prefix-cache
    # pages spill here so match_prefix can restore them. 0 = tier
    # disabled (the pre-offload recompute behavior, byte for byte).
    kv_host_pool_gb: float = 0.0
    # Explicit host page count override (tests / benchmarks); wins over
    # the GB sizing when set.
    kv_host_pool_pages: Optional[int] = None
    # --swap-policy: "auto" enables the tier iff a host pool is
    # configured; "swap" requires one (config error otherwise);
    # "recompute" forces the legacy free-and-recompute preemption even
    # with a pool configured.
    swap_policy: str = "auto"
    # ---- tiered prefix store (gllm_tpu/kvstore, docs/kv_offload.md) ----
    # Disk tier behind the host pool (--kv-disk-path): content-addressed
    # prefix-page files written on host-tier eviction, probed on host
    # miss, byte-budgeted LRU (--kv-disk-gb). Requires the host pool and
    # prefix caching; None disables the tier (byte-identical legacy).
    kv_disk_path: Optional[str] = None
    kv_disk_gb: float = 4.0
    # Cluster tier (--prefix-peers): comma-separated host:port of peer
    # replicas' prefix servers — match_prefix can restore a prefix
    # another replica computed. --prefix-serve-port starts this
    # replica's serving endpoint (0 = ephemeral; None = don't serve).
    prefix_peers: Optional[str] = None
    prefix_serve_port: Optional[int] = None

    @property
    def host_pool_configured(self) -> bool:
        return (self.swap_policy != "recompute"
                and (self.kv_host_pool_gb > 0
                     or bool(self.kv_host_pool_pages)))

    @property
    def kvstore_configured(self) -> bool:
        return bool(self.kv_disk_path or self.prefix_peers
                    or self.prefix_serve_port is not None)


@dataclasses.dataclass
class ParallelConfig:
    """Mesh geometry. The reference exposes --pp/--tp/--dp/--enable-ep
    (dist_utils.py:149-263); on TPU these become named mesh axes over which
    jit/GSPMD lays out shardings and inserts ICI collectives."""

    pp: int = 1
    tp: int = 1
    dp: int = 1
    # Sequence/context parallelism (beyond the reference, which has none —
    # SURVEY.md §2.2): long single-seq prefill chunks run causal ring
    # attention over the ``sp`` mesh axis (parallel/ring_attention.py);
    # decode and mixed batches use the paged path with activations
    # sharded over sp. Composes with tp; requires pp == dp == 1.
    sp: int = 1
    enable_ep: bool = False
    # Explicit per-stage layer counts (reference --assigned-layers,
    # dist_utils.py:494-528); None → even split.
    assigned_layers: Optional[list] = None

    @property
    def world_size(self) -> int:
        return self.pp * self.tp * self.dp * self.sp


@dataclasses.dataclass
class EngineConfig:
    model: str = ""
    tokenizer: Optional[str] = None
    dtype: str = "bfloat16"
    seed: int = 0
    max_model_len: int = 4096
    max_num_seqs: int = 256
    load_format: str = "auto"             # auto | dummy (weight-less bring-up,
                                          # reference api_server.py:293-299)
    # Overlap scheduling (reference --overlap-scheduling + OverlapWorker):
    # chain decode steps on-device so the host round trip between decode
    # iterations disappears.
    overlap_scheduling: bool = False
    # In-flight chained decode steps when overlap_scheduling is on. Depth
    # 2 hides host batch-building; deeper pipelines also hide the
    # dispatch round trip.
    overlap_depth: int = 2
    # Fuse up to K chained decode steps into ONE device program
    # (lax.scan over the step axis): one dispatch + one token fetch per K
    # tokens/seq. The lever when dispatch latency is high; trades up to K-1 wasted steps per EOS
    # unless ondevice_finish is on. Legacy name — decode_chain_len is the
    # canonical knob and wins when both are set.
    multi_step_decode: int = 1
    # Canonical fused-chain length (--decode-chain-len): K decode steps
    # per device dispatch. None defers to multi_step_decode, except that
    # ondevice_finish (which removes the post-EOS waste that made long
    # chains risky) raises an unset chain length to 16 — the scheduler's
    # page-feasibility check still shortens any individual block that
    # would not fit its page bucket.
    decode_chain_len: Optional[int] = None
    # On-device finish detection (--ondevice-finish, fused multi-step
    # blocks only): the fused scan compares each sampled token against
    # the row's EOS/stop-token set and folds the result into a carried
    # alive mask (position frozen, KV writes to the dummy page — the
    # same freeze machinery length deaths use), and the block driver
    # early-exits once every row is dead instead of burning the
    # remaining sub-steps. The precomputed active_until becomes a
    # conservative upper bound instead of the only death mechanism; the
    # per-row finish step returns with the token block. Token streams
    # are byte-identical either way (the host discards post-death
    # tokens in both modes); off = byte-identical legacy device
    # programs. docs/overlap_scheduling.md#on-device-finish.
    ondevice_finish: bool = False
    # Bubble-zero pipelined engine loop (--pipelined-loop,
    # docs/overlap_scheduling.md#pipelined-loop): when a decode chain
    # cannot extend (finish, compaction, membership growth), the engine
    # speculatively RE-FORMS the next pure-decode batch off *promised*
    # token counts — the sampled ids stay on device and are spliced in
    # as the new batch's inputs — instead of draining the pipeline and
    # rebuilding only after the collect lands. Divergence between
    # promised and actual state (host-side EOS/stop, stop strings) is
    # reconciled at collect time by invalidating and rebuilding exactly
    # the speculated entries (the reference's OverlapWorker/FutureMap
    # design, PAPER.md §4-5). Greedy and seeded token streams are
    # byte-identical to the sync loop; implies overlap_scheduling.
    # False = today's loop, byte for byte.
    pipelined_loop: bool = False
    # Persistent-slot decode batching (--decode-slot-batching, overlap
    # scheduling only): chain membership becomes slot-based, so fused
    # decode chains survive sequence finishes — a finished row is masked
    # dead (a HOLE: position frozen, KV writes to the dummy page, sampled
    # tokens discarded) instead of forcing a sync re-form, newly
    # decode-ready sequences JOIN vacant slots at chain boundaries
    # without a shape-signature change, and the batch compacts only when
    # live occupancy drops below its pow2 seq bucket. False = legacy
    # all-or-nothing chain membership, byte-identical token streams.
    decode_slot_batching: bool = False
    # Ramp policy (--chain-under-prefill): with prefill work waiting,
    # chain up to this many decode steps before yielding ONE sync pass to
    # prefill (the chain then resumes off its on-device tokens). 0 =
    # legacy: any waiting arrival forces every subsequent step through
    # the unfused sync path until the queue empties. Only meaningful with
    # overlap_scheduling; the token-throttling decode budget bounds how
    # much decode each yielded pass carries.
    chain_under_prefill: int = 0
    # In-flight microbatches for pp>1 (None → pp, the reference's depth:
    # pp_size batches running, scheduler.py:358-364). 1 forces serialized
    # launch-collect — the control arm for measuring pipeline overlap.
    pp_pipeline_depth: Optional[int] = None
    # Prompt-lookup (n-gram) speculative decoding — beyond the reference:
    # propose up to spec_k draft tokens from the most recent spec_ngram
    # match in the sequence's own history and verify them in ONE forward
    # pass (k+1 rows through the chunked-prefill machinery). Greedy
    # verification makes outputs byte-identical to plain greedy decoding
    # by construction; per-seq eligibility (temperature 0, no penalties,
    # no logprobs) gates drafts, everything else runs normally in the
    # same batch. On TPU this multiplies tokens-per-dispatch and turns
    # decode GEMVs into small GEMMs for the MXU.
    spec_decode: Optional[str] = None        # None | "ngram"
    spec_k: int = 4
    spec_ngram: int = 2
    # On-device speculation (--spec-fused, requires spec_decode="ngram";
    # docs/speculative_decoding.md#fused): draft → verify →
    # accept/reject → correction-token emission run INSIDE the jitted
    # multi-step program, so a decode chain of K sub-steps emits up to
    # K·(spec_k+1) tokens in one dispatch. The runner keeps a bounded
    # per-slot recent-token ring on device (seeded from committed tokens
    # at chain splice time, then advanced by the loop carry), a
    # vectorized n-gram match proposes drafts without host readback, and
    # verify rows ride the ragged kernel as q_len=k+1 rows with
    # on-device acceptance. Speculation and chained dispatch stop being
    # mutually exclusive: schedule_chain accepts spec rows (the
    # chain_breaks reason="spec" class is retired) and the FutureMap's
    # scheduled frontiers become token-count UPPER bounds trimmed to the
    # actual accepted counts at collect. Greedy token streams stay
    # byte-identical to host-driven spec decode AND to plain decode;
    # sampled rows keep the rejection-sampling distribution guarantee
    # (draws keyed by fold_in(seed, out_step)). Inert (warned) for
    # hybrid GDN, multimodal, pp>1 and dp>1 — those keep the host-driven
    # snapshot path. Implies overlap_scheduling; off = byte-identical
    # host-driven speculation.
    spec_fused: bool = False
    # Quantization: None | "int8" | "fp8" | "int4" (weight-only,
    # per-output-channel, XLA-fused dequant) | "w8a8" (int8 weights +
    # per-token int8 activations on the MXU) — reference quantization
    # stack SURVEY §2.6
    quantization: Optional[str] = None
    enforce_eager: bool = False           # disable donation/async tricks (debug)
    # Minimum single-seq prefill chunk (tokens) that routes through ring
    # attention when parallel.sp > 1; shorter chunks / mixed batches /
    # decode use the paged path with activations sharded over sp.
    sp_ring_threshold: int = 1024
    # Bounds on the pixel count the multimodal processor resizes images /
    # video frames into (reference --mm-processor-min/max-pixels,
    # api_server.py:488-494 → encoder_engine.py:67-74). max_pixels is the
    # operator lever that keeps large-image ViT inputs inside HBM.
    mm_processor_min_pixels: Optional[int] = None
    mm_processor_max_pixels: Optional[int] = None
    # Resolve a non-local model id via HF-hub snapshot download (file-lock
    # serialized, reference model_loader.py hub path). Off by default:
    # loads are local-path-only unless explicitly opted in.
    allow_hub_download: bool = False
    attention_impl: str = "auto"          # auto | pallas | xla
    # Performance-attribution tracing (docs/observability.md#tracing):
    # request-scoped span trees (gllm_tpu/obs/spans.py) + the per-step
    # phase fields on steptrace events, exported via GET /trace and
    # ``obs.dump --format chrome``. Default ON — pure host dict work off
    # the device path (a span a prompt chunk and the roll-ups at a
    # request's finish; nothing per decoding row or step);
    # ``--no-tracing`` disables the span layer for this engine (token
    # streams are byte-identical either way; the steptrace ring, its
    # ``first_token`` event a request included, stays on).
    tracing: bool = True
    # ---- request-lifecycle robustness (docs/robustness.md) ----
    # Admission control: cap the serving engine's intake queue and the
    # number of resident (handle-open) requests; over-limit submits are
    # rejected (HTTP 429 with Retry-After) instead of queueing without
    # bound. 0 = unbounded (legacy).
    max_queued_requests: int = 0
    max_resident_requests: int = 0
    # Default per-request wall-clock TTL in seconds: a request still
    # waiting or still generating this long after submit is aborted with
    # finish reason "deadline". Per-request SamplingParams.deadline_s /
    # submit(deadline_s=...) override. 0 = no TTL (legacy).
    request_deadline_s: float = 0.0
    # Consecutive failed engine steps before the serving engine latches
    # "unhealthy" (readiness 503, admission closed; liveness stays up).
    # Individual failures only quarantine their own batch.
    max_step_failures: int = 3
    # Watchdog: flip readiness while the engine-thread heartbeat is
    # older than this many seconds (a hung device dispatch blocks the
    # loop inside collect). Must exceed the longest legitimate blocking
    # operation (first-dispatch XLA compiles!). 0 = watchdog off.
    watchdog_stall_s: float = 0.0
    # shutdown(drain=True): how long to wait for in-flight requests
    # before aborting them with terminal chunks.
    drain_timeout_s: float = 5.0
    # ---- self-healing recovery (docs/robustness.md#recovery-lifecycle) ----
    # Supervised in-process rebuild (--engine-recovery): when the
    # unhealthy latch fires (max_step_failures consecutive failures, an
    # engine-loop death, or a watchdog HARD stall), an EngineSupervisor
    # tears the engine down and rebuilds it in-process — /readyz reports
    # "recovering" with Retry-After, journaled retry-safe requests
    # (seeded or greedy) replay onto the rebuilt engine and continue
    # from their committed prefix, and the rebuilt engine warms from the
    # disk prefix tier + the persistent compile cache. False = today's
    # one-way latch (permanent unhealthy until process restart).
    engine_recovery: bool = False
    # Crash-loop latch: this many FAILED rebuild attempts within
    # rebuild_window_s seconds latch the permanent unhealthy state (the
    # pre-recovery behavior is the bounded fallback — never an infinite
    # rebuild loop).
    max_rebuilds: int = 3
    rebuild_window_s: float = 300.0
    # Exponential backoff between rebuild attempts: first retry waits
    # rebuild_backoff_s, doubling per failure, capped at
    # rebuild_backoff_max_s. (The first attempt runs immediately.)
    rebuild_backoff_s: float = 0.25
    rebuild_backoff_max_s: float = 30.0
    # Watchdog HARD stall: a heartbeat older than this abandons the
    # wedged engine thread and triggers the supervised rebuild (the soft
    # watchdog_stall_s threshold only flips readiness). Requires
    # engine_recovery and a running watchdog; 0 = soft flips only.
    watchdog_hard_stall_s: float = 0.0
    # Deterministic fault injection spec (gllm_tpu/faults.py grammar:
    # "point[:after_n[:count]][,...]"), armed when the serving engine
    # starts; also armable via GLLM_FAULT_INJECT. Empty = disarmed.
    fault_inject: str = ""
    # Disagg LM nodes: drop the vision tower from params after load —
    # visual embeddings arrive from the encoder fleet (reference
    # DisaggConfig.skip_visual). The engine can then only serve disagg
    # (or text-only) requests.
    skip_visual_load: bool = False
    scheduler: SchedulerConfig = dataclasses.field(default_factory=SchedulerConfig)
    cache: CacheConfig = dataclasses.field(default_factory=CacheConfig)
    parallel: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)

    @property
    def max_pages_per_seq(self) -> int:
        return cdiv(self.max_model_len, self.cache.page_size)

    def validate(self) -> None:
        if self.enforce_eager:
            # The reference's enforce_eager drops CUDA-graph capture; the
            # analogues here are the async-execution tricks — chained
            # overlap decode and the fused multi-step loop. Plain
            # one-dispatch-per-step execution remains.
            if self.overlap_scheduling or self.multi_step_decode > 1:
                import logging
                logging.getLogger(__name__).warning(
                    "enforce_eager overrides overlap_scheduling/"
                    "multi_step_decode (were %s/%d) — plain per-step "
                    "execution", self.overlap_scheduling,
                    self.multi_step_decode)
            self.overlap_scheduling = False
            self.multi_step_decode = 1
            self.decode_chain_len = None
            self.ondevice_finish = False
            self.decode_slot_batching = False
            self.chain_under_prefill = 0
            self.pipelined_loop = False
            self.spec_fused = False
        if self.pipelined_loop and not self.overlap_scheduling:
            # the pipelined loop is the overlap machinery run one step
            # further ahead — chains are its primary edge; lifting the
            # flag keeps "--pipelined-loop" a one-flag opt-in
            self.overlap_scheduling = True
        if self.chain_under_prefill < 0:
            raise ValueError("chain_under_prefill must be >= 0")
        if self.overlap_depth < 1:
            raise ValueError("overlap_depth (--inflight-depth) must be "
                             ">= 1")
        if self.pipelined_loop and self.parallel.pp > 1 \
                and self.parallel.dp > 1:
            # The pipelined loop composes with pp OR dp, but the combined
            # grid would need per-replica stage pipelines driven by the
            # run-ahead loop — refuse loudly rather than silently fall
            # back to the legacy sync dispatch
            # (docs/overlap_scheduling.md#topology-matrix).
            raise ValueError(
                "--pipelined-loop composes with pp>1 OR dp>1, not both at "
                "once: run pp with dp=1 or dp with pp=1, or drop the flag "
                "for the legacy sync pipeline")
        if self.spec_fused:
            if self.spec_decode != "ngram":
                raise ValueError(
                    "spec_fused (--spec-fused) requires "
                    "spec_decode='ngram'")
            if self.parallel.pp > 1:
                # The fused draft+verify block is ONE device program (a
                # while_loop over sub-steps spanning the whole layer
                # stack); pipeline stages are separate per-device
                # programs, so the block cannot span them. A loud error
                # replaces the old warn-and-clear (flags must never
                # silently no-op); host-driven speculation
                # (--spec-decode ngram without --spec-fused) works
                # under pp.
                raise ValueError(
                    "--spec-fused is not supported with pp > 1: the "
                    "fused block cannot span pipeline stages — drop "
                    "--spec-fused to keep host-driven speculation")
            if self.parallel.dp > 1:
                # The dp fast path runs lockstep super-steps over ONE
                # stacked program; fused spec blocks would need stacked
                # per-replica carry state (not yet built). Loud error,
                # same rationale as the pp case above.
                raise ValueError(
                    "--spec-fused is not supported with dp > 1: fused "
                    "blocks are single-replica — drop --spec-fused to "
                    "keep host-driven speculation")
            if not self.overlap_scheduling:
                # fused draft+verify lives in the chained dispatch body —
                # lifting the flag keeps "--spec-fused" a one-flag opt-in
                # (same discipline as pipelined_loop)
                self.overlap_scheduling = True
        if self.decode_chain_len is not None:
            if self.decode_chain_len < 1:
                raise ValueError("decode_chain_len must be >= 1")
            self.multi_step_decode = self.decode_chain_len
        elif (self.spec_fused and self.multi_step_decode == 1):
            # one fused block should amortize several verify rounds per
            # dispatch; page feasibility still shortens individual blocks
            self.multi_step_decode = 8
        elif (self.ondevice_finish and self.overlap_scheduling
                and self.multi_step_decode == 1):
            # with post-EOS waste gone, the conservative single-step
            # default stops paying for itself — chain 16 steps per
            # dispatch (page feasibility still bounds each block)
            self.multi_step_decode = 16
        if not self.overlap_scheduling and not self.enforce_eager and (
                self.ondevice_finish or self.decode_chain_len is not None):
            # same silent-drop class the assigned_layers check guards:
            # the engine only forms fused chains under overlap scheduling
            import logging
            logging.getLogger(__name__).warning(
                "ondevice_finish/decode_chain_len have no effect without "
                "overlap_scheduling — fused decode chains never form")
        if self.parallel.assigned_layers is not None \
                and len(self.parallel.assigned_layers) != self.parallel.pp:
            # catch --assigned-layers with a forgotten/mismatched --pp at
            # config time (pp_runner re-checks per-stage sums later, but
            # only engages for pp > 1 — pp=1 would silently drop the flag)
            raise ValueError(
                f"assigned_layers has {len(self.parallel.assigned_layers)}"
                f" entries but pp={self.parallel.pp}")
        if self.cache.page_size <= 0:
            raise ValueError("page_size must be positive")
        if self.cache.kv_cache_dtype not in (
            "auto", "bfloat16", "float16", "float32", "fp8", "int8",
        ):
            raise ValueError(
                f"unknown kv_cache_dtype {self.cache.kv_cache_dtype!r} "
                "(choices: auto, bfloat16, float16, float32, fp8, int8)")
        if self.scheduler.max_prefill_tokens < self.cache.page_size:
            raise ValueError("max_prefill_tokens must cover at least one page")
        if self.scheduler.schedule_method not in (
            "chunked_prefill", "token_throttling", "split_pd",
        ):
            raise ValueError(
                f"unknown schedule_method {self.scheduler.schedule_method!r}")
        if self.scheduler.pool_role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"unknown pool_role {self.scheduler.pool_role!r} "
                "(choices: prefill, decode, mixed)")
        if self.quantization not in (None, "int8", "fp8", "int4",
                                     "w8a8", "fp8_block"):
            raise ValueError(
                f"unknown quantization {self.quantization!r} "
                "(choices: int8, fp8, int4, w8a8, fp8_block)")
        if self.spec_decode not in (None, "ngram"):
            raise ValueError(
                f"unknown spec_decode {self.spec_decode!r} "
                "(choices: ngram)")
        if self.spec_decode is not None:
            # May be combined with overlap_scheduling/multi_step_decode:
            # speculation then OWNS decode dispatch (schedule_chain
            # defers — drafting needs committed token values a chained
            # step leaves on device), each accepted draft replacing the
            # dispatch round trip a chain would have hidden; prefill
            # batches still pipeline through the in-flight depth.
            if self.spec_k < 1 or self.spec_ngram < 1:
                raise ValueError("spec_k and spec_ngram must be >= 1")
        if self.parallel.sp > 1 and (self.parallel.pp > 1
                                     or self.parallel.dp > 1):
            raise ValueError(
                "sp (sequence parallelism) composes with tp only; "
                "set pp = dp = 1")
        if self.max_queued_requests < 0 or self.max_resident_requests < 0:
            raise ValueError("admission limits must be >= 0 (0 = off)")
        if self.request_deadline_s < 0 or self.watchdog_stall_s < 0 \
                or self.drain_timeout_s < 0:
            raise ValueError("robustness timeouts must be >= 0")
        if self.max_step_failures < 1:
            raise ValueError("max_step_failures must be >= 1")
        if self.max_rebuilds < 1:
            raise ValueError("max_rebuilds must be >= 1")
        if self.rebuild_window_s <= 0 or self.rebuild_backoff_s < 0 \
                or self.rebuild_backoff_max_s < self.rebuild_backoff_s:
            raise ValueError(
                "rebuild_window_s must be > 0 and 0 <= rebuild_backoff_s "
                "<= rebuild_backoff_max_s")
        if self.watchdog_hard_stall_s < 0:
            raise ValueError("watchdog_hard_stall_s must be >= 0")
        if self.watchdog_hard_stall_s > 0:
            if not self.engine_recovery:
                raise ValueError(
                    "watchdog_hard_stall_s needs --engine-recovery (the "
                    "hard-stall escalation IS a supervised rebuild)")
            if self.watchdog_stall_s <= 0:
                raise ValueError(
                    "watchdog_hard_stall_s needs --watchdog-stall-s > 0 "
                    "(the watchdog thread detects the stall)")
            if self.watchdog_hard_stall_s < self.watchdog_stall_s:
                raise ValueError(
                    "watchdog_hard_stall_s must be >= watchdog_stall_s "
                    "(soft flip first, then the hard escalation)")
        if self.fault_inject:
            # fail fast on a bad spec instead of at first fire
            from gllm_tpu.faults import FaultInjector
            FaultInjector().arm(self.fault_inject)
        if self.cache.swap_policy not in ("auto", "swap", "recompute"):
            raise ValueError(
                f"unknown swap_policy {self.cache.swap_policy!r} "
                "(choices: auto, swap, recompute)")
        if self.cache.swap_policy == "swap" \
                and self.cache.kv_host_pool_gb <= 0 \
                and not self.cache.kv_host_pool_pages:
            raise ValueError(
                "swap_policy='swap' needs a host pool: set "
                "kv_host_pool_gb (--kv-host-pool-gb) > 0")
        if self.cache.kvstore_configured:
            # the lower tiers stage every restore through the host pool
            # and only cache digest-keyed prefix pages — both upper
            # layers must exist or the flags silently do nothing
            if not self.cache.enable_prefix_caching:
                raise ValueError(
                    "--kv-disk-path/--prefix-peers/--prefix-serve-port "
                    "extend the prefix cache: add "
                    "--enable-prefix-caching")
            if not self.cache.host_pool_configured:
                raise ValueError(
                    "the disk/peer prefix tiers stage restores through "
                    "the host pool: set --kv-host-pool-gb > 0")
            if self.cache.kv_disk_path and self.cache.kv_disk_gb <= 0:
                raise ValueError("kv_disk_gb (--kv-disk-gb) must be > 0 "
                                 "when --kv-disk-path is set")
            if self.cache.prefix_peers:
                # a typo'd peer must fail startup, not the first
                # scheduling probe
                from gllm_tpu.kvstore.peer import parse_peer_addr
                for a in self.cache.prefix_peers.split(","):
                    if a.strip():
                        parse_peer_addr(a)
            if self.cache.prefix_serve_port is not None \
                    and self.cache.prefix_serve_port < 0:
                raise ValueError("prefix_serve_port must be >= 0 "
                                 "(0 = ephemeral)")
