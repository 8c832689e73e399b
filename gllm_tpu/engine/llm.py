"""Offline LLM engine.

TPU-native analogue of the reference LLM frontend
(/root/reference/gllm/llm_engine.py:33-697) with the process topology
collapsed: the reference spawns one worker process per GPU and speaks zmq;
on TPU a single controller process drives all local chips through one
jit-compiled program, so ``LLM`` owns the scheduler and runner directly and
the zmq/IPC layer only reappears for multi-host pipeline stages
(gllm_tpu/distributed/).

Public surface mirrors the reference: ``generate(prompts | prompt_token_ids,
sampling_params)`` and ``chat(messages)``; per-request outputs carry text,
token ids, finish reason, and usage.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
import time
from typing import Callable, List, Optional, Sequence as Seq, Union

from gllm_tpu import faults
from gllm_tpu.config import EngineConfig
from gllm_tpu.memory_manager import make_memory_manager
from gllm_tpu.models.config import ModelConfig, from_hf_config
from gllm_tpu.obs import metrics as obs
from gllm_tpu.obs import spans
from gllm_tpu.obs.spans import SpanTrace
from gllm_tpu.obs.steptrace import TRACE
from gllm_tpu.sampling_params import SamplingParams
from gllm_tpu.scheduler import Scheduler, SeqOutput
from gllm_tpu.sequence import Sequence
from gllm_tpu.engine.detokenizer import detokenize_incrementally
from gllm_tpu.engine.pipeline import DPBatches, FutureMap, InFlight

logger = logging.getLogger(__name__)

# Engine-step metrics (docs/observability.md). Step kind: "prefill" =
# batch carries at least one prefill chunk, "decode" = single-step pure
# decode (the UNfused path), "fused_block" = K chained decode steps in
# one dispatch. All timing is host wall clock around the collect — the
# device program is untouched.
_M_STEP_LAT = obs.histogram(
    "gllm_step_latency_seconds",
    "engine-iteration collect latency (host blocked on device tokens)",
    ("kind",), buckets=obs.FAST_LATENCY_BUCKETS)
_M_STEPS = obs.counter("gllm_steps_total",
                       "engine iterations by step kind", ("kind",))
_M_STEP_TOKENS = obs.counter("gllm_step_tokens_total",
                             "tokens computed by step kind", ("kind",))
# A step with a row of more than one token (a joining prompt's chunk, a
# spec-decode row) splits its attention on the Pallas path: the leading
# one-token rows go to the decode kernel, the rest to the ragged kernel
# (ops/attention._mixed_step_attention). Counted from the scheduled batch
# by the rule the device reads off ``cu_q_lens``.
_M_MIXED_ROWS = obs.counter(
    "gllm_mixed_step_rows_total",
    "sequences of mixed steps by the attention kernel that serves them "
    "(decode|ragged)", ("kernel",))
_M_DECODE_STEPS = obs.counter(
    "gllm_decode_steps_total",
    "decode steps by fusion (fused counts each sub-step of a block)",
    ("fused",))
# Request-latency histograms (OpenAI-serving vocabulary): TTFT = arrival
# to first sampled token, TPOT = mean inter-token time after the first,
# ITL = per-token inter-arrival, queue = arrival to first schedule.
# "Arrival" is Sequence.arrival_time: the allocation in submit, on the
# HANDLER thread, before the sequence goes onto the intake queue; the
# first token is stamped at the collect (_observe_outputs), before the
# hand-over to the handler threads.
_M_TTFT = obs.histogram("gllm_request_ttft_seconds",
                        "submit (handler thread, before the intake "
                        "queue) to the collect that brought the first "
                        "token (before the hand-over to the handler)")
_M_TPOT = obs.histogram("gllm_request_tpot_seconds",
                        "mean time per output token after the first",
                        buckets=obs.FAST_LATENCY_BUCKETS)
_M_ITL = obs.histogram("gllm_request_itl_seconds",
                       "inter-token latency per sampled token",
                       buckets=obs.FAST_LATENCY_BUCKETS)
_M_E2E = obs.histogram("gllm_request_e2e_seconds",
                       "arrival-to-finish latency per request")
_M_QUEUE = obs.histogram("gllm_request_queue_seconds",
                         "submit (handler thread, before the intake "
                         "queue) to first schedule: holds the intake "
                         "wait gllm_http_admit_lag_seconds also holds")
_M_FINISHED = obs.counter("gllm_requests_finished_total",
                          "requests finished by reason", ("reason",))
# Overlap decode-chain breaks by reason (docs/overlap_scheduling.md):
#   waiting - prefill pressure (ramp yield) or ready seqs the chain's
#             slots can't seat (batch must grow)
#   pages   - no chain link fits the KV pool without preemption
#   shape   - batch not pure-decode / compaction below the seq bucket /
#             client abort / per-seq features needing host work
#             between steps
#   spec    - speculative decoding owns decode dispatch
#   finish  - a sequence finish forced the sync re-form (legacy
#             membership; zero under --decode-slot-batching)
_M_CHAIN_BREAKS = obs.counter(
    "gllm_chain_breaks_total",
    "overlap decode-chain breaks by reason "
    "(waiting/pages/shape/spec/finish)", ("reason",))
# On-device finish detection (config.ondevice_finish,
# docs/overlap_scheduling.md#on-device-finish): finishes committed from
# fused blocks whose death the device detected in-loop, by kind, and the
# per-block wasted-sub-step fraction (dead rows the block still executed
# — the quantity on-device finish + early exit drives toward 0; with
# slot batching it also counts hole rows). Under on-device finish the
# chain_breaks_total{reason="finish"} label is retired: finishes become
# masked rows, never breaks.
_M_ONDEV_FINISH = obs.counter(
    "gllm_ondevice_finish_total",
    "sequence finishes detected on device inside fused decode blocks",
    ("kind",))                            # eos | stop | length
_M_DEAD_FRAC = obs.gauge(
    "gllm_dead_substep_frac",
    "wasted (dead-row) sub-step fraction of the latest fused block")
# Fused on-device speculation (config.spec_fused,
# docs/speculative_decoding.md#fused): tokens moving through fused
# draft+verify blocks, by what they were — accepted drafts (the
# dispatch-amortization win), rejected drafts (wasted verify rows), and
# corrections (the per-sub-step resample/bonus token every emitting
# sub-step contributes).
_M_SPEC_FUSED = obs.counter(
    "gllm_spec_fused_tokens_total",
    "tokens through fused speculation blocks by kind "
    "(accepted|rejected|correction)", ("kind",))
# Pipelined loop (config.pipelined_loop,
# docs/overlap_scheduling.md#pipelined-loop): dispatched-but-uncollected
# entries after the latest fill pass — the run-ahead depth the loop
# actually achieved. Stall *reasons* (why it failed to run further
# ahead) ride loop_stall steptrace events: readback (the next step needs
# host-committed state), rebuild (promised-vs-actual divergence
# invalidated speculated entries), pages (no KV room to speculate),
# depth (the overlap_depth cap was the binding constraint).
# Prepared launch (docs/overlap_scheduling.md#prepared-launch): decode
# steps that were scheduled, built and placed under the step before them,
# by what became of them at that step's collect. ``fired``: launched from
# the collect, before its output; ``dropped_arrival``: a request was on
# the intake queue (or waiting), so the joining step is formed as always;
# ``dropped_finish``: a collected token ended a row (EOS, stop id, the
# model length); ``dropped_other``: an abort, a deadline about to close a
# stream, push work, the loop stopping, a collect that raised.
_M_PREPARED = obs.counter(
    "gllm_prepared_steps_total",
    "decode steps prepared under the running step, by outcome "
    "(fired|dropped_arrival|dropped_finish|dropped_other)", ("outcome",))
_M_INFLIGHT = obs.gauge(
    "gllm_inflight_depth",
    "dispatched-but-uncollected engine entries after the latest fill "
    "pass (pipelined loop)")


@dataclasses.dataclass
class RequestOutput:
    seq_id: int
    prompt_token_ids: List[int]
    output_token_ids: List[int]
    text: str
    finish_reason: Optional[str]
    num_prompt_tokens: int = 0
    num_output_tokens: int = 0
    # per output token: (chosen_logprob, top_ids, top_logprobs); None when
    # not requested
    logprobs: Optional[list] = None
    # per prompt position (index 0 is None)
    prompt_logprobs: Optional[list] = None

    @property
    def finished(self) -> bool:
        return self.finish_reason is not None


def prepares_next_step(config: EngineConfig) -> bool:
    """Does this engine's loop prepare the next decode step under the
    running one (docs/overlap_scheduling.md#prepared-launch)? The default
    loop does: one runner, one step in flight. Whatever runs ahead by its
    own means keeps its loop (``overlap_scheduling``'s chain, the pp
    depth, the dp super-step), and ``enforce_eager`` stays the plain arm
    the tests compare against. No option of its own."""
    par = config.parallel
    return (par.dp == 1 and par.pp == 1
            and not config.overlap_scheduling and not config.enforce_eager
            and (config.pp_pipeline_depth or 1) == 1)


def _asked_for(config: EngineConfig) -> dict:
    """What a start-up fence may name, by a short key: (the option as a
    user wrote it, whether this configuration asks for it)."""
    cache, par = config.cache, config.parallel
    return {
        "prefix": ("--enable-prefix-caching", cache.enable_prefix_caching),
        "tiers": ("a host or disk KV tier (--kv-host-pool-*, "
                  "--kv-disk-path, --prefix-peers)",
                  cache.host_pool_configured or cache.kvstore_configured),
        "spec": ("--spec-decode / --spec-fused", bool(config.spec_decode)),
        "fused": ("fused multi-step decoding (--multi-step-decode, "
                  "--decode-chain-len, --ondevice-finish)",
                  config.multi_step_decode > 1 or config.ondevice_finish
                  or config.decode_chain_len is not None),
        "mesh": ("tp / pp / dp / sp > 1", par.world_size > 1),
        "int8_kv": ("--kv-cache-dtype int8", cache.kv_cache_dtype == "int8"),
    }


def _refuse(config: EngineConfig, keys, why: str) -> None:
    asked = _asked_for(config)
    bad = [asked[k][0] for k in keys if asked[k][1]]
    if bad:
        raise ValueError(why + "; ".join(bad))


def refuse_for_rings(config: EngineConfig) -> None:
    """A model whose windowed layers keep RINGS (``ModelConfig.use_swa``:
    the windowed latent layers of models/deepseek.py) holds of each
    sequence, in those layers, the window's last rows and nothing older.
    So whatever needs a sequence's rows again after the fact, or writes
    rows that may be taken back, is refused at start-up (ROADMAP,
    "Windowed layers", lists these): a cached prefix has no rows to
    resume from, a tier below the pages would hold the full layers' half
    of a sequence, and a rejected draft or a discarded fused block has
    already overwritten the ring. A model whose windowed layers keep
    PAGES is not refused here (``refuse_for_paged_windows``)."""
    _refuse(config, ("prefix", "tiers", "spec", "fused", "mesh"),
            "a model whose windowed layers keep rings (windowed latent "
            "attention: a slot of the window's last rows a sequence and "
            "layer, nothing older) cannot give a sequence's rows back; "
            "not supported with it: ")


def refuse_for_paged_windows(config: EngineConfig) -> None:
    """A model whose windowed GQA layers keep their rows in the paged pool
    (``ModelConfig.paged_windows``: models/cohere2_moe.py)
    has the prefix cache and the plain and the prepared loop. What would
    need work that has not been done is refused by name, never run
    without its window: the windowed Pallas calls have no shard_map and
    the expert layer no exchange (any mesh); the int8 cache's kernels
    know no window; the fused and speculative
    programs and the tiers below the pages have not been run with this
    family's cache."""
    _refuse(config, ("mesh", "int8_kv", "tiers", "spec", "fused"),
            "a model whose windowed GQA layers keep their rows in the "
            "paged pool is served on the plain path with or without the "
            "prefix cache; not supported with it yet: ")


class LLM:
    def __init__(
        self,
        model: str = "",
        *,
        config: Optional[EngineConfig] = None,
        model_cfg: Optional[ModelConfig] = None,
        params=None,
        tokenizer=None,
        **overrides,
    ):
        if config is None:
            config = EngineConfig(model=model)
            for k, v in overrides.items():
                if hasattr(config, k):
                    setattr(config, k, v)
                elif hasattr(config.scheduler, k):
                    setattr(config.scheduler, k, v)
                elif hasattr(config.cache, k):
                    setattr(config.cache, k, v)
                elif hasattr(config.parallel, k):
                    setattr(config.parallel, k, v)
                else:
                    raise TypeError(f"unknown engine option {k!r}")
        config.validate()
        self.config = config

        # Persistent XLA compilation cache: a restarted server (or the
        # next process of one chip command) reads every previously
        # compiled bucket back from disk instead of compiling it again.
        # Skipped on the CPU backend (tests, library embeds): sub-second
        # CPU compiles aren't worth the disk churn.
        import jax
        if jax.default_backend() != "cpu":
            from gllm_tpu.utils import enable_compilation_cache
            enable_compilation_cache()

        if config.model and not os.path.isdir(config.model):
            from gllm_tpu.models.loader import resolve_model_path
            config.model = resolve_model_path(
                config.model, allow_download=config.allow_hub_download)
        if model_cfg is None:
            from gllm_tpu.models.loader import load_hf_config
            model_cfg = from_hf_config(load_hf_config(config.model))
        self.model_cfg = model_cfg
        if model_cfg.use_swa:
            refuse_for_rings(config)
        elif model_cfg.paged_windows:
            refuse_for_paged_windows(config)
        if model_cfg.use_short_conv and config.parallel.world_size > 1:
            raise ValueError(
                "a model with gated short-convolution layers (layer_types: "
                "conv) is served by one chip: its window pool is not "
                "partitioned, its packed KV layout is one replica's and "
                "the pp runner cuts no stages for it; not supported with "
                "it: tp / pp / dp / sp > 1")
        if model_cfg.use_mamba and config.parallel.world_size > 1:
            raise ValueError(
                "a model with Mamba-2 layers (layer_types: mamba or "
                "parallel_hybrid) is served by one chip: its slot pool "
                "and kernels are not partitioned and the pp runner cuts "
                "no stages for it (NemotronH's pattern has no period); "
                "not supported with it: tp / pp / dp / sp > 1")

        self.tokenizer = tokenizer
        if self.tokenizer is None and config.model and config.tokenizer != "":
            try:
                from transformers import AutoTokenizer
                self.tokenizer = AutoTokenizer.from_pretrained(
                    config.tokenizer or config.model, local_files_only=True)
            except Exception:
                logger.warning("no tokenizer loaded; token-id I/O only")

        if config.parallel.pp > 1:
            if params is not None:
                raise ValueError(
                    "explicit params are not supported with pp > 1")
            from gllm_tpu.runner.pp_runner import PPModelRunner
            self.runner = PPModelRunner(config, model_cfg)
        else:
            from gllm_tpu.runner.runner import ModelRunner
            self.runner = ModelRunner(config, model_cfg, params=params)
        # DP attention: one scheduler + KV pool per replica; the frontend
        # round-robins requests (reference llm_engine.py:121-133,490-519).
        self.dp = config.parallel.dp
        self.memory_managers = [
            make_memory_manager(
                self.runner.num_pages, config.cache.page_size,
                config.cache.enable_prefix_caching,
                ssm_working_slots=getattr(self.runner,
                                          "ssm_working_slots", 0),
                ssm_snapshot_slots=getattr(self.runner,
                                           "ssm_snapshot_slots", 0),
                ssm_chunk=model_cfg.ssm_chunk)
            for _ in range(self.dp)]
        self.memory_manager = self.memory_managers[0]
        if model_cfg.use_swa:
            from gllm_tpu.memory_manager import _M_SWA_SLOTS
            self.memory_manager.slot_gauge = _M_SWA_SLOTS
        if getattr(self.runner, "kv_quant", False):
            # int8 KV cache: minted pages queue a device-side scale
            # reset (drained by the runner at dispatch time) so a
            # recycled page quantizes exactly like a fresh one —
            # numerics never depend on page-reuse history.
            for mm in self.memory_managers:
                mm.track_scale_resets = True
        self.runner.memory_manager = self.memory_manager
        if self.dp > 1:
            # per-replica SSM intents apply to the stacked pools by index
            self.runner.memory_managers = self.memory_managers
        self.swap_manager = self._maybe_init_kvswap()
        self.schedulers = [Scheduler(config, mm,
                                     pp_size=config.parallel.pp)
                           for mm in self.memory_managers]
        for r, s in enumerate(self.schedulers):
            s.dp_rank = r               # metric label (see scheduler.py)
        self.scheduler = self.schedulers[0]
        if config.spec_decode == "ngram":
            # Works under every topology: single runner, pp pipelines
            # (the last stage verifies), dp replicas (per-replica verify
            # in the stacked program), and overlap scheduling — there
            # speculation owns decode dispatch (schedule_chain defers;
            # drafting needs committed token VALUES a chained step leaves
            # on device). Hybrid (GDN) speculates via snapshot-rollback:
            # the pre-draft recurrent state is checkpointed into an SSM
            # snapshot slot and restored on a partial acceptance, with
            # the accepted tokens re-fed so the state re-advances over
            # exactly the committed run (paged KV needs no rollback: the
            # real token's KV overwrites the slot later). validate()
            # already rejected any other spec_decode value.
            for s in self.schedulers:
                s.spec_cfg = (config.spec_ngram, config.spec_k)
        # Fused on-device speculation (--spec-fused,
        # docs/speculative_decoding.md#fused): draft+verify move inside
        # the chained multi-step dispatch — schedule_chain accepts spec
        # rows (reason="spec" breaks retired), the runner's block driver
        # drafts from a device-resident recent-token ring and verifies
        # in-loop, and one dispatch emits up to K·(spec_k+1) tokens.
        # Genuinely incompatible model families refuse LOUDLY (flags
        # never silently no-op): hybrid GDN (cumulative SSM state cannot
        # replay a discarded block) and multimodal (mrope is not in the
        # spec carry). Topology gates (pp/dp > 1) already errored in
        # config.validate().
        self.spec_fused = (bool(getattr(config, "spec_fused", False))
                           and config.spec_decode == "ngram")
        if self.spec_fused and model_cfg.use_hybrid:
            raise ValueError(
                "--spec-fused is not supported for hybrid (GDN) models: "
                "the cumulative SSM state cannot replay a discarded "
                "fused block — drop --spec-fused to keep host-driven "
                "speculation")
        if self.spec_fused and model_cfg.use_mm:
            raise ValueError(
                "--spec-fused is not supported for multimodal models: "
                "mrope position state is not part of the fused spec "
                "carry — drop --spec-fused to keep host-driven "
                "speculation")
        # worst-case tokens one spec sub-step may emit (drafts + the
        # correction/bonus token) — the scheduler's token-unit stride
        self.spec_mult = (config.spec_k + 1) if self.spec_fused else 1
        for s in self.schedulers:
            s.spec_fused = self.spec_fused
        self._rr = 0
        self._seq_replica: dict = {}
        # Persistent-slot decode batching (config.decode_slot_batching):
        # the current chain's newest (batch, handle) — unlike
        # _in_flight[-1] it survives interleaved prefill dispatches, so
        # a chain keeps extending off its own on-device tokens while a
        # ramp yield's prefill batch rides the pipeline between links.
        # None = no chain rooted (next sync pure-decode batch roots one).
        self._chain_tip = None
        # Decode steps chained while prefill work waited — the
        # chain_under_prefill ramp policy yields one sync pass every
        # config.chain_under_prefill steps instead of unfusing everything.
        self._chained_under_pressure = 0
        # One 'waiting' chain_break per chain interruption: set when the
        # yield is recorded, cleared when a chain extends/roots again —
        # a backed-up queue must not count every fill-loop pass as a
        # separate break of the same chain.
        self._yield_noted = False
        self.eos_token_ids = frozenset(model_cfg.eos_token_ids)
        if not self.eos_token_ids and self.tokenizer is not None \
                and self.tokenizer.eos_token_id is not None:
            self.eos_token_ids = frozenset([self.tokenizer.eos_token_id])
        self._next_seq_id = 0
        from collections import deque
        self._in_flight = deque()
        # Pipelined loop (docs/overlap_scheduling.md#pipelined-loop): the
        # FutureMap owns promise reconciliation — a finish committing for
        # a seq some speculatively re-formed entry assumed alive
        # invalidates that entry (and its chained descendants) at collect
        # time; the sync path rebuilds from committed state.
        self.pipelined = bool(getattr(config, "pipelined_loop", False))
        self.futures = FutureMap()
        # Prepared launch (docs/overlap_scheduling.md#prepared-launch):
        # the default loop (one runner, one step in flight, nothing
        # queued behind it) prepares the next decode step under the
        # running one; every loop that runs ahead by its own means, and
        # the plain arm the tests compare against, stays as it is.
        self._prepares = prepares_next_step(config)
        # ... and only while there is room to prepare it in: the last
        # collect of a decode step blocked for twice as long as the
        # loop's host work took between two collects (the device's step
        # outlasts the host's turn by far; the same reading in either
        # order, since the work is the same). Where the host is the
        # slower side (a model so small that its tokens are all but
        # ready when the loop comes for them) the plain order costs the
        # same, and its blocking wait is what gives the handler threads
        # the interpreter once a pass. A clock is one host's own: the
        # hosts of a multihost engine have to decide alike, so they
        # always prepare.
        import jax
        self._own_clock = jax.process_count() == 1
        self._room_to_prepare = not self._own_clock
        self._t_collected = time.monotonic()
        # Encoder disaggregation (gllm_tpu/disagg/): set by init_disagg on
        # LM nodes; monolith engines leave it None.
        self.disagg_coordinator = None
        # Performance-attribution layer (gllm_tpu/obs/spans.py,
        # docs/observability.md#tracing): request-scoped spans are gated
        # per ENGINE by config.tracing and recorded on a PER-ENGINE ring
        # — seq_ids restart at 0 per LLM, so a process-global ring would
        # merge co-resident engines' trees.
        self.tracing = bool(getattr(config, "tracing", True))
        self.spans = SpanTrace()
        for s in self.schedulers:
            s.spans = self.spans      # admission opens the span tree

    @property
    def eos_token_ids(self) -> frozenset:
        return self._eos_token_ids

    @eos_token_ids.setter
    def eos_token_ids(self, ids) -> None:
        # Mirrored into the runner on every assignment (tests and
        # embedders set it post-init): on-device finish detection builds
        # its per-row stop sets from the runner's copy, and the device
        # and host checks must read the SAME set or a fused block would
        # freeze rows the host keeps alive.
        self._eos_token_ids = frozenset(ids)
        self.runner.eos_token_ids = self._eos_token_ids

    def _maybe_init_kvswap(self):
        """Attach the host-RAM KV tier (gllm_tpu/kvswap) when configured
        and the topology supports it. Gated to the single-program runner
        (pp = dp = 1) and paged-only KV layouts (hybrid GDN state lives
        in slot pools, not pages — swapping its KV without the recurrent
        state would corrupt the recurrence). When disk/peer prefix tiers
        are configured (gllm_tpu/kvstore) they attach below the host
        pool here too."""
        cache = self.config.cache
        self.prefix_tiers = None
        if not cache.host_pool_configured:
            return None
        import jax
        why = None
        if self.config.parallel.pp > 1 or self.dp > 1:
            why = "pp/dp > 1"
        elif self.model_cfg.use_hybrid:
            why = "hybrid (GDN) models"
        elif jax.process_count() > 1:
            # host fetches of a non-addressable global array can't work;
            # each host would also need its own pool + deterministic drains
            why = "multi-host meshes"
        if why is not None:
            logger.warning(
                "kv host pool configured but unsupported for %s; "
                "falling back to recompute preemption", why)
            return None
        from gllm_tpu.kvswap import KVSwapManager
        n = cache.kv_host_pool_pages or KVSwapManager.host_pages_for(
            self.runner.kv, cache.kv_host_pool_gb)
        if n < 1:
            logger.warning(
                "kv host pool of %.2f GiB holds no page for this model; "
                "tier disabled", cache.kv_host_pool_gb)
            return None
        sw = KVSwapManager(self.runner.kv, cache.page_size, n)
        self.memory_manager.swap = sw
        self.runner.swap_manager = sw
        logger.info("KV host tier: %d pages x %d tokens (%.2f GiB)",
                    n, cache.page_size,
                    n * sw.pool.bytes_per_page / (1 << 30))
        if cache.kvstore_configured and cache.enable_prefix_caching:
            # tiered prefix store (docs/kv_offload.md): disk behind the
            # host pool + cluster-wide digest-addressed sharing. Probes
            # run HBM → host → disk → peer; every restore stages through
            # the host pool and rides the swap intent queue, so device
            # ordering guarantees are untouched.
            from gllm_tpu.kvstore import build_tiers
            self.prefix_tiers = sw.tiers = build_tiers(sw.pool, cache)
            logger.info(
                "prefix store tiers: disk=%s peers=%s serving=%s",
                cache.kv_disk_path or "off",
                cache.prefix_peers or "off",
                f"port {self.prefix_tiers.server.port}"
                if self.prefix_tiers.server is not None else "off")
        return sw

    def demote_prefix_cache(self) -> int:
        """Persist the warm prefix cache down the tier stack: spill
        every unclaimed (refcount-0) HBM prefix page through the host
        tier, drain the gathers, then flush host-resident prefix pages
        to the disk tier and drop the upper-tier keys — subsequent
        probes (this engine or any replica sharing the store/peering to
        it) restore from disk instead of recomputing. The operational
        use is a graceful shutdown/restart or a bench A/B; call it only
        between requests (no batch may be in flight). Returns the
        number of pages flushed to disk; 0 when no disk tier is
        configured."""
        mm, sw = self.memory_manager, self.swap_manager
        if sw is None or self.prefix_tiers is None \
                or self.prefix_tiers.disk is None:
            return 0
        for page, meta in list(mm.page_meta.items()):
            digest, canary = meta[0], meta[1]
            if mm.hash_to_page.get(digest) == page \
                    and page not in mm.ref_count:
                sw.spill_prefix(page, digest, canary,
                                parent=mm._digest_parent.get(digest))
        # drain like a dispatch would, then land the gathers NOW (the
        # usual double buffer has no next step to ride)
        self.runner.kv = sw.apply(self.runner.kv)
        sw._materialize()
        moved = self.prefix_tiers.flush_host_to_disk(drop=True)
        mm.hash_to_page.clear()
        mm.page_meta.clear()
        mm._seq_chain.clear()
        return moved

    def export_prefix_chain(self, token_ids) -> list:
        """Pack one prompt's finished prefix KV chain for a pd-pool push
        (docs/pd_pools.md): ``[(digest, canary_tokens, payload), ...]``
        in chain order, covering the whole-page prefix of ``token_ids``
        (the same ``(len-1)//page_size`` pages ``prefix_digests``
        addresses). Pages still HBM-only are spilled host-side first —
        a targeted ``demote_prefix_cache`` that copies without dropping
        any key, so this replica's own cache is untouched. ENGINE
        THREAD ONLY: the spill drains through ``apply``/
        ``_materialize`` exactly like a dispatch would. Returns [] when
        the host tier is off; a chain gap truncates (a child page is
        useless to the receiver without its parents)."""
        mm, sw = self.memory_manager, self.swap_manager
        if sw is None or self.prefix_tiers is None:
            return []
        from gllm_tpu.kvswap.host_pool import CANARY_TOKENS
        from gllm_tpu.memory_manager import prefix_digests
        digests = prefix_digests(list(token_ids), len(token_ids),
                                 self.config.cache.page_size)
        queued = False
        for digest, _toks in digests:
            with sw.pool.lock:
                if digest in sw.pool.hash_to_page:
                    continue             # already host-resident
            page = mm.hash_to_page.get(digest)
            if page is None:
                continue
            meta = mm.page_meta.get(page)
            if meta is None or meta[0] != digest:
                continue
            sw.spill_prefix(page, digest, meta[1],
                            parent=mm._digest_parent.get(digest))
            queued = True
        if queued:
            # land the copies NOW (the usual double buffer has no next
            # step to ride; export refuses still-pinned pages)
            self.runner.kv = sw.apply(self.runner.kv)
            sw._materialize()
        out = []
        for digest, toks in digests:
            payload = self.prefix_tiers.serve(digest)
            if payload is None:
                break
            out.append((digest,
                        tuple(int(t) for t in toks[:CANARY_TOKENS]),
                        payload))
        return out

    def close(self) -> None:
        """Release the resources a SUCCESSOR engine needs to re-adopt
        (docs/robustness.md#recovery-lifecycle): stop serving prefix
        peers and drain pending disk writes — the serve port frees for
        the rebuilt engine and the disk tier's content-addressed pages
        survive for its construction-time adoption. Device buffers are
        NOT touched here (a wedged dispatch may still hold them); they
        free with the object. Idempotent."""
        tiers = getattr(self, "prefix_tiers", None)
        self.prefix_tiers = None
        if self.swap_manager is not None:
            self.swap_manager.tiers = None
        if tiers is not None:
            try:
                tiers.close()
            except Exception:  # pragma: no cover - teardown must finish
                logger.exception("prefix tier close failed")

    def init_disagg(self, disagg_cfg) -> None:
        """Become a disagg LM node: start the coordinator (slot pool,
        discovery, meta server). Reference Worker._maybe_init_disagg."""
        from gllm_tpu.disagg.lm_manager import DisaggCoordinator
        if not self.model_cfg.use_mm:
            raise ValueError("disagg LM mode needs a VL checkpoint")
        # Any LM topology can front a disagg encoder fleet (reference
        # dispatches from every dp/pp grid, disagg/lm_manager.py:256-900):
        # admits route through add_seq (dp round-robin over per-replica
        # schedulers) and the coordinator poll runs before either step
        # path, so no parallelism guard is needed.
        self.disagg_coordinator = DisaggCoordinator(self.model_cfg,
                                                    disagg_cfg)

    def submit_disagg(self, seq: Sequence, raw_items) -> None:
        """Hand a skeleton-tokenized MM request to the coordinator; it is
        admitted to the scheduler once all item metas arrive (gate A)."""
        self.disagg_coordinator.submit(seq, raw_items)

    def encode_skeleton(self, messages, **template_kwargs):
        """Text-only chat tokenization: one placeholder sentinel per mm
        item, pixels never opened (reference mm_common.tokenize_text_only).
        Returns (token_ids, [(modality, raw_content), ...])."""
        from gllm_tpu.engine.mm_processing import extract_mm_items
        if self.tokenizer is None:
            raise ValueError("skeleton tokenization needs a tokenizer")
        items = extract_mm_items(messages)
        ids = self.tokenizer.apply_chat_template(
            messages, add_generation_prompt=True, **template_kwargs)
        if ids and isinstance(ids[0], list):
            ids = ids[0]
        return [int(t) for t in ids], items

    def _poll_disagg(self) -> None:
        from gllm_tpu.sequence import SequenceStatus
        events = self.disagg_coordinator.poll()
        for seq in events.admits:
            try:
                self.add_seq(seq)
            except ValueError as e:
                # e.g. the expanded prompt exceeds max_model_len — reject
                # THIS request; don't let the error escape step() and fail
                # every in-flight stream
                logger.warning("disagg admit rejected seq %d: %s",
                               seq.seq_id, e)
                self.disagg_coordinator.abort([seq.seq_id])
                seq.status = SequenceStatus.ABORTED
                seq.finish_reason = "abort"
        for seq in events.aborts:
            if seq.seq_id in self._seq_replica:     # already admitted
                self.abort(seq.seq_id)
            else:                                   # never reached a
                seq.status = SequenceStatus.ABORTED  # scheduler
                seq.finish_reason = "abort"

    # ---- intake -----------------------------------------------------------

    def _allocate_seq(self, token_ids: List[int],
                      sp: SamplingParams) -> Sequence:
        sp.validate()
        seq = Sequence(self._next_seq_id, token_ids, sp,
                       arrival_time=time.monotonic())
        self._next_seq_id += 1
        return seq

    def encode(self, prompt: str) -> List[int]:
        if self.tokenizer is None:
            raise ValueError("no tokenizer available; pass prompt_token_ids")
        return self.tokenizer.encode(prompt)

    def add_seq(self, seq: Sequence) -> None:
        """Admit a sequence: pinned to ``seq.target_dp`` when set
        (per-DP-endpoint affinity keeps a conversation's prefix cache on
        one replica, reference llm_engine.py:121-133); otherwise
        CACHE-AWARE routing (beyond the reference's round-robin): the
        replica whose prefix cache covers the most of this prompt wins —
        a multi-turn conversation naturally sticks to the replica holding
        its history even without endpoint pinning. No match → plain
        round-robin (also the single-replica / no-prefix-cache path)."""
        t = getattr(seq, "target_dp", None)
        if t is not None and 0 <= t < self.dp:
            r = t
        else:
            r = -1
            if self.dp > 1 and self.config.cache.enable_prefix_caching:
                from gllm_tpu.memory_manager import prefix_digests
                # hash the prompt chain ONCE; probe every replica's maps
                digests = prefix_digests(seq.cache_token_ids,
                                         seq.prompt_len,
                                         self.config.cache.page_size)
                hits = [s.mm.peek_digests(digests)
                        for s in self.schedulers]
                best = max(hits)
                cand = hits.index(best)
                loads = [len(s.running) + len(s.waiting)
                         for s in self.schedulers]
                # Route by cache only when the hit is real AND substantial
                # (at least half the prompt — a short shared system prompt
                # must not funnel all traffic to one replica) and the
                # winner isn't already far more loaded than the idlest
                # replica (cache affinity must not starve the fleet).
                if (best > 0 and best >= seq.prompt_len // 2
                        and loads[cand] <= min(loads) + 8):
                    r = cand
            if r < 0:
                r = self._rr % self.dp
                self._rr += 1
        self._seq_replica[seq.seq_id] = r
        self.schedulers[r].add_seq(seq)

    @property
    def has_unfinished(self) -> bool:
        return (any(s.has_unfinished for s in self.schedulers)
                or bool(self._in_flight)
                or (self.disagg_coordinator is not None
                    and self.disagg_coordinator.num_pending > 0))

    # ---- main loops -------------------------------------------------------

    def step(self, after_dispatch: Optional[Callable[[], None]] = None,
             hold_launch: Optional[Callable[[], Optional[str]]] = None
             ) -> List[SeqOutput]:
        """One engine iteration.

        ``after_dispatch``, if given, is called once in a pass that has
        work on the device: after the fill pass has returned from its
        last dispatch and before the blocking collect. It is the seam at
        which a caller does work that should run under the device's step
        and not between two of them (``ServingEngine._run_loop`` hands
        the previous step's chunks to the handler threads there). A pass
        that returns without anything in flight never calls it.

        ``hold_launch``, beside it, is asked at the collect, where a
        decode step prepared under the running one is about to be
        launched (:meth:`_prepare_next`,
        docs/overlap_scheduling.md#prepared-launch): what the loop cannot
        see from in here, as a reason to drop the prepared step
        (``"arrival"``: a request is on the caller's intake queue;
        ``"other"``) or None. A caller with no queue of its own
        (``generate``) passes none, and the step is launched whenever no
        collected token has ended a row.

        Keeps up to ``pp`` microbatches in flight (the pipeline depth —
        reference scheduler.py:358-364 keeps pp_size batches running), then
        collects the oldest and advances scheduler state. With pp=1 this is
        launch-one/collect-one, with jax async dispatch hiding host work
        behind the device step.

        Under ``config.pipelined_loop`` the fill pass additionally runs
        ahead ACROSS chain breaks: when a membership change refuses the
        chain, the next batch is speculatively re-formed off promised
        token counts (``_dispatch_reform``; FutureMap contract in
        gllm_tpu/engine/pipeline.py) instead of draining the pipeline,
        and promised-vs-actual divergence is reconciled at collect time
        (``_commit_outputs``) by invalidating exactly the speculated
        entries. Flag off = the pre-flag loop, byte for byte.
        """
        if self.disagg_coordinator is not None:
            # multihost: the MultihostEngine polls the coordinator itself
            # (events must ride the tick broadcast) — skip the local poll
            # but keep the don't-spin-hot sleep
            if not getattr(self, "disagg_external_poll", False):
                self._poll_disagg()
            if not any(s.has_unfinished for s in self.schedulers) \
                    and not self._in_flight:
                # only disagg-pending work: don't spin the poll loop hot
                time.sleep(0.002)
                return []
        if self.dp > 1:
            # dp fast path (docs/overlap_scheduling.md#topology-matrix):
            # the stacked program forces replica lockstep (donated
            # stacked KV), so run-ahead happens in SUPER-STEPS — one
            # dp-wide chained re-form per pass. Requires the pipelined
            # loop's reform machinery; overlap alone keeps the legacy
            # sync dp loop.
            if self.pipelined and self.config.overlap_scheduling:
                return self._step_dp_overlap(after_dispatch)
            return self._step_dp(after_dispatch)
        pp = self.config.parallel.pp
        depth = max(1, self.config.pp_pipeline_depth or pp)
        overlap = self.config.overlap_scheduling
        if overlap:
            # --inflight-depth is honored exactly: depth 1 is the
            # serialized launch-collect control arm (no run-ahead).
            # Under pp > 1 the pipeline must stay at least pp deep or
            # the stages drain between passes (bubbles) — the depth is
            # whichever constraint is larger.
            depth = (max(depth, self.config.overlap_depth) if pp > 1
                     else max(1, self.config.overlap_depth))
        # Multi-step fused blocks are ONE device program spanning the
        # whole layer stack — they cannot cross per-stage programs, so
        # pp > 1 chains are single-step re-forms scheduled ahead to the
        # pipeline depth instead (that IS the no-bubble pp loop).
        multi = self.config.multi_step_decode if overlap and pp == 1 else 1
        slot_mode = overlap and self.config.decode_slot_batching
        cup = self.config.chain_under_prefill if overlap else 0
        # Pipelined loop: run ahead across chain breaks via speculative
        # re-forms; ``ran_dry`` marks a fill pass that stopped early for
        # a reason other than the depth cap (stall classification).
        pipelined = self.pipelined and overlap
        ran_dry = False
        while len(self._in_flight) < depth:
            # engine-loop phase attribution: everything from here to the
            # runner call is "schedule" wall for the entry this pass
            # produces (obs/spans.py, docs/observability.md#tracing);
            # _launch closes it, or the pass's end where nothing launched
            sched_ph = spans.phase("schedule").start()
            if overlap and self._in_flight:
                # chain the next decode step(s) off the chain's newest
                # on-device tokens (overlap scheduling). Slot mode tracks
                # the chain tip explicitly so it survives interleaved
                # prefill dispatches; legacy chains off _in_flight[-1].
                # an INVALIDATED entry can never be a tip: its tokens
                # will be discarded, so chaining or re-forming off its
                # promises would commit positions that skip a token —
                # the rebuild must root from committed state instead
                tip = (self._chain_tip if slot_mode
                       else (None if self._in_flight[-1].invalid
                             else self._in_flight[-1].tip))
                pressure = bool(self.scheduler.waiting)
                if not pressure:
                    # pressure subsided without a yield: a later burst
                    # starts its ramp budget from zero, not a stale count
                    self._chained_under_pressure = 0
                allow = tip is not None and (
                    not pressure
                    or (cup > 0 and self._chained_under_pressure < cup))
                if tip is not None and not allow:
                    # ramp yield: prefill pressure sends this pass to the
                    # sync path (schedule_once below admits/advances the
                    # waiting work). With chain_under_prefill the chain
                    # RESUMES afterwards — only the yielded pass is
                    # unfused; legacy (cup=0) stays unfused until the
                    # queue drains. Record ONE break per interruption,
                    # and only when a decode chain actually exists — a
                    # prefill tip (legacy _in_flight[-1]) has no chain
                    # to yield.
                    prev = (tip[0][-1] if isinstance(tip[0], list)
                            else tip[0])
                    if (not self._yield_noted
                            and prev.num_decode == prev.num_seqs
                            and not prev.has_drafts):
                        self._note_chain_break(tip[0], "waiting")
                        self._yield_noted = True
                    self._chained_under_pressure = 0
                if allow:
                    prev_batch, prev_handle = tip
                    if isinstance(prev_batch, list):
                        prev_batch = prev_batch[-1]
                    chain = self._schedule_multi(prev_batch, multi)
                    if not chain:
                        # the sync path re-forms the batch next iteration
                        # — each break is a dispatch round trip the chain
                        # would have hidden (step-kind attribution reads
                        # these next to the decode/fused_block split).
                        self._note_chain_break(
                            prev_batch,
                            self.scheduler.chain_break_reason or "shape")
                        # Pipelined loop: a membership change is not a
                        # reason to drain — speculatively RE-FORM the
                        # next batch off promised token counts and keep
                        # the device fed; the sync path only takes over
                        # when re-forming needs host-committed state.
                        if pipelined and self._dispatch_reform(
                                prev_batch, prev_handle, sched_ph, multi,
                                slot_mode, pressure):
                            continue
                        self._chain_tip = None
                        self._chained_under_pressure = 0
                        ran_dry = True
                        sched_ph.stop()
                        break
                    if pressure:
                        self._chained_under_pressure += len(chain)
                    self._yield_noted = False
                    if getattr(chain[0], "spec_block", False):
                        # fused on-device speculation: even a 1-link
                        # chain runs the draft+verify block driver (it
                        # emits up to spec_k+1 tokens per dispatch)
                        entry = self._launch(
                            sched_ph, chain, self.runner.step_spec_multi,
                            chain, prev_handle, chained=True)
                    elif len(chain) > 1:
                        entry = self._launch(
                            sched_ph, chain, self.runner.step_multi,
                            chain, prev_handle, chained=True)
                    else:
                        entry = self._launch(
                            sched_ph, chain[0],
                            self.runner.step_async_chained,
                            chain[0], prev_handle, chained=True)
                    self._in_flight.append(entry)
                    if slot_mode:
                        self._chain_tip = entry.tip
                    continue
            batch = self.scheduler.schedule_once()
            if batch is None:
                if (pipelined and self._in_flight
                        and self.scheduler.has_unfinished):
                    # unfinished work, nothing schedulable from committed
                    # state, no chain/re-form edge to run ahead on — the
                    # loop must block on readback before it can proceed
                    self._note_stall("readback")
                ran_dry = True
                sched_ph.stop()
                break
            if (overlap and multi > 1
                    and not self.scheduler.waiting
                    and batch.num_decode == batch.num_seqs
                    and not batch.has_drafts):
                # A freshly re-formed pure-decode batch (the step after a
                # finish changed the composition) fuses with its chain
                # into ONE multi-step dispatch instead of paying a full
                # single-step round trip first (r5 on-chip: these singles
                # were 57 of 162 iterations at ~73 ms each). The sync
                # step rides as the block's first step; its items are all
                # alive, so the links' death counts shift by one.
                links = self._schedule_multi_links(batch, multi - 1)
                if links:
                    au = links[0].active_until
                    k = 1 + len(links)
                    spec_chain = getattr(links[0], "spec_block", False)
                    if spec_chain:
                        # token-unit budget merge: the sync batch rides
                        # as sub-step 0, adding one token of budget in
                        # front of the links' (uncapped, carried-across-
                        # blocks) remaining budgets
                        first = dataclasses.replace(
                            batch, spec_block=True,
                            active_until=[d + 1 for d in au])
                    else:
                        first = dataclasses.replace(
                            batch, active_until=(
                                [min(d + 1, k) for d in au]
                                if au is not None else None))
                    chain = [first] + links
                    entry = self._launch(
                        sched_ph, chain,
                        self.runner.step_spec_multi if spec_chain
                        else self.runner.step_multi, chain, roots=True)
                    self._in_flight.append(entry)
                    self._yield_noted = False
                    if slot_mode:
                        self._chain_tip = entry.tip
                    continue
            entry = self._launch(
                sched_ph, batch, self.runner.step_async, batch,
                roots=(batch.num_decode == batch.num_seqs
                       and not batch.has_drafts))
            self._in_flight.append(entry)
            if entry.roots:
                self._yield_noted = False
                if slot_mode:
                    # a sync pure-decode batch roots a new persistent chain
                    self._chain_tip = entry.tip
        if pipelined:
            _M_INFLIGHT.set(len(self._in_flight))
            if not ran_dry and len(self._in_flight) >= depth:
                # the fill pass stopped ONLY because the pipeline is
                # full — overlap_depth was the binding constraint on
                # running further ahead
                self._note_stall("depth")
        if not self._in_flight:
            if self.disagg_coordinator is not None:
                # gate-B-blocked seqs park in waiting; don't spin hot
                time.sleep(0.002)
            return []
        # the step after the one in flight is prepared BEFORE the seam:
        # the handler threads are idle then (the chunks they will send
        # are handed over at the seam), so the work shares the
        # interpreter with nobody, and the handlers have the whole of the
        # wait to themselves
        prepared = (self._prepare_next(self._in_flight[0])
                    if self._prepares and self._room_to_prepare
                    and len(self._in_flight) == 1 else None)
        try:
            if after_dispatch is not None:
                after_dispatch()
            # Fault points (gllm_tpu/faults.py, docs/robustness.md): fired
            # BEFORE the in-flight pop so quarantine_step_failure still
            # sees the batch it must attribute the failure to; the stall
            # mimics a hung device dispatch blocking the loop inside
            # collect.
            faults.FAULTS.maybe_stall("dispatch_stall")
            faults.FAULTS.maybe_raise("step_exception")
            entry = self._in_flight.popleft()
            batch, handle, t_dispatch, phases = (entry.batch, entry.handle,
                                                 entry.t_dispatch,
                                                 entry.phases)
            if not self._in_flight:
                # pipeline drained: the tip (this very batch, or older) is
                # collected — a future burst must root a fresh chain, not
                # retain the old batch/handle or fail a stale extension
                self._chain_tip = None
            if entry.invalid:
                # reconciliation discard (pipelined loop): the speculated
                # schedule assumed a sequence alive that has since
                # finished — unwind the in-flight bookkeeping WITHOUT
                # committing tokens or blocking on the device (its writes
                # are harmless: live rows' positions are rewritten
                # identically by the rebuild, dead rows' pages free once
                # the counts drain); the sync path re-schedules the same
                # positions from committed state next pass.
                self.scheduler.discard_batch(batch)
                return []
            t0 = time.monotonic()
            tokens, aux = self.runner.collect(handle)
        except BaseException:
            if prepared is not None:
                self._drop_prepared(prepared, "other")
            raise
        t_collected = time.monotonic()
        if self._prepares and batch.num_decode == batch.num_seqs:
            # room to prepare in, read off a decode step (the kind that
            # is prepared, and the shortest): the loop's whole turn, from
            # the last collect's end to this one's start (launch, output,
            # the caller's part, intake, this pass's forming or
            # preparing, the seam), against the wait
            self._room_to_prepare = (
                not self._own_clock
                or t_collected - t0 > 2 * (t0 - self._t_collected))
        self._t_collected = t_collected
        if prepared is not None:
            self._launch_prepared(prepared, entry, tokens, hold_launch)
        # ``output``: everything between the collect and the return —
        # the step's own record keeping first (it takes the phases
        # measured up to here, so this span rides with the NEXT event)
        with spans.phase("output"):
            extra = None
            if isinstance(batch, list) and aux.get("finish") is not None:
                extra = self._ondevice_block_stats(
                    aux["finish"][0][:batch[0].num_seqs])
            if isinstance(batch, list) \
                    and aux.get("spec_counts") is not None:
                extra = self._spec_block_stats(batch, aux)
            self._record_step(batch, t0, t_dispatch, extra, phases,
                              entry.prepared, t_collected)
            return self._commit_step(batch, tokens, aux, extra)

    def _prepare_next(self, entry: InFlight):
        """Prepared launch, first half, between the dispatch of
        ``entry`` (the one step in flight) and the ``after_dispatch``
        seam: if every row of it samples and nothing waits, schedule the
        step after it from token COUNTS (``schedule_chain``: pages,
        positions, slots) and let the runner build, pack and place it,
        its input tokens the running step's on-device sampled tokens.
        The work keeps its phase names, ``schedule`` and ``build``; it
        lies under the device's step. Returns the runner's prepared step,
        or None where today's order stands: work waiting (a sequence
        parked for its embeddings too), a running sequence the batch
        does not hold, a host-side stop scan, a hybrid model's row that
        is about to snapshot its state for the prefix cache, and
        whatever ``schedule_chain`` refuses (a mid-prompt chunk, a row
        at its length, penalties, host-side drafts, an abort, no page
        without preemption). Every gate in here reads state that the
        hosts of a multihost engine share, so they decide alike."""
        batch, sched = entry.batch, self.scheduler
        if (sched.waiting or len(batch.items) != len(sched.running)
                or any(it.seq.sampling_params.stop for it in batch.items)):
            return None
        mm = self.memory_manager
        if getattr(mm, "ssm_snap_alloc", None) is not None and any(
                (it.computed_before + it.num_new_tokens) % mm.page_size == 0
                for it in batch.items):
            # a hybrid model under the prefix cache: a row of the running
            # step ends on a page boundary, where its commit snapshots
            # the recurrent state (``register_computed_pages``), and that
            # needs the state as this step leaves it: nothing of the row
            # in flight
            return None
        with spans.phase("schedule"):
            chain = sched.schedule_chain(batch, 1)
        if not chain:
            return None
        return self.runner.prepare_step(chain[0], entry.handle)

    def _drop_prepared(self, prepared, why: str) -> None:
        """The prepared step is not launched: its rows' in-flight counts
        and the sampling ordinal go back, the pages it reserved stay on
        the tables (``Scheduler.discard_batch``), and the pass goes on in
        today's order."""
        self.scheduler.discard_batch(prepared.sched_batch)
        self.runner.discard_prepared()
        _M_PREPARED.inc(outcome="dropped_" + why)

    def _launch_prepared(self, prepared, entry: InFlight, tokens,
                         hold_launch) -> None:
        """Prepared launch, second half, with ``entry``'s tokens on the
        host: launch the prepared step BEFORE ``entry``'s output, unless
        something has come up that it could not know of when it was
        prepared — a token that ends a row, an abort, or what
        ``hold_launch`` reports. Nothing launched is ever taken back."""
        mml = self.config.max_model_len
        eos = self.eos_token_ids
        sched = self.scheduler
        if any(it.seq.would_finish(tok, eos) is not None
               or it.seq.num_tokens + 1 >= mml
               for it, tok in zip(entry.batch.items, tokens.tolist())):
            why = "finish"
        elif sched.holds_abort(entry.batch):
            why = "other"
        elif sched.waiting:
            why = "arrival"
        else:
            why = hold_launch() if hold_launch is not None else None
        if why is not None:
            self._drop_prepared(prepared, why)
            return
        # this pass's host work and the wait belong to the collected
        # step's event, as they always have; the launched step's own
        # phases begin here
        for name, sec in spans.take_phases().items():
            entry.phases[name] = entry.phases.get(name, 0.0) + sec
        t_launch = time.monotonic()
        handle = self.runner.launch_prepared(prepared)
        phases = spans.take_phases()
        phases["t_enter"] = t_launch
        self._in_flight.append(InFlight(
            prepared.sched_batch, handle, time.monotonic(), phases,
            chained=True, prepared=True))
        sched.count_pass()
        _M_PREPARED.inc(outcome="fired")

    def _commit_step(self, batch, tokens, aux, extra) -> List[SeqOutput]:
        """Advance scheduler state by one collected single-runner entry
        (the ``output`` phase): logprobs, speculation accept runs,
        process_output, then the shared commit tail."""
        if isinstance(batch, list):
            if aux.get("spec_counts") is not None:
                # fused speculation block: variable per-sub-step commits
                return self._commit_outputs(
                    self._commit_spec_block(batch, tokens, aux))
            # multi-step block: tokens [K, S]; advance K scheduler steps
            outs = []
            for b, row in zip(batch, tokens):
                outs.extend(self.scheduler.process_output(
                    b, row.tolist(), self.eos_token_ids))
            if extra is not None:
                self._count_ondevice_finishes(outs)
            return self._commit_outputs(outs)
        spec = aux.pop("spec", None) if aux else None
        spec_lp = aux.pop("spec_lp", None) if aux else None
        if aux:
            # before process_output: ScheduledSeq.samples reads the seq's
            # CURRENT token count, which process_output advances
            self._record_logprobs(batch, aux)
        if spec is not None:
            # speculative step: draft items commit their verified run +
            # correction token; everything else commits its sampled token
            tok_mat, accept = spec
            token_lists = []
            for i, it in enumerate(batch.items):
                if it.draft_tokens:
                    a = min(int(accept[i]), len(it.draft_tokens))
                    token_lists.append(
                        [int(t) for t in tok_mat[i, :a + 1]])
                else:
                    token_lists.append([int(tokens[i])])
            outs = self.scheduler.process_output_multi(
                batch, token_lists, self.eos_token_ids)
            self._record_spec_logprobs(batch, spec_lp, outs)
        else:
            outs = self.scheduler.process_output(batch, tokens.tolist(),
                                                 self.eos_token_ids)
        return self._commit_outputs(outs)

    def _commit_outputs(self, outs) -> List[SeqOutput]:
        """Shared commit tail for one collected entry: stop-string
        trimming, promise reconciliation (pipelined loop — a finish for
        a sequence some later speculative entry assumed alive
        invalidates that entry and its chained descendants), and the
        per-request latency bookkeeping."""
        self._check_stop_strings(outs)
        if self.pipelined and self._in_flight:
            finished = frozenset(o.seq.seq_id for o in outs
                                 if o.finish_reason is not None)
            n = self.futures.reconcile(self._in_flight, finished)
            if n:
                # drop the tip only if the tip entry ITSELF was
                # invalidated — a tip descending from a later valid
                # sync root keeps extending (the legacy tip guards via
                # _in_flight[-1].invalid instead)
                if self._chain_tip is not None and any(
                        e.invalid and e.handle is self._chain_tip[1]
                        for e in self._in_flight):
                    self._chain_tip = None
                self._note_stall("rebuild", invalidated=n)
        self._observe_outputs(outs)
        return outs

    def _dispatch_reform(self, prev_batch, prev_handle, sched_ph,
                         multi: int, slot_mode: bool,
                         pressure: bool) -> bool:
        """Speculatively re-form and dispatch the next decode batch off
        ``prev_batch``'s promised token counts (pipelined loop;
        scheduler.schedule_reform holds the FutureMap contract). The
        re-formed batch fuses with chain links into one multi-step
        dispatch when eligible — finishes no longer cost the fused-block
        shape. Returns False (with a loop_stall recorded) when
        re-forming needs host-committed state."""
        if self.model_cfg.use_hybrid:
            # the GDN recurrent state is CUMULATIVE: a discarded
            # speculative step leaves the slot advanced by a token that
            # never committed, and the rebuild advances it again.
            # Paged-KV rewrites are idempotent; SSM state is not — so
            # hybrid models keep the drain-and-sync edge (no snapshot
            # pool is budgeted for per-step rollback here).
            self._note_stall("readback")
            return False
        batch = self.scheduler.schedule_reform(prev_batch)
        if batch is None:
            reason = self.scheduler.reform_fail_reason
            # pp_budget gets its own stall row: the per-stage throttled
            # decode share shrank below the promised row count, so the
            # sync pass must re-balance the stage batches — distinct
            # from waiting on readback (docs/observability.md).
            self._note_stall(reason if reason in ("pages", "pp_budget")
                             else "readback")
            return False
        promises = FutureMap.promised_ids(batch)
        links = (self._schedule_multi_links(batch, multi - 1)
                 if multi > 1 else [])
        if links:
            au = links[0].active_until
            k = 1 + len(links)
            first = dataclasses.replace(
                batch, active_until=([min(d + 1, k) for d in au]
                                     if au is not None else None))
            chain = [first] + links
            entry = self._launch(sched_ph, chain, self.runner.step_multi,
                                 chain, prev_handle, chained=True,
                                 promises=promises)
        else:
            entry = self._launch(sched_ph, batch,
                                 self.runner.step_async_chained, batch,
                                 prev_handle, chained=True,
                                 promises=promises)
        self._in_flight.append(entry)
        self._yield_noted = False
        if pressure:
            # a speculative re-form spends ramp budget like the chain it
            # replaced — prefill admission must still get its yields
            self._chained_under_pressure += 1 + len(links)
        if slot_mode:
            self._chain_tip = entry.tip
        return True

    def _note_stall(self, reason: str, **fields) -> None:
        """One loop_stall steptrace event (pipelined loop only): why the
        fill pass failed to run further ahead — readback / rebuild /
        pages / depth / pp_budget (docs/observability.md event
        catalog)."""
        TRACE.record("loop_stall", reason=reason,
                     depth=len(self._in_flight), **fields)

    def _note_chain_break(self, batch, reason: str) -> None:
        """One overlap chain break: steptrace event + labeled counter.
        ``batch`` is the chain tip (a ScheduledBatch or a fused chain
        list) whose extension failed or was yielded."""
        if isinstance(batch, list):
            batch = batch[-1]
        TRACE.record("chain_break", num_seqs=batch.num_seqs,
                     reason=reason)
        _M_CHAIN_BREAKS.inc(reason=reason)

    def _ondevice_block_stats(self, finish_step) -> dict:
        """Host bookkeeping over a fused block's per-row finish steps
        (runner aux ``finish``): executed sub-steps (the while_loop ran
        to the latest-finishing row, possibly < the scheduled K — early
        exit) and dead sub-steps (row frozen but the block still ran).
        Feeds the gllm_dead_substep_frac gauge and the fused_block
        steptrace event."""
        k_exec = int(finish_step.max()) if finish_step.size else 0
        dead = int((k_exec - finish_step).sum())
        if k_exec and finish_step.size:
            _M_DEAD_FRAC.set(dead / (k_exec * finish_step.size))
        return {"k_exec": k_exec, "dead_substeps": dead}

    def _spec_block_stats(self, chain, aux) -> dict:
        """Host bookkeeping over a fused-speculation block's aux: the
        actually-committed token count (the scheduled 1-per-link count
        is meaningless under variable emission), executed sub-steps
        (every executed sub-step emits at least one token on some live
        row, so the zero tail marks the early exit), dead-row shares,
        and the window accounting summarize() turns into
        spec_accept_rate / tokens_per_dispatch (k_drafted /
        k_accepted)."""
        n = chain[0].num_seqs
        counts = aux["spec_counts"][0][:, :n]
        d_arr, a_arr = aux["spec_totals"]
        k_exec = int((counts > 0).any(axis=1).sum())
        dead = int((counts[:k_exec] == 0).sum()) if k_exec else 0
        if k_exec and n:
            _M_DEAD_FRAC.set(dead / (k_exec * n))
        return {"k_exec": k_exec, "dead_substeps": dead,
                "k_drafted": int(d_arr[:n].sum()),
                "k_accepted": int(a_arr[:n].sum()),
                "spec_tokens": int(counts.sum())}

    def _commit_spec_block(self, chain, toks, aux):
        """Commit one collected fused-speculation block
        (docs/speculative_decoding.md#fused): sub-step k of row i
        commits ``counts[k, i]`` of its k+1 verify tokens (the accepted
        run + the correction/bonus token, possibly truncated by the
        budget or an on-device stop hit). The scheduled per-link
        ``computed_before`` values were worst-case UPPER bounds — each
        link re-anchors on the sequence's committed state before
        process_output_multi advances it, in-flight descendants' bounds
        trim to the actuals (FutureMap.trim_overpromise), and the AIMD
        draft length + acceptance stats reconcile from the handle aux."""
        from gllm_tpu.sequence import HOLE_SEQ_ID, SequenceStatus
        counts = aux["spec_counts"][0]
        n = chain[0].num_seqs
        outs = []
        for k, b in enumerate(chain):
            items, lists = [], []
            for i, it in enumerate(b.items):
                seq = it.seq
                if (seq.seq_id != HOLE_SEQ_ID
                        and seq.status is SequenceStatus.RUNNING):
                    # upper-bound → actual: the device carried the real
                    # frontier; the host adopts it from committed state
                    it = dataclasses.replace(
                        it, computed_before=seq.num_computed_tokens)
                items.append(it)
                c = int(counts[k, i])
                lists.append([int(t) for t in toks[k, i, :c]])
            nb = dataclasses.replace(b, items=items)
            outs.extend(self.scheduler.process_output_multi(
                nb, lists, self.eos_token_ids))
        d_arr, a_arr = aux["spec_totals"]
        drafted, accepted = int(d_arr[:n].sum()), int(a_arr[:n].sum())
        tok = int(counts[:, :n].sum())
        self.scheduler.spec_stats["proposed"] += drafted
        self.scheduler.spec_stats["accepted"] += accepted
        if drafted:
            _M_SPEC_FUSED.inc(accepted, kind="accepted")
            _M_SPEC_FUSED.inc(drafted - accepted, kind="rejected")
        if tok > accepted:
            _M_SPEC_FUSED.inc(tok - accepted, kind="correction")
        kc = aux["spec_kcur"][0]
        frontiers = {}
        for i, it in enumerate(chain[0].items):
            seq = it.seq
            if seq.seq_id == HOLE_SEQ_ID:
                continue
            if i < n:
                seq.spec_k_cur = max(1, min(int(kc[i]),
                                            self.config.spec_k))
            frontiers[seq.seq_id] = seq.num_computed_tokens
        if self._in_flight:
            self.futures.trim_overpromise(self._in_flight, frontiers)
        if self.config.ondevice_finish:
            self._count_ondevice_finishes(outs)
        return outs

    def _count_ondevice_finishes(self, outs) -> None:
        """gllm_ondevice_finish_total{kind}: finishes that committed out
        of an on-device-finish fused block, classified the way the device
        saw them (stop-string finishes come later, from host scanning)."""
        for out in outs:
            if out.finish_reason == "length":
                _M_ONDEV_FINISH.inc(kind="length")
            elif out.finish_reason == "stop":
                sp = out.seq.sampling_params
                eos = (not sp.ignore_eos
                       and out.new_token_id in self.eos_token_ids)
                _M_ONDEV_FINISH.inc(kind="eos" if eos else "stop")

    def _launch(self, sched_ph, batch, dispatch, *args, **flags) -> InFlight:
        """The one dispatch tail of every fill pass: close the pass's
        ``schedule`` phase, run the runner call (it times ``build`` and
        ``dispatch`` itself), and wrap the handle in an in-flight entry
        that takes the thread's open phase dict with it — everything the
        loop did since the previous take (obs/spans.take_phases)."""
        sched_ph.stop()
        handle = dispatch(*args)
        phases = spans.take_phases()
        phases["t_enter"] = sched_ph.t0
        return InFlight(batch, handle, time.monotonic(), phases, **flags)

    def _record_spans(self, batch, t_dispatch: float, now: float) -> None:
        """Request-scoped span events for one collected step that holds
        a prompt chunk: each row that carries one gets a
        ``prefill_chunk`` child [dispatch → collect] (obs/spans.py; no-op
        for requests the span tracker never opened). The rows that
        decode get nothing: their steps are on the ring once, as step
        events, and a request's tree gets one ``decode`` child at its
        finish."""
        dur = (now - t_dispatch) * 1e3
        for it in batch.items:
            if (it.num_new_tokens > 1
                    or it.computed_before < it.seq.prompt_len):
                self.spans.event(it.seq.seq_id, "prefill_chunk",
                                 t_dispatch, dur,
                                 tokens=it.num_new_tokens)

    def _record_step(self, batch, t0: float, t_dispatch: float,
                     extra: Optional[dict], phases: dict,
                     prepared: bool = False,
                     now: Optional[float] = None) -> None:
        """Step-kind attribution for one collected single-runner
        iteration: what kind of step it was and how many tokens it
        carried; :meth:`_emit_step` does the rest. Host wall clock only
        — the handle was already collected, at ``now`` (the loop may
        have launched the step it had prepared since)."""
        if now is None:
            now = time.monotonic()
        fused = isinstance(batch, list)
        b = batch[-1] if fused else batch
        if fused:
            kind = "fused_block"
            tokens = sum(x.total_tokens for x in batch)
            if extra and extra.get("spec_tokens") is not None:
                # fused speculation: the block committed a variable
                # token count (scheduled 1/link is only an upper-bound
                # anchor) — report what actually emitted
                tokens = extra["spec_tokens"]
        else:
            kind = ("decode" if b.num_decode == b.num_seqs
                    else "prefill")
            tokens = b.total_tokens
        ev = dict(num_seqs=b.num_seqs, tokens=tokens,
                  # entries still in flight AFTER this collect — the
                  # run-ahead depth the loop sustained (summarize() →
                  # mean_inflight_depth)
                  inflight=len(self._in_flight))
        if fused:
            ev["k"] = len(batch)
        if prepared:
            ev["prepared"] = True
        if extra:
            ev.update(extra)
        self._emit_step(kind, ev, [batch], phases, t0, t_dispatch, now,
                        decode_steps=(len(batch) if fused
                                      else int(kind == "decode")),
                        fused=fused)

    def _record_step_dp(self, live, t0: float, t_dispatch: float,
                        phases: dict, inflight: Optional[int] = None) -> None:
        """One step event for the stacked dp program (all replicas run
        in it): the sync dp loop and the dp super-step loop record the
        same fields through the same tail as the single runner."""
        now = time.monotonic()
        decode_only = all(b.num_decode == b.num_seqs for b in live)
        kind = "decode" if decode_only else "prefill"
        ev = dict(num_seqs=sum(b.num_seqs for b in live),
                  tokens=sum(b.total_tokens for b in live),
                  dp=len(live))
        if inflight is not None:
            ev["inflight"] = inflight
        self._emit_step(kind, ev, live, phases, t0, t_dispatch, now,
                        decode_steps=int(decode_only))

    def _emit_step(self, kind: str, ev: dict, batches, phases, t0: float,
                   t_dispatch: float, now: float, decode_steps: int = 0,
                   fused: bool = False) -> None:
        """The tail every step path shares (single runner, sync dp, dp
        super-step — one implementation so they cannot drift): the
        latency histogram, per-kind counters, the steptrace event with
        the engine-loop phase breakdown (the entry's own dict from
        dispatch time plus what the thread measured since — the
        collect's ``wait`` / ``readback``; docs/observability.md#tracing),
        and the ``prefill_chunk`` spans of a step that holds a prompt
        chunk (``decode_steps`` is 0 there: a decode-only step and a
        fused block make no call into ``SpanTrace``)."""
        wall = now - t0
        _M_STEP_LAT.observe(wall, kind=kind)
        _M_STEPS.inc(kind=kind)
        _M_STEP_TOKENS.inc(ev["tokens"], kind=kind)
        if not fused and self.runner.attn_impl == "pallas":
            for b in batches:
                for kernel, n in zip(("decode", "ragged"),
                                     b.mixed_step_rows or ()):
                    _M_MIXED_ROWS.inc(n, kernel=kernel)
        if decode_steps:
            _M_DECODE_STEPS.inc(decode_steps,
                                fused="true" if fused else "false")
        ev["wall_ms"] = round(wall * 1e3, 3)
        merged = dict(phases)
        for name, sec in spans.take_phases().items():
            merged[name] = merged.get(name, 0.0) + sec
        ev.update(spans.step_phases(merged))
        ev["step_wall_ms"] = round((now - phases["t_enter"]) * 1e3, 3)
        TRACE.record(kind, t_mono=now, **ev)
        if self.tracing and not decode_steps:
            for b in batches:
                self._record_spans(b, t_dispatch, now)

    def _observe_outputs(self, outs) -> None:
        """Per-request latency bookkeeping over one iteration's outputs
        (after stop-string trimming so finish reasons are final). Tokens
        that commit together (fused blocks, accepted drafts) observe
        near-zero ITL — truthful: the client receives them together."""
        if not outs:
            return
        now = time.monotonic()
        for out in outs:
            seq = out.seq
            if out.new_token_id is not None:
                if not seq.first_token_time:
                    seq.first_token_time = now
                    if seq.arrival_time:
                        _M_TTFT.observe(now - seq.arrival_time)
                        if seq.first_sched_time:
                            _M_QUEUE.observe(seq.first_sched_time
                                             - seq.arrival_time)
                    if not seq.submitted_t:
                        # no serving engine submitted it (generate):
                        # its last stage ends here. A served request's
                        # event is written by the handler thread that
                        # takes its first chunk (deliver_output)
                        seq.first_token_out = True
                        spans.FirstToken(seq).record()
                elif seq.last_token_time:
                    _M_ITL.observe(now - seq.last_token_time)
                seq.last_token_time = now
            if out.finish_reason is not None:
                _M_FINISHED.inc(reason=out.finish_reason)
                if seq.arrival_time:
                    _M_E2E.observe(now - seq.arrival_time)
                n = seq.num_output_tokens
                if n > 1 and seq.first_token_time:
                    _M_TPOT.observe((seq.last_token_time
                                     - seq.first_token_time) / (n - 1))
                if self.tracing:
                    # close the request's span tree with its roll-ups
                    # (one decode child, the detokenize wall)
                    self.spans.close(seq, out.finish_reason, now)

    def _schedule_multi(self, prev_batch, multi: int):
        """Chain up to ``multi`` decode steps off ``prev_batch`` for one
        fused dispatch. Greedy, sampled, and SEEDED rows all fuse (their
        device draws advance with the scan); penalties / logit_bias /
        logprobs / stop-strings / hybrid-SSM fall back to single chained
        steps."""
        fusable = self._fuse_ok(prev_batch)
        k_max = multi if fusable else 1
        return self.scheduler.schedule_chain(
            prev_batch, k_max,
            spec_mult=self.spec_mult if fusable else 1)

    def _fuse_ok(self, batch) -> bool:
        """May ``batch``'s sequences ride a fused multi-step block?

        The fused block's OWN batches are all-decode, so prompt-only
        extras (mm, plp) can never apply to them — gate only on per-seq
        properties that would need per-step host work: logit_bias (device
        scatter not in the fused program), logprobs (not plumbed through
        it), stop strings (must be checked between steps or the block
        streams past the match). Penalties are refused inside
        schedule_chain; SEEDED rows fuse fine — their draws are a pure
        function of (seed, out_step), which the fused scan advances on
        device."""
        if self.model_cfg.use_hybrid:
            return False
        return not any(it.seq.sampling_params.logit_bias
                       or it.seq.sampling_params.logprobs is not None
                       or it.seq.sampling_params.stop
                       or it.draft_tokens
                       for it in batch.items)

    def _schedule_multi_links(self, batch, k_max: int):
        """Chain links to fuse BEHIND a sync decode batch (the batch
        itself becomes the block's first step — see step())."""
        if k_max < 1 or not self._fuse_ok(batch):
            return []
        return self.scheduler.schedule_chain(batch, k_max,
                                             include_prev=True,
                                             spec_mult=self.spec_mult)

    def _step_dp(self, after_dispatch=None) -> List[SeqOutput]:
        """One synchronous step over all DP replicas (single jit program;
        idle replicas run dummy batches inside it). ``after_dispatch``:
        as in :meth:`step`."""
        sched_ph = spans.phase("schedule").start()
        batches = [s.schedule_once() for s in self.schedulers]
        sched_ph.stop()
        if all(b is None for b in batches):
            return []
        faults.FAULTS.maybe_stall("dispatch_stall")
        faults.FAULTS.maybe_raise("step_exception")
        t_dispatch = time.monotonic()
        handle = self.runner.step_async_dp(batches)
        phases = spans.take_phases()
        phases["t_enter"] = sched_ph.t0
        if after_dispatch is not None:
            after_dispatch()
        t0 = time.monotonic()
        rows, auxes = self.runner.collect_dp(handle)
        live = [b for b in batches if b is not None]
        with spans.phase("output"):
            # the dp step is synchronous: device wall ≈ collect block
            self._record_step_dp(live, t0, t_dispatch, phases)
            outs = self._dp_process_outputs(batches, rows, auxes)
            self._check_stop_strings(outs)
            self._observe_outputs(outs)
        return outs

    def _dp_process_outputs(self, batches, rows, auxes) -> List[SeqOutput]:
        """Per-replica commit tail shared by the sync dp loop and the dp
        super-step pipelined loop: logprobs, host-driven speculation,
        process_output against each replica's own scheduler."""
        outs: List[SeqOutput] = []
        for sched, b, row, aux in zip(self.schedulers, batches, rows,
                                      auxes):
            if b is None:
                continue
            spec = aux.pop("spec", None) if aux else None
            spec_lp = aux.pop("spec_lp", None) if aux else None
            if aux:
                self._record_logprobs(b, aux)
            if spec is not None and b.has_drafts:
                tok_mat, accept = spec
                token_lists = []
                for i, it in enumerate(b.items):
                    if it.draft_tokens:
                        a = min(int(accept[i]), len(it.draft_tokens))
                        token_lists.append(
                            [int(t) for t in tok_mat[i, :a + 1]])
                    else:
                        token_lists.append([int(row[i])])
                b_outs = sched.process_output_multi(
                    b, token_lists, self.eos_token_ids)
                self._record_spec_logprobs(b, spec_lp, b_outs)
                outs.extend(b_outs)
            else:
                outs.extend(sched.process_output(b, row.tolist(),
                                                 self.eos_token_ids))
        return outs

    def _step_dp_overlap(self, after_dispatch=None) -> List[SeqOutput]:
        """dp fast path (docs/overlap_scheduling.md#topology-matrix):
        the stacked replica program forces lockstep (it donates the
        stacked KV), so the pipelined loop runs ahead in dp-wide
        SUPER-STEPS — each fill pass either re-forms EVERY live replica
        off its promised token counts (one chained stacked dispatch,
        spliced per replica from the previous super-step's on-device
        tokens) or drains to the sync path. Replicas idle at the chain
        root admit committed-state work as non-chained rows riding the
        same super-step. An entry's promises are the union over
        replicas; reconciliation invalidates whole super-steps
        (conservative — one replica's divergence costs the others a
        rebuild, never correctness), and greedy/seeded streams stay
        byte-identical to the sync dp loop for the usual reason:
        context- resp. (seed, out_step)-determined draws."""
        depth = max(1, self.config.overlap_depth)
        ran_dry = False
        while len(self._in_flight) < depth:
            sched_ph = spans.phase("schedule").start()
            tip = self._in_flight[-1] if self._in_flight else None
            if tip is not None and tip.invalid:
                # an invalidated super-step can never be a tip — the
                # rebuild must root from committed state
                tip = None
            if tip is not None:
                prev_batches = tip.batch.batches
                nxt = [None] * self.dp
                stall = None
                promises = frozenset()
                for r, sched in enumerate(self.schedulers):
                    prev_r = prev_batches[r]
                    if prev_r is None:
                        # replica idle since the chain root: admissions
                        # and prefill from committed state ride the
                        # super-step as non-chained rows (src_rows None)
                        nxt[r] = sched.schedule_once()
                        continue
                    b = sched.schedule_reform(prev_r)
                    if b is None:
                        reason = sched.reform_fail_reason
                        stall = (reason
                                 if reason in ("pages", "pp_budget")
                                 else "readback")
                        break
                    nxt[r] = b
                    promises |= FutureMap.promised_ids(b)
                if stall is not None \
                        or not any(b is not None for b in nxt):
                    # replica lockstep: one refusal drains the whole
                    # super-step chain — unwind the replicas already
                    # scheduled this pass, fall to the sync path
                    for r, b in enumerate(nxt):
                        if b is not None:
                            self.schedulers[r].discard_batch(b)
                    self._note_stall(stall or "readback")
                    ran_dry = True
                    sched_ph.stop()
                    break
                entry = self._launch(
                    sched_ph, DPBatches(nxt),
                    functools.partial(self.runner.step_async_dp,
                                      prev_handle=tip.handle), nxt,
                    chained=True, promises=promises)
                self._in_flight.append(entry)
                continue
            batches = [s.schedule_once() for s in self.schedulers]
            if all(b is None for b in batches):
                if (self._in_flight
                        and any(s.has_unfinished
                                for s in self.schedulers)):
                    self._note_stall("readback")
                ran_dry = True
                sched_ph.stop()
                break
            entry = self._launch(sched_ph, DPBatches(batches),
                                 self.runner.step_async_dp, batches,
                                 roots=True)
            self._in_flight.append(entry)
        _M_INFLIGHT.set(len(self._in_flight))
        if not ran_dry and len(self._in_flight) >= depth:
            self._note_stall("depth")
        if not self._in_flight:
            return []
        if after_dispatch is not None:
            after_dispatch()
        faults.FAULTS.maybe_stall("dispatch_stall")
        faults.FAULTS.maybe_raise("step_exception")
        entry = self._in_flight.popleft()
        batches = entry.batch.batches
        if entry.invalid:
            # reconciliation discard: unwind per-replica bookkeeping
            # without committing tokens; the sync super-step rebuilds
            # from committed state (same contract as the single-runner
            # pipelined loop)
            for sched, b in zip(self.schedulers, batches):
                if b is not None:
                    sched.discard_batch(b)
            return []
        t0 = time.monotonic()
        rows, auxes = self.runner.collect_dp(entry.handle)
        live = [b for b in batches if b is not None]
        with spans.phase("output"):
            self._record_step_dp(live, t0, entry.t_dispatch, entry.phases,
                                 inflight=len(self._in_flight) + 1)
            outs = self._dp_process_outputs(batches, rows, auxes)
            return self._commit_outputs(outs)

    def _record_logprobs(self, batch, aux) -> None:
        """Attach per-token logprobs from the step's aux arrays to their
        sequences (reference sampler.py:71-91 → llm_engine logprob lists)."""
        if "lp" in aux:
            chosen, top_ids, top_lps = aux["lp"]
            for i, it in enumerate(batch.items):
                sp = it.seq.sampling_params
                if not it.samples or sp.logprobs is None:
                    continue
                if it.draft_tokens:
                    # speculative items commit tok_mat rows, not the
                    # last-row sample this aux describes — their logprobs
                    # come from the verify rows (_record_spec_logprobs)
                    continue
                if it.seq.output_logprobs is None:
                    it.seq.output_logprobs = []
                k = sp.logprobs
                it.seq.output_logprobs.append(
                    (float(chosen[i]), top_ids[i, :k].tolist(),
                     top_lps[i, :k].tolist()))
        if "plp" in aux:
            chosen, top_ids, top_lps = aux["plp"]
            off = 0
            for it in batch.items:
                n, seq = it.num_new_tokens, it.seq
                rows = n + len(it.draft_tokens)   # row layout incl. drafts
                sp = seq.sampling_params
                if (sp.prompt_logprobs is not None
                        and it.computed_before < seq.prompt_len):
                    if seq.prompt_logprobs is None:
                        # position 0 has no conditional logprob
                        seq.prompt_logprobs = [None] * seq.prompt_len
                    k = sp.prompt_logprobs
                    for j in range(n):
                        pos = it.computed_before + j + 1
                        if pos >= seq.prompt_len:
                            break
                        row = off + j
                        seq.prompt_logprobs[pos] = (
                            float(chosen[row]), top_ids[row, :k].tolist(),
                            top_lps[row, :k].tolist())
                off += rows

    def _record_spec_logprobs(self, batch, spec_lp, outs) -> None:
        """Logprobs for speculatively committed tokens, from the verify
        rows' adjusted distributions (runner aux ``spec_lp``). Appended
        AFTER process_output_multi so the count matches the tokens
        actually emitted (a finish mid-run discards the rest)."""
        if spec_lp is None:
            return
        chosen, top_ids, top_lps = spec_lp
        emitted = {}
        for out in outs:
            if out.new_token_id is not None:
                emitted[out.seq.seq_id] = emitted.get(out.seq.seq_id,
                                                      0) + 1
        for i, it in enumerate(batch.items):
            sp = it.seq.sampling_params
            if not it.draft_tokens or sp.logprobs is None:
                continue
            m = emitted.get(it.seq.seq_id, 0)
            if it.seq.output_logprobs is None:
                it.seq.output_logprobs = []
            k = sp.logprobs
            for j in range(m):
                it.seq.output_logprobs.append(
                    (float(chosen[i, j]), top_ids[i, j, :k].tolist(),
                     top_lps[i, j, :k].tolist()))

    def _check_stop_strings(self, outs) -> None:
        """Host-side stop-string matching over the incrementally detokenized
        output; the response text is truncated BEFORE the match (OpenAI
        semantics, reference frontend stop handling). Only the tail window
        (new text plus len(stop)-1 overlap chars) is rescanned per step.

        Multi-token commits (speculative decoding) replay this step's
        tokens one at a time through the incremental detokenizer — exactly
        the scan a sequence of single-token steps would have run — so the
        match lands on the token that completed it: later tokens are
        trimmed from the sequence (ids, computed count, logprobs) and
        their SeqOutputs dropped, keeping streamed text AND usage
        accounting identical to non-speculative stop handling.
        Finished seq ids also drop out of the DP routing table here."""
        n_new: dict = {}
        for out in outs:
            if out.finish_reason is not None:
                self._seq_replica.pop(out.seq.seq_id, None)
            if out.new_token_id is not None:
                sid = out.seq.seq_id
                n_new[sid] = n_new.get(sid, 0) + 1
        cuts: dict = {}
        scanned_ids = set()
        for out in outs:
            seq = out.seq
            sp = seq.sampling_params
            if (out.new_token_id is None or not sp.stop
                    or self.tokenizer is None
                    or seq.seq_id in scanned_ids):
                continue
            scanned_ids.add(seq.seq_id)
            max_stop = max(len(s) for s in sp.stop)
            first = seq.num_tokens - n_new[seq.seq_id]
            hit = -1
            for j in range(first, seq.num_tokens):
                text, seq.detok_prefix_offset, seq.detok_read_offset = (
                    detokenize_incrementally(self.tokenizer,
                                             seq.token_ids,
                                             seq.detok_prefix_offset,
                                             seq.detok_read_offset,
                                             end=j + 1))
                if not text:
                    continue
                seq.output_text += text
                start = max(0, getattr(seq, "_stop_scanned", 0)
                            - max_stop + 1)
                window = seq.output_text[start:]
                hit = min((start + idx for idx in (window.find(s)
                                                   for s in sp.stop)
                           if idx >= 0), default=-1)
                seq._stop_scanned = len(seq.output_text)
                if hit >= 0:
                    cuts[seq.seq_id] = j + 1 - first
                    break
            if hit < 0:
                continue
            keep = first + cuts[seq.seq_id]
            if keep < seq.num_tokens:
                dropped = seq.num_tokens - keep
                del seq.token_ids[keep:]
                if seq.mm is not None:
                    del seq.mm.hash_token_ids[
                        len(seq.mm.hash_token_ids) - dropped:]
                seq._pt_np = None
                seq.num_computed_tokens = min(seq.num_computed_tokens,
                                              keep)
                if seq.output_logprobs is not None:
                    del seq.output_logprobs[keep - seq.prompt_len:]
            seq.output_text = seq.output_text[:hit]
            # stop any further (re-)detokenization of trimmed state
            seq.detok_read_offset = seq.num_tokens
            seq.detok_prefix_offset = min(seq.detok_prefix_offset,
                                          seq.num_tokens)
            seq._stop_scanned = len(seq.output_text)
            r = self._seq_replica.pop(seq.seq_id, 0)
            self.schedulers[r].finish_seq(seq, "stop")
            seq.finish_reason = "stop"
        if cuts:
            kept, cnt = [], {}
            for out in outs:
                sid = out.seq.seq_id
                if sid in cuts and out.new_token_id is not None:
                    c = cnt.get(sid, 0)
                    if c >= cuts[sid]:
                        continue               # past-match token: drop
                    cnt[sid] = c + 1
                    out.finish_reason = ("stop" if cnt[sid] == cuts[sid]
                                         else None)
                kept.append(out)
            outs[:] = kept

    def generate(
        self,
        prompts: Optional[Union[str, Seq[str]]] = None,
        sampling_params: Optional[Union[SamplingParams,
                                        Seq[SamplingParams]]] = None,
        prompt_token_ids: Optional[Seq[List[int]]] = None,
        stream_cb: Optional[Callable[[SeqOutput], None]] = None,
        mm_inputs: Optional[Seq[Optional[dict]]] = None,
    ) -> List[RequestOutput]:
        if prompts is not None and prompt_token_ids is not None:
            raise ValueError(
                "pass either prompts or prompt_token_ids, not both")
        if prompts is None and prompt_token_ids is None:
            raise ValueError("pass prompts or prompt_token_ids")
        if prompts is not None and isinstance(prompts, str):
            prompts = [prompts]
        if prompt_token_ids is None:
            prompt_token_ids = [self.encode(p) for p in prompts]
        n = len(prompt_token_ids)
        if sampling_params is None:
            sampling_params = SamplingParams()
        if isinstance(sampling_params, SamplingParams):
            sampling_params = [dataclasses.replace(sampling_params)
                               for _ in range(n)]
        elif len(sampling_params) != n:
            raise ValueError(
                f"{len(sampling_params)} sampling_params for {n} prompts")

        seqs = [self._allocate_seq(ids, sp)
                for ids, sp in zip(prompt_token_ids, sampling_params)]
        if mm_inputs is not None:
            # HF-processor outputs per request (pixel_values,
            # image_grid_thw, ...) → per-seq MMState (hashing, mrope
            # positions, visual-row index; gllm_tpu/engine/mm.py).
            if len(mm_inputs) != n:
                raise ValueError(f"{len(mm_inputs)} mm_inputs for {n} "
                                 "prompts")
            from gllm_tpu.engine.mm import build_mm_state
            for seq, mi in zip(seqs, mm_inputs):
                if mi:
                    seq.mm = build_mm_state(seq.token_ids, self.model_cfg,
                                            **mi)
        for s in seqs:
            self.add_seq(s)

        while self.has_unfinished:
            for out in self.step():
                if out.new_token_id is not None \
                        and self.tokenizer is not None:
                    self._stream_detokenize(out.seq)
                if stream_cb is not None and out.new_token_id is not None:
                    stream_cb(out)

        return [self._finalize(s) for s in seqs]

    def chat(self, messages: List[dict],
             sampling_params: Optional[SamplingParams] = None,
             **kwargs) -> RequestOutput:
        """Apply the tokenizer/processor chat template and generate
        (reference llm_engine.py:647; multimodal content routes through
        the HF processor like the reference's MM pipeline)."""
        if self.model_cfg.use_mm:
            ids, mm_input = self.process_mm_messages(messages, **kwargs)
            return self.generate(prompt_token_ids=[ids],
                                 sampling_params=sampling_params,
                                 mm_inputs=[mm_input])[0]
        if self.tokenizer is None:
            raise ValueError("chat() requires a tokenizer")
        ids = self.render_chat_ids(messages, **kwargs)
        return self.generate(prompt_token_ids=[ids],
                             sampling_params=sampling_params)[0]

    @property
    def dsv32_encoder(self):
        """The DeepSeek-V3.2 checkpoint-bundled message encoder, or None
        (lazy; cached by model path in gllm_tpu.tokenizers)."""
        if (self.model_cfg.architecture != "DeepseekV32ForCausalLM"
                or not self.config.model):
            return None
        from gllm_tpu.tokenizers.deepseek_v32 import load_encoder
        return load_encoder(self.config.model)

    def render_chat_ids(self, messages, **kwargs) -> List[int]:
        """Chat messages → prompt token ids: the model-native DSv3.2
        encoder when the checkpoint bundles one, else the tokenizer's
        chat template (reference api_server.py:554-567)."""
        enc = self.dsv32_encoder
        if enc is not None:
            from gllm_tpu.tokenizers.deepseek_v32 import render_chat
            tools = kwargs.pop("tools", None)
            return render_chat(enc, messages, self.tokenizer,
                               tools=tools, **kwargs)
        ids = self.tokenizer.apply_chat_template(
            messages, add_generation_prompt=True, **kwargs)
        if ids and isinstance(ids[0], list):
            ids = ids[0]
        return [int(t) for t in ids]

    @property
    def processor(self):
        """Lazy HF processor for multimodal chat templates + pixels."""
        if getattr(self, "_processor", None) is None:
            from transformers import AutoProcessor

            from gllm_tpu.engine.mm_processing import apply_pixel_bounds
            self._processor = apply_pixel_bounds(
                AutoProcessor.from_pretrained(
                    self.config.model, local_files_only=True),
                self.config.mm_processor_min_pixels,
                self.config.mm_processor_max_pixels)
        return self._processor

    def process_mm_messages(self, messages: List[dict], **kwargs):
        """messages (OpenAI-style, with image content parts) → (token_ids,
        mm_input dict for build_mm_state). AutoProcessor when loadable,
        else the skeleton-tokenization fallback (engine/mm_processing.py)."""
        from gllm_tpu.engine.mm_processing import encode_mm_messages
        return encode_mm_messages(self, messages, **kwargs)

    # ---- output -----------------------------------------------------------

    def _stream_detokenize(self, seq: Sequence) -> str:
        t0 = time.monotonic() if self.tracing else 0.0
        text, seq.detok_prefix_offset, seq.detok_read_offset = (
            detokenize_incrementally(self.tokenizer, seq.token_ids,
                                     seq.detok_prefix_offset,
                                     seq.detok_read_offset))
        seq.output_text += text
        if self.tracing:
            # accumulated per request; emitted as ONE rolled-up
            # "detokenize" span at finish (one event per token would
            # blow the span-phase cap on long streams)
            seq._detok_s = (getattr(seq, "_detok_s", 0.0)
                            + (time.monotonic() - t0))
        return text

    def _finalize(self, seq: Sequence) -> RequestOutput:
        text = seq.output_text
        if self.tokenizer is not None:
            if seq.detok_read_offset < seq.num_tokens:
                # Flush tokens still held back by the partial-character
                # check — emit them even if they end incomplete.
                done = self.tokenizer.decode(
                    seq.token_ids[seq.detok_prefix_offset:
                                  seq.detok_read_offset])
                full = self.tokenizer.decode(
                    seq.token_ids[seq.detok_prefix_offset:])
                text += full[len(done):]
                seq.detok_read_offset = seq.num_tokens
                seq.output_text = text
            elif not text and seq.detok_read_offset <= seq.prompt_len:
                # never detokenized (offline batch path); a stop-string
                # truncation to "" leaves read_offset at num_tokens and
                # must NOT be undone here
                text = self.tokenizer.decode(seq.output_token_ids)
                seq.output_text = text
        return RequestOutput(
            seq_id=seq.seq_id,
            prompt_token_ids=seq.token_ids[:seq.prompt_len],
            output_token_ids=seq.output_token_ids,
            text=text,
            finish_reason=seq.finish_reason,
            num_prompt_tokens=seq.prompt_len,
            num_output_tokens=seq.num_output_tokens,
            logprobs=seq.output_logprobs,
            prompt_logprobs=seq.prompt_logprobs,
        )

    def abort(self, seq_id: int) -> None:
        # aborted seqs never emit a finishing SeqOutput — drop the routing
        # entry here
        if self.disagg_coordinator is not None:
            self.disagg_coordinator.abort([seq_id])
        r = self._seq_replica.pop(seq_id, 0)
        self.schedulers[r].abort_seq(seq_id)

    # ---- fault isolation ---------------------------------------------------

    def quarantine_step_failure(self, everything: bool = False
                                ) -> List[int]:
        """Roll the engine back to a consistent state after ``step()``
        raised (docs/robustness.md).

        The dispatched-but-uncollected batches in ``_in_flight`` are the
        failure's blast radius: their device state is unknown, so their
        sequences are dropped wholesale (pages freed, status ABORTED,
        in-flight counts zeroed) while everything else — the waiting
        queue, running sequences not in a failed dispatch — survives and
        reschedules. When the exception fired before any dispatch (no
        in-flight entries), the running set is the suspect: re-scheduling
        it would retry the identical failing step forever, which is
        exactly the hot-retry loop this path removes. ``everything=True``
        (unhealthy escalation / shutdown) additionally drops the waiting
        queue. Returns the dropped seq ids so the serving engine can
        deliver terminal error chunks."""
        from gllm_tpu.sequence import HOLE_SEQ_ID
        failed: set = set()
        for entry in self._in_flight:
            batch = entry.batch
            for b in (batch if isinstance(batch, list) else [batch]):
                for it in b.items:
                    if it.seq.seq_id != HOLE_SEQ_ID:
                        failed.add(it.seq.seq_id)
        self._in_flight.clear()
        self._chain_tip = None
        self._chained_under_pressure = 0
        self._yield_noted = False
        if everything:
            for s in self.schedulers:
                failed.update(x.seq_id for x in s.running)
                failed.update(x.seq_id for x in s.waiting)
        elif not failed:
            for s in self.schedulers:
                failed.update(x.seq_id for x in s.running)
        if self.swap_manager is not None:
            # queued transfer intents may reference pages the quarantine
            # frees — drop them first (swap-outs revert to recompute)
            self.swap_manager.quarantine()
        for s in self.schedulers:
            s.quarantine(failed)
        for sid in failed:
            self._seq_replica.pop(sid, None)
        if self.disagg_coordinator is not None and failed:
            try:
                self.disagg_coordinator.abort(sorted(failed))
            except Exception:
                logger.exception("disagg abort during quarantine failed")
        if self.tracing:
            # quarantined requests never emit a finishing SeqOutput —
            # close their span trees here (reason matches the terminal
            # error chunk the serving engine delivers)
            now = time.monotonic()
            for sid in failed:
                self.spans.finish(sid, "error", now)
        TRACE.record("quarantine", num_seqs=len(failed))
        return sorted(failed)
