"""ModelRunner: owns params + KV cache + the jit-compiled step function.

TPU-native analogue of the reference ModelRunner
(/root/reference/gllm/model_runner.py:223-2312). The re-design collapses most
of its machinery:

- CUDA-graph capture per bucket (capture_graph :1525) → jit compile-cache:
  each (token-bucket, seq-bucket, max-q) signature compiles once, replays
  forever. ``warmup()`` pre-compiles the decode buckets like the reference's
  capture loop.
- 3 CUDA streams + events (OverlapRuntime) → jax async dispatch: ``step()``
  returns a device array future; the host only blocks when it reads tokens.
- profile_run + cuda.mem_get_info KV sizing (:1482, memory_manager.py:476) →
  ``determine_num_pages`` from device memory_stats after a peak-shape dummy
  step.
- KV in-place update → buffer donation on the stacked cache arrays.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import threading
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gllm_tpu.batching import PackedBatch, StepBatch, pack, unpack
from gllm_tpu.config import EngineConfig
from gllm_tpu.models import ModelConfig, get_model_def
from gllm_tpu.obs import metrics as obs
from gllm_tpu.obs.spans import phase
from gllm_tpu.obs.steptrace import TRACE
from gllm_tpu.ops.sampling import sample
from gllm_tpu.runner.prepare import BatchBuilder
from gllm_tpu.scheduler import ScheduledBatch
from gllm_tpu.utils import (bucket_size, cdiv, next_pow2,
                            tpu_compiler_options)

logger = logging.getLogger(__name__)

# Dispatch-side metrics (docs/observability.md). All pure host counters
# on values the dispatch path already computes — the jit cache key set is
# untouched (nothing here feeds a static argument).
_M_SAMPLER = obs.counter(
    "gllm_sampler_program_total",
    "step dispatches by compiled sampler variant (greedy compiles the "
    "sampled branch away; see ops/sampling.sample)", ("program",))
_M_NEW_SHAPE = obs.counter(
    "gllm_jit_new_shape_signatures_total",
    "first dispatch of a (shape-bucket, static-flag) signature this "
    "process — an XLA compile unless the persistent cache held it")
_M_H2D = obs.counter(
    "gllm_step_h2d_arrays_total",
    "host arrays the runner placed on the device for step dispatches "
    "(counted where they are placed; a transfer each). Over "
    "gllm_sampler_program_total: arrays per dispatch, 2 on the default "
    "path (the packed batch and the tokens)")
# KV-cache dtype observability (docs/observability.md): an info gauge
# naming the active storage dtype.
_M_KV_DTYPE = obs.gauge(
    "gllm_kv_cache_dtype",
    "info gauge: 1 for the active paged-KV storage dtype", ("dtype",))

# What set-up costs (docs/observability.md): every executable this process
# obtained from XLA, split by whether it was compiled here or read back
# from the persistent compilation cache, and the seconds that took. jax
# reports both through jax.monitoring; the listeners are process-global
# and cannot be removed one by one, so they are registered once, here.
_M_XLA_PROGRAMS = obs.counter(
    "gllm_xla_programs_total",
    "executables obtained from XLA, by source: compiled in this process "
    "or read from the persistent compilation cache", ("source",))
_M_XLA_SECONDS = obs.counter(
    "gllm_xla_compile_seconds_total",
    "seconds spent obtaining executables from XLA (compiling, or reading "
    "the persistent cache), by source", ("source",))
# The first use of a step signature (a program this process has not
# dispatched yet) stalls every stream for its trace + lower + compile or
# cache read, seconds even where the persistent cache has the program:
# gllm_xla_programs_total reads 0 compiled in a run that lost most of its
# throughput this way (PERF.md, PR 23). The steptrace ``compile`` event
# carries the same wall as ``first_use_ms``.
_M_FIRST_USE = obs.counter(
    "gllm_step_first_use_seconds_total",
    "wall seconds of the jit calls that first used a step signature "
    "(trace + lower + compile, or the persistent cache read), by source",
    ("source",))
_M_SSM_APPLY = obs.counter(
    "gllm_ssm_apply_calls_total",
    "slot maintenance programs dispatched in front of a step (one for "
    "every hybrid stage under pp). gllm_ssm_intents_total over it: slots "
    "named a call, which the program's device bytes follow")
_xla_cache_hit = threading.local()


def _on_xla_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _xla_cache_hit.flag = True      # precedes its duration event


def _on_xla_duration(event: str, seconds: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        source = ("cache" if getattr(_xla_cache_hit, "flag", False)
                  else "compiled")
        _xla_cache_hit.flag = False
        if source == "compiled":
            _xla_cache_hit.compiled = True      # read by first_use
        _M_XLA_PROGRAMS.inc(source=source)
        _M_XLA_SECONDS.inc(seconds, source=source)


class first_use:
    """Times the jit call that first uses a step signature: a
    ``first_use`` span nested in ``dispatch`` (not added to the step's
    phases a second time), the ``compile`` steptrace event with
    ``first_use_ms`` and ``source`` — ``compiled`` if XLA compiled a
    program during the call, else ``cache`` — and the counter. A no-op
    where ``fields`` (from ``_note_dispatch``) is None: the signature
    has been used before."""

    __slots__ = ("fields", "span")

    def __init__(self, fields: Optional[dict]):
        self.fields = fields

    def __enter__(self):
        if self.fields is not None:
            _xla_cache_hit.compiled = False
            self.span = phase("first_use", add=False).start()
        return self

    def __exit__(self, exc_type, *_):
        if self.fields is None:
            return False
        self.span.stop()
        if exc_type is None:
            source = ("compiled" if getattr(_xla_cache_hit, "compiled",
                                            False) else "cache")
            _M_FIRST_USE.inc(self.span.seconds, source=source)
            TRACE.record("compile", source=source,
                         first_use_ms=round(self.span.seconds * 1e3, 3),
                         **self.fields)
        return False


jax.monitoring.register_event_listener(_on_xla_event)
jax.monitoring.register_event_duration_secs_listener(_on_xla_duration)

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16,
           # fp8 KV storage (MLA latent / dense KV) — reference
           # concat_and_cache_mla_fp8 packed cache, cache_kernels.py
           "fp8": jnp.float8_e4m3fn,
           # int8 KV storage with per-page per-head scales — only valid
           # as cache.kv_cache_dtype (ops/kv_cache.write_kv_quant)
           "int8": jnp.int8}



def _all_greedy(items) -> bool:
    """Static greedy flag for the step programs (see ops/sampling.sample):
    True compiles the sampled branch away for this batch."""
    return all(it.seq.sampling_params.temperature == 0.0 for it in items)


def _start_host_copy(tree) -> None:
    """Begin the device→host copy of every array ``collect`` will fetch,
    at DISPATCH time: a copy started when the step is enqueued overlaps
    the host work before collect, which then finds the values local
    instead of paying a synchronous fetch. No-op where the backend lacks
    it."""
    for leaf in jax.tree.leaves(tree):
        try:
            leaf.copy_to_host_async()
        except (AttributeError, RuntimeError, TypeError):
            pass


def _to_host(x) -> np.ndarray:
    """Device→host that also works for multi-host global arrays: sampled
    tokens / logprobs are replicated, so the local shard IS the value."""
    if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
        return np.asarray(x.addressable_data(0))
    return np.asarray(x)


def _ssm_update(conv, rec, idx, snap_src, snap_dst, zero_slots, rest_src,
                rest_dst):
    """Shared SSM slot maintenance body (snapshot → zero → restore).
    ``idx``: index prefix — () for a single pool ([Lg, slots, ...]),
    (r,) for one replica of dp-stacked pools ([dp, Lg, slots, ...]).

    A slot at a time, in place: each entry is one ``dynamic_slice`` and
    one ``dynamic_update_slice`` of the donated pools along the slot axis,
    so the program moves the slots it is handed and knows nothing else of
    a pool's shape (a gather and scatter over that axis rewrote the whole
    pool wherever its last dimension is not one 128-lane tile). The three
    classes run as ONE loop over their entries in class order, a zero as
    a copy of the slot onto itself with zeros chosen for the value; the
    padding entries (destination 0, the dummy slot no sequence holds) are
    sorted behind the others and the loop stops short of them."""
    src = jnp.concatenate([snap_src, zero_slots, rest_src])
    dst = jnp.concatenate([snap_dst, zero_slots, rest_dst])
    wipe = jnp.asarray(np.repeat(
        [False, True, False], [snap_dst.size, zero_slots.size, rest_dst.size]))
    order = jnp.argsort(dst == 0, stable=True)
    src, dst, wipe = src[order], dst[order], wipe[order]
    lead = (1,) * len(idx)

    def moved(pool, i):
        layers, _, *inner = pool.shape[len(idx):]
        at = lambda s: (*idx, 0, s, *(0,) * len(inner))
        slot = jax.lax.dynamic_slice(pool, at(src[i]),
                                     (*lead, layers, 1, *inner))
        slot = jnp.where(wipe[i], jnp.zeros((), pool.dtype), slot)
        return jax.lax.dynamic_update_slice(pool, slot, at(dst[i]))

    def move(i, pools):
        # a model without a recurrent stack hands None for it
        return jax.tree.map(lambda pool: moved(pool, i), pools)

    return jax.lax.fori_loop(0, jnp.sum(dst != 0), move, (conv, rec))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _ssm_apply(conv, rec, snap_src, snap_dst, zero_slots, rest_src,
               rest_dst):
    return _ssm_update(conv, rec, (), snap_src, snap_dst, zero_slots,
                       rest_src, rest_dst)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _ssm_apply_replica(conv, rec, r, snap_src, snap_dst, zero_slots,
                       rest_src, rest_dst):
    return _ssm_update(conv, rec, (r,), snap_src, snap_dst, zero_slots,
                       rest_src, rest_dst)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def reset_page_scales(k_scale, v_scale, pages):
    """Zero the quantization scales of freshly MINTED pages (int8 KV
    cache): a zero scale is the fresh-page mark — the first write
    zero-fills the stale payload and starts a new running absmax, so a
    recycled page quantizes exactly like a never-used one. ``pages`` is
    pow2-padded with the dummy page 0 (whose scale is meaningless).
    Leaves are [L, P, H]; the dp-stacked [dp, L, P, H] layout goes
    through :func:`reset_page_scales_replica` instead."""
    return (k_scale.at[:, pages].set(0.0), v_scale.at[:, pages].set(0.0))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def reset_page_scales_replica(k_scale, v_scale, r, pages):
    """dp-stacked variant: zero replica ``r``'s minted-page scales on
    [dp, L, P, H] leaves (each replica drains its own memory manager)."""
    return (k_scale.at[r, :, pages].set(0.0),
            v_scale.at[r, :, pages].set(0.0))


@functools.partial(jax.jit, static_argnames=("k",))
def _fold_in_range(key, start, *, k: int):
    """[k] per-sub-step keys for a fused decode block:
    fold_in(key, start + i) for i in range(k). The fused blocks call it
    inside their programs, on the ``step`` their packed batch carries
    (batching.unpack); the vmapped fold_in is bit-identical to k
    fold_in calls from the host (fold_in folds the integer in as data,
    traced or not) and keeps working as chain lengths grow."""
    steps = start + jnp.arange(k, dtype=jnp.uint32)
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(steps)


@jax.jit
def _scatter_prev(tokens, prev, ir):
    """``tokens[..., ir[-2]] = prev[..., ir[-1]]``: the promised rows of a
    re-formed batch take the previous entry's on-device sampled tokens.
    ``ir`` is ONE host-built index array, [2, n] (flat offset, source
    row) or, over dp-stacked tokens, [3, n] (replica first). Neither
    argument is donated: the previous entry's collect still reads
    ``prev`` (its async host copy may be in flight)."""
    lead = tuple(ir[:-2])
    return tokens.at[lead + (ir[-2],)].set(prev[lead + (ir[-1],)])


def device_free_bytes(device, memory_util: float) -> float:
    """Bytes of ``device`` still free under the ``memory_util`` share of
    its limit. A TPU that cannot report its memory is an error: a guessed
    pool either starves the scheduler or overruns the chip."""
    stats = device.memory_stats()
    if not stats or "bytes_limit" not in stats:
        raise RuntimeError(
            f"{device} reports no memory_stats(); cannot size the KV "
            "pool — pass num_pages explicitly")
    return stats["bytes_limit"] * memory_util - stats["bytes_in_use"]


def stable_page_count(num: int) -> int:
    """``num`` rounded down to a multiple of 256 pages (large pools only).
    A pool sized to the byte from live memory stats lands a page or two
    apart from one start to the next, and the page count is a shape of
    every step program: each restart would then miss the persistent
    compile cache on all of them (measured on v5e: 23264 / 23265 / 23263
    pages over three starts, 287 s of recompiles). At most 1.6% of a pool
    of 16 x 256 pages or more."""
    return num - num % 256 if num >= 16 * 256 else num


def pallas_tp_ok(cfg: ModelConfig, tp: int) -> bool:
    """Can the Pallas attention run tp-sharded for this model? Only the
    head-count split over tp must divide (dp>1 runs the kernels per
    replica under manual shard_map and adds no constraint). Shared by
    ModelRunner and PPModelRunner."""
    from gllm_tpu.ops.attention import pallas_tp_compatible
    hkv = 1 if cfg.use_mla else cfg.num_kv_heads
    return pallas_tp_compatible(cfg.num_heads, hkv, tp)


def pick_kv_pack(cfg: ModelConfig, tp_sharded: bool) -> int:
    """Mosaic lane-packing policy, shared by ModelRunner and PPModelRunner.

    Returns 0 when the Pallas kernels cannot compile for this model
    (caller falls back to XLA or raises), 1 when no packing is needed, or
    the pack factor (2/4 adjacent kv heads per 128-lane cache row) for
    head_dim < 128 models. Packing is a single-replica layout: tp/dp
    shard the unpacked specs, so sharded meshes need native alignment.
    models/hybrid.py and the Mamba-2 decoders build their paged cache
    themselves (``kv_cache_heads``: padded KV heads, no ``kv_pack``), so
    they are never packed; a slot-pool model whose decoder serves its
    attention through ``dense._attention`` over a cache built with
    ``kv_pack`` (models/lfm2_moe.py) packs as a dense model does.

    On the CPU backend the kernels run in interpret mode, which has no
    Mosaic lane constraints (same escape as ops/gdn.py) — any layout is
    viable, keeping CPU e2e coverage of the Pallas engine path alive for
    arbitrary head_dim."""
    def native() -> int:
        if cfg.use_mla:
            # latent cache is tile-padded by construction; the in-kernel
            # value slice k[..., :lora] still needs lane alignment (512
            # for DeepSeek)
            return 1 if cfg.kv_lora_rank % 128 == 0 else 0
        if cfg.head_dim % 128 == 0:
            return 1
        if tp_sharded or (cfg.use_hybrid and not cfg.use_short_conv):
            return 0
        for p in (2, 4):
            if cfg.head_dim * p % 128 == 0 and cfg.num_kv_heads % p == 0:
                return p
        return 0

    pack = native()
    if pack == 0 and jax.default_backend() == "cpu":
        return 1
    return pack


def plp_block_rows(tokens: int, vocab: int) -> int:
    """Rows of a step whose prompt logprobs are computed at a time: all of
    them while their float32 logits stay under 1.5 GB (every vocabulary up
    to 183 k at a 2048-token chunk), else the largest divisor of
    ``tokens`` whose logits stay under 0.6 GB (512 of 2048 and 528 of 2112
    at a vocabulary of 261120)."""
    if tokens * vocab * 4 <= 1.5e9:
        return tokens
    return max((d for d in range(1, tokens + 1)
                if tokens % d == 0 and d * vocab * 4 <= 0.6e9), default=1)


def build_in_place(make, mesh, specs, device=None):
    """Run the array-building ``make()`` so that every leaf is created
    where it will live: straight into its shards under ``mesh`` (``specs``
    is the matching PartitionSpec tree), else on ``device`` (None = the
    default device). Weights and KV pools are sized to fill the devices
    they are spread over; built whole on one device first, a model that
    needs the mesh to fit would not survive its own set-up."""
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.jit(make, out_shardings=jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec)))()
    if device is not None:
        with jax.default_device(device):
            return make()
    return make()


def init_dummy_params(model_def, cfg: ModelConfig, seed: int, dtype,
                      mesh, tp: int, device=None):
    """Seeded random weights (``load_format="dummy"``) built in place,
    shared by ModelRunner and PPModelRunner (jax's random bits are the
    same however the array is partitioned)."""
    init = functools.partial(model_def.init_params, cfg, seed=seed,
                             dtype=dtype)
    specs = None
    if mesh is not None:
        specs = model_def.param_specs(cfg, tp)
        if "visual" in specs and "visual" not in jax.eval_shape(init):
            del specs["visual"]
    return build_in_place(init, mesh, specs, device)


def resolve_attn_impl(impl: str, cfg: ModelConfig, tp: int, pack: int,
                      tp_sharded: bool) -> str:
    """``attention_impl`` as the runner will run it, shared by ModelRunner
    and PPModelRunner. ``auto`` is Pallas on a TPU wherever the kernels
    can serve the model and layout; where they cannot, the XLA fallback
    is announced with its reason, never taken silently. An explicit
    ``pallas`` that cannot be served raises. Off the TPU ``auto`` is the
    XLA path (the platform's own; Pallas there is interpret mode, which
    has no lane constraints and is only ever asked for by name)."""
    on_tpu = jax.default_backend() == "tpu"
    if impl == "xla" or (impl == "auto" and not on_tpu):
        return "xla"
    why = None
    if not pack:
        why = ("no 128-lane-aligned KV layout: head_dim (x pack 2/4) % 128 "
               "== 0, or kv_lora_rank % 128 == 0 for MLA, and no lane "
               "packing under tp or for the decoders that build their own "
               "paged cache (models/hybrid.py, the Mamba-2 models)")
    elif tp_sharded and not pallas_tp_ok(cfg, tp):
        why = f"head counts do not divide over tp={tp}"
    if cfg.use_short_conv:
        # which kernel serves which kind of step of the attention layers;
        # the operator has no recurrent kernel
        from gllm_tpu.ops.attention import DECODE_ROWS_NAME
        kinds = cfg.stage_layer_types
        logger.info(
            "[startup] short-convolution model (%d conv + %d attention "
            "layers): attention (%d query heads over %d KV heads of %d) "
            "-> %s; the gated short convolution (%d taps, a window of %d "
            "rows its whole state) -> xla (ops/short_conv.py: one gather "
            "over the flat token axis, no recurrent kernel, no chunked "
            "rule)", kinds.count("conv"), kinds.count("full_attention"),
            cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            ("pallas over kv_pack %d (%d KV heads abreast in a 128-lane "
             "cache row): paged_decode_attention (decode steps), "
             "ragged_paged_attention (a mixed step's chunks) and %s (its "
             "decoding rows)" % (pack, pack, DECODE_ROWS_NAME))
            if why is None else f"xla ({why})",
            cfg.linear_conv_kernel_dim, cfg.linear_conv_kernel_dim - 1)
    elif cfg.use_hybrid:
        # the two kinds of layer choose apart: the recurrent layers' head
        # dims (96 / 192 in Olmo-Hybrid, 64 / 128 in NemotronH) say nothing
        # about the full-attention layers' kernels
        from gllm_tpu.ops.gdn import gdn_impl_for
        rule, files, half = (
            ("Mamba-2", "mamba2_recurrent.py, mamba2_scan.py",
             "ops/mamba2.py") if cfg.use_mamba else
            ("GDN", "gdn_recurrent.py, gdn_scan.py", "ops/gdn.py"))
        if gdn_impl_for("pallas" if why is None else "xla",
                        tp > 1) == "pallas":
            gdn = f"pallas (ops/pallas/{files})"
        elif why is not None:
            gdn = "xla (attention runs no Pallas kernel here)"
        else:
            gdn = f"xla (the slot pool is sharded over tp={tp})"
        logger.info(
            "[startup] hybrid: full attention -> %s; %s recurrent step "
            "and chunk scan -> %s; %s in-chunk half -> xla (%s)",
            "pallas" if why is None else f"xla ({why})", rule, gdn, rule,
            half)
    if cfg.dense_mla:
        # dense latent attention: every layer attends its whole context
        # over the paged latent pool, one KV head under all query heads
        from gllm_tpu.ops.pallas.tuning import decode_blocks, ragged_blocks
        if why is None:
            dec = decode_blocks(1)
            blocks = ragged_blocks(cfg.num_heads // max(tp, 1)
                                   if tp_sharded else cfg.num_heads, 1)
            logger.info(
                "[startup] latent attention (%d heads over rows of %d "
                "lanes, values the first %d): decode steps -> pallas "
                "paged_decode_attention (kv_block %d, group %d); mixed "
                "steps -> pallas ragged_paged_attention (q_block %d, "
                "kv_block %d: the blocks follow heads x lanes, "
                "ops/pallas/tuning.ragged_blocks) and, for the rows that "
                "decode beside the chunks, the decode kernel",
                cfg.num_heads, cfg.mla_cache_width, cfg.kv_lora_rank,
                dec["kv_block"], int(dec.get("group", 1)),
                blocks["q_block"], blocks["kv_block"])
        else:
            logger.info("[startup] latent attention: decode steps and "
                        "mixed steps -> xla (%s)", why)
    if cfg.paged_windows:
        # windowed GQA layers beside full ones, all in the paged pool:
        # which kernel serves which kind of layer and step
        from gllm_tpu.ops.attention import DECODE_ROWS_NAME, WINDOW_NAMES
        from gllm_tpu.ops.pallas.tuning import decode_blocks, ragged_blocks
        kinds = cfg.stage_layer_types
        n_swa = kinds.count("sliding_attention")
        if why is None:
            geo = (cfg.num_heads, cfg.num_kv_heads)
            dec = decode_blocks(geo[1], num_q_heads=geo[0])
            rag = ragged_blocks(*geo)
            logger.info(
                "[startup] windowed GQA (%d query heads over %d KV heads "
                "of %d; window %d in %d of %d layers, rotary there only): "
                "windowed layers -> pallas %s (decode steps), %s (a mixed "
                "step's chunks) and %s (its decoding rows): pages behind "
                "the window are neither fetched nor scored; full layers "
                "-> pallas paged_decode_attention, ragged_paged_attention "
                "and %s; both kinds at the geometry's blocks: decode "
                "kv_block %d, group %d; ragged q_block %d, kv_block %d",
                *geo, cfg.head_dim, cfg.sliding_window, n_swa, len(kinds),
                WINDOW_NAMES["decode"], WINDOW_NAMES["ragged"],
                WINDOW_NAMES["rows"], DECODE_ROWS_NAME, dec["kv_block"],
                int(dec.get("group", 1)), rag["q_block"], rag["kv_block"])
        else:
            logger.info(
                "[startup] windowed GQA (window %d in %d of %d layers): "
                "every layer and step -> xla under the window's mask, "
                "whole page tables gathered (%s)", cfg.sliding_window,
                n_swa, len(kinds), why)
    if why is None:
        return "pallas"
    if impl == "pallas":
        raise NotImplementedError(
            f"attention_impl='pallas' cannot serve this model: {why}; "
            "use attention_impl='xla'")
    logger.warning("attention_impl=auto resolves to XLA on this TPU: %s",
                   why)
    return "xla"


def spec_aux(params, hidden, residual, batch, cfg, token_counts,
             logprobs_k: int, spec_sampled: bool) -> dict:
    """Speculative-verify aux entries, shared by the single-runner step,
    the DP per-replica body, and the PP last stage: gather only the verify
    rows (a full [T, V] logits materialization per decode step would cost
    hundreds of MB of HBM at large vocab), adjust for penalties/bias with
    draft-prefix counts, verify (greedy argmax acceptance or rejection
    sampling), and emit logprobs for the committed run when requested."""
    from gllm_tpu.models.dense import compute_full_logits
    from gllm_tpu.ops.sampling import (compute_logprobs,
                                       spec_adjust_logits, spec_verify)
    rows = batch.spec_rows.reshape(-1)              # [S*(k+1)]
    sl = compute_full_logits(params, hidden[rows], residual[rows], cfg)
    sl3 = spec_adjust_logits(
        sl.reshape(batch.spec_rows.shape + sl.shape[-1:]),
        batch.spec_drafts, batch.sampling, token_counts)
    aux = {"spec": spec_verify(sl3, batch.spec_drafts, batch.sampling,
                               sampled=spec_sampled)}
    if logprobs_k >= 0:
        Sk, K1k = batch.spec_rows.shape
        slp = compute_logprobs(sl3.reshape(Sk * K1k, -1),
                               aux["spec"][0].reshape(-1),
                               max(logprobs_k, 1))
        aux["spec_lp"] = tuple(x.reshape((Sk, K1k) + x.shape[1:])
                               for x in slp)
    return aux


def _spec_carry(seeds, prev_state, prev_tokens):
    """A spec block's carry state (ring, ring_len, last_tok, pos, alive,
    out_step, k_cur), inside its program: the host's seeds
    (``ModelRunner._spec_seed_state``, out of the packed buffer) merged
    with what the block chains off. Rows chaining off a previous SPEC
    block carry its device state wholesale, except joins, which re-seed
    from the host's arrays, and rows the host has since finished, which
    are forced dead; rows chaining off a sync single step shift its
    on-device sampled token into the ring tail (shift-in count 0 =
    identity for the rows the host knows); a chain root is all seeds."""
    from gllm_tpu.ops.sampling import ring_shift_in
    ring, rlen, last, pos, alive, kcur = (
        seeds[f"spec_{n}"]
        for n in ("ring", "rlen", "last", "pos", "alive", "kcur"))
    ostep = seeds.get("spec_ostep")
    if prev_state is not None:
        ring_c, rlen_c, last_c, pos_c, alive_c, ostep_c, kcur_c = prev_state
        rs, dd = seeds["spec_reseed"], seeds["spec_dead"]
        ring = jnp.where(rs[:, None], ring, ring_c)
        rlen = jnp.where(rs, rlen, rlen_c)
        last = jnp.where(rs, last, last_c)
        pos = jnp.where(rs, pos, pos_c)
        alive = jnp.where(dd, 0, jnp.where(rs, alive, alive_c))
        kcur = jnp.where(rs, kcur, kcur_c)
        if ostep is not None and ostep_c is not None:
            ostep = jnp.where(rs, ostep, ostep_c)
    elif prev_tokens is not None:
        pt = prev_tokens[-1] if prev_tokens.ndim == 2 else prev_tokens
        pt = pt.astype(jnp.int32)
        hk = seeds["spec_host_known"]
        ring, rlen = ring_shift_in(ring, rlen, pt[:, None],
                                   jnp.where(hk, 0, 1).astype(jnp.int32))
        last = jnp.where(hk, last, pt)
    return ring, rlen, last, pos, alive, ostep, kcur


def _spec_sampled(items) -> bool:
    """Any draft row in this batch samples (temperature > 0)? Trace-time
    flag for spec_verify: the all-greedy case keeps the argmax-only
    verify program (ops/sampling.py)."""
    return any(it.draft_tokens
               and it.seq.sampling_params.temperature != 0
               for it in items)


def resolve_kv_quant(config: EngineConfig, model_cfg: ModelConfig):
    """(kv_quant, model_cfg) for a runner: spec builders
    (kv_cache_specs) mirror the cache's scale leaves off
    ``model_cfg.kv_cache_quant``; the forward detects quant structurally
    (KVCache.k_scale is not None). Shared by ModelRunner and
    PPModelRunner so the propagation can never diverge."""
    import dataclasses as _dc
    kv_quant = config.cache.kv_cache_dtype == "int8"
    if kv_quant and not model_cfg.kv_cache_quant:
        model_cfg = _dc.replace(model_cfg, kv_cache_quant=True)
    if config.cache.kv_cache_dtype == "fp8" and not model_cfg.kv_cache_fp8:
        model_cfg = _dc.replace(model_cfg, kv_cache_fp8=True)
    return kv_quant, model_cfg


@dataclasses.dataclass
class PreparedStep:
    """One step built, packed and placed, not yet launched: what
    ``ModelRunner._build_step`` hands to ``_launch_step``. ``host`` is
    the batch as built (its shapes name the signature, its ``kv_lens``
    the rows a latent step reads), ``batch`` the same on the device,
    ``flags`` the step program's static arguments in the order the
    signature has always had them."""

    sched_batch: ScheduledBatch
    host: StepBatch
    batch: PackedBatch
    token_counts: object
    layout: object
    flags: dict


class ModelRunner:
    # Total runner dispatches (every step path notes exactly one per
    # device program launched via _note_dispatch) — the denominator-free
    # half of the dispatches-per-token acceptance metric (tests).
    # Class default so subclasses sharing _note_dispatch (PPModelRunner)
    # count too; first increment creates the instance attribute.
    num_dispatches = 0
    # PPModelRunner never builds the spec block driver (the engine gates
    # --spec-fused to pp == dp == 1); class default keeps the attribute
    # readable there.
    spec_fused = False

    def __init__(self, config: EngineConfig, model_cfg: ModelConfig,
                 params=None, mesh=None):
        self.config = config
        self.kv_quant, model_cfg = resolve_kv_quant(config, model_cfg)
        self.model_cfg = model_cfg
        if mesh is None and config.parallel.world_size > 1:
            from gllm_tpu.parallel.mesh import make_mesh
            mesh = make_mesh(dp=config.parallel.dp, tp=config.parallel.tp,
                             sp=config.parallel.sp)
        self.mesh = mesh
        self.dtype = _DTYPES[config.dtype]
        self.model_def = get_model_def(model_cfg)
        self.kv_pack = 1   # may be raised by _pick_attn_impl (lane packing)
        self.attn_impl = self._pick_attn_impl()
        if self.kv_quant:
            self._check_kv_quant()
        # (Re)set the module-level TP shard context the attention dispatch
        # reads at trace time — cleared when this runner doesn't need it so
        # a later runner in the same process never sees a stale mesh.
        from gllm_tpu.ops.attention import set_shard_context
        from gllm_tpu.parallel.mesh import AXIS_TP
        set_shard_context(
            self.mesh if (self.attn_impl == "pallas" and mesh is not None
                          and config.parallel.tp > 1) else None, AXIS_TP)
        self.builder = BatchBuilder(config, config.cache.page_size,
                                    vocab_size=model_cfg.vocab_size,
                                    hidden_size=model_cfg.hidden_size,
                                    use_mm=model_cfg.use_mm,
                                    use_ssm=model_cfg.use_hybrid,
                                    seq_slots=model_cfg.use_swa,
                                    mm_embed_dim=model_cfg.mm_embed_dim,
                                    ssm_chunk=model_cfg.ssm_chunk,
                                    ssm_kind=("mamba" if model_cfg.use_mamba
                                              else "sconv"
                                              if model_cfg.use_short_conv
                                              else "gdn"))
        if model_cfg.use_mm:
            from gllm_tpu.utils import LRUBytesCache
            self._mm_cache = LRUBytesCache()
        self.rng_key = jax.random.key(config.seed)
        # Effective EOS set for ON-DEVICE finish detection in fused
        # blocks (config.ondevice_finish). Seeded from the checkpoint
        # config; the engine overwrites it with its tokenizer-resolved
        # set so device and host finish checks can never diverge.
        self.eos_token_ids = frozenset(model_cfg.eos_token_ids)
        self._step_count = 0
        # (shape-bucket, static-flag) signatures already dispatched —
        # first sightings count as compile events (obs layer)
        self._seen_sigs = set()

        ep_loaded = False
        _t_load = time.monotonic()
        if params is not None:
            self.params = params
        elif config.load_format == "dummy" or not config.model:
            self.params = init_dummy_params(
                self.model_def, model_cfg, config.seed, self.dtype,
                self.mesh, config.parallel.tp)
        elif (self.mesh is not None
              and self.model_def.family in ("moe", "deepseek")):
            # Sharded-aware MoE load: expert stacks are built per device
            # shard straight from the checkpoint — peak host memory is one
            # shard, and a multi-host EP mesh never reads non-local
            # experts (reference EP-pruned loading,
            # model_loader.py:363-369).
            from gllm_tpu.models import loader as loader_mod
            logger.info("loading weights from %s (EP-sharded experts)",
                        config.model)
            self.params = loader_mod.load_params_ep(
                config.model, model_cfg, self.dtype, self.mesh,
                self.model_def.param_specs(model_cfg, config.parallel.tp),
                self.model_def.family)
            ep_loaded = True
        else:
            logger.info("loading weights from %s", config.model)
            kwargs = {}
            if config.skip_visual_load and model_cfg.use_mm:
                # disagg LM node: never read the visual.* shards
                kwargs["skip_visual"] = True
            self.params = self.model_def.load_params(
                config.model, model_cfg, dtype=self.dtype, **kwargs)
        self.cos_sin = self.model_def.make_rope_table(model_cfg)

        if config.quantization:
            from gllm_tpu.ops.quant import param_bytes, quantize_params
            before = param_bytes(self.params)
            self.params = quantize_params(self.params,
                                          mode=config.quantization)
            logger.info("quantized weights (%s): %.2f GB -> %.2f GB",
                        config.quantization, before / 1e9,
                        param_bytes(self.params) / 1e9)

        if config.skip_visual_load and "visual" in self.params:
            # dummy-init path (load skips the tower at the rules level)
            del self.params["visual"]

        if self.mesh is not None and not ep_loaded:
            from gllm_tpu.parallel.shardings import shard_params
            specs = self.model_def.param_specs(model_cfg, config.parallel.tp)
            if "visual" not in self.params:
                specs.pop("visual", None)
            self.params = shard_params(self.params, specs, self.mesh)
        # Startup latency breakdown (reference: CUDA-graph capture logs);
        # one structured line per phase so serving-readiness regressions
        # show up in logs, not just vibes.
        logger.info("[startup] phase=weight_load seconds=%.2f",
                    time.monotonic() - _t_load)

        self.dp = config.parallel.dp
        if model_cfg.use_hybrid:
            # slot 0 dummy + one working slot per live seq + snapshot range
            self.ssm_working_slots = config.max_num_seqs
            # snapshot pool serves prefix-cache boundary states AND
            # speculative-decoding pre-draft checkpoints (restored on
            # rejection)
            self.ssm_snapshot_slots = (
                config.cache.ssm_snapshot_slots
                if (config.cache.enable_prefix_caching
                    or (config.spec_decode
                        and not config.overlap_scheduling)) else 0)
        elif model_cfg.use_swa:
            # slot 0 dummy + one ring per live seq in every windowed layer
            self.ssm_working_slots = config.max_num_seqs
            self.ssm_snapshot_slots = 0
        else:
            self.ssm_working_slots = self.ssm_snapshot_slots = 0
        self.num_pages = (config.cache.num_pages
                          or self.determine_num_pages())
        kw = {"kv_pack": self.kv_pack} if self.kv_pack > 1 else {}
        if model_cfg.use_seq_slots:
            kw["num_slots"] = (1 + self.ssm_working_slots
                               + self.ssm_snapshot_slots)

        def make_kv():
            kv = self.model_def.init_kv_cache(
                model_cfg, self.num_pages, config.cache.page_size,
                self._kv_dtype(), **kw)
            if self.dp > 1:
                # One KV pool per DP replica, stacked on a leading axis
                # that shards over the mesh's dp axis (the reference's
                # per-replica KV caches, llm_engine.py:121-133 — here one
                # program, one array, GSPMD placement).
                kv = jax.tree.map(
                    lambda a: jnp.zeros((self.dp,) + a.shape, a.dtype), kv)
            return kv

        kspecs = None
        if self.mesh is not None:
            from jax.sharding import PartitionSpec
            kspecs = self.model_def.kv_specs(model_cfg, config.parallel.tp)
            if self.dp > 1:
                kspecs = jax.tree.map(
                    lambda s: PartitionSpec("dp", *s), kspecs,
                    is_leaf=lambda x: isinstance(x, PartitionSpec))
        self.kv = build_in_place(make_kv, self.mesh, kspecs)
        self.memory_manager = None   # attached by the engine (SSM intents)
        # Host-RAM KV tier (gllm_tpu/kvswap) — attached by the engine
        # when configured; drained at dispatch time on every step path.
        self.swap_manager = None
        logger.info("KV cache: %d pages × %d tokens (%s)", self.num_pages,
                    config.cache.page_size, self._kv_dtype().__name__)
        window, state = (model_cfg.ssm_slot_shapes if model_cfg.use_hybrid
                         else ((), ()))
        logger.info(
            "[startup] pools: paged KV %d pages = %d bytes; GDN state %d "
            "slots = %d bytes (a layer's slot: window %s + state %s "
            "float32, as the TPU stores them); beside them the largest "
            "mixed step's chunked-rule temporaries, ~%d bytes",
            self.num_pages, self.num_pages * self._kv_bytes_per_page(),
            1 + self.ssm_working_slots + self.ssm_snapshot_slots
            if model_cfg.use_hybrid else 0, self._ssm_pool_bytes(),
            window, state, self._gdn_chunk_temp_bytes())
        if model_cfg.use_mamba:
            # a state-space hybrid: what the chip holds, in one line
            slots = 1 + self.ssm_working_slots + self.ssm_snapshot_slots
            La, page = model_cfg.num_attn_layers, config.cache.page_size
            held = ("%d of %d routed experts a layer held here" % (
                model_cfg.num_local_experts, model_cfg.num_experts)
                if model_cfg.num_experts else "no experts")
            # a layer that is both kinds (models/falcon_h1.py): the same
            # layer count sizes the page stack and the slot stack
            both = (" (%d pages x %d tokens x %d layers x %d B; every layer "
                    "holds pages AND a slot)" % (
                        self.num_pages, page, La,
                        self._kv_bytes_per_page() // (page * La))
                    if "parallel_hybrid" in model_cfg.layer_types else "")
            logger.info(
                "[startup] state-space model: weights %d bytes (%s); "
                "Mamba-2 slot pool %d "
                "slots x %d layers x %d bytes as the TPU stores them = %d "
                "bytes; KV pool of the %d attention layers %d bytes%s",
                self.weight_bytes(), held, slots,
                model_cfg.num_linear_layers,
                self._ssm_pool_bytes() // (model_cfg.num_linear_layers
                                           * slots),
                self._ssm_pool_bytes(), La,
                self.num_pages * self._kv_bytes_per_page(), both)
            if model_cfg.num_experts:
                # which kernel multiplies the held experts (models/
                # deepseek._grouped_dot): it falls back silently otherwise,
                # and a --quantization run times another kernel than a
                # plain one
                from gllm_tpu.ops.gdn import gdn_impl_for
                if gdn_impl_for(self.attn_impl,
                                config.parallel.tp > 1) != "pallas":
                    experts = ("xla ragged_dot (the Mamba-2 kernels run in "
                               "XLA)")
                elif config.quantization:
                    experts = ("xla ragged_dot (the Pallas kernel reads "
                               f"plain stacks, these are "
                               f"{config.quantization})")
                else:
                    experts = "pallas gmm (ops/pallas/grouped_matmul.py)"
                logger.info("[startup] held experts: grouped products -> "
                            "%s", experts)
        if model_cfg.use_short_conv:
            # the window pool, in one line beside the model's own
            # (``ModelDef.startup_line``)
            slots = 1 + self.ssm_working_slots + self.ssm_snapshot_slots
            Lc = model_cfg.num_linear_layers
            logger.info(
                "[startup] window pool: %d slots x %d conv layers x %d "
                "bytes as the TPU stores them = %d bytes (a layer's slot: "
                "%s float32, its whole state; no recurrent stack); KV "
                "rows: %d KV heads of %d lanes, %d abreast in a cache row "
                "(kv_pack %d)", slots, Lc,
                self._ssm_pool_bytes() // (Lc * slots),
                self._ssm_pool_bytes(), model_cfg.ssm_slot_shapes[0],
                model_cfg.num_kv_heads, model_cfg.head_dim, self.kv_pack,
                self.kv_pack)
        if model_cfg.dense_mla:
            # dense latent attention: what the chip holds, in one line
            # beside the line that says which kernel serves which kind of
            # step (resolve_attn_impl)
            logger.info(
                "[startup] latent model: weights %d bytes (%d of %d routed "
                "experts a layer held here); latent pool %d pages of %d "
                "tokens x %d layers x %d stored lanes = %d bytes%s",
                self.weight_bytes(),
                model_cfg.num_local_experts, model_cfg.num_experts,
                self.num_pages, config.cache.page_size,
                model_cfg.num_stage_layers, model_cfg.mla_cache_width,
                self.latent_pool_bytes()[0],
                "; prefix cache on: a request claims the cached whole "
                "pages of its prompt and computes the rest"
                if config.cache.enable_prefix_caching else "")
        if self.model_def.startup_line is not None:
            logger.info("[startup] %s", self.model_def.startup_line(
                model_cfg, weight_bytes=self.weight_bytes(),
                num_pages=self.num_pages,
                page_bytes=self._kv_bytes_per_page(),
                page_size=config.cache.page_size,
                prefix_cache=config.cache.enable_prefix_caching,
                attn_impl=self.attn_impl,
                quantized=bool(config.quantization)))
        if model_cfg.use_swa:
            latent, index, rings = self.latent_pool_bytes()
            logger.info(
                "[startup] latent pools: %d pages of %d tokens in %d full "
                "layers: latent rows %d bytes, index keys %d bytes; "
                "window rings %d slots of %d rows in %d windowed layers "
                "= %d bytes", self.num_pages, config.cache.page_size,
                model_cfg.num_attn_layers, latent, index,
                1 + self.ssm_working_slots,
                model_cfg.swa_ring_len(config.cache.page_size),
                model_cfg.num_swa_layers, rings)
        if model_cfg.use_dsa:
            from gllm_tpu.models.deepseek import BQ, dsa_rows_path
            from gllm_tpu.ops.pallas.tuning import decode_blocks
            self.dsa_rows_path = dsa_rows_path(self.attn_impl,
                                               self.mesh is not None)
            if self.dsa_rows_path == "kernel":
                blocks = decode_blocks(1, chosen=True)
                how = ("pallas paged_decode_attention under the "
                       "selection's mask (kv_block %d, group %d)"
                       % (blocks["kv_block"], blocks.get("group", 1)))
            elif self.attn_impl == "pallas":
                how = ("xla (whole pages gathered and attended under the "
                       "mask: the masked kernel call has no shard_map to "
                       "run under this mesh)")
            else:
                how = ("xla (whole pages gathered and attended under the "
                       f"mask: attention runs as {self.attn_impl})")
            logger.info(
                "[startup] selected attention: a decoding row's chosen "
                "positions -> %s; a chunk's work items of %d queries -> "
                "xla", how, BQ)
        _M_KV_DTYPE.set(1, dtype=jnp.dtype(self._kv_dtype()).name)
        # Fused on-device speculation (config.spec_fused,
        # docs/speculative_decoding.md#fused): draft+verify inside the
        # multi-step block driver. Gated off hybrid (cumulative SSM
        # state can't rewind over rejected rows) and multimodal (mrope
        # extrapolation not threaded through the spec carry); pp/dp
        # topologies never reach this runner's block path. The engine
        # mirrors the same gate and warns when the flag goes inert.
        self.spec_fused = (bool(getattr(config, "spec_fused", False))
                           and config.spec_decode == "ngram"
                           and not model_cfg.use_hybrid
                           and not model_cfg.use_mm)
        self._step_fn = self._build_step_fn()
        self._multi_step_fn = self._build_multi_step_fn()
        self._spec_multi_fn = (self._build_spec_multi_step_fn()
                               if self.spec_fused else None)

    # ---- setup ------------------------------------------------------------

    def _pick_attn_impl(self) -> str:
        impl = self.config.attention_impl
        cfg = self.model_cfg
        tp = self.config.parallel.tp
        tp_sharded = self.mesh is not None and (
            tp > 1 or self.config.parallel.dp > 1)

        # Lane packing is a per-replica layout: the dp axis stacks whole
        # replicas (manual shard_map), so only a tp kv-head split forces
        # native alignment.
        pack = pick_kv_pack(cfg, self.mesh is not None and tp > 1)
        impl = resolve_attn_impl(impl, cfg, tp, pack, tp_sharded)
        if impl == "pallas":
            self.kv_pack = pack
        return impl

    def _check_kv_quant(self) -> None:
        """Reject model/topology combos the int8 KV cache does not
        support — explicitly, instead of silently degrading (the auto |
        bfloat16 | fp8 cache dtypes remain available everywhere)."""
        cfg, config = self.model_cfg, self.config
        if cfg.use_mla:
            raise NotImplementedError(
                "kv_cache_dtype='int8' unsupported for MLA latent "
                "caches (DeepSeek/Kimi); use kv_cache_dtype='auto' "
                "or 'fp8'")
        if cfg.use_hybrid:
            raise NotImplementedError(
                "kv_cache_dtype='int8' unsupported for hybrid (GDN) "
                "models; use kv_cache_dtype='auto'")
        if jax.default_backend() == "tpu" and self.attn_impl == "pallas":
            # The three Pallas kernels do not compile with an int8 cache:
            # Mosaic refuses the per-page scale-row DMA into the
            # [slots, ppb, Hkv] VMEM scratch (ops/pallas/paged_kv.py —
            # "Slice shape along dimension 1 must be aligned to tiling
            # (128)"). Until that layout is repaired (ROADMAP A3) the
            # choice is explicit: never a quiet drop to XLA.
            raise NotImplementedError(
                "kv_cache_dtype='int8' does not compile on the Pallas "
                "attention path on TPU (scale-row DMA not lane-aligned); "
                "pass attention_impl='xla' to serve an int8 KV cache, or "
                "use kv_cache_dtype='auto'")
        if self.attn_impl == "pallas":
            if cfg.num_kv_heads // max(self.kv_pack, 1) == 1:
                raise NotImplementedError(
                    "kv_cache_dtype='int8' unsupported on the pallas "
                    "MQA kernel path (num_kv_heads == 1); use "
                    "attention_impl='xla'")
            if (config.parallel.tp > 1
                    and cfg.num_kv_heads % config.parallel.tp != 0):
                raise NotImplementedError(
                    "kv_cache_dtype='int8' on the pallas path needs "
                    "num_kv_heads % tp == 0 (the replicated-KV slice "
                    "path is gated); use attention_impl='xla'")

    def weight_bytes(self) -> int:
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(self.params))

    def _kv_dtype(self):
        kd = self.config.cache.kv_cache_dtype
        return self.dtype if kd == "auto" else _DTYPES[kd]

    def _kv_bytes_per_page(self, n_layers: Optional[int] = None) -> int:
        """Per-DEVICE bytes per page (the cache shards over kv heads when
        divisible, so each chip holds 1/tp of every page). ``n_layers``
        overrides the layer count (PP sizes per stage)."""
        cfg, page = self.model_cfg, self.config.cache.page_size
        itemsize = jnp.dtype(self._kv_dtype()).itemsize
        if cfg.use_mla:
            # MLA latent cache: one tile-padded [lora+rope] row per token,
            # replicated over tp (MQA-shaped); DSA adds the index-K cache
            # (fp8 payload + f32 per-token scale by default — the
            # reference's 132-byte store_index_k_fp8 layout).
            per_tok = cfg.mla_cache_width * itemsize
            if cfg.use_dsa:
                # the index keys beside the rows, in the cache's dtype;
                # an fp8 cache adds their f32 per-token scale
                per_tok += cfg.index_head_dim * itemsize + (
                    4 if itemsize == 1 else 0)
            # windowed layers hold rings, not pages
            return (n_layers or (cfg.num_attn_layers if cfg.use_swa
                                 else cfg.num_stage_layers)) * page * per_tok
        tp = self.config.parallel.tp
        shards = tp if (self.mesh is not None
                        and cfg.num_kv_heads % tp == 0) else 1
        # Hybrid: only the full-attention layers hold paged KV.
        n_kv_layers = n_layers or (cfg.num_attn_layers if cfg.use_hybrid
                                   else cfg.num_stage_layers)
        kv_heads = (cfg.kv_cache_heads if cfg.use_hybrid
                    else cfg.num_kv_heads)
        per_page = (2 * n_kv_layers * page * kv_heads
                    * cfg.head_dim * itemsize) // shards
        if self.kv_quant:
            # int8 cache rides per-page per-head f32 scales (k and v) —
            # ~0.2% of the page, but sizing must not over-promise
            per_page += (2 * n_kv_layers * cfg.num_kv_heads * 4) // shards
        return per_page

    def latent_pool_bytes(self) -> Tuple[int, int, int]:
        """Device bytes of a windowed-latent model's three pools: the full
        layers' latent rows, their index keys, the windowed layers' rings
        (what the start-up line says; tests/test_tpu_compile.py holds it
        to the TPU compiler's count)."""
        cfg, page = self.model_cfg, self.config.cache.page_size
        itemsize = jnp.dtype(self._kv_dtype()).itemsize
        tokens = cfg.num_attn_layers * self.num_pages * page
        return (tokens * cfg.mla_cache_width * itemsize,
                tokens * (cfg.index_head_dim * itemsize
                          + (4 if itemsize == 1 else 0))
                if cfg.use_dsa else 0,
                self._ring_pool_bytes())

    def _ring_pool_bytes(self) -> int:
        cfg = self.model_cfg
        if not cfg.use_swa:
            return 0
        rows = (1 + self.ssm_working_slots) * cfg.swa_ring_len(
            self.config.cache.page_size)
        return (cfg.num_swa_layers * rows * cfg.swa_cache_width
                * jnp.dtype(self._kv_dtype()).itemsize)

    def _swa_temp_bytes(self) -> int:
        """What the largest step of a windowed-latent model needs beside
        the 512 MB of other step buffers (models/deepseek.py): the rows of
        every sequence's context read as pages with their float32 scores
        (the decoding rows' way through a full layer), one work item's
        scores over the longest context, and the experts' gathered rows
        and products at a quarter of the step's assignments. 2.8 GB at
        dots3_note's widths, 64 rows and 9472 tokens; the TPU compiler
        counts 2.43 GiB of temporaries for that step
        (tests/test_tpu_compile.py)."""
        cfg = self.model_cfg
        if not cfg.use_swa:
            return 0
        from gllm_tpu.models.deepseek import BQ
        ctx = self.config.max_model_len
        rows = self.builder.max_seqs * ctx * (
            cfg.mla_cache_width * 2 + cfg.num_heads * 4)
        item = BQ * ctx * 4 * (cfg.index_n_heads + 2 * cfg.num_heads)
        assigned = self.builder.max_tokens * cfg.num_experts_per_tok // 4
        moe = assigned * (cfg.hidden_size * 6
                          + cfg.moe_intermediate_size * 8)
        return rows + item + moe

    def _ssm_pool_bytes(self, cfg: Optional[ModelConfig] = None) -> int:
        """Device bytes of the recurrent layers' slot pools of ``cfg``'s
        stage (this runner's whole model by default; what a slot stores:
        ``ModelConfig.ssm_slot_shapes``), as the TPU stores them: a
        float32 array lies in tiles of 8 x 128 over its last two
        dimensions. The slot shapes are whole tiles wherever the heads
        allow it, so the pool takes its elements' bytes: Olmo-Hybrid's
        GDN states of 96 x 192 lie two heads abreast, 96 x 384 (a head
        alone would take 96 x 256, a third more than its elements; it
        does where the heads do not pair up), a Mamba-2 state of 64 x 128
        is a tile's multiple as it is; the convolution pool's last two
        dimensions are folded into the slot axis's tile (measured with
        the TPU compiler: tests/test_tpu_compile.py)."""
        cfg = cfg or self.model_cfg
        if not cfg.use_hybrid:
            return 0
        slots = 1 + self.ssm_working_slots + self.ssm_snapshot_slots
        (taps, channels), state = cfg.ssm_slot_shapes

        def up(n, m):
            return -(-n // m) * m
        if not state:
            # the window is all there is (a gated short convolution): the
            # compiler tiles the pool's [2, channels] face (2, 128), its
            # elements' bytes
            return cfg.num_linear_layers * slots * taps * channels * 4
        heads, rows, lanes = state
        rec = slots * heads * up(rows, 8) * up(lanes, 128)
        conv = up(slots, 8) * channels * taps
        return cfg.num_linear_layers * (rec + conv) * 4

    def _gdn_chunk_temp_bytes(self) -> int:
        """What the largest mixed step of a hybrid model needs beside the
        512 MB of other step buffers: the chunked rule's float32
        temporaries, per token slot of the packed layout q, k, k_cumdecay,
        the scan's two decayed operands and a spare (6 Dk), v, v_beta, v2,
        v_new, the output and a spare (6 Dv) and four C x C matrices a
        head, over the slots of the largest layout
        ``BatchBuilder.shape_signature`` can return (the largest token
        bucket at the largest row bucket). Olmo-Hybrid at 2080 tokens and
        32 rows: 4096 slots, 0.98 GB here; that step's temporaries are
        1.04 GiB in all by the TPU compiler's count (the 1024-token
        bucket's 0.61: tests/test_tpu_compile.py)."""
        cfg = self.model_cfg
        if not cfg.ssm_chunked_rule:
            return 0
        from gllm_tpu.ops.gdn import gdn_chunk_slots
        n, c = gdn_chunk_slots(self.builder.max_tokens,
                               self.builder.max_seqs, cfg.ssm_chunk)
        if cfg.use_mamba:
            # Mamba-2's rule (ops/mamba2.py): dt x, its decayed and
            # transposed forms, the in-chunk output, the output and a
            # spare (6 P), C e^l and a spare (2 N) and three C x C
            # matrices a head
            per_slot = 4 * cfg.mamba_num_heads * (
                6 * cfg.mamba_head_dim + 2 * cfg.ssm_state_size
                + 3 * cfg.ssm_chunk)
        else:
            per_slot = 4 * cfg.linear_num_value_heads * (
                6 * cfg.linear_key_head_dim + 6 * cfg.linear_value_head_dim
                + 4 * cfg.ssm_chunk)
        return n * c * per_slot

    def determine_num_pages(self) -> int:
        """Size the KV pool from live device memory after model load
        (reference memory_manager.py:476-526)."""
        if jax.default_backend() != "tpu":
            return 2048         # CPU reports no memory stats: modest pool
        # every device of the mesh holds its shard of weights and KV;
        # the tightest one bounds the (global) page count
        devices = (self.mesh.local_devices if self.mesh is not None
                   else jax.local_devices()[:1])
        free = min(device_free_bytes(d, self.config.cache.memory_util)
                   for d in devices)
        # Headroom for activations at peak batch shape (a full profile-run
        # pass would refine this; 512 MB covers the bucketed step buffers).
        free -= 512 * 1024 * 1024
        free -= self._ssm_pool_bytes() + self._gdn_chunk_temp_bytes()
        if self.model_cfg.use_swa:
            free -= self._ring_pool_bytes() + self._swa_temp_bytes()
        num = stable_page_count(int(free // self._kv_bytes_per_page()))
        min_pages = cdiv(self.config.max_model_len,
                         self.config.cache.page_size) + 2
        if num < min_pages:
            raise RuntimeError(
                f"not enough device memory for KV cache: {num} pages "
                f"(need >= {min_pages})")
        return num

    def _build_step_fn(self):
        cfg = self.model_cfg
        fwd = self.model_def.forward
        logits_fn = self.model_def.compute_logits
        attn_impl = self.attn_impl

        def lp_aux(params, cfg_, logits, tokens, hidden, residual, batch,
                   token_counts, logprobs_k, prompt_lp):
            aux = {}
            if logprobs_k >= 0:
                # Output logprobs of the SAMPLED tokens over the
                # penalty-adjusted distribution (reference sampler.py:71-91)
                from gllm_tpu.ops.sampling import (adjust_logits,
                                                   compute_logprobs)
                lp_logits = adjust_logits(logits, token_counts,
                                          batch.sampling)
                aux["lp"] = compute_logprobs(lp_logits, tokens,
                                             max(logprobs_k, 1))
            if prompt_lp:
                # Prompt logprobs: full-position logits against the known
                # next tokens (targets built host-side; pad rows target 0).
                from gllm_tpu.models.dense import compute_full_logits
                from gllm_tpu.ops.sampling import compute_logprobs

                def rows_lp(h, r, targets):
                    return compute_logprobs(
                        compute_full_logits(params, h, r, cfg_), targets,
                        max(logprobs_k, 1))
                T = hidden.shape[0]
                rows = plp_block_rows(T, cfg_.vocab_size)
                if rows == T:
                    aux["plp"] = rows_lp(hidden, residual,
                                         batch.plp_targets)
                else:
                    # a block of rows at a time: the logits of a whole
                    # chunk never exist (2048 x 261120 in float32 are 2 GB
                    # beside their bf16 product's 1 GB)
                    out = jax.lax.map(
                        lambda a: rows_lp(*a),
                        tuple(x.reshape((T // rows, rows) + x.shape[1:])
                              for x in (hidden, residual,
                                        batch.plp_targets)))
                    aux["plp"] = jax.tree.map(
                        lambda a: a.reshape((T,) + a.shape[2:]), out)
            return aux

        @functools.partial(jax.jit,
                           static_argnames=("layout", "max_q_len",
                                            "logprobs_k", "prompt_lp",
                                            "ring", "spec_sampled",
                                            "all_greedy"),
                           donate_argnums=(1,),
                           compiler_options=tpu_compiler_options())
        def step(params, kv, packed: PackedBatch, cos_sin, token_counts,
                 rng_key, *, layout, max_q_len: int, logprobs_k: int = -1,
                 prompt_lp: bool = False, ring: bool = False,
                 spec_sampled: bool = False, all_greedy: bool = False):
            batch, _ = unpack(packed, layout, rng_key)
            hidden, residual, kv = fwd(params, kv, batch, cfg,
                                       cos_sin=cos_sin,
                                       attn_impl=("ring" if ring
                                                  else attn_impl),
                                       max_q_len=max_q_len)
            logits = logits_fn(params, hidden, residual, batch, cfg)
            tokens = sample(logits, batch.sampling, token_counts,
                            all_greedy=all_greedy)
            aux = lp_aux(params, cfg, logits, tokens, hidden, residual,
                         batch, token_counts, logprobs_k, prompt_lp)
            if batch.spec_rows is not None:
                aux.update(spec_aux(params, hidden, residual, batch, cfg,
                                    token_counts, logprobs_k,
                                    spec_sampled))
            if getattr(kv, "stats", None) is not None:
                # what the step's indexer and expert layers counted
                # (models/deepseek.py STATS): to the host with the tokens
                aux["stats"] = (kv.stats,)
            return tokens, kv, aux

        if self.dp > 1:
            import dataclasses as _dc
            cfg_dp = _dc.replace(cfg, moe_force_dense=True)
            mesh = self.mesh
            from jax.sharding import PartitionSpec as P
            from gllm_tpu.parallel.mesh import AXIS_DP

            def one(kv_r, packed_r, counts_r, params, cos_sin, rng_key, *,
                    layout, max_q_len, logprobs_k, prompt_lp,
                    spec_sampled=False, all_greedy=False):
                # each replica unpacks its own buffer: its key is
                # fold_in(fold_in(rng_key, step), replica)
                batch_r, _ = unpack(packed_r, layout, rng_key)
                hidden, residual, kv_r = fwd(params, kv_r, batch_r,
                                             cfg_dp, cos_sin=cos_sin,
                                             attn_impl=attn_impl,
                                             max_q_len=max_q_len)
                logits = logits_fn(params, hidden, residual, batch_r,
                                   cfg_dp)
                tokens = sample(logits, batch_r.sampling, counts_r,
                                all_greedy=all_greedy)
                aux = lp_aux(params, cfg_dp, logits, tokens, hidden,
                             residual, batch_r, counts_r, logprobs_k,
                             prompt_lp)
                if batch_r.spec_rows is not None:
                    # per-replica speculative verify (same math as the
                    # single-runner step)
                    aux.update(spec_aux(params, hidden, residual, batch_r,
                                        cfg_dp, counts_r, logprobs_k,
                                        spec_sampled))
                return tokens, kv_r, aux

            @functools.partial(jax.jit,
                               static_argnames=("layout", "max_q_len",
                                                "logprobs_k", "prompt_lp",
                                                "spec_sampled",
                                                "all_greedy"),
                               donate_argnums=(1,),
                               compiler_options=tpu_compiler_options())
            def step_dp(params, kv, batch: PackedBatch, cos_sin,
                        token_counts, rng_key, *, layout,
                        max_q_len: int, logprobs_k: int = -1,
                        prompt_lp: bool = False,
                        spec_sampled: bool = False,
                        all_greedy: bool = False):
                kw = dict(layout=layout, max_q_len=max_q_len,
                          logprobs_k=logprobs_k, prompt_lp=prompt_lp,
                          spec_sampled=spec_sampled, all_greedy=all_greedy)
                if attn_impl != "pallas" or mesh is None:
                    # XLA attention: plain vmap over stacked replicas —
                    # GSPMD partitions the batched program over the
                    # dp-sharded leading axis on its own.
                    if token_counts is None:
                        return jax.vmap(lambda k, b: one(
                            k, b, None, params, cos_sin, rng_key,
                            **kw))(kv, batch)
                    return jax.vmap(lambda k, b, c: one(
                        k, b, c, params, cos_sin, rng_key,
                        **kw))(kv, batch, token_counts)

                # Pallas attention: GSPMD cannot partition a custom call
                # over the dp axis, so the replica loop runs MANUAL over
                # dp via shard_map — each device sees its own replica
                # slice ([1, ...]) and invokes the kernels locally; tp
                # stays an auto axis inside (the attention dispatch nests
                # its tp shard_map over the context mesh). This is the
                # TPU answer to the reference's per-replica worker
                # processes each calling FA3 (worker.py:750-829,
                # layers/attention.py:92-140).
                from jax import shard_map
                dp_s = lambda t: jax.tree.map(lambda _: P(AXIS_DP), t)
                rep = lambda t: jax.tree.map(lambda _: P(), t)
                aux_spec = {}
                if logprobs_k >= 0:
                    aux_spec["lp"] = (P(AXIS_DP),) * 3
                if prompt_lp:
                    aux_spec["plp"] = (P(AXIS_DP),) * 3
                if layout.has("spec_rows"):
                    aux_spec["spec"] = (P(AXIS_DP),) * 2
                    if logprobs_k >= 0:
                        aux_spec["spec_lp"] = (P(AXIS_DP),) * 3

                def body(kv_s, batch_s, counts_s, params_s, cos_s, key_s):
                    sq = lambda t: jax.tree.map(lambda x: x[0], t)
                    tokens, kv_r, aux = one(
                        sq(kv_s), sq(batch_s),
                        None if counts_s is None else sq(counts_s),
                        params_s, cos_s, key_s, **kw)
                    ex = lambda t: jax.tree.map(lambda x: x[None], t)
                    return ex(tokens), ex(kv_r), ex(aux)

                out_specs = (P(AXIS_DP), dp_s(kv), aux_spec)
                if token_counts is None:
                    fn = shard_map(
                        lambda k, b, p, c, r: body(k, b, None, p, c, r),
                        mesh=mesh,
                        in_specs=(dp_s(kv), dp_s(batch), rep(params),
                                  rep(cos_sin), P()),
                        out_specs=out_specs,
                        axis_names={AXIS_DP}, check_vma=False)
                    return fn(kv, batch, params, cos_sin, rng_key)
                fn = shard_map(
                    body, mesh=mesh,
                    in_specs=(dp_s(kv), dp_s(batch), dp_s(token_counts),
                              rep(params), rep(cos_sin), P()),
                    out_specs=out_specs,
                    axis_names={AXIS_DP}, check_vma=False)
                return fn(kv, batch, token_counts, params, cos_sin,
                          rng_key)

            self._step_fn_dp = step_dp
        return step

    # ---- execution --------------------------------------------------------

    def _prepare_mm(self, sched_batch: ScheduledBatch) -> None:
        """Run the vision tower for sequences entering prefill with pending
        visual items; ViT outputs are LRU-cached by content hash (reference
        MultiModalEmbeddingCache) and attached to the sequence as host rows
        for the batch builder to splice."""
        for it in sched_batch.items:
            mm = it.seq.mm
            if mm is None or mm.vis_embeds is not None:
                continue
            chunks = []
            for item in mm.items:
                cached = self._mm_cache.get(item.hash)
                if cached is None:
                    out = self.model_def.embed_mm(
                        self.params, self.model_cfg,
                        jnp.asarray(item.pixels).astype(self.dtype),
                        item.grid_thw)
                    cached = np.asarray(out, np.float32)
                    self._mm_cache.put(item.hash, cached)
                chunks.append(cached)
            mm.vis_embeds = (np.concatenate(chunks) if chunks
                             else np.zeros((0, self.model_cfg.mm_embed_dim),
                                           np.float32))
            assert mm.vis_embeds.shape[0] == mm.num_vis_tokens, \
                (mm.vis_embeds.shape, mm.num_vis_tokens)

    def _drained_ssm_ops(self):
        """Per replica: drain the memory manager's pending SSM intents and
        pow2-pad them into device index arrays. Yields
        (replica, (s_src, s_dst, zero, r_src, r_dst)) for replicas with
        work (shared by the single-program and PP runners)."""
        mms = (self.memory_managers if getattr(self, "memory_managers",
                                               None)
               else [self.memory_manager])

        def pad_pairs(pairs, n):
            pairs = pairs + [(0, 0)] * (n - len(pairs))
            return (np.asarray([p[0] for p in pairs], np.int32),
                    np.asarray([p[1] for p in pairs], np.int32))

        for r, mm in enumerate(mms):
            if mm is None or not getattr(mm, "use_ssm", False):
                continue
            intents = mm.drain_ssm_intents()
            if not intents:
                continue
            snap = [(a, b) for k, a, b in intents if k == "snapshot"]
            zero = [a for k, a, _ in intents if k == "zero"]
            rest = [(a, b) for k, a, b in intents if k == "restore"]
            # one length for the three lists, 4 at least and a power of
            # two: the maintenance program is compiled per shape, and a
            # shape of its own for "two sequences ended in one step" was a
            # compile in the middle of serving (padding entries touch the
            # dummy slot)
            n = next_pow2(max(len(snap), len(zero), len(rest)), 4)
            s_src, s_dst = pad_pairs(snap, n)
            z = np.asarray(zero + [0] * (n - len(zero)), np.int32)
            r_src, r_dst = pad_pairs(rest, n)
            yield r, (s_src, s_dst, z, r_src, r_dst)

    def _apply_ssm_intents(self) -> None:
        """Apply pending SSM slot ops (snapshot / zero / restore) recorded
        by the memory manager, in class order: snapshots capture states
        from completed steps, zeros clear freed slots, restores fill fresh
        slots from snapshots — all before the next step reads them
        (reference SSMSegment.copy_state / free_working zeroing)."""
        for r, (s_src, s_dst, z, r_src, r_dst) in self._drained_ssm_ops():
            if not self.model_cfg.use_hybrid:
                # a windowed layer's ring needs no zeroing: what a row
                # holds follows from the positions its tenant has written
                continue
            if self.dp > 1:
                conv, rec = _ssm_apply_replica(
                    self.kv.conv, self.kv.rec, jnp.int32(r), s_src, s_dst,
                    z, r_src, r_dst)
            else:
                conv, rec = _ssm_apply(self.kv.conv, self.kv.rec, s_src,
                                       s_dst, z, r_src, r_dst)
            _M_SSM_APPLY.inc()
            self.kv = self.kv._replace(conv=conv, rec=rec)

    def _apply_swap_intents(self) -> None:
        """Drain queued host-tier swap intents (gllm_tpu/kvswap) against
        the KV cache. MUST run before the step program is dispatched:
        per-device program order then guarantees swap-out/spill gathers
        read their pages before the forward overwrites them, and
        swap-in/restore scatters land before the forward reads them —
        that ordering is the whole correctness argument for letting the
        scheduler free and re-mint a swapped-out page immediately."""
        sw = self.swap_manager
        if sw is not None and sw.has_work:
            self.kv = sw.apply(self.kv)
        self._apply_scale_resets()

    def _drained_scale_resets(self):
        """Per-replica minted-page lists queued by the memory manager(s)
        since the last dispatch, minus pages whose scales the swap drain
        just scattered in from the host tier (restore targets carry the
        host scale — zeroing it would corrupt the restored page).
        Ordering: runs AFTER :meth:`_apply_swap_intents` dispatched its
        gathers, so a spill still reads the outgoing tenant's scale."""
        mm0 = getattr(self, "memory_manager", None)
        if not self.kv_quant or mm0 is None:
            return
        sw = getattr(self, "swap_manager", None)
        skip = sw.consume_last_scatter_dev() if sw is not None else ()
        mms = (getattr(self, "memory_managers", None) or [mm0])
        for r, mm in enumerate(mms):
            if not mm.track_scale_resets:
                continue
            pages = [p for p in mm.drain_scale_resets() if p not in skip]
            if pages:
                idx = np.zeros(next_pow2(len(pages), 1), np.int32)
                idx[:len(pages)] = pages     # pad → dummy page 0
                yield r, idx

    def _apply_scale_resets(self) -> None:
        """int8 KV cache: zero the scales of pages minted since the last
        dispatch so a recycled page quantizes exactly like a fresh one
        (quantization never depends on page-reuse history)."""
        for r, idx in self._drained_scale_resets() or ():
            if self.dp > 1:
                ks, vs = reset_page_scales_replica(
                    self.kv.k_scale, self.kv.v_scale, jnp.int32(r), idx)
            else:
                ks, vs = reset_page_scales(self.kv.k_scale,
                                           self.kv.v_scale, idx)
            self.kv = self.kv._replace(k_scale=ks, v_scale=vs)

    def _note_dispatch(self, kind: str, batch, static_flags: tuple,
                       all_greedy: bool) -> Optional[dict]:
        """Host-side dispatch bookkeeping: sampler-variant counter and,
        on the first sighting of a (padded-shape, static-flag) signature,
        the fields of its ``compile`` event — the caller wraps the jit
        call that follows in :class:`first_use` with them, which records
        the event once the call's wall is known. None for a signature
        seen before. Reads only shapes of already-built host arrays —
        never forces a device sync."""
        self.num_dispatches += 1
        _M_SAMPLER.inc(program="greedy" if all_greedy else "sampled")
        key = (kind, batch.token_ids.shape,
               batch.attn.page_table.shape) + static_flags
        if key in self._seen_sigs:
            return None
        self._seen_sigs.add(key)
        _M_NEW_SHAPE.inc()
        return dict(dispatch=kind,
                    tokens_pad=int(batch.token_ids.shape[-1]),
                    seqs_pad=int(batch.attn.page_table.shape[-2]),
                    pages_pad=int(batch.attn.page_table.shape[-1]),
                    flags=repr(static_flags))

    def _span_args(self, rows: int, tokens: int) -> dict:
        """What a ``build`` / ``dispatch`` span says of its step in the
        profiler's trace: the dispatch's ordinal and its size."""
        return {"step": self.num_dispatches, "rows": rows,
                "tokens": tokens}

    @staticmethod
    def _put(tree, sharding=None):
        """Place the host (numpy) leaves of ``tree`` for a step dispatch:
        one jax call, one transfer per leaf, each counted
        (gllm_step_h2d_arrays_total). Leaves that are on the device
        already (spliced-in tokens of the previous step) pass through;
        with no ``sharding`` given they are not handed to jax at all
        (across processes such an array is not one ``device_put``
        takes)."""
        leaves, treedef = jax.tree.flatten(tree)
        host = [i for i, x in enumerate(leaves) if isinstance(x, np.ndarray)]
        _M_H2D.inc(len(host))
        if sharding is not None:
            return jax.device_put(tree, sharding)
        for i, x in zip(host, jax.device_put([leaves[i] for i in host])):
            leaves[i] = x
        return jax.tree.unflatten(treedef, leaves)

    @staticmethod
    def _lp_flags(sched_batch: ScheduledBatch):
        """(logprobs_k, prompt_lp) static flags for this batch."""
        k = -1
        want_plp = False
        for it in sched_batch.items:
            sp = it.seq.sampling_params
            if sp.logprobs is not None:
                k = max(k, sp.logprobs)
            if (sp.prompt_logprobs is not None
                    and it.computed_before < it.seq.prompt_len):
                # only prefill chunks pay the prompt-logprob k; decode
                # steps of the same request don't widen top-k
                k = max(k, sp.prompt_logprobs)
                want_plp = True
        return k, want_plp

    def step_async_dp(self, sched_batches, prev_handle=None):
        """One step over all DP replicas in ONE program: per-replica
        batches (None → idle dummy batch) are stacked on a leading axis
        sharded over the mesh's dp axis; the vmapped step runs each
        replica's forward/sample on its own devices. No cross-replica
        lockstep barriers needed — it is a single jit program (reference
        needs dp_all_gather_meta + idle dummy batches, worker.py:750-829).

        ``prev_handle``: chain this SUPER-STEP off the previous dp
        dispatch's on-device sampled tokens (the dp pipelined loop,
        docs/overlap_scheduling.md#topology-matrix). Replica batches
        that carry ``src_rows`` (re-formed off promised counts) splice
        their promised rows from ``prev_tokens[r]``; sync-scheduled
        replica batches (src_rows None) keep their host-built tokens.

        Returns a handle; ``collect_dp`` yields per-replica token rows.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        assert len(sched_batches) == self.dp
        build = phase("build").start()
        self._apply_ssm_intents()
        self._apply_swap_intents()   # no-op under dp>1 (tier is gated)
        self._step_count += 1

        live = [b for b in sched_batches if b is not None]
        assert live, "step_async_dp needs at least one non-empty batch"
        if self.model_cfg.use_mm:
            for b in live:
                self._prepare_mm(b)   # ViT per replica (shared LRU cache)
        sigs = [self.builder.shape_signature(b) for b in live]
        sig = tuple(max(s[i] for s in sigs) for i in range(4))
        max_q = sig[2]
        # Replicas must agree on optional-field structure too (a seeded
        # request on one replica vs an idle/unseeded other would otherwise
        # stack mismatched pytrees).
        extras = frozenset().union(
            *[self.builder.batch_extras(b) for b in live])

        # Penalty id lists are length-bucketed per batch — replicas must
        # share one L so the stacked PenaltyTokens match structurally.
        pen_len = None
        if "penalties" in extras:
            pen_len = self.builder.penalty_len_bucket(
                [len(it.seq.token_ids) for b in live for it in b.items])
        # logit_bias entry lists likewise share one B across replicas
        bias_len = None
        if "bias" in extras:
            bias_len = self.builder.bias_len_bucket(
                [len(it.seq.sampling_params.logit_bias)
                 for b in live for it in b.items
                 if it.seq.sampling_params.logit_bias])

        # per replica: build, pack (its key folds the step, then its
        # index r); the packed batches stack on a leading axis that is
        # placed over the mesh's dp axis
        parts, layouts = [], set()
        counts_any = False
        for r, b in enumerate(sched_batches):
            if b is None:
                host, counts = self.builder.empty(
                    sig, extras, force_bias_len=bias_len), None
            else:
                host, _, counts = self.builder.build(
                    b, force_signature=sig, force_extras=extras,
                    force_penalty_len=pen_len, force_bias_len=bias_len)
                counts_any = counts_any or counts is not None
            packed, layout = pack(host, (self._step_count, r))
            layouts.add(layout)
            parts.append((packed, counts))
        assert len(layouts) == 1, "dp replicas disagree on a batch layout"
        token_counts = None
        if counts_any:
            from gllm_tpu.ops.sampling import PenaltyTokens
            blank = PenaltyTokens(np.zeros((sig[1], pen_len), np.int32),
                                  np.zeros((sig[1], pen_len), bool))
            token_counts = jax.tree.map(
                lambda *xs: np.stack(xs),
                *[c if c is not None else blank for _, c in parts])
        stacked = jax.tree.map(lambda *xs: np.stack(xs),
                               *[p[0] for p in parts])
        over_dp = (NamedSharding(self.mesh, P("dp"))
                   if self.mesh is not None else None)
        stacked, token_counts = self._put((stacked, token_counts), over_dp)
        if prev_handle is not None:
            stacked = self._splice_prev_dp(stacked, sched_batches,
                                           prev_handle[0])

        lp_k, want_plp = -1, False
        for b in live:
            k, plp = self._lp_flags(b)
            lp_k, want_plp = max(lp_k, k), want_plp or plp

        all_greedy_dp = all(_all_greedy(b.items) for b in live)
        spec_sampled_dp = any(_spec_sampled(b.items) for b in live)
        new_sig = self._note_dispatch("dp_step", host,
                                      (max_q, lp_k, want_plp,
                                       spec_sampled_dp, all_greedy_dp),
                                      all_greedy_dp)
        build.stop()
        from gllm_tpu.parallel.mesh import mesh_context
        with phase("dispatch", **self._span_args(
                sum(b.num_seqs for b in live),
                sum(b.total_tokens for b in live))):
            with mesh_context(self.mesh), first_use(new_sig):
                tokens, self.kv, aux = self._step_fn_dp(
                    self.params, self.kv, stacked, self.cos_sin,
                    token_counts, self.rng_key, layout=layout,
                    max_q_len=max_q, logprobs_k=lp_k,
                    prompt_lp=want_plp, spec_sampled=spec_sampled_dp,
                    all_greedy=all_greedy_dp)
            _start_host_copy((tokens, aux))
        return tokens, aux, [b.num_seqs if b is not None else 0
                             for b in sched_batches]

    def collect_dp(self, handle):
        """Per-replica sampled-token rows + per-replica aux slices:
        (List[np [n_r]], List[aux dict])."""
        tokens, aux, ns = handle
        with phase("wait"):         # see collect()
            host = np.asarray(tokens)
        with phase("readback"):
            aux_host = jax.tree.map(np.asarray, aux)
            auxes = [jax.tree.map(lambda a: a[r], aux_host)
                     for r in range(len(ns))]
            return [host[r, :n] for r, n in enumerate(ns)], auxes

    def step_async(self, sched_batch: ScheduledBatch, prev_handle=None):
        """Launch one step; returns an opaque handle whose tokens are an
        uncommitted device future (jax async dispatch — the host does not
        block until ``collect``). Two halves with nothing between them:
        :meth:`_build_step` (everything up to the jit call) and
        :meth:`_launch_step`; a PREPARED launch runs the same halves at
        two times (:meth:`prepare_step`, :meth:`launch_prepared`).

        ``prev_handle``: chain this step off a previous entry's
        ON-DEVICE sampled tokens — rows whose ``src_rows`` entry is >= 0
        splice their input token from that array (``_splice_prev``)."""
        build = phase("build").start()
        self._apply_ssm_intents()
        self._apply_swap_intents()
        return self._launch_step(
            self._build_step(sched_batch, prev_handle), build)

    def prepare_step(self, sched_batch: ScheduledBatch,
                     prev_handle) -> "PreparedStep":
        """The first half of :meth:`step_async`, while ``prev_handle``'s
        step still runs (docs/overlap_scheduling.md#prepared-launch): the
        batch built, packed and placed, its input tokens the previous
        step's on-device sampled tokens. Nothing here says that a
        program ran, and nothing touches ``self.kv`` or the pending
        slot / swap intents: :meth:`launch_prepared` does, or
        :meth:`discard_prepared` takes the step's ordinal back."""
        with phase("build"):
            return self._build_step(sched_batch, prev_handle)

    def launch_prepared(self, prepared: "PreparedStep"):
        """The second half of :meth:`step_async` for a prepared step: the
        intents that precede it, the counts of a dispatch, the jit call.
        Returns the handle."""
        build = phase("build").start()
        self._apply_ssm_intents()
        self._apply_swap_intents()
        return self._launch_step(prepared, build)

    def discard_prepared(self) -> None:
        """The prepared step is not launched: the sampling ordinal goes
        back, so the step that runs in its place draws as it would have
        (the scheduler's side is ``Scheduler.discard_batch``)."""
        self._step_count -= 1

    def _build_step(self, sched_batch: ScheduledBatch,
                    prev_handle) -> "PreparedStep":
        """Host work of one step up to the jit call, inside the caller's
        ``build`` phase: batch build, pack, the splice of chained tokens,
        the transfer, the static flags."""
        if self.model_cfg.use_mm:
            self._prepare_mm(sched_batch)
        self._step_count += 1
        host, max_q, token_counts = self.builder.build(sched_batch)
        batch, layout = pack(host, (self._step_count,))
        if prev_handle is not None:
            batch = self._splice_prev(batch, sched_batch, prev_handle[0])
        batch, token_counts = self._put((batch, token_counts))
        lp_k, want_plp = self._lp_flags(sched_batch)
        ring = (prev_handle is None
                and self._use_ring(sched_batch, host.token_ids.shape[0]))
        all_greedy = _all_greedy(sched_batch.items)
        return PreparedStep(
            sched_batch, host, batch, token_counts, layout,
            dict(max_q_len=max_q, logprobs_k=lp_k, prompt_lp=want_plp,
                 ring=ring, spec_sampled=_spec_sampled(sched_batch.items),
                 all_greedy=all_greedy))

    def _launch_step(self, prepared: "PreparedStep", build):
        """What says that a program ran, then the jit call: the counts
        (``num_dispatches``, the sampler counter every ``*_per_step``
        metric divides by, the rows a latent step reads, the first
        sighting of a signature) close the caller's ``build`` phase;
        ``self.kv`` is read here, whatever ran since the step was built."""
        sched_batch, host, flags = (prepared.sched_batch, prepared.host,
                                    prepared.flags)
        max_q = flags["max_q_len"]
        if self.model_cfg.dense_mla:
            from gllm_tpu.models.deepseek import count_rows_read
            count_rows_read(self.model_cfg, host.attn.kv_lens, max_q == 1)
        if self.model_def.count_rows_read is not None:
            self.model_def.count_rows_read(self.model_cfg,
                                           host.attn.kv_lens, max_q == 1)
        if self.model_cfg.use_dsa:
            from gllm_tpu.models.deepseek import count_rows_attended
            count_rows_attended(self.model_cfg, host.attn.cu_q_lens,
                                self.dsa_rows_path)
        if self.model_cfg.use_swa:
            from gllm_tpu.models.deepseek import count_swa_tokens
            count_swa_tokens(self.model_cfg, host.attn.cu_q_lens)
        new_sig = self._note_dispatch(
            "step", host, tuple(flags.values()), flags["all_greedy"])
        build.stop()
        from gllm_tpu.parallel.mesh import mesh_context
        with phase("dispatch", **self._span_args(
                sched_batch.num_seqs, sched_batch.total_tokens)):
            with mesh_context(self.mesh), first_use(new_sig):
                tokens, self.kv, aux = self._step_fn(
                    self.params, self.kv, prepared.batch, self.cos_sin,
                    prepared.token_counts, self.rng_key,
                    layout=prepared.layout, **flags)
            _start_host_copy((tokens, aux))
        if "stats" in aux:
            # beside the step's counts, whether it was a decode-only step
            aux = dict(aux, stats=aux["stats"] + (np.asarray(max_q == 1),))
        return tokens, aux, sched_batch.num_seqs

    def _use_ring(self, sched_batch: ScheduledBatch, t_pad: int) -> bool:
        """Route a long single-seq from-position-0 prefill chunk through
        ring attention over the sp mesh axis (parallel/ring_attention.py;
        the reference has no CP at all). Everything else — decode, mixed
        batches, later chunks attending cached prefix, MM/hybrid/MLA
        models — keeps the paged path (still sharded over the mesh by
        GSPMD)."""
        sp = self.config.parallel.sp
        if sp <= 1 or len(sched_batch.items) != 1:
            return False
        if self.model_def.family not in ("dense", "moe"):
            return False
        if self.model_cfg.use_mm or self.model_cfg.use_hybrid \
                or self.model_cfg.use_mla:
            return False
        it = sched_batch.items[0]
        return (it.computed_before == 0 and not it.draft_tokens
                and it.num_new_tokens >= self.config.sp_ring_threshold
                and t_pad % sp == 0)

    def _splice_chain_tokens(self, batch: PackedBatch, prev_tokens,
                             host_rows):
        """Input tokens for a chained step: the previous step's on-device
        sampled tokens, except rows JOINING the chain through a vacant
        slot this boundary (ScheduledBatch.host_rows) — their last token
        is host-known and the device array has no row for them, so those
        rows keep the host-built value. One tiny [S] select on device;
        no new jit-step variant. ``batch`` comes with its host-built
        ``token_ids`` not yet placed: an identity chain never places
        them, a join places them and the mask (the dispatch's third
        array)."""
        if prev_tokens.ndim == 2:
            prev_tokens = prev_tokens[-1]   # preceding multi-step block
        assert prev_tokens.shape[0] == batch.token_ids.shape[0], \
            (prev_tokens.shape, batch.token_ids.shape)
        if host_rows:
            from_host, tokens = self._put(
                (self.builder.host_row_mask(host_rows,
                                            batch.token_ids.shape[0]),
                 batch.token_ids))
            return batch._replace(token_ids=jnp.where(from_host, tokens,
                                                      prev_tokens))
        return batch._replace(token_ids=prev_tokens)

    def _splice_mapped_tokens(self, batch: PackedBatch, prev_tokens,
                              sched_batch: ScheduledBatch):
        """Input tokens for a speculatively RE-FORMED batch (pipelined
        loop): item j takes the previous decode entry's on-device
        sampled token at row ``src_rows[j]`` (a promised in-flight
        row), or keeps the host-built value (-1: a joining decode-ready
        seq). Unlike :meth:`_splice_chain_tokens` the two sides' row
        buckets may differ (membership changed), so the splice is a tiny
        scatter into the flat token axis at each promised item's row
        offset (``_scatter_prev``; its offsets and source rows are the
        dispatch's third array); no new jit-step variant. NOTE
        prev_tokens is NOT donated into the new step: the previous
        entry's collect still reads it (its async host copy may be in
        flight)."""
        if prev_tokens.ndim == 2:
            prev_tokens = prev_tokens[-1]   # preceding multi-step block
        ir = self._promised_rows(sched_batch)
        if not ir:
            return batch
        tokens, ir = self._put((batch.token_ids,
                                np.asarray(ir, np.int32).T))
        return batch._replace(
            token_ids=_scatter_prev(tokens, prev_tokens, ir))

    @staticmethod
    def _promised_rows(sched_batch: ScheduledBatch):
        """[(flat token offset, row of the previous entry's tokens)] of a
        re-formed batch's promised items. A promised row is always a
        single decode token at the item's flat offset (prefill chunks
        and joins carry src -1)."""
        out, off = [], 0
        for it, src in zip(sched_batch.items, sched_batch.src_rows):
            if src >= 0:
                out.append((off, src))
            off += it.num_new_tokens + len(it.draft_tokens)
        return out

    def _splice_prev_dp(self, stacked, sched_batches, prev_tokens):
        """Dispatch-time input-token splice for a chained dp SUPER-STEP:
        for every replica whose batch was re-formed off promised counts
        (``src_rows`` set), scatter the previous super-step's on-device
        sampled tokens ``prev_tokens[r]`` into that replica's row of the
        stacked token_ids at each promised item's flat offset — the
        per-replica analogue of :meth:`_splice_mapped_tokens`. Replicas
        scheduled from committed state (src_rows None, including idle
        dummies) keep their host-built tokens. ``stacked`` is placed
        already (its tokens lie over the dp axis); every replica's
        (replica, offset, source row) triples go over as one array, into
        one scatter. prev_tokens is NOT donated: the previous entry's
        collect still reads it."""
        ir = [(r, off, src) for r, b in enumerate(sched_batches)
              if b is not None and b.src_rows is not None
              for off, src in self._promised_rows(b)]
        if not ir:
            return stacked
        return stacked._replace(token_ids=_scatter_prev(
            stacked.token_ids, prev_tokens,
            self._put(np.asarray(ir, np.int32).T)))

    def _splice_prev(self, batch: PackedBatch, sched_batch: ScheduledBatch,
                     prev_tokens):
        """Dispatch-time input-token splice for a batch that chains off
        on-device sampled tokens: the mapped re-form splice when the
        scheduler set ``src_rows`` (membership changed), else the
        identity chain splice (+ host_rows joins). Takes the packed
        batch before it is placed and returns it with its tokens on the
        device."""
        if sched_batch.src_rows is not None:
            return self._splice_mapped_tokens(batch, prev_tokens,
                                              sched_batch)
        return self._splice_chain_tokens(batch, prev_tokens,
                                         sched_batch.host_rows)

    def step_async_chained(self, sched_batch: ScheduledBatch, prev_handle):
        """Launch a chained step whose input tokens are the PREVIOUS
        step's on-device sampled tokens (overlap scheduling: the reference's
        FutureMap placeholder resolution, async_utils.py:56-61, without the
        negative-id dance — the sampled-token array is simply spliced in as
        the next step's token_ids). Delegates to :meth:`step_async` with
        ``prev_handle``."""
        prev_tokens, _, prev_n = prev_handle
        if sched_batch.src_rows is None:
            # re-formed batches (src_rows) legitimately change the seq
            # count across the edge; identity chains must not
            assert prev_n == sched_batch.num_seqs
        return self.step_async(sched_batch, prev_handle=prev_handle)

    def step_multi(self, chain, prev_handle=None):
        """Launch K chained decode steps as ONE device program (lax.scan
        over the step axis): one dispatch, one token fetch for the whole
        block. This is the dispatch-latency countermeasure the per-step
        chain can't provide — every dispatch pays a host round trip, so K
        steps per dispatch divides that cost by K. ``chain`` is K ScheduledBatches produced by
        Scheduler.schedule_chain over the SAME sequences.

        Returns a handle whose collect() yields tokens [K, n]; chainable
        (the last step's on-device tokens feed the next block)."""
        K = len(chain)
        build = phase("build").start()
        # chain scheduling may have minted prefix-cached pages (spill
        # intents) — drain before the block overwrites them
        self._apply_swap_intents()
        # per-sub-step keys matching the single-step schedule exactly
        # (fold_in of consecutive step counts) → byte-identical sampling
        # across multi/single scheduling modes; the block folds them
        # inside its program from the first sub-step's ordinal
        step0 = self._step_count + 1
        self._step_count += K
        # pages allocated by the chained schedules must fit the page
        # bucket → size the signature from the LAST step's state
        sig = self.builder.shape_signature(chain[-1])
        host, max_q, token_counts = self.builder.build(
            chain[0], force_signature=sig)
        # chains are all-decode by construction
        assert token_counts is None
        assert all(it.num_new_tokens == 1 for it in chain[0].items)
        # Per-row alive-link count: rows whose seq dies (length cap)
        # inside the block freeze their position and write KV to the
        # dummy page from their death step on; bucket-padding rows are
        # dead for the whole block. None → every real row runs all K.
        s_bucket = host.token_ids.shape[0]
        au_np = np.zeros(s_bucket, np.int32)
        n = chain[0].num_seqs
        if chain[0].active_until is not None:
            au_np[:n] = chain[0].active_until
        else:
            au_np[:n] = K
        odf = self.config.ondevice_finish
        e_bucket = 0
        if odf:
            # on-device EOS/stop-token detection: thread the per-row
            # stop sets into the block's sampling metadata; active_until
            # stays as the (length-exact, EOS-conservative) upper bound
            stop_ids, stop_from = self.builder.stop_sets(
                chain[0].items, s_bucket, self.eos_token_ids)
            if stop_ids is not None:
                e_bucket = stop_ids.shape[1]
                host = host._replace(sampling=host.sampling._replace(
                    stop_ids=stop_ids, stop_from=stop_from))
        # the stop sets and the alive counts ride the packed buffer
        batch, layout = pack(host, (step0,), active_until=au_np)
        if prev_handle is not None:
            batch = self._splice_prev(batch, chain[0], prev_handle[0])
        batch = self._put(batch)
        all_greedy = _all_greedy(chain[0].items)
        # e_bucket is part of the compile signature: stop-set presence
        # changes the batch layout and its pow2 width E the shapes
        new_sig = self._note_dispatch(
            "multi_step", host, (K, all_greedy, odf, e_bucket),
            all_greedy)
        build.stop()
        from gllm_tpu.parallel.mesh import mesh_context
        with phase("dispatch", **self._span_args(n, n * K)):
            with mesh_context(self.mesh), first_use(new_sig):
                tokens, finish_step, self.kv = self._multi_step_fn(
                    self.params, self.kv, batch, self.cos_sin,
                    self.rng_key, layout=layout, num_steps=K,
                    all_greedy=all_greedy, ondevice_finish=odf)
            aux = ({"finish": (finish_step,)}
                   if finish_step is not None else {})
            _start_host_copy((tokens, aux))
        return tokens, aux, chain[0].num_seqs

    def _build_multi_step_fn(self):
        cfg = self.model_cfg
        fwd = self.model_def.forward
        logits_fn = self.model_def.compute_logits
        attn_impl = self.attn_impl
        page = self.config.cache.page_size

        @functools.partial(jax.jit, static_argnames=("layout", "num_steps",
                                                     "all_greedy",
                                                     "ondevice_finish"),
                           compiler_options=tpu_compiler_options(),
                           donate_argnums=(1,))
        def step_multi(params, kv, packed: PackedBatch, cos_sin, rng_key,
                       *, layout, num_steps: int,
                       all_greedy: bool = False,
                       ondevice_finish: bool = False):
            batch, extra = unpack(packed, layout)
            active_until = extra["active_until"]
            # sub-step k samples with fold_in(rng_key, step + k)
            keys = _fold_in_range(rng_key, extra["step"][0], k=num_steps)

            def substep(kv, tokens, alive_n, k, key):
                # rows whose seq died earlier in the block (length cap
                # via active_until; EOS/stop via the carried alive count
                # under ondevice_finish) freeze: position stops advancing
                # (stays in-bounds of the page bucket) and KV writes land
                # in the dummy page (slot 0) so a finished seq's —
                # possibly prefix-cached — pages are never clobbered by
                # its dead steps
                adv = jnp.minimum(k, alive_n)
                alive = k < alive_n
                pos = batch.positions + adv
                # decode rows: one token per seq; recompute flat KV slots
                # from the (pre-allocated) page table as positions advance
                page_idx = jnp.take_along_axis(
                    batch.attn.page_table, (pos // page)[:, None],
                    axis=1)[:, 0]
                slots = jnp.where(alive, page_idx * page + pos % page, 0)
                b = batch._replace(
                    token_ids=tokens,
                    positions=pos,
                    slot_mapping=slots,
                    attn=batch.attn._replace(
                        kv_lens=batch.attn.kv_lens + adv),
                    # seeded rows draw from (seed, out_step): advancing
                    # out_step per sub-step keeps the fused block
                    # byte-identical to K single seeded steps
                    sampling=batch.sampling._replace(
                        step_key=key,
                        out_step=(batch.sampling.out_step + k
                                  if batch.sampling.out_step is not None
                                  else None)),
                    # [3, T]: broadcast the per-row advance over the
                    # coordinate axis (text-only decode steps advance all
                    # three mrope coords together)
                    mrope_positions=(batch.mrope_positions + adv[None, :]
                                     if batch.mrope_positions is not None
                                     else None),
                )
                hidden, residual, kv = fwd(params, kv, b, cfg,
                                           cos_sin=cos_sin,
                                           attn_impl=attn_impl,
                                           max_q_len=1)
                logits = logits_fn(params, hidden, residual, b, cfg)
                toks = sample(logits, b.sampling, None,
                              all_greedy=all_greedy)
                return kv, toks

            if not ondevice_finish:
                # legacy block: fixed-trip scan, active_until is the ONLY
                # death mechanism (byte-identical pre-ondevice program)
                def body(carry, xs):
                    k, key = xs
                    kv, tokens = carry
                    kv, toks = substep(kv, tokens, active_until, k, key)
                    return (kv, toks), toks

                (kv, _), all_tokens = jax.lax.scan(
                    body, (kv, batch.token_ids),
                    (jnp.arange(num_steps, dtype=jnp.int32), keys))
                return all_tokens, None, kv              # [K, S]

            # On-device finish: the block driver is a while_loop over
            # sub-steps whose carried per-row alive count starts at the
            # active_until upper bound and DROPS when a sampled token
            # hits the row's EOS/stop set — the row freezes from the next
            # sub-step (same dummy-page machinery), and once every row is
            # dead the loop exits instead of burning the remaining
            # sub-steps. Sub-step k's tokens land at out[k]; rows beyond
            # a row's finish step hold garbage the host discards (legacy
            # did too — its garbage just cost real forward work).
            from gllm_tpu.ops.sampling import stop_token_hit

            out0 = jnp.zeros((num_steps,) + batch.token_ids.shape,
                             jnp.int32)

            def cond(carry):
                _, _, _, alive_n, k = carry
                return (k < num_steps) & jnp.any(alive_n > k)

            def wbody(carry):
                kv, tokens, out, alive_n, k = carry
                kv, toks = substep(kv, tokens, alive_n, k, keys[k])
                # a live row whose token hits its stop set (past the
                # min_tokens arming step) keeps this token and dies:
                # finish step = k + 1. Dead rows' garbage tokens must
                # not re-arm anything — gate on alive.
                hit = (stop_token_hit(toks, batch.sampling, k)
                       & (k < alive_n))
                alive_n = jnp.where(hit, k + 1, alive_n)
                out = jax.lax.dynamic_update_index_in_dim(out, toks, k, 0)
                return kv, toks, out, alive_n, k + 1

            kv, _, all_tokens, alive_n, _ = jax.lax.while_loop(
                cond, wbody,
                (kv, batch.token_ids, out0, active_until, jnp.int32(0)))
            # [K, S] tokens + per-row finish step (== K for survivors)
            return all_tokens, jnp.minimum(alive_n, num_steps), kv

        return step_multi

    # ---- fused on-device speculation (config.spec_fused) -------------------

    def _build_spec_multi_step_fn(self):
        """K draft+verify sub-steps as ONE device program
        (docs/speculative_decoding.md#fused): each sub-step proposes up
        to k drafts per row from a carried recent-token ring (vectorized
        n-gram match — ops/sampling.ngram_propose), feeds the committed
        token + drafts as a q_len=k+1 verify row through the ragged
        attention path, accepts on device (greedy cumprod / rejection
        sampling — the SAME spec_verify the host-driven path uses, keyed
        by fold_in(seed, out_step)), and advances per-row positions by
        the variable emitted counts. The carried state (ring, frontier,
        token budget, AIMD k) crosses block boundaries through the
        handle, so chained blocks run off ACTUAL device frontiers while
        the host schedules worst-case upper bounds. Rejected rows' KV
        writes land at positions the real tokens overwrite later (the
        host-driven precedent); dead rows freeze on the dummy page."""
        cfg = self.model_cfg
        fwd = self.model_def.forward
        attn_impl = self.attn_impl
        page = self.config.cache.page_size
        ngram_n = self.config.spec_ngram

        from gllm_tpu.models.dense import compute_full_logits
        from gllm_tpu.ops.sampling import (ngram_propose, ring_shift_in,
                                           spec_verify)

        @functools.partial(jax.jit,
                           static_argnames=("layout", "num_steps",
                                            "k_draft", "all_greedy"),
                           compiler_options=tpu_compiler_options(),
                           donate_argnums=(1,))
        def step_spec(params, kv, packed: PackedBatch, cos_sin, rng_key,
                      prev_state, prev_tokens, *, layout, num_steps: int,
                      k_draft: int, all_greedy: bool = False):
            batch, extra = unpack(packed, layout)
            keys = _fold_in_range(rng_key, extra["step"][0], k=num_steps)
            ring0, rlen0, last0, pos0, alive0, ostep0, kcur0 = \
                _spec_carry(extra, prev_state, prev_tokens)
            S = ring0.shape[0]
            K1 = k_draft + 1
            iota = jnp.arange(K1, dtype=jnp.int32)[None, :]   # [1, K1]
            pt_width = batch.attn.page_table.shape[1]
            cu = jnp.arange(S + 1, dtype=jnp.int32) * K1
            karr = jnp.arange(k_draft, dtype=jnp.int32)[None, :]

            def substep(kv, ring, rlen, last, pos, alive, ostep, kcur,
                        key):
                alive_b = alive > 0
                # a row may emit at most ``alive`` tokens, so at most
                # alive-1 drafts are worth verifying (AIMD k_cur caps
                # further; -1 drafts never accept)
                allow = jnp.clip(jnp.minimum(kcur, alive - 1), 0,
                                 k_draft)
                drafts = ngram_propose(ring, rlen, n=ngram_n, k=k_draft)
                drafts = jnp.where(karr < allow[:, None], drafts, -1)
                # what was REALLY proposed (the n-gram may find no match
                # or a short continuation — valid drafts are a prefix
                # run): drafted/accepted ACCOUNTING runs on this, like
                # the host path, where a no-match row proposes nothing
                # and never counts toward spec_stats / the accept-rate
                # denominator (a draft-hostile window reads None, not 0)
                prop = (drafts >= 0).sum(axis=1, dtype=jnp.int32)
                tok_row = jnp.concatenate(
                    [last[:, None], jnp.maximum(drafts, 0)], axis=1)
                # dead rows freeze (position stays, writes → dummy page);
                # garbage draft rows (past ``allow``) also write dummy —
                # their positions may exceed the allocated frontier
                prow = pos[:, None] + jnp.where(alive_b[:, None], iota, 0)
                write = alive_b[:, None] & (iota <= allow[:, None])
                pidx = jnp.take_along_axis(
                    batch.attn.page_table,
                    jnp.minimum(prow // page, pt_width - 1), axis=1)
                slots = jnp.where(write, pidx * page + prow % page, 0)
                kvl = jnp.where(alive_b, pos + 1 + k_draft, K1)
                md = batch.sampling._replace(
                    step_key=key,
                    out_step=ostep if ostep0 is not None else None)
                b = batch._replace(
                    token_ids=tok_row.reshape(-1),
                    positions=prow.reshape(-1),
                    slot_mapping=slots.reshape(-1),
                    attn=batch.attn._replace(cu_q_lens=cu, kv_lens=kvl),
                    sampling=md)
                hidden, residual, kv = fwd(params, kv, b, cfg,
                                           cos_sin=cos_sin,
                                           attn_impl=attn_impl,
                                           max_q_len=K1)
                # verify-row logits: T == S*(k+1) exactly, so the full-
                # position projection IS the verify gather (same size
                # the host-driven spec_aux materializes)
                logits = compute_full_logits(params, hidden, residual,
                                             cfg)
                tok_mat, accept = spec_verify(
                    logits.reshape(S, K1, -1), drafts, md,
                    sampled=not all_greedy)
                emitted = jnp.minimum(accept + 1, alive)   # 0 when dead
                hit_any = jnp.zeros(S, bool)
                if batch.sampling.stop_ids is not None:
                    # on-device EOS/stop scan over the WHOLE accepted
                    # run: first hit truncates the emission and kills
                    # the row (stop_from is the absolute min_tokens
                    # position threshold — prepare.stop_sets(absolute))
                    hitm = (tok_mat[:, :, None]
                            == batch.sampling.stop_ids[:, None, :]
                            ).any(-1)
                    armed = ((pos[:, None] + iota)
                             >= batch.sampling.stop_from[:, None])
                    hm = hitm & armed & (iota < emitted[:, None])
                    hit_any = hm.any(axis=1)
                    first = jnp.argmax(hm, axis=1)
                    emitted = jnp.where(hit_any, first + 1, emitted)
                new_last = jnp.take_along_axis(
                    tok_mat, jnp.maximum(emitted - 1, 0)[:, None],
                    axis=1)[:, 0]
                last = jnp.where(emitted > 0, new_last, last)
                pos = pos + emitted
                ring, rlen = ring_shift_in(ring, rlen, tok_mat, emitted)
                alive = jnp.where(hit_any, 0, alive - emitted)
                if ostep0 is not None:
                    ostep = ostep + emitted
                # AIMD: a clean sweep of the ALLOWANCE grows k by one
                # (cap k_draft), anything less collapses to the accepted
                # run length. Deliberately stricter than the host rule
                # (which skips no-proposal rounds): in-loop, a no-match
                # or short-continuation sub-step is a draft-dry signal —
                # collapsing k and re-probing via clean sweeps keeps the
                # tail of a draft-dry stream from fragmenting into
                # 1-2-token blocks (measured: the dispatch-drop headline
                # regresses under the host gate)
                kcur = jnp.where(
                    (emitted > 0) & (allow > 0),
                    jnp.where(accept >= allow,
                              jnp.minimum(kcur + 1, jnp.int32(k_draft)),
                              jnp.maximum(accept, 1)),
                    kcur)
                n_acc = jnp.where(alive_b, jnp.minimum(accept, prop), 0)
                n_drf = jnp.where(alive_b, prop, 0)
                return (kv, ring, rlen, last, pos, alive, ostep, kcur,
                        tok_mat, emitted, n_drf, n_acc)

            out0 = jnp.zeros((num_steps, S, K1), jnp.int32)
            cnt0 = jnp.zeros((num_steps, S), jnp.int32)

            def cond(carry):
                alive, k = carry[5], carry[-1]
                return (k < num_steps) & jnp.any(alive > 0)

            def wbody(carry):
                (kv, ring, rlen, last, pos, alive, ostep, kcur, out,
                 counts, drafted, accepted, k) = carry
                (kv, ring, rlen, last, pos, alive, ostep, kcur, tok_mat,
                 emitted, n_drf, n_acc) = substep(
                    kv, ring, rlen, last, pos, alive, ostep, kcur,
                    keys[k])
                out = jax.lax.dynamic_update_index_in_dim(
                    out, tok_mat, k, 0)
                counts = jax.lax.dynamic_update_index_in_dim(
                    counts, emitted, k, 0)
                return (kv, ring, rlen, last, pos, alive, ostep, kcur,
                        out, counts, drafted + n_drf, accepted + n_acc,
                        k + 1)

            z = jnp.zeros(S, jnp.int32)
            (kv, ring, rlen, last, pos, alive, ostep, kcur, out, counts,
             drafted, accepted, k_exec) = jax.lax.while_loop(
                cond, wbody,
                (kv, ring0, rlen0, last0, pos0, alive0, ostep0, kcur0,
                 out0, cnt0, z, z, jnp.int32(0)))
            state_out = (ring, rlen, last, pos, alive, ostep, kcur)
            return out, counts, (drafted, accepted), kcur, state_out, kv

        return step_spec

    # On-device recent-token ring width (per row): bounds the n-gram
    # lookup window like the host proposer's ``window`` argument —
    # repetitive/structured output (the regime where prompt-lookup pays)
    # recurs well inside 128 tokens; [S, R] int32 is a few KB per row.
    SPEC_RING = 128

    def step_spec_multi(self, chain, prev_handle=None):
        """Launch K fused draft+verify sub-steps as ONE device program:
        one dispatch may emit up to K·(spec_k+1) tokens per row. The
        handle's aux carries the per-sub-step emitted counts (host
        commit), drafted/accepted totals + final AIMD k (host
        reconciliation), and — under the ``_``-prefixed key collect
        skips — the device-resident carry state the NEXT chained block
        seeds from (actual frontiers; the host's scheduled bounds are
        upper bounds only)."""
        K = len(chain)
        build = phase("build").start()
        self._apply_swap_intents()
        step0 = self._step_count + 1
        self._step_count += K
        sig = self.builder.shape_signature(chain[-1])
        host, _, token_counts = self.builder.build(chain[0],
                                                   force_signature=sig)
        assert token_counts is None, "penalties never reach spec chains"
        assert all(it.num_new_tokens == 1 for it in chain[0].items)
        k_draft = self.config.spec_k
        s_bucket = host.attn.page_table.shape[0]
        n = chain[0].num_seqs
        au_np = np.zeros(s_bucket, np.int32)
        au_np[:n] = chain[0].active_until    # token budgets (spec chain)
        e_bucket = 0
        if self.config.ondevice_finish:
            stop_ids, stop_from = self.builder.stop_sets(
                chain[0].items, s_bucket, self.eos_token_ids,
                absolute=True)
            if stop_ids is not None:
                e_bucket = stop_ids.shape[1]
                host = host._replace(sampling=host.sampling._replace(
                    stop_ids=stop_ids, stop_from=stop_from))
        # the host's seeds of the carry ride the packed buffer; what the
        # block chains off stays on the device and is merged with them
        # inside the program (_spec_carry)
        seeds, prev_state, prev_tokens = self._spec_seed_state(
            host.sampling.out_step is not None, chain[0], au_np,
            prev_handle)
        batch, layout = pack(host, (step0,), **seeds)
        # the block feeds itself from the carry: its token_ids are read
        # by nothing, so nothing is spliced into them
        batch = self._put(batch)
        all_greedy = _all_greedy(chain[0].items)
        new_sig = self._note_dispatch(
            "spec_block", host, (K, k_draft, all_greedy, e_bucket),
            all_greedy)
        build.stop()
        from gllm_tpu.parallel.mesh import mesh_context
        with phase("dispatch", **self._span_args(n, n * K)):
            with mesh_context(self.mesh), first_use(new_sig):
                tokens, counts, totals, kcur, state_out, self.kv = \
                    self._spec_multi_fn(self.params, self.kv, batch,
                                        self.cos_sin, self.rng_key,
                                        prev_state, prev_tokens,
                                        layout=layout, num_steps=K,
                                        k_draft=k_draft,
                                        all_greedy=all_greedy)
            aux = {"spec_counts": (counts,), "spec_totals": totals,
                   "spec_kcur": (kcur,), "_spec_state": state_out}
            _start_host_copy((tokens, {k: v for k, v in aux.items()
                                       if not k.startswith("_")}))
        return tokens, aux, n

    def _spec_seed_state(self, seeded: bool, sched0, au_np, prev_handle):
        """The host's share of a spec block's carry state (ring,
        ring_len, last_tok, pos, alive, out_step, k_cur, each
        [S_bucket]) as numpy arrays for the packed buffer, and what the
        block chains off, left on the device: (seeds, prev_state,
        prev_tokens). :func:`_spec_carry` merges them in the program.

        Seeding discipline (docs/speculative_decoding.md#fused): rows
        whose link-0 token is HOST-known (chain roots, slot joins) seed
        fully from committed ``token_ids``; rows chaining off a sync
        single-step splice the previous entry's on-device sampled token
        into the ring tail; rows chaining off a previous SPEC block
        carry its device state wholesale (the actual frontier — the
        host's scheduled bounds stay upper bounds). HOLE rows and rows
        the host has since finished are forced dead (alive 0)."""
        from gllm_tpu.sequence import HOLE_SEQ_ID, SequenceStatus
        R = self.SPEC_RING
        items = sched0.items
        s_bucket = au_np.shape[0]
        n = len(items)
        ring = np.full((s_bucket, R), -1, np.int32)
        rlen = np.zeros(s_bucket, np.int32)
        last = np.zeros(s_bucket, np.int32)
        pos = np.zeros(s_bucket, np.int32)
        ostep = np.zeros(s_bucket, np.int32) if seeded else None
        kcur = np.ones(s_bucket, np.int32)
        host_known = np.ones(s_bucket, bool)
        dead = np.zeros(s_bucket, bool)
        join_rows = set(sched0.host_rows or ())
        for i, it in enumerate(items):
            seq = it.seq
            if (seq.seq_id == HOLE_SEQ_ID
                    or seq.status is not SequenceStatus.RUNNING):
                dead[i] = True
                continue
            cb = it.computed_before
            toks = seq.token_ids
            kcur[i] = min(getattr(seq, "spec_k_cur", None)
                          or self.config.spec_k, self.config.spec_k)
            if seeded and seq.sampling_params.seed is not None:
                ostep[i] = cb + 1 - seq.prompt_len
            pos[i] = cb
            if cb < seq.num_tokens:
                # fully host-known (root / join): ring covers tokens
                # [0, cb] INCLUDING the link-0 input token
                tail = toks[max(0, cb + 1 - R):cb + 1]
                last[i] = toks[cb]
            else:
                # the link-0 token is the previous entry's on-device
                # sample — ring holds everything committed; the splice
                # below appends the device token
                tail = toks[max(0, len(toks) - R):]
                host_known[i] = False
            ring[i, R - len(tail):] = tail
            rlen[i] = len(tail)
        dead[n:] = True
        alive = np.where(dead, 0, au_np).astype(np.int32)
        prev_state = None
        prev_tokens = None
        if prev_handle is not None:
            prev_aux = prev_handle[1] or {}
            prev_state = prev_aux.get("_spec_state")
            if prev_state is None:
                prev_tokens = prev_handle[0]

        seeds = dict(spec_ring=ring, spec_rlen=rlen, spec_last=last,
                     spec_pos=pos, spec_alive=alive, spec_ostep=ostep,
                     spec_kcur=kcur)
        if prev_state is not None:
            # chained off a previous spec block: carry its device state;
            # joins/holes re-seed from the host arrays built above
            assert prev_state[0].shape[0] == s_bucket, \
                (prev_state[0].shape, s_bucket)    # identity membership
            reseed = np.zeros(s_bucket, bool)
            reseed[sorted(join_rows)] = True
            seeds.update(spec_reseed=reseed, spec_dead=dead)
        elif prev_tokens is not None:
            # chained off a sync single step: its on-device sampled
            # token becomes the ring tail + link-0 input of every row
            # the host doesn't know
            assert prev_tokens.shape[-1] == s_bucket, \
                (prev_tokens.shape, s_bucket)
            seeds.update(spec_host_known=host_known)
        else:
            assert host_known[:n].all(), \
                "spec chain root with device-only tokens but no handle"
        return seeds, prev_state, prev_tokens

    def collect(self, handle):
        """(sampled tokens [n] / [K, n] / [K, n, k+1], aux dict of host
        arrays). Aux keys starting with ``_`` are device-resident carry
        state (fused speculation) — never fetched to host here; the next
        chained dispatch consumes them directly."""
        tokens, aux, n = handle
        out_aux = {}
        # ``wait`` is the one phase in which an idle device is not the
        # host's doing: blocked until the step's tokens are on the host
        # (the program, then the copy that was started at dispatch,
        # _start_host_copy). Blocking on the program alone first
        # (block_until_ready) and fetching afterwards would separate the
        # copy from the wait, at the price of a second wake-up: 0.4 ms a
        # step on a v5e, 1 % of this cell's throughput (PERF.md, PR 24).
        # ``readback`` is what is left: the step's other outputs, ready
        # with the tokens.
        with phase("wait"):
            host = _to_host(tokens)
        with phase("readback"):
            if aux:
                out_aux = {k: tuple(_to_host(a) for a in v)
                           for k, v in aux.items()
                           if not k.startswith("_")}
        if "stats" in out_aux:
            from gllm_tpu.models.deepseek import count_stats
            count_stats(*out_aux.pop("stats"))
        if host.ndim == 3:              # spec block: [K, S, k+1]
            return host[:, :n, :], out_aux
        return (host[..., :n] if host.ndim == 2 else host[:n]), out_aux

    def step(self, sched_batch: ScheduledBatch) -> np.ndarray:
        """Run one step; returns sampled token per batch item (host numpy)."""
        return self.collect(self.step_async(sched_batch))[0]

    def warmup(self, decode_buckets: Optional[Tuple[int, ...]] = None,
               page_buckets: Optional[Tuple[int, ...]] = None):
        """Pre-compile the hot decode shapes (reference capture_graph loop
        model_runner.py:1525-1615).

        The compile key is (seq-bucket, page-bucket); warming the full grid
        is quadratic in compiles, so by default we warm every seq bucket at
        the largest page bucket plus the largest seq bucket at every page
        bucket — the shapes live decode traffic hits first.
        """
        from gllm_tpu.sampling_params import SamplingParams
        from gllm_tpu.scheduler import ScheduledSeq
        from gllm_tpu.sequence import Sequence

        def pow2_range(lo, hi):
            out, b = [], lo
            while b < hi:
                out.append(b)
                b *= 2
            out.append(hi)
            return tuple(out)

        maxd = self.config.scheduler.max_decode_seqs
        if decode_buckets is None:
            decode_buckets = pow2_range(8, maxd)
        if page_buckets is None:
            page_buckets = pow2_range(4, min(self.config.max_pages_per_seq,
                                             self.num_pages - 1))
        combos = [(s, page_buckets[-1]) for s in decode_buckets]
        combos += [(decode_buckets[-1], p) for p in page_buckets[:-1]]

        page = self.config.cache.page_size
        _t_warm = time.monotonic()
        # Each combo warms BOTH sampler program variants: temperature=0
        # compiles the all_greedy=True fast path (the common serving/
        # eval/bench case) and temperature=1 the sampled path — so
        # neither a greedy nor a sampled first request pays a mid-serving
        # XLA compile stall (every compile lands in the persistent cache,
        # so the doubled warmup is a one-time cost per machine).
        for nseq, npages in combos:
            for temp in (0.0, 1.0):
                items = []
                for i in range(nseq):
                    ctx = npages * page - 1  # context filling npages pages
                    seq = Sequence(i, [1] * (ctx + 1),
                                   SamplingParams(temperature=temp,
                                                  max_tokens=4))
                    # All warmup rows may share pages: decode only READS
                    # pages and writes one fresh slot; sharing keeps
                    # warmup within any pool size.
                    seq.page_table = [1 + (j % max(1, self.num_pages - 1))
                                      for j in range(npages)]
                    seq.num_computed_tokens = ctx
                    items.append(ScheduledSeq(seq, 1, ctx))
                if items:
                    t0 = time.monotonic()
                    self.step(ScheduledBatch(items))
                    logger.info("[startup] phase=warmup_bucket seqs=%d "
                                "pages=%d temp=%g seconds=%.2f", nseq,
                                npages, temp, time.monotonic() - t0)

        # Mixed prefill+decode signatures — the shapes a newly admitted
        # request hits mid-serving (chunked prefill riding with the decode
        # wave); round 1 left these to first-hit compiles.
        chunk = min(self.config.scheduler.max_prefill_tokens,
                    self.config.max_model_len)
        mixed = 0
        for nseq in decode_buckets:
            items = []
            seq = Sequence(0, [1] * chunk, SamplingParams(max_tokens=4))
            seq.page_table = [1 + (j % max(1, self.num_pages - 1))
                              for j in range(cdiv(chunk, page))]
            seq.num_computed_tokens = 0
            items.append(ScheduledSeq(seq, chunk, 0))
            for i in range(1, nseq):
                ctx = page_buckets[-1] * page - 1
                s2 = Sequence(i, [1] * (ctx + 1),
                              SamplingParams(max_tokens=4))
                s2.page_table = [1 + (j % max(1, self.num_pages - 1))
                                 for j in range(page_buckets[-1])]
                s2.num_computed_tokens = ctx
                items.append(ScheduledSeq(s2, 1, ctx))
            t0 = time.monotonic()
            self.step(ScheduledBatch(items))
            logger.info("[startup] phase=warmup_bucket seqs=%d "
                        "prefill_chunk=%d seconds=%.2f", nseq, chunk,
                        time.monotonic() - t0)
            mixed += 1
        logger.info("[startup] phase=warmup seconds=%.2f buckets=%d",
                    time.monotonic() - _t_warm, len(combos) + mixed)
        logger.info("warmed %d decode + %d mixed shape buckets",
                    len(combos), mixed)
