"""Pipeline-parallel model runner.

TPU-native re-design of the reference's PP machinery (per-GPU worker
processes, NCCL isend/recv of hidden states, zmq delta-schedule broadcast to
follower ranks — /root/reference/gllm/worker.py:504-544,
dist_utils.py:8-22,494-528, dist_schedule.py). On TPU one controller process
owns every stage:

- layers split into ``pp`` contiguous stages (even split, or
  ``--assigned-layers``; reference get_pp_layers dist_utils.py:494-528);
  each stage's params + its layers' KV cache live on a disjoint device
  group (optionally TP-sharded within the stage). Hybrid (GDN) stages are
  rounded to the model's layer-type period so each stage is itself
  periodic (reference builds per-stage layer lists the same way,
  qwen3_5.py via get_pp_layers).
- one jit program per stage; hidden/residual move between stages with
  ``jax.device_put`` (ICI transfer on real hardware).
- **pipelining comes from async dispatch**: the engine keeps up to
  ``pp_size`` scheduled microbatches in flight (scheduler in-flight
  marking), and because consecutive microbatches' stage programs run on
  different device groups, XLA's per-device queues overlap them — no
  explicit microbatch scheduler needed. Token throttling balances the
  token count across those in-flight microbatches (scheduler policy).
- **dp × pp**: each DP replica owns a full private pipeline on its own
  ``pp × tp`` device block (the reference's dp-grouped rank grid,
  dist_utils.py:149-263). Replicas are independent programs — no
  lockstep dummy batches needed; host-side launch order + async
  dispatch overlaps them.
- the follower-mirror/delta-payload machinery disappears: there is one
  scheduler and one page table per replica, shared by construction.

The sampled-token array returned by ``step_async`` is an uncommitted device
future; ``collect`` blocks on it one pipeline depth later.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gllm_tpu.batching import pack, unpack
from gllm_tpu.config import EngineConfig
from gllm_tpu.models import ModelConfig, get_model_def
from gllm_tpu.obs import metrics as obs
from gllm_tpu.obs.spans import phase
from gllm_tpu.obs.steptrace import TRACE
from gllm_tpu.ops.sampling import sample
from gllm_tpu.runner.runner import (ModelRunner, _DTYPES, build_in_place,
                                    device_free_bytes, init_dummy_params,
                                    pick_kv_pack, reset_page_scales,
                                    resolve_attn_impl, resolve_kv_quant,
                                    stable_page_count)
from gllm_tpu.utils import cdiv, tpu_compiler_options

logger = logging.getLogger(__name__)

_M_MICROBATCH = obs.counter(
    "gllm_pp_microbatches_total",
    "microbatches dispatched through the stage pipeline")
_M_STAGE_INFLIGHT = obs.gauge(
    "gllm_pp_stage_inflight",
    "microbatches dispatched but not yet collected, per pipeline stage "
    "(dispatch-side: a microbatch occupies every stage of its replica's "
    "chain until its collect)", ("stage",))


def split_layers(num_layers: int, pp: int,
                 assigned: Optional[List[int]] = None,
                 multiple: int = 1):
    """[(first, last)] per stage: even split with remainder spread from the
    front, or an explicit per-stage layer-count list. ``multiple`` forces
    each stage's layer count to a multiple (hybrid layer-type period)."""
    if assigned is not None:
        if sum(assigned) != num_layers or len(assigned) != pp:
            raise ValueError(
                f"assigned_layers {assigned} must sum to {num_layers} "
                f"over {pp} stages")
        if any(c % multiple for c in assigned):
            raise ValueError(
                f"assigned_layers {assigned} must each be a multiple of "
                f"the hybrid layer-type period {multiple}")
        counts = assigned
    else:
        if num_layers % multiple:
            raise ValueError(f"{num_layers} layers not divisible by the "
                             f"hybrid layer-type period {multiple}")
        units = num_layers // multiple
        if units < pp:
            raise ValueError(f"pp={pp} needs at least {pp} period-units, "
                             f"model has {units}")
        base, rem = divmod(units, pp)
        counts = [(base + (1 if i < rem else 0)) * multiple
                  for i in range(pp)]
    bounds, first = [], 0
    for c in counts:
        bounds.append((first, first + c))
        first += c
    return bounds


@dataclasses.dataclass
class _Stage:
    cfg: ModelConfig
    params: dict
    kv: object
    device: object          # placement target (Device or NamedSharding mesh)
    mesh: object
    fn: object              # jit'd stage program
    cos_sin: object = None  # rope table pre-placed on this stage's devices
                            # (re-transferring it every call costs a
                            # host→device copy per stage per step)
    rng_key: object = None  # the LAST stage samples: the runner's key,
                            # pre-placed there (the step's key is folded
                            # from it inside the stage program)


class PPModelRunner(ModelRunner):
    """Same interface as ModelRunner; executes one multi-stage pipeline
    per DP replica."""

    def __init__(self, config: EngineConfig, model_cfg: ModelConfig,
                 params=None, mesh=None):
        # Deliberately NOT calling super().__init__: the single-program
        # setup doesn't apply. Shared helpers are used piecemeal.
        if params is not None or mesh is not None:
            raise NotImplementedError(
                "PPModelRunner builds its own per-stage params/meshes")
        self.config = config
        self.kv_quant, model_cfg = resolve_kv_quant(config, model_cfg)
        self.model_cfg = model_cfg
        self.mesh = None
        self.dtype = _DTYPES[config.dtype]
        self.model_def = get_model_def(model_cfg)
        pp, tp = config.parallel.pp, config.parallel.tp
        dp = self.dp = config.parallel.dp
        devices = jax.devices()
        if len(devices) < dp * pp * tp:
            raise ValueError(f"dp={dp} pp={pp} tp={tp} needs "
                             f"{dp * pp * tp} devices, have {len(devices)}")
        from gllm_tpu.ops.attention import set_shard_context
        # PP builds per-stage meshes; the shard context (if any) is set
        # below once those exist — clear a prior runner's first.
        set_shard_context(None)

        pack = pick_kv_pack(model_cfg, tp_sharded=tp > 1)
        impl = resolve_attn_impl(config.attention_impl, model_cfg, tp,
                                 pack, tp > 1)
        self.kv_pack = pack if impl == "pallas" else 1
        self.attn_impl = impl
        if self.kv_quant:
            self._check_kv_quant()
        from gllm_tpu.runner.prepare import BatchBuilder
        self.builder = BatchBuilder(config, config.cache.page_size,
                                    vocab_size=model_cfg.vocab_size,
                                    hidden_size=model_cfg.hidden_size,
                                    use_mm=model_cfg.use_mm,
                                    use_ssm=model_cfg.use_hybrid,
                                    mm_embed_dim=model_cfg.mm_embed_dim)
        if model_cfg.use_mm:
            from gllm_tpu.utils import LRUBytesCache
            self._mm_cache = LRUBytesCache()
        self.rng_key = jax.random.key(config.seed)
        self._step_count = 0
        self._seen_sigs = set()          # see ModelRunner._note_dispatch
        self._mb_inflight = 0            # feeds gllm_pp_stage_inflight

        if model_cfg.use_hybrid:
            from gllm_tpu.models.hybrid import period_pattern
            period = len(period_pattern(model_cfg))
            self.ssm_working_slots = config.max_num_seqs
            self.ssm_snapshot_slots = (
                config.cache.ssm_snapshot_slots
                if (config.cache.enable_prefix_caching
                    or (config.spec_decode
                        and not config.overlap_scheduling)) else 0)
        else:
            period = 1
            self.ssm_working_slots = self.ssm_snapshot_slots = 0
        bounds = split_layers(model_cfg.num_layers, pp,
                              config.parallel.assigned_layers,
                              multiple=period)
        # surfaced by /server_info (per-stage layer assignment)
        self.stage_bounds = bounds

        # Per-(replica, stage) device groups: replica r owns the
        # contiguous block devices[r*pp*tp : (r+1)*pp*tp], stage i the
        # tp-slice within it.
        def stage_devices(r, i):
            base = (r * pp + i) * tp
            return devices[base:base + tp]

        def stage_mesh(devs):
            if tp <= 1:
                return None
            from jax.sharding import Mesh
            return Mesh(np.asarray(devs).reshape(1, tp), ("dp", "tp"))

        # Phase 1: load (and optionally quantize) every stage's weights and
        # place them on REPLICA 0's device block as we go (peak host memory
        # is one stage; page sizing then reads live device stats).
        staged = []
        import time as _time
        _t_load = _time.monotonic()
        for i, (first, last) in enumerate(bounds):
            scfg = dataclasses.replace(model_cfg, first_layer=first,
                                       last_layer=last)
            sdevs = stage_devices(0, i)
            smesh = stage_mesh(sdevs)
            if config.load_format == "dummy" or not config.model:
                sparams = init_dummy_params(
                    self.model_def, scfg, config.seed, self.dtype, smesh,
                    tp, device=sdevs[0])
                if model_cfg.use_mm and first > 0:
                    sparams.pop("visual", None)
            elif model_cfg.use_mm and first > 0:
                # only stage 0 embeds visual rows — later stages never
                # read the tower (disagg-LM skip_visual rule filtering)
                sparams = self.model_def.load_params(
                    config.model, scfg, dtype=self.dtype, skip_visual=True)
            else:
                sparams = self.model_def.load_params(config.model, scfg,
                                                     dtype=self.dtype)
            if config.quantization:
                from gllm_tpu.ops.quant import (param_bytes,
                                                quantize_params)
                before = param_bytes(sparams)
                sparams = quantize_params(sparams,
                                          mode=config.quantization)
                logger.info(
                    "stage %d quantized (%s): %.2f GB -> %.2f GB", i,
                    config.quantization, before / 1e9,
                    param_bytes(sparams) / 1e9)
            if smesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                from gllm_tpu.parallel.shardings import shard_params
                sparams = shard_params(
                    sparams, self.model_def.param_specs(scfg, tp), smesh)
                place = NamedSharding(smesh, PartitionSpec())
            else:
                place = sdevs[0]
                sparams = jax.device_put(sparams, place)
            # one jit wrapper per stage, shared by all replicas (their
            # calls differ only in arg placement → per-sharding compiles
            # dedupe through the jit cache)
            staged.append((scfg, sparams, self._make_stage_fn(scfg)))
            logger.info("[startup] phase=weight_load stage=%d seconds=%.2f",
                        i, _time.monotonic() - _t_load)
            _t_load = _time.monotonic()

        # Phase 2: one shared page count from the TIGHTEST stage device
        # (page tables are global; honors cache.memory_util). Replicas are
        # identical, so replica 0 prices all of them.
        self.num_pages = (config.cache.num_pages
                          or self._determine_num_pages(bounds, staged,
                                                       stage_devices))

        # Phase 3: init per-stage KV everywhere; replicas r>0 copy their
        # params device-to-device from replica 0 (ICI, no host re-load).
        kv_dtype = self._kv_dtype()
        num_slots = (1 + self.ssm_working_slots + self.ssm_snapshot_slots)
        self.replicas: List[List[_Stage]] = []
        for r in range(dp):
            stages: List[_Stage] = []
            for i, (scfg, sparams, fn) in enumerate(staged):
                sdevs = stage_devices(r, i)
                smesh = stage_mesh(sdevs)
                if model_cfg.use_hybrid:
                    kw = {"num_slots": num_slots}
                else:
                    kw = ({"kv_pack": self.kv_pack}
                          if self.kv_pack > 1 else {})
                skv = build_in_place(
                    functools.partial(
                        self.model_def.init_kv_cache, scfg, self.num_pages,
                        config.cache.page_size, kv_dtype, **kw),
                    smesh,
                    self.model_def.kv_specs(scfg, tp) if smesh else None,
                    device=sdevs[0])
                if smesh is not None:
                    from jax.sharding import NamedSharding, PartitionSpec
                    if r == 0:
                        rparams = sparams
                    else:
                        pspecs = self.model_def.param_specs(scfg, tp)
                        rparams = jax.tree.map(
                            lambda x, s: jax.device_put(
                                x, NamedSharding(smesh, s)),
                            sparams, pspecs)
                    # Activations/batch enter the stage replicated over
                    # its mesh.
                    place = NamedSharding(smesh, PartitionSpec())
                else:
                    place = sdevs[0]
                    rparams = (sparams if r == 0
                               else jax.device_put(sparams, place))
                stages.append(_Stage(scfg, rparams, skv, place, smesh, fn))
            self.replicas.append(stages)
        self.stages = self.replicas[0]
        if impl == "pallas" and tp > 1:
            # Any mesh with the tp axis works for the dispatch decision;
            # each stage's trace runs under mesh_context(stage.mesh), so
            # the nested tp shard_map binds the CONTEXT mesh — i.e. that
            # stage's own device group (ops/attention.py).
            set_shard_context(self.stages[0].mesh, "tp")
        self.cos_sin = self.model_def.make_rope_table(model_cfg)
        for stages in self.replicas:
            for stage in stages:
                stage.cos_sin = jax.device_put(self.cos_sin, stage.device)
                if stage.cfg.is_last_stage:
                    stage.rng_key = jax.device_put(self.rng_key,
                                                   stage.device)
        if model_cfg.use_mm:
            # the inherited _prepare_mm embeds on stage 0 (visual tower)
            self.params = self.stages[0].params
        self.memory_manager = None     # attached by the engine
        from gllm_tpu.runner.runner import _M_KV_DTYPE
        _M_KV_DTYPE.set(1, dtype=jnp.dtype(kv_dtype).name)
        logger.info("pipeline: dp=%d × %d stages %s × tp=%d, "
                    "%d KV pages/stage", dp, pp, bounds, tp,
                    self.num_pages)

    def _determine_num_pages(self, bounds, staged, stage_devices) -> int:
        """Size the shared KV page count from the TIGHTEST stage: every
        stage's weights are already resident on replica 0 (phase 1), so
        each stage device's free memory divided by that stage's per-page
        KV bytes (via the shared _kv_bytes_per_page, with the stage's
        attention-layer count) bounds its page budget; take the minimum
        (reference profile-then-size discipline,
        memory_manager.py:476-526)."""
        if jax.default_backend() != "tpu":
            return 2048         # CPU reports no memory stats: modest pool
        best = None
        for i, ((first, last), (scfg, _, _)) in enumerate(
                zip(bounds, staged)):
            free = min(device_free_bytes(d, self.config.cache.memory_util)
                       for d in stage_devices(0, i))
            free -= 512 * 1024 * 1024      # activation headroom
            free -= self._ssm_pool_bytes(scfg)
            n_kv = (scfg.num_attn_layers if scfg.use_hybrid
                    else last - first)
            per_page = self._kv_bytes_per_page(n_layers=n_kv)
            num = int(free // per_page) if per_page else 1 << 30
            best = num if best is None else min(best, num)
        best = stable_page_count(best)
        min_pages = cdiv(self.config.max_model_len,
                         self.config.cache.page_size) + 2
        if best < min_pages:
            raise RuntimeError(
                f"not enough device memory for PP KV cache: {best} pages "
                f"(need >= {min_pages})")
        return best

    # ---- stage programs ---------------------------------------------------

    def _make_stage_fn(self, scfg: ModelConfig):
        fwd = self.model_def.forward
        logits_fn = self.model_def.compute_logits
        attn_impl = self.attn_impl

        @functools.partial(jax.jit,
                           static_argnames=("layout", "max_q_len",
                                            "logprobs_k", "prompt_lp",
                                            "spec_sampled", "all_greedy"),
                           compiler_options=tpu_compiler_options(),
                           donate_argnums=(1,))
        def stage(params, kv, packed, cos_sin, hidden, residual,
                  token_counts, rng_key, *, layout, max_q_len: int,
                  logprobs_k: int = -1, prompt_lp: bool = False,
                  spec_sampled: bool = False, all_greedy: bool = False):
            # rng_key is None on every stage but the last: only it samples
            batch, _ = unpack(packed, layout, rng_key)
            hidden, residual, kv = fwd(params, kv, batch, scfg,
                                       cos_sin=cos_sin,
                                       attn_impl=attn_impl,
                                       max_q_len=max_q_len,
                                       hidden_in=hidden,
                                       residual_in=residual)
            if scfg.is_last_stage:
                logits = logits_fn(params, hidden, residual, batch, scfg)
                tokens = sample(logits, batch.sampling, token_counts,
                                all_greedy=all_greedy)
                aux = {}
                if logprobs_k >= 0:
                    # same shapes as the single-runner step (reference
                    # computes logprobs on the last rank too,
                    # sampler.py:71-91)
                    from gllm_tpu.ops.sampling import (adjust_logits,
                                                       compute_logprobs)
                    lp_logits = adjust_logits(logits, token_counts,
                                              batch.sampling)
                    aux["lp"] = compute_logprobs(lp_logits, tokens,
                                                 max(logprobs_k, 1))
                if prompt_lp:
                    from gllm_tpu.models.dense import compute_full_logits
                    from gllm_tpu.ops.sampling import compute_logprobs
                    full_logits = compute_full_logits(params, hidden,
                                                      residual, scfg)
                    aux["plp"] = compute_logprobs(full_logits,
                                                  batch.plp_targets,
                                                  max(logprobs_k, 1))
                if batch.spec_rows is not None:
                    # speculative verify on the LAST stage — same math as
                    # the single runner (runner.py spec_aux)
                    from gllm_tpu.runner.runner import spec_aux
                    aux.update(spec_aux(params, hidden, residual, batch,
                                        scfg, token_counts, logprobs_k,
                                        spec_sampled))
                return (tokens, aux), kv
            return (hidden, residual), kv

        return stage

    # ---- execution --------------------------------------------------------

    def _apply_ssm_intents(self) -> None:
        """PP version: each replica's drained+padded intents (shared
        helper) apply to every hybrid stage's slot pools — slot indices
        are global; each stage holds its own layers' pools."""
        from gllm_tpu.runner.runner import _M_SSM_APPLY, _ssm_apply
        for r, (s_src, s_dst, z, r_src, r_dst) in self._drained_ssm_ops():
            for stage in self.replicas[r]:
                if stage.cfg.num_linear_layers == 0:
                    continue
                conv, rec = _ssm_apply(stage.kv.conv, stage.kv.rec,
                                       s_src, s_dst, z, r_src, r_dst)
                _M_SSM_APPLY.inc()
                stage.kv = stage.kv._replace(conv=conv, rec=rec)

    def _run_pipeline(self, stages, sched_batch, step,
                      prev_handle=None):
        """Launch one microbatch through one replica's stage chain; all
        dispatch is async — returns (tokens_future, aux, num_seqs).

        ``step``: the integers the microbatch's PRNG key is folded from
        in the last stage's program (the dispatch's ordinal, then the dp
        replica where there is one).

        ``prev_handle``: chain this microbatch off a previous entry's
        on-device sampled tokens (the pipelined loop under pp,
        docs/overlap_scheduling.md#topology-matrix). Only stage 0 reads
        ``token_ids`` (later stages consume hidden_in; positions, slots
        and page tables are host-known from promised counts), so the
        splice rewrites only the stage-0 placed batch — the previous
        tokens hop last-stage → stage-0 device first."""
        from gllm_tpu.parallel.mesh import mesh_context
        from gllm_tpu.runner.runner import _spec_sampled, first_use
        build = phase("build").start()
        host, max_q, presence = self.builder.build(sched_batch)
        batch, layout = pack(host, step)
        lp_k, want_plp = self._lp_flags(sched_batch)
        spec_sampled = _spec_sampled(sched_batch.items)
        from gllm_tpu.runner.runner import _all_greedy as _ag
        new_sig = self._note_dispatch(
            "pp", host, (max_q, lp_k, want_plp, spec_sampled,
                          _ag(sched_batch.items)), _ag(sched_batch.items))
        _M_MICROBATCH.inc()
        # one pp_stage event PER STAGE, carrying the dispatch family the
        # stage ran (family). Dispatch-side only; summarize() skips
        # these rows.
        decode_only = (sched_batch.num_decode == sched_batch.num_seqs
                       and not sched_batch.has_drafts)
        family = "decode" if decode_only else "prefill"
        for i in range(len(stages)):
            TRACE.record("pp_stage", stage=i, stages=len(stages),
                         family=family, num_seqs=sched_batch.num_seqs,
                         tokens=sched_batch.total_tokens)
        self._mb_inflight += 1
        for i in range(len(stages)):
            _M_STAGE_INFLIGHT.set(self._mb_inflight, stage=str(i))
        build.stop()
        # one ``dispatch`` phase for the whole stage chain: the placement
        # of the step batch and every stage's jit call; a first use of
        # the signature covers all of them
        with phase("dispatch", **self._span_args(
                sched_batch.num_seqs, sched_batch.total_tokens)), \
                first_use(new_sig):
            hidden = residual = None
            out = None
            # one call fans the packed batch out to every stage (and
            # presence to the last): the buffer and the tokens once per
            # stage, where handing over the StepBatch was 13 transfers
            # per stage
            last = stages[-1]
            targets = [batch] * len(stages)
            devices = [s.device for s in stages]
            if prev_handle is not None:
                prev_tokens = prev_handle[0]
                if getattr(prev_tokens, "ndim", 1) == 2:
                    prev_tokens = prev_tokens[-1]
                prev_tokens = jax.device_put(prev_tokens, stages[0].device)
                targets[0] = self._splice_prev(batch, sched_batch,
                                               prev_tokens)
            if presence is not None:
                targets.append(presence)
                devices.append(last.device)
            placed = self._put(targets, devices)
            sbs = placed[:len(stages)]
            presence = placed[len(stages)] if presence is not None else None
            for stage, sb in zip(stages, sbs):
                if hidden is not None:
                    hidden = jax.device_put(hidden, stage.device)
                    residual = jax.device_put(residual, stage.device)
                pm = presence if stage.cfg.is_last_stage else None
                # lp flags are static jit args — only the last stage reads
                # them, so earlier stages keep their (-1, False) cache entry
                # for every logprobs pattern (no pipeline-wide recompiles)
                from gllm_tpu.runner.runner import _all_greedy
                lp_kw = (dict(logprobs_k=lp_k, prompt_lp=want_plp,
                              spec_sampled=spec_sampled,
                              all_greedy=_all_greedy(sched_batch.items))
                         if stage.cfg.is_last_stage else {})
                with mesh_context(stage.mesh):
                    out, stage.kv = stage.fn(stage.params, stage.kv, sb,
                                             stage.cos_sin, hidden, residual,
                                             pm, stage.rng_key,
                                             layout=layout, max_q_len=max_q,
                                             **lp_kw)
                if not stage.cfg.is_last_stage:
                    hidden, residual = out
        tokens, aux = out
        return tokens, aux, sched_batch.num_seqs

    def _apply_scale_resets(self) -> None:
        """int8 KV cache under pp: zero minted-page scales on EVERY
        stage's cache (pages are logical across stages — each stage owns
        the same page id for its own layers)."""
        for r, idx in self._drained_scale_resets() or ():
            for stage in self.replicas[r]:
                ks, vs = reset_page_scales(stage.kv.k_scale,
                                           stage.kv.v_scale, idx)
                stage.kv = stage.kv._replace(k_scale=ks, v_scale=vs)

    def step_async(self, sched_batch, prev_handle=None):
        self._step_count += 1
        if self.model_cfg.use_mm:
            # ViT embedding on stage 0's params (visual tower lives there)
            self._prepare_mm(sched_batch)
        self._apply_ssm_intents()
        self._apply_scale_resets()
        return self._run_pipeline(self.stages, sched_batch,
                                  (self._step_count,),
                                  prev_handle=prev_handle)

    def collect(self, handle):
        tokens, aux, n = handle
        with phase("wait"):         # see ModelRunner.collect
            host = np.asarray(tokens)[:n]
        with phase("readback"):
            if aux:
                aux = jax.tree.map(np.asarray, aux)
        self._mb_inflight = max(0, self._mb_inflight - 1)
        for i in range(len(self.stages)):
            _M_STAGE_INFLIGHT.set(self._mb_inflight, stage=str(i))
        return host, aux

    def step(self, sched_batch) -> np.ndarray:
        return self.collect(self.step_async(sched_batch))[0]

    # ---- dp × pp ----------------------------------------------------------

    def step_async_dp(self, sched_batches):
        """One step over all DP replicas: each replica's private pipeline
        is launched back-to-back (async dispatch overlaps them on their
        disjoint device blocks); idle replicas simply don't run — no
        lockstep dummy batches, unlike the single-program dp runner."""
        assert len(sched_batches) == self.dp
        self._step_count += 1
        if self.model_cfg.use_mm:
            for b in sched_batches:
                if b is not None:
                    self._prepare_mm(b)
        self._apply_ssm_intents()
        self._apply_scale_resets()
        handles = []
        for r, b in enumerate(sched_batches):
            if b is None:
                handles.append(None)
                continue
            handles.append(self._run_pipeline(self.replicas[r], b,
                                              (self._step_count, r)))
        return handles

    def collect_dp(self, handles):
        rows, auxes = [], []
        for h in handles:
            if h is None:
                rows.append(np.zeros((0,), np.int32))
                auxes.append({})
                continue
            tokens, aux, n = h
            self._mb_inflight = max(0, self._mb_inflight - 1)
            with phase("wait"):
                rows.append(np.asarray(tokens)[:n])
            with phase("readback"):
                auxes.append(jax.tree.map(np.asarray, aux) if aux else {})
        for i in range(len(self.stages)):
            _M_STAGE_INFLIGHT.set(self._mb_inflight, stage=str(i))
        return rows, auxes
