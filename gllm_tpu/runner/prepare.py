"""Host-side batch building: ScheduledBatch → StepBatch of host arrays.

Mirrors the reference InputData.cal_input path
(/root/reference/gllm/input_data.py:338-533): flat token/position/slot
buffers, query-start offsets, per-seq kv lens and page tables, all padded to
*bucketed* static shapes so the jit cache stays small (the reference's
power-of-two CUDA-graph buckets → our compile-cache buckets).

Staging happens in numpy and stays there: ``build`` returns host arrays
and touches no jax. The runner then packs them (``batching.pack``: every
field but ``token_ids`` and ``mm_embeds`` into ONE int32 buffer with a
static layout) and places the buffer and the tokens, two transfers a
dispatch. Handing jax the StepBatch pytree as it is was one call but a
transfer per leaf (13 for a plain decode batch, 2.1 ms of a 7 ms
``gllm:build`` on a v5e host: PERF.md, PR 25). The base fill is vectorized
(flat scatters over ragged rows — the reference's vectorized-fill war
story, input_data.py:436-476); only rare per-item features (seeds, mm
splicing, prompt-logprob targets) loop, and only over the items that use
them. ~0.7 ms at the 32-seq decode bucket, amortized further by the fused
multi-step decode.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from gllm_tpu.batching import StepBatch
from gllm_tpu.config import EngineConfig
from gllm_tpu.ops.attention import AttentionMetadata
from gllm_tpu.ops.gdn import (GDN_CHUNK, gdn_chunk_slots,
                              gdn_chunks_needed)
from gllm_tpu.obs import metrics as obs
from gllm_tpu.ops.sampling import SamplingMetadata
from gllm_tpu.scheduler import ScheduledBatch
from gllm_tpu.utils import bucket_size, cdiv


# Which of its two rules a GDN layer ran each row through, and how full
# the chunked rule's packed layout was (docs/observability.md). Counted
# once per step, not per layer.
_M_GDN_ROWS = obs.counter(
    "gllm_gdn_rows_total",
    "rows of hybrid-model steps by the GDN rule they took: recurrent (one "
    "new token) or chunk (more)", ("path",))
_M_GDN_CHUNK_SLOTS = obs.counter(
    "gllm_gdn_chunk_slots_total",
    "token slots of the packed layouts the chunked rule computed over "
    "(chunks x tokens per chunk of each mixed step)")
_M_GDN_CHUNK_TOKENS = obs.counter(
    "gllm_gdn_chunk_tokens_total",
    "real tokens in those layouts (the new tokens of the rows that "
    "prefilled)")
# the same three for a model whose recurrent layers are Mamba-2
_M_MAMBA_ROWS = obs.counter(
    "gllm_mamba_rows_total",
    "rows of a Mamba-2 model's steps by the rule they took: recurrent "
    "(one new token) or chunk (more)", ("path",))
_M_MAMBA_CHUNK_SLOTS = obs.counter(
    "gllm_mamba_chunk_slots_total",
    "token slots of the packed layouts the Mamba-2 chunked rule computed "
    "over (chunks x tokens per chunk of each mixed step)")
_M_MAMBA_CHUNK_TOKENS = obs.counter(
    "gllm_mamba_chunk_tokens_total",
    "real tokens in those layouts (the new tokens of the rows that "
    "prefilled)")
# a gated short convolution (ops/short_conv.py) has one path for every
# row and no packed layout: its rows by the kind of row, and the tokens of
# the rows that held more than one
_M_SCONV_ROWS = obs.counter(
    "gllm_sconv_rows_total",
    "rows of a short-convolution model's steps through the operator, by "
    "kind: decode (one new token) or chunk (more)", ("kind",))
_M_SCONV_CHUNK_TOKENS = obs.counter(
    "gllm_sconv_chunk_tokens_total",
    "new tokens of the rows that went through the operator with more "
    "than one (prompt chunks)")
_SSM_COUNTERS = {
    "gdn": (_M_GDN_ROWS, _M_GDN_CHUNK_SLOTS, _M_GDN_CHUNK_TOKENS),
    "mamba": (_M_MAMBA_ROWS, _M_MAMBA_CHUNK_SLOTS, _M_MAMBA_CHUNK_TOKENS),
    "sconv": (_M_SCONV_ROWS, None, _M_SCONV_CHUNK_TOKENS),
}


class BatchBuilder:
    def __init__(self, config: EngineConfig, page_size: int,
                 vocab_size: int = 0, hidden_size: int = 0,
                 use_mm: bool = False, use_ssm: bool = False,
                 mm_embed_dim: int = 0, seq_slots: bool = False,
                 ssm_chunk: int = GDN_CHUNK, ssm_kind: str = "gdn"):
        self.config = config
        self.page_size = page_size
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        # visual-row width: hidden_size, or (1+n_deepstack)*hidden for
        # Qwen3-VL stacked features
        self.mm_embed_dim = mm_embed_dim or hidden_size
        self.use_mm = use_mm
        self.use_ssm = use_ssm
        # the recurrent layers' chunk (ModelConfig.ssm_chunk) sizes a
        # mixed step's packed layout (0: the slot state has no chunked
        # rule and no layout, ModelConfig.ssm_chunked_rule); their kind
        # names the counters
        self.ssm_chunk = ssm_chunk
        self._m_rows, self._m_slots, self._m_tokens = _SSM_COUNTERS[ssm_kind]
        # a per-sequence slot rides the batch (``ssm_slots``): recurrent
        # state, or a windowed layer's ring
        self.use_slots = use_ssm or seq_slots
        sc = config.scheduler
        # Upper bounds for the shape buckets. Speculative decoding adds up
        # to spec_k draft rows per decode seq.
        spec_rows = (config.spec_k if config.spec_decode else 0)
        self.max_tokens = (sc.max_prefill_tokens
                           + sc.max_decode_seqs * (1 + spec_rows))
        self.max_seqs = min(config.max_num_seqs,
                            sc.max_decode_seqs + sc.max_prefill_tokens)
        self.max_pages_per_seq = config.max_pages_per_seq
        # the buckets' floors (--min-row-bucket, --min-token-bucket,
        # --min-page-bucket); a step has a token for every row
        self.min_row_bucket = min(sc.min_row_bucket, self.max_seqs)
        self.min_token_bucket = max(sc.min_token_bucket, self.min_row_bucket)
        self.min_page_bucket = sc.min_page_bucket

    def shape_signature(self, batch: ScheduledBatch) -> Tuple[int, int, int,
                                                              int]:
        """(T_bucket, S_bucket, max_q_len, pages_bucket) — the compile key.

        pages_bucket bounds the page-table width (and thus the attention
        gather extent) by the *live* maximum context in this batch instead
        of max_model_len — decode cost tracks actual sequence lengths.
        """
        s = bucket_size(batch.num_seqs, self.min_row_bucket, self.max_seqs)
        rows = [it.num_new_tokens + len(it.draft_tokens)
                for it in batch.items]
        max_q = max(rows)
        if max_q == 1:
            t, q = s, 1          # pure decode: one token per seq
        else:
            t = bucket_size(sum(rows), self.min_token_bucket,
                            self.max_tokens)
            if self.use_ssm and self.ssm_chunk:
                # the rows that prefill run the chunked rule in a packed
                # layout sized by the token bucket (models/hybrid.py): take
                # the first bucket whose layout holds them (many short
                # prompts in one step can need the next one). The largest
                # holds every step the scheduler forms
                # (ops/gdn.gdn_chunk_rows_cap)
                while True:
                    n, c = gdn_chunk_slots(t, s, self.ssm_chunk)
                    if gdn_chunks_needed(rows, c) <= n:
                        break
                    if t >= self.max_tokens:
                        raise ValueError(
                            f"{sum(r > 1 for r in rows)} rows with more "
                            f"than one new token need "
                            f"{gdn_chunks_needed(rows, c)} chunks, the "
                            f"largest layout ({t} tokens, {s} rows) has "
                            f"{n}: the scheduler's cap was bypassed")
                    t = min(2 * t, self.max_tokens)
            q = t
        # a seq's table can be LONGER than this step needs (a previous
        # speculative step allocated for drafts that were then rejected) —
        # the scatter writes whole table rows, so the bucket must cover
        # the real lengths
        max_pages = max(
            max(cdiv(it.computed_before + it.num_new_tokens
                     + len(it.draft_tokens), self.page_size),
                len(it.seq.page_table))
            for it in batch.items)
        p = bucket_size(max_pages, self.min_page_bucket,
                        self.max_pages_per_seq)
        return t, s, q, p

    def empty(self, signature, force_extras=frozenset(),
              force_bias_len=None):
        """An all-padding StepBatch of the given signature (idle DP
        replicas run these so every replica contributes the same jit
        signature — the TPU analogue of the reference's idle-replica dummy
        batches, worker.py:750-829). ``force_extras`` must match the live
        replicas' optional-field structure."""
        t_pad, s_pad, _, p_pad = signature
        bias_len = force_bias_len or 8
        return StepBatch(
            token_ids=np.zeros(t_pad, np.int32),
            positions=np.zeros(t_pad, np.int32),
            slot_mapping=np.zeros(t_pad, np.int32),
            logits_indices=np.zeros(s_pad, np.int32),
            attn=AttentionMetadata(
                cu_q_lens=np.zeros(s_pad + 1, np.int32),
                kv_lens=np.zeros(s_pad, np.int32),
                page_table=np.zeros((s_pad, p_pad), np.int32),
                num_seqs=np.asarray(0, np.int32)),
            sampling=SamplingMetadata(
                temperature=np.zeros(s_pad, np.float32),
                top_p=np.ones(s_pad, np.float32),
                top_k=np.full((s_pad,), -1, np.int32),
                repetition_penalty=np.ones(s_pad, np.float32),
                step_key=None,       # made in the program (unpack)
                presence_penalty=(np.zeros(s_pad, np.float32)
                                  if "penalties" in force_extras else None),
                frequency_penalty=(np.zeros(s_pad, np.float32)
                                   if "penalties" in force_extras
                                   else None),
                seed=(np.full((s_pad,), -1, np.int32)
                      if "seed" in force_extras else None),
                out_step=(np.zeros(s_pad, np.int32)
                          if "seed" in force_extras else None),
                min_p=np.zeros(s_pad, np.float32),
                bias_ids=(np.zeros((s_pad, bias_len), np.int32)
                          if "bias" in force_extras else None),
                bias_vals=(np.zeros((s_pad, bias_len), np.float32)
                           if "bias" in force_extras else None)),
            spec_rows=(np.zeros(
                (s_pad, self.config.spec_k + 1), np.int32)
                if "spec" in force_extras else None),
            spec_drafts=(np.full(
                (s_pad, self.config.spec_k), -1, np.int32)
                if "spec" in force_extras else None),
            plp_targets=(np.zeros(t_pad, np.int32)
                         if "plp" in force_extras else None),
            ssm_slots=(np.zeros(s_pad, np.int32) if self.use_slots
                       else None),
            mrope_positions=(np.zeros((3, t_pad), np.int32)
                             if self.use_mm else None),
            # mm_mask rides with mm_embeds (build's structure): both exist
            # iff a replica this step carries visual rows ("mm" forced)
            mm_mask=(np.zeros(t_pad, bool)
                     if self.use_mm and "mm" in force_extras else None),
            mm_embeds=(np.zeros((t_pad, self.mm_embed_dim), np.float32)
                       if self.use_mm and "mm" in force_extras else None),
        )

    @staticmethod
    def host_row_mask(host_rows, s_bucket: int) -> np.ndarray:
        """[S_bucket] bool slot map for chained-step token splicing: True
        rows (sequences that JOINED the persistent chain through a vacant
        slot) keep the host-built token value, False rows take the
        previous step's on-device sampled token. Padding rows stay False
        — their device token is garbage either way and their slot maps
        to the dummy page."""
        mask = np.zeros(s_bucket, bool)
        mask[np.asarray(host_rows, np.int64)] = True
        return mask

    def stop_sets(self, items, s_bucket: int, eos_token_ids,
                  absolute: bool = False):
        """On-device finish detection inputs for a fused multi-step
        block: ([S, E] padded per-row EOS/stop-token-id sets, [S] arming
        sub-step) for ``SamplingMetadata.stop_ids`` / ``stop_from``.

        ``items`` are the chain's FIRST batch items (their
        computed_before anchors the output-token indexing: the token
        committed by sub-step k is output number
        ``computed_before + k + 2 - prompt_len``, so min_tokens arms the
        check from sub-step ``min_tokens + prompt_len - computed_before
        - 2``). The id bucket E is pow2 (min 8) so the jit signature
        stays bounded; -1 padding never matches a sampled id. Returns
        (None, None) when no row carries any stop id (e.g. ignore_eos
        benchmarks) — the device program then skips the compare and
        on-device deaths come only from the active_until length bound.

        ``absolute=True`` (fused on-device speculation, whose carried
        frontier makes sub-step indices meaningless across blocks):
        ``stop_from`` becomes the ABSOLUTE position threshold
        ``min_tokens + prompt_len - 2`` — the device arms the check when
        the emitted token's feed position ``pos + j`` reaches it, which
        is the same inequality the relative form encodes (legacy:
        sub-step k at position cb + k armed when k >= mt + prompt - cb
        - 2 ⟺ cb + k >= mt + prompt - 2). Rows without min_tokens get
        a large negative threshold (always armed).
        """
        from gllm_tpu.sequence import HOLE_SEQ_ID
        from gllm_tpu.utils import next_pow2
        # HOLE rows (persistent-slot mode) are dead for the whole block
        # (alive count 0) — they must never contribute ids, or a finish
        # in an all-ignore_eos workload would widen the id bucket and
        # force a mid-run recompile
        sets = [([] if it.seq.seq_id == HOLE_SEQ_ID
                 else it.seq.device_stop_ids(eos_token_ids))
                for it in items]
        if not any(sets):
            return None, None
        E = max(8, next_pow2(max(len(s) for s in sets)))
        stop_ids = np.full((s_bucket, E), -1, np.int32)
        stop_from = np.full(s_bucket, -(1 << 30) if absolute else 0,
                            np.int32)
        for i, (it, ids) in enumerate(zip(items, sets)):
            stop_ids[i, :len(ids)] = ids
            mt = it.seq.sampling_params.min_tokens
            if absolute:
                stop_from[i] = (mt + it.seq.prompt_len - 2 if mt
                                else -(1 << 30))
            elif mt:
                stop_from[i] = max(0, mt + it.seq.prompt_len
                                   - it.computed_before - 2)
        return stop_ids, stop_from

    @staticmethod
    def penalty_len_bucket(lens) -> int:
        """Shared penalty id-list length bucket (build + dp wrapper must
        agree on the jit-signature L)."""
        from gllm_tpu.utils import next_pow2
        return max(16, next_pow2(max(lens))) if lens else 16

    @staticmethod
    def bias_len_bucket(ns) -> int:
        """Shared logit_bias entry-count bucket (build + dp wrapper must
        agree on the jit-signature B)."""
        from gllm_tpu.utils import next_pow2
        return max(8, next_pow2(max(ns))) if ns else 8

    @staticmethod
    def batch_extras(batch: ScheduledBatch) -> frozenset:
        """Which optional StepBatch fields this batch populates — DP
        replicas must agree on the union so stacked pytrees match."""
        extras = set()
        for it in batch.items:
            sp = it.seq.sampling_params
            if sp.seed is not None:
                extras.add("seed")
            if (sp.repetition_penalty != 1.0 or sp.presence_penalty != 0.0
                    or sp.frequency_penalty != 0.0):
                extras.add("penalties")
            if sp.logit_bias:
                extras.add("bias")
            if (sp.prompt_logprobs is not None
                    and it.computed_before < it.seq.prompt_len):
                extras.add("plp")
            mm = getattr(it.seq, "mm", None)
            if (mm is not None
                    and it.computed_before + it.num_new_tokens
                    <= it.seq.prompt_len
                    and (mm.vis_index[it.computed_before:
                                      it.computed_before
                                      + it.num_new_tokens] >= 0).any()):
                extras.add("mm")
            if it.draft_tokens:
                extras.add("spec")
        return frozenset(extras)

    def build(self, batch: ScheduledBatch,
              force_signature=None, force_extras=frozenset(),
              force_penalty_len=None, force_bias_len=None):
        """Returns (StepBatch, max_q_len, token_counts_or_None), all of
        host numpy leaves: the caller packs and places them
        (``batching.pack``, ``ModelRunner._put``), alone, stacked over dp
        replicas or once per pipeline stage. ``sampling.step_key`` is
        None here: the step program folds it from the dispatch's ordinal
        (``batching.unpack``).

        ``force_signature`` overrides the computed shape buckets and
        ``force_extras`` forces optional fields to exist (DP replicas must
        agree on one signature + structure per step)."""
        t_pad, s_pad, max_q, p_pad = (force_signature
                                      or self.shape_signature(batch))
        page = self.page_size
        force_seeded = "seed" in force_extras
        force_penalties = "penalties" in force_extras
        force_plp = "plp" in force_extras

        tokens = np.zeros(t_pad, np.int32)
        positions = np.zeros(t_pad, np.int32)
        slots = np.zeros(t_pad, np.int32)          # padding → dummy page slot
        cu = np.zeros(s_pad + 1, np.int32)
        kv_lens = np.zeros(s_pad, np.int32)
        page_table = np.zeros((s_pad, p_pad), np.int32)
        logits_idx = np.zeros(s_pad, np.int32)
        temperature = np.zeros(s_pad, np.float32)
        top_p = np.ones(s_pad, np.float32)
        top_k = np.full(s_pad, -1, np.int32)
        min_p = np.zeros(s_pad, np.float32)
        rep_penalty = np.ones(s_pad, np.float32)
        seeds = np.full(s_pad, -1, np.int32)
        out_steps = np.zeros(s_pad, np.int32)
        any_seeded = False
        # VL batches always carry mrope; the dense [T, H] visual-row
        # buffer is allocated lazily on first visual row so text-only /
        # decode steps (the common case) skip the host→device transfer
        # entirely (one extra jit variant).
        mm_embeds = None
        if self.use_mm:
            mrope = np.zeros((3, t_pad), np.int32)
            mm_mask = np.zeros(t_pad, bool)
            if "mm" in force_extras:
                # DP replicas must agree on the visual-row buffer's
                # presence even when this replica's batch has none
                mm_embeds = np.zeros((t_pad, self.mm_embed_dim),
                                     np.float32)
        if self.use_slots:
            ssm_slots = np.zeros(s_pad, np.int32)   # padding → dummy slot 0

        want_plp = force_plp or any(
            it.seq.sampling_params.prompt_logprobs is not None
            and it.computed_before < it.seq.prompt_len
            for it in batch.items)
        plp_targets = np.zeros(t_pad, np.int32) if want_plp else None

        # Vectorized base fill: the per-item python loop cost ~8 ms at a
        # 256-seq decode bucket (numpy-op overhead × 15 ops × items); the
        # flat-scatter form is ~C-speed. Rare per-item features (seeds,
        # mm, plp) fall to targeted loops over only the items that use
        # them. Semantics byte-identical (engine identity tests).
        items = batch.items
        K = len(items)
        # Host-tier invariant (gllm_tpu/kvswap): a seq that reaches the
        # builder must have had its swap-in recorded at admission — its
        # restore intent drains before this batch's forward, so building
        # rows over still-host-resident KV here would read garbage.
        assert not any(it.seq.swap_host_pages for it in items), \
            "SWAPPED seq scheduled without a recorded swap-in"
        # speculative drafts add verify rows after each item's committed
        # chunk; everything downstream (positions, slots, kv_lens, causal
        # attention) treats them as ordinary chunk rows
        ns = np.fromiter(
            (it.num_new_tokens + len(it.draft_tokens) for it in items),
            np.int64, count=K)
        befores = np.fromiter((it.computed_before for it in items),
                              np.int64, count=K)
        ends = np.cumsum(ns)
        offs = ends - ns
        total = int(ends[-1]) if K else 0
        cu[1:K + 1] = ends
        cu[K + 1:] = total
        kv_lens[:K] = befores + ns
        logits_idx[:K] = ends - 1

        rows = np.repeat(np.arange(K), ns)            # item idx per token
        pos = (np.arange(total) - np.repeat(offs, ns)
               + np.repeat(befores, ns))              # absolute positions
        positions[:total] = pos

        # ragged page-table rows → one flat scatter; the np form of each
        # row is cached on the Sequence (rows only change on page alloc,
        # every page_size-th decode step)
        def _pt_arr(seq):
            pt = seq.page_table
            c = getattr(seq, "_pt_np", None)
            if c is None or len(c) != len(pt):
                c = np.asarray(pt, np.int32)
                seq._pt_np = c
            return c

        pt_lens = np.fromiter((len(it.seq.page_table) for it in items),
                              np.int64, count=K)
        if K:
            flat_pt = np.concatenate([_pt_arr(it.seq) for it in items])
            pt_rows = np.repeat(np.arange(K), pt_lens)
            pt_cols = (np.arange(int(pt_lens.sum()))
                       - np.repeat(np.cumsum(pt_lens) - pt_lens, pt_lens))
            page_table[pt_rows, pt_cols] = flat_pt
        slots[:total] = (page_table[rows, pos // page] * page
                         + pos % page)

        # token values; chained overlap-decode rows have no host-side
        # value yet (it lives on device; the runner splices it in) → 0s
        def _tok_vals(it):
            tid = it.seq.token_ids
            b, n = it.computed_before, it.num_new_tokens
            v = tid[b:b + n]
            if len(v) != n:
                v = list(v) + [0] * (n - len(v))
            if it.draft_tokens:
                v = list(v) + list(it.draft_tokens)
            return v

        tokens[:total] = np.fromiter(
            (t for it in items for t in _tok_vals(it)), np.int32,
            count=total)

        sps = [it.seq.sampling_params for it in items]
        temperature[:K] = np.fromiter((sp.temperature for sp in sps),
                                      np.float32, count=K)
        top_p[:K] = np.fromiter((sp.top_p for sp in sps), np.float32,
                                count=K)
        top_k[:K] = np.fromiter((sp.top_k for sp in sps), np.int32,
                                count=K)
        min_p[:K] = np.fromiter((sp.min_p for sp in sps), np.float32,
                                count=K)
        rep_penalty[:K] = np.fromiter((sp.repetition_penalty for sp in sps),
                                      np.float32, count=K)
        if self.use_slots:
            ssm_slots[:K] = np.fromiter(
                (getattr(it.seq, "ssm_slot", None) or 0 for it in items),
                np.int32, count=K)
        if self.use_ssm and not self.ssm_chunk:
            # no chunked rule, no layout: rows by kind, and their tokens
            n_chunk = int((ns > 1).sum())
            self._m_rows.inc(K - n_chunk, kind="decode")
            if n_chunk:
                self._m_rows.inc(n_chunk, kind="chunk")
                self._m_tokens.inc(int(ns[ns > 1].sum()))
        elif self.use_ssm:
            n_chunk = int((ns > 1).sum())
            self._m_rows.inc(K - n_chunk, path="recurrent")
            if n_chunk:
                n, c = gdn_chunk_slots(t_pad, s_pad, self.ssm_chunk)
                self._m_rows.inc(n_chunk, path="chunk")
                self._m_slots.inc(n * c)
                self._m_tokens.inc(int(ns[ns > 1].sum()))

        for i, it in enumerate(items):
            sp = sps[i]
            if sp.seed is not None:
                any_seeded = True
                seeds[i] = sp.seed
                # index of the output token this step will sample
                out_steps[i] = int(befores[i] + ns[i]) - it.seq.prompt_len
            if want_plp and sp.prompt_logprobs is not None:
                seq, b, n = it.seq, int(befores[i]), int(ns[i])
                off = int(offs[i])
                # row at position p scores prompt token p+1
                nxt = np.asarray(
                    seq.token_ids[b + 1:min(b + n + 1, seq.prompt_len)],
                    np.int32)
                plp_targets[off:off + len(nxt)] = nxt

        if self.use_mm:
            # default: text rows use 1-D positions on all three axes
            mrope[:, :total] = pos[None, :]
            for i, it in enumerate(items):
                mm = it.seq.mm
                if mm is None:
                    continue
                seq, b, n = it.seq, int(befores[i]), int(ns[i])
                off = int(offs[i])
                p_i = pos[off:off + n]
                if b + n <= seq.prompt_len:
                    # prefill chunk: precomputed 3-D prompt positions +
                    # visual-row splicing
                    mrope[:, off:off + n] = mm.mrope_positions[:, b:b + n]
                    vis = mm.vis_index[b:b + n]
                    sel = vis >= 0
                    if sel.any():
                        if mm_embeds is None:
                            mm_embeds = np.zeros(
                                (t_pad, self.mm_embed_dim), np.float32)
                        mm_mask[off:off + n] = sel
                        mm_embeds[off:off + n][sel] = \
                            mm.vis_embeds[vis[sel]]
                else:
                    # decode: extrapolate all three axes with the prompt's
                    # mrope delta (reference get_next_input_positions)
                    mrope[:, off:off + n] = (p_i + mm.mrope_delta)[None, :]

        # Repetition/presence/frequency penalties need per-token occurrence
        # counts (reference keeps a persistent GPU mask pool,
        # memory_manager.py:723-828; we build counts host-side only for
        # batches that actually use a penalty).
        token_counts = None
        pres = freq = None

        def _uses_penalty(sp):
            return (sp.repetition_penalty != 1.0
                    or sp.presence_penalty != 0.0
                    or sp.frequency_penalty != 0.0)

        if self.vocab_size and (force_penalties or any(
                _uses_penalty(it.seq.sampling_params)
                for it in batch.items)):
            from gllm_tpu.ops.sampling import PenaltyTokens
            from gllm_tpu.utils import next_pow2
            lens = [len(it.seq.token_ids) for it in batch.items
                    if _uses_penalty(it.seq.sampling_params)]
            # DP replicas must agree on L (the stacked pytrees share one
            # jit signature) — the dp wrapper passes the cross-replica max
            L = force_penalty_len or self.penalty_len_bucket(lens)
            ids = np.zeros((s_pad, L), np.int32)
            mask = np.zeros((s_pad, L), bool)
            pres = np.zeros(s_pad, np.float32)
            freq = np.zeros(s_pad, np.float32)
            for i, it in enumerate(batch.items):
                sp = it.seq.sampling_params
                if _uses_penalty(sp):
                    row = np.asarray(it.seq.token_ids, np.int64)
                    # visual placeholder ids can sit past the LM vocab
                    # (Kimi's media pad) — they never appear in logits
                    row = row[row < self.vocab_size][:L]
                    ids[i, :len(row)] = row
                    mask[i, :len(row)] = True
                    pres[i] = sp.presence_penalty
                    freq[i] = sp.frequency_penalty
            token_counts = PenaltyTokens(ids, mask)

        # OpenAI logit_bias: sparse per-seq (id, bias) pairs, padded to a
        # shared bucket B (reference protocol.py logit_bias → sampler add).
        bias_ids = bias_vals = None
        if "bias" in force_extras or any(sp.logit_bias for sp in sps):
            B = force_bias_len or self.bias_len_bucket(
                [len(sp.logit_bias) for sp in sps if sp.logit_bias])
            bias_ids = np.zeros((s_pad, B), np.int32)
            bias_vals = np.zeros((s_pad, B), np.float32)
            for i, sp in enumerate(sps):
                if sp.logit_bias:
                    # ids past the bucket (or the LM vocab) are dropped;
                    # value 0 padding keeps the scatter-add a no-op
                    pairs = [(t, b) for t, b in sp.logit_bias.items()
                             if t < (self.vocab_size or 1 << 30)][:B]
                    for j, (t, b) in enumerate(pairs):
                        bias_ids[i, j] = t
                        bias_vals[i, j] = b

        spec_rows_arr = spec_drafts_arr = None
        if any(it.draft_tokens for it in items) or "spec" in force_extras:
            kmax = self.config.spec_k
            spec_rows = np.zeros((s_pad, kmax + 1), np.int32)
            spec_drafts = np.full((s_pad, kmax), -1, np.int32)
            for i, it in enumerate(items):
                d = len(it.draft_tokens)
                # verify rows: the item's LAST committed row + its draft
                # rows (row r predicts the token at r's position + 1);
                # no-draft / padded entries point at row 0 with -1 drafts
                # (never accepted, argmax there unused)
                if d:
                    base = int(offs[i]) + it.num_new_tokens - 1
                    spec_rows[i, :d + 1] = base + np.arange(d + 1)
                    spec_drafts[i, :d] = it.draft_tokens
            spec_rows_arr = spec_rows
            spec_drafts_arr = spec_drafts

        step_batch = StepBatch(
            token_ids=tokens,
            positions=positions,
            slot_mapping=slots,
            logits_indices=logits_idx,
            attn=AttentionMetadata(
                cu_q_lens=cu,
                kv_lens=kv_lens,
                page_table=page_table,
                num_seqs=np.asarray(batch.num_seqs, np.int32)),
            sampling=SamplingMetadata(
                temperature=temperature,
                top_p=top_p,
                top_k=top_k,
                repetition_penalty=rep_penalty,
                step_key=None,
                presence_penalty=pres,
                frequency_penalty=freq,
                # None keeps the fused single-draw gumbel path (the common
                # all-unseeded case); per-row keys only when a request
                # actually asked for a seed (one extra jit variant).
                seed=(seeds if any_seeded or force_seeded else None),
                out_step=(out_steps
                          if any_seeded or force_seeded else None),
                min_p=min_p,
                bias_ids=bias_ids,
                bias_vals=bias_vals),
            mrope_positions=mrope if self.use_mm else None,
            mm_embeds=mm_embeds,
            mm_mask=(mm_mask
                     if self.use_mm and mm_embeds is not None else None),
            ssm_slots=ssm_slots if self.use_slots else None,
            plp_targets=plp_targets,
            spec_rows=spec_rows_arr,
            spec_drafts=spec_drafts_arr,
        )
        return step_batch, max_q, token_counts
