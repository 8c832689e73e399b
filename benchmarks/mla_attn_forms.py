"""Dense latent attention on the chip, kernel by kernel: which blocks, and
which form for a step with many query tokens.

    python benchmarks/mla_attn_forms.py [--cpu-rehearsal] [--heads 64]
    python benchmarks/mla_attn_forms.py --chosen-rows [--cpu-rehearsal]

through the chip tool (one chip, ~6 min). At A.X-K1's geometry (64 query
heads over one latent row of 512 + 64 lanes stored as 640, values the first
512) it times, one layer's attention call each, by the host's clock around
``iters`` back-to-back calls that end in ``block_until_ready``:

- ``ragged``: ``ragged_paged_attention`` in the ABSORBED form (queries
  folded through W_uk into latent space, 2 x 640 + 2 x 512 FLOP a head and
  query-key pair) for a 2048-token chunk over 8192 and 16384 cached rows,
  and for a cell's mixed step (31 decoding rows at 12.6 k of context and a
  320-token question behind a 12.6 k document), over ``q_rows`` x
  ``kv_block`` (``ops/pallas/tuning.ragged_blocks``); the mixed step twice:
  the ragged kernel alone over all 32 sequences (as every mixed step ran
  before PR 38) and as the dispatch serves it since
  (``ops/attention._mixed_step_attention``: the 31 riding rows by the
  decode kernel at the table's ``decode_mqa`` blocks, the question by the
  ragged kernel at the swept pair);
- ``decode``: ``paged_decode_attention`` for 32 rows at 12.6 k of context;
- ``decompressed``: the same chunks with keys and values EXPANDED per head
  (c_kv W_uk -> [ctx, 64, 128] beside the shared rotary part, c_kv W_uv ->
  [ctx, 64, 128]; 2 x 192 + 2 x 128 FLOP a pair and 2 x 512 x 64 x 256 a
  context row to expand), in plain XLA, a group of heads at a time.

Each line gives the call's milliseconds, the FLOP it has to do (causal:
what the mask leaves) and that as a share of the chip's peak
(perfbench/peaks.json). Results: ``chiprun_out/mla_attn_forms.json``.

``--chosen-rows`` times, instead of all that (one chip, ~3 min), the
decoding rows of a selected-attention layer at dots3-note-prev's geometry
(PR 43): 64 rows of 128 heads over rows of 640 lanes, contexts drawn
4096-9216 under a page table of 592 pages, each row attending 2048
positions drawn from those it sees. ``chosen_rows_kernel``:
``paged_decode_attention`` under the choice's mask over kv_block x group
(``decode_mqa_chosen`` in ops/pallas/tables.json), with the same call
without the mask beside it; ``chosen_rows_xla``: what the kernel took the
place of (``models/deepseek._attend`` over the rows' whole pages), whose
result the kernel's is compared with. Results:
``chiprun_out/dsa_rows_forms.json``.
"""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--chosen-rows", action="store_true")
    args = ap.parse_args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from gllm_tpu.ops import attention
    from gllm_tpu.ops.pallas import tuning
    from gllm_tpu.ops.pallas.decode_attention import paged_decode_attention
    from gllm_tpu.ops.pallas.ragged_attention import ragged_paged_attention
    from gllm_tpu.ops.pallas.tuning import get as tuned
    from gllm_tpu.utils import tpu_compiler_options

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.cpu_rehearsal:
        sys.exit("no TPU here: run through the chip tool, or pass "
                 "--cpu-rehearsal")
    small = not on_chip
    H = 4 if small else args.heads
    lora, rope, nope, vd = (32, 8, 16, 16) if small else (512, 64, 128, 128)
    width = lora + rope + (-(lora + rope)) % (8 if small else 128)
    page = 16
    chunk = 64 if small else 2048
    contexts = (128, 256) if small else (8192, 16384)
    doc, question, rows = ((96, 24, 4) if small else (12600, 320, 32))
    dtype = jnp.float32 if small else jnp.bfloat16
    scale = (nope + rope) ** -0.5 * 1.8134
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    kind = jax.devices()[0].device_kind
    peak = peaks.get(kind, {}).get("flops_per_s") if on_chip else None
    opts = tpu_compiler_options() if on_chip else None
    key = jax.random.key(0)

    def pool_for(n_seqs, ctx):
        """A pool that holds ``n_seqs`` sequences of ``ctx`` tokens, and
        the page table that lays them out one after the other."""
        per = -(-ctx // page)
        pt = 1 + np.arange(n_seqs * per, dtype=np.int32).reshape(n_seqs, per)
        pool = jax.random.normal(key, (n_seqs * per + 1, page, 1, width),
                                 jnp.float32).astype(dtype)
        return pool, jnp.asarray(pt)

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))         # compile + first run
        t0 = time.monotonic()
        for _ in range(args.iters):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.monotonic() - t0) / args.iters * 1e3

    results = []

    def save(name, **kw):
        out_dir = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump({"device": kind, "results": results, **kw}, f,
                      indent=1)

    def note(what, ms, flop, **kw):
        line = dict(what=what, ms=round(ms, 3), gflop=round(flop / 1e9, 1),
                    **kw)
        if peak:
            line["peak_pct"] = round(100 * flop / (ms * 1e-3) / peak, 1)
        results.append(line)
        print(json.dumps(line), flush=True)

    pair_abs = 2 * width + 2 * lora         # as the kernel computes it
    pair_dec = 2 * (nope + rope) + 2 * vd

    if args.chosen_rows:
        chosen_rows(args, small, dtype, width, lora, scale, pair_abs,
                    peaks.get(kind, {}).get("bytes_per_s"), opts, timed,
                    note)
        return save("dsa_rows_forms.json")

    def causal_pairs(q_len, ctx):
        """(query, key) pairs a chunk of ``q_len`` at the end of a context
        of ``ctx`` + ``q_len`` attends."""
        return q_len * ctx + q_len * (q_len + 1) // 2

    # ---- the absorbed form on the ragged kernel -------------------------
    grid = ([(64, 32)] if small else
            [(r, b) for r in (512, 1024, 2048) for b in (128, 256, 512)])

    def split_call(q, k, cu, kl, pt, qb, kvb):
        """The mixed step as the dispatch serves it, the ragged kernel at
        ``qb`` x ``kvb`` (the decode kernel's blocks are the table's)."""
        tuning.ragged_blocks = lambda *_, **__: {"q_block": qb,
                                                  "kv_block": kvb}
        return attention._mixed_step_attention(
            q, k, None, attention.AttentionMetadata(
                cu, kl, pt, jnp.asarray(kl.shape[0], jnp.int32)),
            None, None, scale=scale, interpret=small, v_dim=lora)

    def sweep_ragged(what, flop, q, pool, cu, kl, pt, split=False, **kw):
        """One ragged call's time at every block pair of the grid
        (``split``: the call the dispatch makes of a mixed step)."""
        for q_rows, kvb in grid:
            call = split_call if split else (
                lambda q, k, cu, kl, pt, qb, kvb: ragged_paged_attention(
                    q, k, None, cu, kl, pt, scale=scale, q_block=qb,
                    kv_block=kvb, v_dim=lora, interpret=small))
            fn = jax.jit(functools.partial(call, qb=max(8, q_rows // H),
                                           kvb=kvb), compiler_options=opts)
            try:
                ms = timed(fn, q, pool, cu, kl, pt)
            except Exception as e:      # Mosaic refusing a block pair
                print(f"{what} {kw} q_rows={q_rows} kv_block={kvb}: "
                      f"{str(e)[:200]}", flush=True)
                continue
            note(what, ms, flop, q_rows=q_rows, kv_block=kvb, **kw)

    for ctx in contexts:
        pool, pt = pool_for(1, ctx + chunk)
        q = jax.random.normal(key, (chunk, H, width), jnp.float32
                              ).astype(dtype)
        sweep_ragged("ragged_absorbed_chunk",
                     causal_pairs(chunk, ctx) * H * pair_abs, q, pool,
                     jnp.asarray([0, chunk], jnp.int32),
                     jnp.asarray([ctx + chunk], jnp.int32), pt, ctx=ctx)

    # a cell's mixed step: rows - 1 decoding rows and one question
    pool, pt = pool_for(rows, doc + question)
    T = rows - 1 + question
    q = jax.random.normal(key, (T, H, width), jnp.float32).astype(dtype)
    # the ragged kernel alone over all the sequences (every mixed step
    # before PR 38), then the step as the dispatch splits it
    for what, split in (("ragged_absorbed_mixed_step", False),
                        ("split_mixed_step", True)):
        sweep_ragged(
            what,
            ((rows - 1) * doc + causal_pairs(question, doc)) * H * pair_abs,
            q, pool, jnp.asarray(list(range(rows)) + [T], jnp.int32),
            jnp.asarray([doc] * (rows - 1) + [doc + question], jnp.int32),
            pt, split=split, rows=rows, doc=doc, question=question)

    # ---- the decode kernel ----------------------------------------------
    cfg = tuned("decode")
    qd = jax.random.normal(key, (rows, H, width), jnp.float32).astype(dtype)
    kld = jnp.asarray([doc] * rows, jnp.int32)
    for kvb, grp in ([(32, 2)] if small else
                     [(cfg["kv_block"], int(cfg.get("group", 1))),
                      (512, 4), (256, 8), (128, 4)]):
        fn = jax.jit(lambda q, k, kl, pt, kvb=kvb, grp=grp:
                     paged_decode_attention(
            q, k, None, kl, pt, scale=scale, v_dim=lora, kv_block=kvb,
            group_size=grp, interpret=small), compiler_options=opts)
        try:
            ms = timed(fn, qd, pool, kld, pt)
        except Exception as e:
            print(f"decode kv_block={kvb} group={grp}: {str(e)[:200]}",
                  flush=True)
            continue
        nbytes = rows * doc * width * jnp.dtype(dtype).itemsize
        note("decode_kernel", ms, rows * doc * H * pair_abs, rows=rows,
             ctx=doc, kv_block=kvb, group=grp,
             hbm_pct=(round(100 * nbytes / (ms * 1e-3)
                            / peaks[kind]["bytes_per_s"], 1)
                      if on_chip else None))

    # ---- the decompressed form in plain XLA ------------------------------
    w_uk = (jax.random.normal(key, (H, nope, lora), jnp.float32)
            * lora ** -0.5).astype(dtype)
    w_uv = (jax.random.normal(key, (H, lora, vd), jnp.float32)
            * lora ** -0.5).astype(dtype)
    hg = min(8, H)

    def decompressed(qn, qr, rows_, w_uk, w_uv, ctx):
        """qn [T, H, nope], qr [T, H, rope], rows_ [ctx + T, width] (a
        sequence's latent rows, gathered): expand, then attend a group
        of heads at a time."""
        c, kr = rows_[:, :lora], rows_[:, lora:lora + rope]
        kn = jnp.einsum("sl,hnl->hsn", c, w_uk)
        v = jnp.einsum("sl,hlv->hsv", c, w_uv)
        kp = jnp.arange(rows_.shape[0])
        qp = ctx + jnp.arange(qn.shape[0])
        mask = kp[None, :] <= qp[:, None]

        def group(h0):
            sl = lambda a, ax: jax.lax.dynamic_slice_in_dim(a, h0, hg, ax)
            s = (jnp.einsum("thn,hsn->hts", sl(qn, 1), sl(kn, 0),
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("thr,sr->hts", sl(qr, 1), kr,
                              preferred_element_type=jnp.float32)) * scale
            p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hts,hsv->htv", p.astype(v.dtype), sl(v, 0),
                              preferred_element_type=jnp.float32)
        out = jax.lax.map(group, jnp.arange(0, H, hg))
        return out.reshape(H, qn.shape[0], vd).astype(qn.dtype)

    for ctx in contexts:
        rows_ = jax.random.normal(key, (ctx + chunk, width), jnp.float32
                                  ).astype(dtype)
        qn = jax.random.normal(key, (chunk, H, nope), jnp.float32
                               ).astype(dtype)
        qr = jax.random.normal(key, (chunk, H, rope), jnp.float32
                               ).astype(dtype)
        fn = jax.jit(decompressed, static_argnums=(5,),
                     compiler_options=opts)
        try:
            ms = timed(fn, qn, qr, rows_, w_uk, w_uv, ctx)
        except Exception as e:
            print(f"decompressed ctx={ctx}: {str(e)[:300]}", flush=True)
            continue
        expand = (ctx + chunk) * 2 * lora * H * (nope + vd)
        note("decompressed_xla_chunk", ms,
             causal_pairs(chunk, ctx) * H * pair_dec + expand, ctx=ctx,
             expand_gflop=round(expand / 1e9, 1))

    save("mla_attn_forms.json", heads=H)


def chosen_rows(args, small, dtype, width, lora, scale, pair_abs, hbm,
                opts, timed, note):
    """The ``--chosen-rows`` lines (the module docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from gllm_tpu.models import deepseek
    from gllm_tpu.ops.pallas.decode_attention import paged_decode_attention
    from gllm_tpu.ops.pallas.tuning import decode_blocks
    H, S, page = (4, 6, 16) if small else (128, 64, 16)
    lo, hi, pages, topk = ((40, 150, 10, 32) if small
                           else (4096, 9216, 592, 2048))
    rng = np.random.default_rng(43)
    lens = rng.integers(lo, hi + 1, S).astype(np.int32)
    per = -(-hi // page)
    pt = np.zeros((S, pages), np.int32)
    pt[:, :per] = 1 + np.arange(S * per, dtype=np.int32).reshape(S, per)
    mask = np.zeros((S, pages * page), bool)
    for s, n in enumerate(lens):
        mask[s, rng.choice(n, min(topk, n), replace=False)] = True
    key = jax.random.key(1)
    pool = jax.random.normal(key, (S * per + 1, page, width),
                             jnp.float32).astype(dtype)
    q = jax.random.normal(jax.random.key(2), (S, H, width),
                          jnp.float32).astype(dtype)
    lens_j, pt_j, mask_j = (jnp.asarray(a) for a in (lens, pt, mask))
    ctx_rows = int(lens.sum())
    row_bytes = width * jnp.dtype(dtype).itemsize

    def line(what, ms, read, **kw):
        """``read``: the context rows the call reads."""
        note(what, ms, read * H * pair_abs, ns_a_row=round(
            ms * 1e6 / read, 3), hbm_pct=round(
            100 * read * row_bytes / (ms * 1e-3) / hbm, 1) if hbm else None,
            **kw)

    xla = jax.jit(lambda q, pool, pt, m: deepseek._attend(
        q, pool[pt].reshape(S, pages * page, width), m, scale=scale,
        lora=lora), compiler_options=opts)
    want = np.asarray(xla(q, pool, pt_j, mask_j), np.float32)
    line("chosen_rows_xla", timed(xla, q, pool, pt_j, mask_j),
         S * pages * page, rows=S, padded_ctx=pages * page)

    table = decode_blocks(1, chosen=True)
    grid = ([(32, 2)] if small else
            [(table["kv_block"], int(table.get("group", 1))), (512, 4),
             (256, 4), (1024, 4), (512, 2), (1024, 2), (256, 8), (512, 1),
             (1024, 1), (2048, 1), (2048, 2)])
    for kvb, grp in dict.fromkeys(grid):
        for masked in (True, False):
            fn = jax.jit(lambda q, k, kl, pt, m, kvb=kvb, grp=grp,
                         masked=masked: paged_decode_attention(
                q, k[:, :, None, :], None, kl, pt, scale=scale, v_dim=lora,
                kv_block=kvb, group_size=grp, interpret=small,
                chosen=m if masked else None,
                name=deepseek.DSA_ROWS_NAME if masked else None),
                compiler_options=opts)
            try:
                ms = timed(fn, q, pool, lens_j, pt_j, mask_j)
            except Exception as e:      # Mosaic refusing a block pair
                print(f"chosen_rows kv_block={kvb} group={grp} "
                      f"masked={masked}: {str(e)[:200]}", flush=True)
                continue
            err = None
            if masked:
                got = np.asarray(fn(q, pool, lens_j, pt_j, mask_j),
                                 np.float32)
                err = float(np.abs(got - want).max())
            line("chosen_rows_kernel", ms, ctx_rows, rows=S,
                 ctx_mean=round(ctx_rows / S), kv_block=kvb, group=grp,
                 masked=masked, max_abs_diff_from_xla=err)


if __name__ == "__main__":
    main()
