"""On-chip block-size sweep for the Pallas attention kernels.

TPU analogue of the reference's Triton autotuner runs that produced
``fused_moe_triton/configs/`` (VERDICT r03 next #2): sweep
``q_block``/``kv_block`` over 64-512 on representative prefill/decode
workloads, then write the winners into the committed per-device table
(``gllm_tpu/ops/pallas/tuning.py`` → ``tables.json``).

Every config runs in a fresh timeout-bounded subprocess, one after
another (a chip belongs to one process at a time; this parent never
touches jax): a config that overflows VMEM or stalls the Mosaic pipeline
reports as FAIL/TIMEOUT without taking the sweep down. Timing is
fetch-based (``np.asarray``) over a chained dependency loop, so the
device work is provably finished inside the timed region.

    python benchmarks/kernel_tune.py                 # sweep both kernels
    python benchmarks/kernel_tune.py --write         # ... and update tables.json
    python benchmarks/kernel_tune.py --vmem-probe    # find Mosaic's real VMEM
                                                     # ceiling (validates the 6 MB
                                                     # heuristic in ragged_attention)
"""

import argparse
import functools
import itertools
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.decode_attn_ablation import HBM_BYTES_PER_S  # noqa: E402

CONFIG_TIMEOUT_S = 150
BLOCKS = (64, 128, 256, 512)


# ---------------------------------------------------------------------------
# inner: one timed config in a fresh process
# ---------------------------------------------------------------------------

def _fetch(x):
    import numpy as np
    return np.asarray(x)


def _interp() -> bool:
    """CPU smoke mode: Pallas runs interpreted (no Mosaic on CPU)."""
    import jax
    return jax.default_backend() == "cpu"


def _quant_caches(key, shape):
    """int8 cache + per-page per-head scale buffers for the --kv-dtype
    int8 sweep arm (kv_cache_dtype=int8 serving): contents are random —
    timing only cares about the DMA/dequant pattern, not the values."""
    import jax
    import jax.numpy as jnp
    P, page, Hkv, D = shape
    cache = jax.random.randint(key, shape, -127, 128, jnp.int8)
    scale = jax.random.uniform(key, (P, Hkv), jnp.float32, 0.01, 0.02)
    return cache, scale


def _mixed_workload(T=1024, S=8, Hq=32, Hkv=8, D=128, page=16, ctx=1024,
                    kv_dtype="auto"):
    """Representative prefill batch: S seqs, T packed tokens, ctx KV.

    Returns ``(q, caches, cu, kv_lens, pt, scale)`` where ``caches`` is
    ``(kc, vc)`` for a full-precision cache or ``(kc, vc, ks, vs)`` for
    the int8 arm — only the requested dtype's buffers are allocated."""
    import jax
    import jax.numpy as jnp
    P = S * (ctx // page) + 1
    key = jax.random.key(0)
    q = jax.random.normal(key, (T, Hq, D), jnp.bfloat16)
    if kv_dtype == "int8":
        kq = jax.random.key(1)
        kc, ks = _quant_caches(kq, (P, page, Hkv, D))
        vc, vs = _quant_caches(jax.random.fold_in(kq, 1),
                               (P, page, Hkv, D))
        caches = (kc, vc, ks, vs)
    else:
        caches = (jax.random.normal(key, (P, page, Hkv, D), jnp.bfloat16),
                  jax.random.normal(key, (P, page, Hkv, D), jnp.bfloat16))
    per = T // S
    cu = jnp.asarray([i * per for i in range(S)] + [T], jnp.int32)
    kv_lens = jnp.full((S,), ctx, jnp.int32)
    pt = (jnp.arange(S * (ctx // page), dtype=jnp.int32)
          .reshape(S, ctx // page) + 1)
    return q, caches, cu, kv_lens, pt, D ** -0.5


def _time_reps(run, q, iters, *args, reps=3):
    """min-of-reps timed loops (at the fast end of the sweep a single
    loop's per-dispatch jitter can dominate the ranking)."""
    import jax.numpy as jnp
    out = run(q, *args)
    _fetch(out)                                    # compile + first fetch
    best = None
    for _ in range(reps):
        t0 = time.monotonic()
        for _ in range(iters):
            # chain: next q depends on previous out so device work
            # serializes without a per-iter fetch
            q = q + 0.0 * out.astype(jnp.bfloat16)
            out = run(q, *args)
        _fetch(out)
        dt = (time.monotonic() - t0) / iters * 1e3
        best = dt if best is None else min(best, dt)
    return best


def build_ragged(q_block, kv_block, kv_dtype="auto", **workload):
    """Jitted ragged-sweep body + its buffers, as ``(run, (q, kc, vc))``
    (int8 arm appends the scale buffers: ``(q, kc, vc, ks, vs)``).

    The KV caches ride as ARGUMENTS (device-buffer handles), never
    closure constants: a captured GB-scale cache is baked into the
    compile request and the executable (compile time and memory scale
    with it, and the cache can no longer be donated).
    tests/test_kernel_tuning.py traces this body (on a shrunken
    ``workload``) and asserts no buffer-sized constant rides in its
    jaxpr."""
    import jax
    from gllm_tpu.ops.pallas.ragged_attention import ragged_paged_attention
    from gllm_tpu.utils import tpu_compiler_options
    q, caches, cu, kl, pt, scale = _mixed_workload(kv_dtype=kv_dtype,
                                                   **workload)

    # same scoped-VMEM compile options the serving step jit uses, so the
    # sweep measures what the runner will actually run
    interp = _interp()

    if kv_dtype == "int8":
        @functools.partial(jax.jit,
                           compiler_options=tpu_compiler_options())
        def run(qq, kc, vc, ks, vs):
            return ragged_paged_attention(
                qq, kc, vc, cu, kl, pt, scale=scale, q_block=q_block,
                kv_block=kv_block, interpret=interp, k_scale=ks,
                v_scale=vs)

        return run, (q, *caches)
    kc, vc = caches

    @functools.partial(jax.jit, compiler_options=tpu_compiler_options())
    def run(qq, kc, vc):
        return ragged_paged_attention(qq, kc, vc, cu, kl, pt, scale=scale,
                                      q_block=q_block, kv_block=kv_block,
                                      interpret=interp)

    return run, (q, kc, vc)


# The ragged sweep's inputs: the two ``reason`` cells' mixed steps first
# (31 rows decoding at the mix's contexts and one fresh prompt, in the
# token bucket the runner gives the step, served as the dispatch serves
# them: the riding rows by the decode kernel, the prompt by the ragged
# kernel; chained over layers inside one program; at the dense cell's 8 KV
# heads and the hybrid's 32; these decide the winner), then, for the
# record, a whole ``--maxp`` chunk of a long
# fresh prompt beside the same rows (no cell sends one; a winner must not
# be paid for there) and the old input (8 chunks of 128 tokens over 1024
# of context each, the ragged kernel alone).
RAGGED_SHAPES = (
    dict(name="dense_cell_prompt320", rows=31, prompt=320, tokens=512,
         layers=36),
    dict(name="dense_cell_prompt512", rows=31, prompt=512, tokens=1024,
         layers=36),
    dict(name="hybrid_cell_prompt320", rows=31, prompt=320, tokens=512,
         layers=36, Hkv=32, pool=4320),
    dict(name="chunk2048", rows=31, prompt=2048, tokens=2112, layers=12),
)


def build_mixed_step(q_block, kv_block, rows=31, prompt=320, tokens=512,
                     Hq=32, Hkv=8, D=128, page=16, pool=2800, layers=36,
                     seed=38):
    """Jitted body + buffers of ``layers`` mixed-step attention calls as
    ``ops/attention._mixed_step_attention`` makes them, with the ragged
    kernel at ``q_block`` x ``kv_block`` (a child process sweeps one pair:
    ``tuning.ragged_blocks`` is replaced in it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.decode_attn_ablation import (build_inputs,
                                                 reason_contexts)
    from gllm_tpu.ops import attention
    from gllm_tpu.ops.pallas import tuning
    from gllm_tpu.utils import tpu_compiler_options
    tuning.ragged_blocks = lambda *_, **__: {"q_block": q_block,
                                             "kv_block": kv_block}
    rng = np.random.default_rng(seed)
    lens = np.append(reason_contexts(rng, rows), prompt).astype(np.int32)
    _, kc, vc, kl, pt = build_inputs(rng, Hq, Hkv, pool, rows + 1, lens,
                                     page, D, jnp.bfloat16)
    q = jax.random.normal(jax.random.key(seed), (tokens, Hq, D),
                          jnp.bfloat16)
    md = attention.AttentionMetadata(
        jnp.asarray(list(range(rows + 1)) + [rows + prompt], jnp.int32),
        kl, pt, jnp.asarray(rows + 1, jnp.int32))
    interp = _interp()

    @functools.partial(jax.jit, compiler_options=tpu_compiler_options())
    def run(q, kc, vc):
        def layer(q, _):
            out = attention._mixed_step_attention(
                q, kc, vc, md, None, None, scale=D ** -0.5,
                interpret=interp, v_dim=None)
            return (q + out * 1e-3).astype(q.dtype), None
        return jax.lax.scan(layer, q, None, length=layers)[0]
    return run, (q, kc, vc)


def time_ragged(q_block, kv_block, iters=12, kv_dtype="auto"):
    """Per-layer ms summed over the cells' mixed steps (the ranking;
    ``RAGGED_SHAPES``), each shape's line printed, and the old input's
    beside them."""
    # Interpret mode (CPU smoke) runs each grid program as traced
    # python — the silicon-shaped workload would take hours per point.
    # Shrink so every point times standalone in seconds; the silicon
    # workload is untouched.
    wl, reps = ({"T": 256, "S": 4, "ctx": 256}, 2) if _interp() \
        else ({}, 3)
    iters = 2 if _interp() else iters
    run, (q, *args) = build_ragged(q_block, kv_block, kv_dtype=kv_dtype,
                                   **wl)

    # the VMEM clamp can alias two requested configs to one program; name
    # the program actually compiled so the parent dedupes the ranking
    from gllm_tpu.ops.pallas.ragged_attention import effective_q_block
    bq = effective_q_block(q_block, kv_block, q.shape[1], q.shape[0],
                           *args[0].shape[2:])
    print(f"EFFECTIVE ragged:{bq}:{kv_block}", flush=True)

    old = _time_reps(run, q, iters, *args, reps=reps)
    if _interp() or kv_dtype != "auto":
        return old
    print(f"RAGGED s8_t1024_ctx1024 q={bq} kv={kv_block}: {old:.4f} ms a "
          "call (the ragged kernel alone)", flush=True)
    ranked = 0.0
    for shape in RAGGED_SHAPES:
        shape = dict(shape)
        name = shape.pop("name")
        run, (q, *args) = build_mixed_step(q_block, kv_block, **shape)
        ms = (_time_reps(run, q, max(5, iters // 4), *args, reps=reps)
              / shape["layers"])
        print(f"RAGGED {name} q={bq} kv={kv_block}: {ms:.4f} ms a layer "
              "(decode kernel for the riding rows + ragged kernel)",
              flush=True)
        if "_cell_" in name:
            ranked += ms
    return ranked


# The decode sweep's inputs: the two benchmark cells' geometries first
# (32 rows, contexts drawn as the ``reason`` mix holds them mid-window, the
# kernel chained over a step's layers inside one program so that a 0.2 ms
# call is not timed by its dispatch; these decide the winner), then the
# old input (128 rows, every row 2048 tokens) for the record.
DECODE_SHAPES = (
    dict(name="dense_cell", S=32, Hkv=8, pool=2800, ctx=None, layers=36),
    dict(name="hybrid_cell", S=32, Hkv=32, pool=4320, ctx=None, layers=36),
    dict(name="s128_ctx2048", S=128, Hkv=8, pool=None, ctx=2048, layers=1),
)
DECODE_BLOCKS = (128, 256, 512, 1024)
DECODE_GROUPS = (1, 2, 4, 8)


def build_decode(kv_block, gsz=1, S=128, ctx=2048, kv_dtype="auto", Hkv=8,
                 pool=None, layers=1, seed=28):
    """Jitted decode-sweep body + its buffers (caches as args, not
    closure constants — see build_ragged) + the KV bytes one call must
    read. ``ctx`` None draws the rows' contexts (128-2048, no two alike)
    and scatters their pages over ``pool``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.decode_attn_ablation import (build_inputs, chained,
                                                 reason_contexts)
    Hq, D, page = 32, 128, 16
    rng = np.random.default_rng(seed)
    lens = (reason_contexts(rng, S) if ctx is None
            else np.full((S,), ctx, np.int32))
    pool = pool or int(-(-lens // page).sum()) + 1
    q, kc, vc, kl, pt = build_inputs(rng, Hq, Hkv, pool, S, lens, page, D,
                                     jnp.bfloat16)
    kv_bytes = 2 * Hkv * D * int(lens.sum()) * (1 if kv_dtype == "int8"
                                               else 2)
    args = (q, kc, vc, kl, pt)
    if kv_dtype == "int8":
        key = jax.random.key(seed)
        kc, ks = _quant_caches(key, kc.shape)
        vc, vs = _quant_caches(jax.random.fold_in(key, 1), vc.shape)
        args = (q, kc, vc, kl, pt, ks, vs)
    return chained(layers, kv_block, gsz, _interp(), D), args, kv_bytes


def time_decode(kv_block, gsz=1, iters=25, kv_dtype="auto"):
    """Per-call ms summed over the cells' shapes (the ranking), each
    shape's line printed with the time its KV bytes take at the HBM's
    peak beside it."""
    # On the CPU smoke path a silicon-shaped workload runs for hours.
    # Shrink the interpret workload and announce the geometry up front
    # so a timeout names where it died instead of leaving a bare TIMEOUT.
    shapes = DECODE_SHAPES
    reps = 3
    if _interp():
        shapes = (dict(name="cpu_smoke", S=8, Hkv=8, pool=None, ctx=256,
                       layers=1),)
        iters, reps = 1, 2
    print(f"EFFECTIVE decode:{kv_block}:{gsz}:{kv_dtype} "
          f"shapes={[s['name'] for s in shapes]} iters={iters}", flush=True)
    ranked = 0.0
    for shape in shapes:
        shape = dict(shape)
        name, layers = shape.pop("name"), shape["layers"]
        run, (q, *args), kv_bytes = build_decode(kv_block, gsz,
                                                 kv_dtype=kv_dtype, **shape)
        n = iters if layers == 1 else max(5, iters // layers)
        ms = _time_reps(run, q, n, *args, reps=reps) / layers
        floor_ms = kv_bytes / HBM_BYTES_PER_S * 1e3
        if not _interp():       # an interpreter's time is no device time
            print(f"DECODE {name} kv={kv_block} group={gsz}: {ms:.4f} ms "
                  f"a call; {kv_bytes} KV bytes = {floor_ms:.4f} ms at 819 "
                  f"GB/s ({100 * floor_ms / ms:.1f} % of the floor)",
                  flush=True)
        if name.endswith("_cell") or len(shapes) == 1:
            ranked += ms
    return ranked


# ---------------------------------------------------------------------------
# one geometry's own entries: a windowed GQA cell (--geometry)
# ---------------------------------------------------------------------------

def sweep_geometry(hq: int, hkv: int, window: int, rows: int = 0,
                   layers: int = 6, D: int = 128, page: int = 16,
                   ragged_only: bool = False, pairs=None):
    """Both kernels at ONE geometry in this one process (a refused config
    raises and is reported; nothing here has hung the compiler). With a
    ``window`` the geometry is the document cell's: the decode kernel over
    kv_block x group for ``rows`` (16) rows at its contexts (drawn
    8.5-17 k under a table of 1088 pages), and a mixed step as the
    dispatch serves it (``rows - 1`` riding rows on the decode kernel at
    the blocks the decode sweep chose, a 320-token question behind 12.6 k
    of cached document, or a 2048-token chunk behind 8 k, on the ragged
    kernel over q_block x kv_block). The full layer's calls are swept; a
    windowed layer's are timed once each at the winner, since they take
    the geometry's pair too. Without one (``window`` 0) it is a ``reason``
    cell's: ``rows`` (32) rows mid-answer, and a mixed step of ``rows - 1``
    of them beside a fresh prompt of 320 tokens (512 slots) or 512 (1024).
    ``ragged_only`` leaves the decode sweep out: the riding rows run at
    the table's blocks; ``pairs`` names the (q_block, kv_block) to time
    in place of the product. At the winner the chunk's rows are held against
    float32 arithmetic on the same inputs. Prints one line a config and,
    last, the table entries ``<kernel>@<hq>x<hkv>`` as JSON."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.decode_attn_ablation import (build_inputs,
                                                 reason_contexts)
    from gllm_tpu.ops import attention
    from gllm_tpu.ops.pallas import tuning
    from gllm_tpu.ops.pallas.decode_attention import paged_decode_attention
    from gllm_tpu.utils import tpu_compiler_options
    interp = _interp()
    rows = rows or (16 if window else 32)
    if interp:
        rows, layers, D, window = 4, 1, 32, 64 if window else 0
    rng = np.random.default_rng(44)
    if window:
        lo, hi = (100, 300) if interp else (8500, 17000)
        ctx = np.linspace(lo, hi, rows).astype(np.int32)
        rng.shuffle(ctx)
    else:
        ctx = reason_contexts(rng, rows) // (8 if interp else 1)
    dtype = jnp.float32 if interp else jnp.bfloat16
    if window:
        # the last row's pages hold the longest step sequence (12920)
        pool = int(-(-ctx // page).sum()) + 2
        q, kc, vc, kl, pt = build_inputs(rng, hq, hkv, pool, rows, ctx,
                                         page, D, dtype)
        table = 32 if interp else 1088
        pt = jnp.pad(pt, ((0, 0), (0, max(0, table - pt.shape[1]))))[
            :, :table]
    else:
        # ... a fresh prompt of up to 512 tokens
        own = np.append(ctx[:rows - 1], 64 if interp else 512).astype(
            np.int32)
        pool = int(-(-own // page).sum()) + 2
        q, kc, vc, kl, pt = build_inputs(rng, hq, hkv, pool, rows, own,
                                         page, D, dtype)
        kl = jnp.asarray(ctx)
    opts = None if interp else tpu_compiler_options()

    def timed(run, *args):
        iters, reps = (1, 1) if interp else (4, 3)
        out = jax.block_until_ready(run(*args))
        best = None
        for _ in range(reps):
            t0 = time.monotonic()
            for _ in range(iters):
                out = run(*args)
            jax.block_until_ready(out)
            dt = (time.monotonic() - t0) / iters / layers * 1e3
            best = dt if best is None else min(best, dt)
        return best

    def attempt(label, build):
        try:
            ms = timed(*build())
        except Exception as e:            # Mosaic's refusal, by its text
            print(f"GEOMETRY {label}: FAIL "
                  f"{str(e).strip().splitlines()[0][:160]}", flush=True)
            return None
        print(f"GEOMETRY {label}: {ms:.4f} ms a layer", flush=True)
        return ms

    def decode_call(kb, gsz, win):
        def build():
            @functools.partial(jax.jit, compiler_options=opts)
            def run(q, kc, vc, kl, pt):
                def layer(q, _):
                    out = paged_decode_attention(
                        q, kc, vc, kl, pt, scale=D ** -0.5, kv_block=kb,
                        group_size=gsz, interpret=interp, window=win)
                    return (q + out * 1e-3).astype(q.dtype), None
                return jax.lax.scan(layer, q, None, length=layers)[0]
            return run, q, kc, vc, kl, pt
        tag = "window" if win else "full"
        return attempt(f"decode {tag} kv={kb} group={gsz}", build)

    best = {}
    if not ragged_only:
        for win in (None, window) if window else (None,):
            rows_read = int(np.minimum(ctx, win).sum() if win
                            else ctx.sum())
            floor = 2 * hkv * D * 2 * rows_read / HBM_BYTES_PER_S * 1e3
            print(f"GEOMETRY decode {'window' if win else 'full'}: {rows} "
                  f"rows, {rows_read} rows of context to read = "
                  f"{floor:.4f} ms at 819 GB/s", flush=True)
        res = {}
        for kb, gsz in itertools.product(
                (256,) if interp else (128, 256, 512, 1024),
                (2,) if interp else (1, 2, 4, 8)):
            ms = decode_call(kb, gsz, None)
            if ms:
                res[(kb, gsz)] = ms
        kb, gsz = min(res, key=res.get) if res else (256, 4)
        best["decode"] = {"kv_block": kb, "group": gsz}
        if window:
            decode_call(kb, gsz, window)
        # the mixed steps: the riding rows at the decode sweep's winner
        tuning.decode_blocks = lambda *_, **__: best["decode"]
    if window:
        doc, question, chunk, behind = ((96, 24, 64, 64) if interp
                                        else (12600, 320, 2048, 8192))
        shapes = (("question", question, doc, 32 if interp else 512),
                  ("chunk", chunk, behind, chunk + rows))
    else:
        shapes = ((("prompt40", 40, 0, 64), ("prompt64", 64, 0, 128))
                  if interp else
                  (("prompt320", 320, 0, 512), ("prompt512", 512, 0, 1024)))

    def step_inputs(new, cached, tokens):
        lens = np.append(ctx[:rows - 1], cached + new).astype(np.int32)
        qq = jax.random.normal(jax.random.key(1), (tokens, hq, D), dtype)
        md = attention.AttentionMetadata(
            jnp.asarray(list(range(rows)) + [rows - 1 + new], jnp.int32),
            jnp.asarray(lens), pt, jnp.asarray(rows, jnp.int32))
        return qq, md

    def check(qb, kb, win):
        """The first shape's chunk on the ragged kernel against float32
        arithmetic (XLA, highest precision) on its sequence's pages."""
        _, new, cached, tokens = shapes[0]
        qq, md = step_inputs(new, cached, tokens)
        tuning.ragged_blocks = lambda *_, **__: {"q_block": qb,
                                                 "kv_block": kb}
        got = jax.jit(lambda q, kc, vc: attention._mixed_step_attention(
            q, kc, vc, md, None, None, scale=D ** -0.5, interpret=interp,
            v_dim=None, window=win), compiler_options=opts)(qq, kc, vc)
        got = got[rows - 1:rows - 1 + new].astype(jnp.float32)
        n = cached + new

        @jax.jit
        def want(q, kc, vc):
            f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
            k = f32(kc[pt[rows - 1]]).reshape(-1, hkv, D)[:n]
            v = f32(vc[pt[rows - 1]]).reshape(-1, hkv, D)[:n]
            qs = f32(q[rows - 1:rows - 1 + new]).reshape(new, hkv, -1, D)
            sc = jnp.einsum("thgd,khd->hgtk", qs, k,
                            precision="highest") * D ** -0.5
            pos = cached + jnp.arange(new)[:, None]
            at = jnp.arange(n)[None, :]
            vis = at <= pos
            if win:
                vis &= at > pos - win
            pr = jax.nn.softmax(jnp.where(vis, sc, -jnp.inf), axis=-1)
            return jnp.einsum("hgtk,khd->thgd", pr, v,
                              precision="highest").reshape(new, hq, D)
        ref = want(qq, kc, vc)
        err = float(jnp.sqrt(jnp.mean((got - ref) ** 2)
                             / jnp.mean(ref ** 2)))
        print(f"GEOMETRY check {'window' if win else 'full'} "
              f"{shapes[0][0]} q={qb} kv={kb}: rel_rms {err:.3e} against "
              f"float32 arithmetic ({got.dtype.name} from "
              f"{qq.dtype.name})", flush=True)

    def mixed_calls(qb, kb, win):
        tuning.ragged_blocks = lambda *_, **__: {"q_block": qb,
                                                 "kv_block": kb}
        total = 0.0
        for name, new, cached, tokens in shapes:
            qq, md = step_inputs(new, cached, tokens)

            def build(qq=qq, md=md):
                @functools.partial(jax.jit, compiler_options=opts)
                def run(q, kc, vc):
                    def layer(q, _):
                        out = attention._mixed_step_attention(
                            q, kc, vc, md, None, None, scale=D ** -0.5,
                            interpret=interp, v_dim=None, window=win)
                        return (q + out * 1e-3).astype(q.dtype), None
                    return jax.lax.scan(layer, q, None, length=layers)[0]
                return run, qq, kc, vc
            tag = "window" if win else "full"
            ms = attempt(f"mixed {tag} {name} q={qb} kv={kb}", build)
            total = None if ms is None or total is None else total + ms
        return total

    res = {}
    for qb, kb in pairs or itertools.product(
            (16,) if interp else (16, 32, 64, 128, 256),
            (64,) if interp else (128, 256, 512)):
        total = mixed_calls(qb, kb, None)
        if total:
            res[(qb, kb)] = total
    if res:
        qb, kb = min(res, key=res.get)
        best["ragged"] = {"q_block": qb, "kv_block": kb}
        check(qb, kb, None)
        if window:
            mixed_calls(qb, kb, window)
            check(qb, kb, window)
    print("GEOMETRY_BEST " + json.dumps(
        {f"{k}@{hq}x{hkv}": v for k, v in best.items()}), flush=True)
    return 0.0


VMEM_PROBE_CONFIGS = ((128, 256), (256, 256), (256, 512), (512, 512),
                      (1024, 512), (1024, 1024), (2048, 1024))


def vmem_probe_one(qb: int, kb: int):
    """One oversized-tile compile attempt: the heuristic in
    ragged_attention.py is disabled via its env override so Mosaic itself
    rules on the tile. Runs in its own subprocess (a stalling compile must
    not take the later configs with it); the parent's last-good/first-bad
    pair brackets the REAL VMEM ceiling the 6 MB heuristic guesses at."""
    os.environ["GLLM_TPU_VMEM_TILE_LIMIT_MB"] = "100000"
    import functools as ft

    import jax
    from gllm_tpu.ops.pallas.ragged_attention import ragged_paged_attention
    from gllm_tpu.utils import tpu_compiler_options
    q, (kc, vc), cu, kl, pt, scale = _mixed_workload(T=2048, ctx=2048)
    # binary MB: the consumer (vmem_tile_limit_b) multiplies by 1024²
    tile_mb = q.shape[1] * qb * kb * 4 / (1024 * 1024)

    interp = _interp()

    # caches as args, not closure constants (see time_ragged)
    @ft.partial(jax.jit, compiler_options=tpu_compiler_options())
    def run(qq, kc, vc):
        return ragged_paged_attention(qq, kc, vc, cu, kl, pt, scale=scale,
                                      q_block=qb, kv_block=kb,
                                      interpret=interp)

    try:
        _fetch(run(q, kc, vc))
        print(f"[vmem] q_block={qb} kv_block={kb} "
              f"score_tile={tile_mb:.1f}MB: OK", flush=True)
    except Exception as e:
        msg = str(e).splitlines()[0][:200]
        print(f"[vmem] q_block={qb} kv_block={kb} "
              f"score_tile={tile_mb:.1f}MB: FAIL {msg}", flush=True)


# ---------------------------------------------------------------------------
# outer: subprocess sweep supervisor
# ---------------------------------------------------------------------------

def run_inner(spec: str):
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--inner", spec],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            timeout=CONFIG_TIMEOUT_S)
        out = proc.stdout
        if proc.returncode == 0:
            for line in reversed(out.strip().splitlines()):
                if line.startswith("RESULT "):
                    return float(line.split()[1]), out
        return None, out
    except subprocess.TimeoutExpired as e:
        # A child may finish its measurement and still blow the deadline
        # on teardown (interpret-mode interpreter exit, device
        # release) — salvage the RESULT it already printed rather than
        # discarding a completed timing.
        out = e.stdout
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        for line in reversed((out or "").strip().splitlines()):
            if line.startswith("RESULT "):
                return float(line.split()[1]), "TIMEOUT(after result)\n" \
                    + (out or "")[-500:]
        return None, "TIMEOUT\n" + str(out or "")[-500:]


def effective_spec(out: str, fallback: str) -> str:
    for line in reversed(out.strip().splitlines()):
        if line.startswith("EFFECTIVE "):
            return line.split(None, 1)[1].strip()
    return fallback


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--write", action="store_true",
                    help="merge winners into gllm_tpu/ops/pallas/tables.json")
    ap.add_argument("--vmem-probe", action="store_true")
    ap.add_argument("--kernel", choices=("ragged", "decode"),
                    default=None)
    ap.add_argument("--blocks", default=None,
                    help="comma-separated block sizes of the ragged sweep "
                         f"(default {','.join(map(str, BLOCKS))})")
    ap.add_argument("--geometry", default=None, metavar="HQxHKV[:WINDOW]",
                    help="sweep both kernels at one geometry of query x "
                         "kv heads in one child (sweep_geometry): the "
                         "table's <kernel>@HQxHKV entries. With a window "
                         "the document cell's contexts and mixed steps, "
                         "the windowed calls timed at the winners; "
                         "without, a reason cell's. --kernel ragged "
                         "leaves the decode sweep out")
    ap.add_argument("--pairs", default="", metavar="QxKV[,QxKV...]",
                    help="the ragged pairs a --geometry run times (default: "
                         "the product of 16-256 and 128-512)")
    ap.add_argument("--rows", type=int, default=0,
                    help="rows of a --geometry step (default 16 with a "
                         "window, 32 without)")
    ap.add_argument("--kv-dtype", choices=("auto", "int8"), default="auto",
                    help="sweep the kernels against an int8 quantized "
                         "cache (kv_cache_dtype=int8 serving shape); "
                         "informational A/B — winners are only written "
                         "for the default dtype")
    args = ap.parse_args()

    if args.inner:
        from gllm_tpu.utils import enable_compilation_cache
        enable_compilation_cache()
        parts = args.inner.split(":")
        if parts[0] == "ragged":
            ms = time_ragged(int(parts[1]), int(parts[2]),
                             kv_dtype=(parts[3] if len(parts) > 3
                                       else "auto"))
        elif parts[0] == "decode":
            ms = time_decode(int(parts[1]),
                             int(parts[2]) if len(parts) > 2 else 1,
                             kv_dtype=(parts[3] if len(parts) > 3
                                       else "auto"))
        elif parts[0] == "geometry":
            ms = sweep_geometry(int(parts[1]), int(parts[2]), int(parts[3]),
                                rows=int(parts[4]),
                                ragged_only=parts[5] == "ragged",
                                pairs=[tuple(map(int, p.split("x")))
                                       for p in parts[6].split(",") if p])
        elif parts[0] == "vmem":
            vmem_probe_one(int(parts[1]), int(parts[2]))
            print("RESULT 0.0", flush=True)
            return
        elif parts[0] == "devtag":
            from gllm_tpu.ops.pallas.tuning import device_tag
            print(f"DEVTAG {device_tag()}", flush=True)
            print("RESULT 0.0", flush=True)
            return
        else:
            raise SystemExit(f"unknown inner spec {args.inner}")
        print(f"RESULT {ms:.3f}", flush=True)
        return

    # The PARENT must never import jax: on a single-tenant remote TPU it
    # would hold the device lease and deadlock the sweep children. The
    # device tag comes from a short-lived child, resolved LAZILY at each
    # write (an early probe timing out on a flaky relay must not forfeit
    # winners the later sweep measures).
    def probe_dev_tag() -> str:
        _, out = run_inner("devtag")
        for line in out.splitlines():
            if line.startswith("DEVTAG "):
                return line.split(None, 1)[1].strip()
        return "unknown"

    def write_best(best: dict) -> None:
        """Merge winners into the committed table IMMEDIATELY — an outer
        timeout killing the rest of the sweep must not forfeit results
        already measured."""
        if not (args.write and best):
            return
        if args.kv_dtype != "auto":
            # the committed table keys by kernel only; an int8-workload
            # winner must not overwrite the default-dtype entry
            print("[tune] not writing table: --kv-dtype sweep is "
                  "informational", file=sys.stderr)
            return
        tag = probe_dev_tag()
        if tag.startswith("cpu") or tag in ("unknown", "default"):
            # cpu → interpret-mode timings; unknown/default → the probe
            # couldn't name the device (a "default" entry would layer
            # under EVERY device kind) — either way, don't pollute the
            # committed table
            print(f"[tune] not writing table: device tag {tag!r}",
                  file=sys.stderr)
            return
        from gllm_tpu.ops.pallas.tuning import _TABLES_PATH
        table = {}
        if os.path.exists(_TABLES_PATH):
            with open(_TABLES_PATH) as f:
                table = json.load(f)
        dev = table.setdefault(tag, {})
        for kern, params in best.items():
            entry = dev.setdefault(kern, {})
            entry.update(params)
            # provenance: which sweep artifact produced this entry
            # (tuning.get() strips the field before kernel kwargs)
            entry["comment"] = (
                f"benchmarks/kernel_tune.py sweep on {tag} "
                f"({time.strftime('%Y-%m-%d')}), log in "
                "chiprun_out/kernel_tune.log")
        with open(_TABLES_PATH, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
        print(f"[tune] wrote {_TABLES_PATH} for {tag}",
              file=sys.stderr)

    if args.geometry:
        heads, _, window = args.geometry.partition(":")
        hq, hkv = heads.split("x")
        global CONFIG_TIMEOUT_S
        CONFIG_TIMEOUT_S = 3000             # one child holds the sweep
        _, out = run_inner(f"geometry:{hq}:{hkv}:{window or 0}:"
                           f"{args.rows}:{args.kernel}:{args.pairs}")
        log_path = os.path.join(REPO, "chiprun_out",
                                f"kernel_tune_geometry_{hq}x{hkv}.log")
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        with open(log_path, "w") as f:
            f.write(out)
        print("\n".join(ln for ln in out.splitlines()
                        if ln.startswith("GEOMETRY")), flush=True)
        return

    if args.vmem_probe:
        last_ok_mb = None
        for qb, kb in VMEM_PROBE_CONFIGS:
            ms, out = run_inner(f"vmem:{qb}:{kb}")
            sys.stdout.write(out if ms is not None
                             else f"[vmem] q_block={qb} kv_block={kb}: "
                                  f"TIMEOUT/CRASH\n{out[-300:]}\n")
            sys.stdout.flush()
            if ms is not None and ": OK" in out:
                # parse the score_tile the child itself computed/printed —
                # one source of truth for geometry and MB convention
                for line in out.splitlines():
                    if "score_tile=" in line and line.rstrip().endswith("OK"):
                        last_ok_mb = float(
                            line.split("score_tile=")[1].split("MB")[0])
        if last_ok_mb is not None:
            # INFORMATIONAL only — never auto-written to the table. The
            # score tile is a poor proxy for whole-kernel VMEM: on the r5
            # chip a 16 MiB probe tile compiled fine, yet committing a
            # 12 MiB limit let the SERVING program (bq=512) through and
            # Mosaic's 64 MiB scoped-vmem cap rejected it at 74 MiB total
            # (q block + scores + p + f32 accumulators ≈ 9× the tile).
            # Only a real compile of the exact program validates a config
            # — which is what the block sweep does; the sweep's winners
            # are recorded in EFFECTIVE (clamped) form and deploy as-is.
            print(f"[vmem] largest accepted score tile {last_ok_mb:.1f} "
                  f"MB (informational; 6 MB clamp stays — see comment)",
                  flush=True)
        return

    log_path = os.path.join(REPO, "chiprun_out", "kernel_tune.log")
    os.makedirs(os.path.dirname(log_path), exist_ok=True)

    def say(line):
        print(line, file=sys.stderr, flush=True)
        with open(log_path, "a") as f:
            f.write(line + "\n")

    def report(kind, cfg, ms, out):
        say(f"[tune] {kind} {cfg}: {'%.4f ms' % ms if ms else 'FAIL'}")
        for ln in out.splitlines():
            if ln.startswith(("DECODE ", "RAGGED ")):   # per-shape times
                say("[tune]   " + ln)
        if ms is None:
            # a FAIL without its error is undiagnosable after the
            # single-tenant session ends (r5: the decode sweep failed at
            # all block sizes and left no evidence)
            say("\n".join("[tune]   | " + ln
                          for ln in out[-1200:].splitlines()[-12:]))

    results = {"ragged": {}, "decode": {}}
    best = {}
    if args.kernel in (None, "ragged"):
        # requested configs whose VMEM-clamped program was already timed
        # alias to one entry, keyed by the EFFECTIVE config the child
        # compiled, and share the min of their timings
        eff_ms = {}
        # a config is ranked by its time over the cells' mixed steps
        # (RAGGED_SHAPES)
        blocks = (tuple(int(b) for b in args.blocks.split(","))
                  if args.blocks else BLOCKS)
        for qb, kb in itertools.product(blocks, blocks):
            ms, out = run_inner(f"ragged:{qb}:{kb}:{args.kv_dtype}")
            eff = effective_spec(out, f"ragged:{qb}:{kb}")
            if ms is not None:
                eff_ms[eff] = min(ms, eff_ms.get(eff, ms))
            results["ragged"][f"{qb}x{kb}"] = ms
            tag = "" if eff == f"ragged:{qb}:{kb}" else f" [{eff}]"
            report("ragged", f"q={qb} kv={kb}{tag}", ms, out)
        if eff_ms:
            # commit the EFFECTIVE winning program (clamped bq), not the
            # requested label — the serving-time clamp re-derives the same
            # program from it
            _, qb, kb = min(eff_ms, key=eff_ms.get).split(":")
            best["ragged"] = {"q_block": int(qb), "kv_block": int(kb)}
            write_best({"ragged": best["ragged"]})
    if args.kernel in (None, "decode"):
        # group sweep: gsz seqs per program, one block in flight each.
        # A config is ranked by its time over the two cells' shapes
        # (DECODE_SHAPES); a config whose buffers overflow VMEM at 32 KV
        # heads fails and is left out.
        for kb, gsz in itertools.product(DECODE_BLOCKS, DECODE_GROUPS):
            ms, out = run_inner(f"decode:{kb}:{gsz}:{args.kv_dtype}")
            results["decode"][f"{kb}g{gsz}"] = ms
            report("decode", f"kv={kb} group={gsz} (cells' sum)", ms, out)
        ok_d = {k: v for k, v in results["decode"].items() if v}
        if ok_d:
            kb, gsz = min(ok_d, key=ok_d.get).split("g")
            best["decode"] = {"kv_block": int(kb), "group": int(gsz)}
            write_best({"decode": best["decode"]})
    say(json.dumps({"results": results, "best": best}))
    print(json.dumps({"results": results, "best": best}))


if __name__ == "__main__":
    main()
