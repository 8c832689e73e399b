"""What binds ``paged_decode_attention``: three readings per geometry.

The kernel alone, at the shapes the benchmark's decode-bound cells give it
(32 rows, pages of 16 tokens, head_dim 128, bf16; 8 KV heads with 4 query
heads each, and 32 with 1), over contexts drawn as the ``reason`` traffic
holds them in mid-window (a prompt of 128-512 tokens plus a uniformly
drawn part of an answer of 768-1536: 128-2048, mean ~900, no two alike),
pages scattered over a pool of the cell's size. ``--layers`` calls are
chained inside one program, as a decode step chains its layers, and the
kernel's time is the sum of its events in a profiler trace over the
number of calls, never a host clock.

Readings (``--readings``):

- ``as_is``    the kernel as the tree has it;
- ``dma_only`` the block update stubbed to touch one tile of each buffer:
               DMA issue, waits and the loop, i.e. what this fetch
               discipline can reach whatever the update costs;
- ``compute_only`` only block 0 of every sequence is fetched, every later
               round computes on what is in VMEM: the block update and the
               loop without the HBM traffic.

The stubs are patched in from here; the kernel has no switch for them.

    python benchmarks/decode_attn_ablation.py            # on the chip
    python benchmarks/decode_attn_ablation.py --cpu-rehearsal

Writes ``chiprun_out/decode_attn_ablation.json`` (``--out``).
"""

import argparse
import contextlib
import glob
import json
import os
import re
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

HBM_BYTES_PER_S = 819e9       # perfbench/peaks.json, TPU v5e
KERNEL = "paged_decode_attention"
# (name, Hq, Hkv, pool pages): the two cells' geometries and pools
GEOMETRIES = (("dense_hkv8_g4", 32, 8, 2800), ("hybrid_hkv32_g1", 32, 32,
                                               4320))
PAGE, HEAD_DIM, ROWS = 16, 128, 32


def reason_contexts(rng, rows):
    """Context lengths of ``rows`` streams of the ``reason`` mix caught
    mid-answer."""
    prompt = rng.integers(128, 513, rows)
    answer = rng.integers(768, 1537, rows)
    return (prompt + (rng.random(rows) * answer).astype(int)).astype("int32")


def build_inputs(rng, hq, hkv, pool, rows, ctx, page, head_dim, dtype):
    import jax
    import jax.numpy as jnp
    import numpy as np
    max_pages = -(-int(ctx.max()) // page)
    max_pages = -(-max_pages // 32) * 32
    perm = rng.permutation(np.arange(1, pool))
    pt = np.zeros((rows, max_pages), np.int32)
    at = 0
    for s, c in enumerate(ctx):
        n = -(-int(c) // page)
        pt[s, :n] = perm[at:at + n]
        at += n
    key = jax.random.key(int(rng.integers(1 << 30)))
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (rows, hq, head_dim), dtype)
    kc = jax.random.normal(kk, (pool, page, hkv, head_dim), dtype)
    vc = jax.random.normal(kv, (pool, page, hkv, head_dim), dtype)
    return q, kc, vc, jnp.asarray(ctx), jnp.asarray(pt)


def chained(layers, kv_block, group, interpret, head_dim):
    """One program of ``layers`` kernel calls, each fed by the last
    (``ks`` / ``vs``: the scale rows of an int8 cache)."""
    import functools
    import jax
    from gllm_tpu.ops.pallas import decode_attention as da
    from gllm_tpu.utils import tpu_compiler_options

    # the step programs' own compiler options (scoped VMEM): 32 KV heads
    # at kv_block 512 hold 16 MB of K and V in flight
    @functools.partial(jax.jit, compiler_options=tpu_compiler_options())
    def run(q, kc, vc, kl, pt, ks=None, vs=None):
        def layer(q, _):
            out = da.paged_decode_attention(
                q, kc, vc, kl, pt, scale=head_dim ** -0.5,
                kv_block=kv_block, group_size=group, interpret=interpret,
                k_scale=ks, v_scale=vs)
            return (q + out * 1e-3).astype(q.dtype), None
        return jax.lax.scan(layer, q, None, length=layers)[0]
    return run


@contextlib.contextmanager
def patched(reading):
    """The decode kernel with one of the ablation stubs in place."""
    import jax
    from gllm_tpu.ops.pallas import decode_attention as da
    saved = (da.attend_block, da.make_fetch_fns)
    if reading == "dma_only":
        da.attend_block = _touch_block
    elif reading == "compute_only":
        da.make_fetch_fns = _first_block_only(da.make_fetch_fns)
    elif reading != "as_is":
        raise SystemExit(f"unknown reading {reading!r}")
    jax.clear_caches()
    try:
        yield
    finally:
        da.attend_block, da.make_fetch_fns = saved
        jax.clear_caches()


def _touch_block(q, k_buf, v_buf, slot, own_tokens, tokens_left, scale,
                 v_dim, shared_kv, m, l, acc, **_):
    """A few rows of each buffer go into the accumulator, so the waits
    keep their consumer and nothing else of the update is left."""
    import jax.numpy as jnp
    t = k_buf[slot, 0, :acc.shape[0], :v_dim].astype(jnp.float32)
    if v_buf is not None:
        t = t + v_buf[slot, 0, :acc.shape[0]].astype(jnp.float32)
    return m, l, acc + 1e-9 * t


def _first_block_only(make_fetch_fns):
    def make(*args, **kw):
        from jax.experimental import pallas as pl
        start, wait = make_fetch_fns(*args, **kw)

        def start0(slot, s, blk, *pages):
            pl.when(blk == 0)(lambda: start(slot, s, blk, *pages))

        def wait0(slot, s, blk, *pages):
            pl.when(blk == 0)(lambda: wait(slot, s, blk, *pages))
        return start0, wait0
    return make


def kernel_seconds(trace_dir, name=KERNEL):
    """(seconds in the kernel's events, their count) on the device's
    operation line of the newest trace under ``trace_dir``; the events
    are found as the benchmark's configurations find them."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(files[-1])
    total, count = 0.0, 0
    for plane in data.planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                if re.match(rf"^%?{name}", ev.name):
                    total += ev.duration_ns * 1e-9
                    count += 1
    return total, count


def measure(run, args, calls, layers, on_chip):
    """Microseconds per kernel call: from a trace on the chip, and from
    the host clock around the same calls (the only one in a rehearsal)."""
    import jax
    jax.block_until_ready(run(*args))                 # compile
    jax.block_until_ready(run(*args))
    tmp = tempfile.mkdtemp(prefix="decode_attn_trace_")
    if on_chip:
        jax.profiler.start_trace(tmp)
    t0 = time.monotonic()
    for _ in range(calls):
        out = run(*args)
    jax.block_until_ready(out)
    host_us = (time.monotonic() - t0) / (calls * layers) * 1e6
    if not on_chip:
        return None, host_us
    jax.profiler.stop_trace()
    secs, count = kernel_seconds(tmp)
    if count != calls * layers:
        raise SystemExit(f"trace holds {count} {KERNEL} events, expected "
                         f"{calls * layers}")
    return secs / count * 1e6, host_us


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "decode_attn_ablation.json"))
    ap.add_argument("--readings", default="as_is,dma_only,compute_only")
    ap.add_argument("--configs", default="table",
                    help="comma list of kv_block:group or 'table', the "
                         "tuning table's entry for this device")
    ap.add_argument("--layers", type=int, default=36)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=28)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny shapes, interpret mode, no times reported")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from gllm_tpu.ops.pallas.tuning import get as tuned
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.cpu_rehearsal:
        raise SystemExit("no TPU here: times come from the chip only "
                         "(--cpu-rehearsal walks the control flow)")
    rows, page, head_dim, layers, calls = (
        (ROWS, PAGE, HEAD_DIM, args.layers, args.calls) if on_chip
        else (4, 4, 128, 2, 1))
    table = tuned("decode")
    configs = [(table["kv_block"], int(table.get("group", 1)))
               if c == "table" else tuple(int(x) for x in c.split(":"))
               for c in args.configs.split(",")]
    dev = jax.devices()[0]
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "rows": rows, "page": page, "head_dim": head_dim,
              "layers": layers, "calls": calls, "seed": args.seed,
              "hbm_bytes_per_s": HBM_BYTES_PER_S, "geometries": []}
    for name, hq, hkv, pool in GEOMETRIES:
        rng = np.random.default_rng(args.seed)
        ctx = reason_contexts(rng, rows)
        if not on_chip:
            ctx, pool, hq, hkv = ctx // 32 + 1, 64, hq // 4, hkv // 4
        inputs = build_inputs(rng, hq, hkv, pool, rows, ctx, page, head_dim,
                              jnp.bfloat16)
        kv_bytes = 2 * hkv * head_dim * 2 * int(ctx.sum())
        floor_us = kv_bytes / HBM_BYTES_PER_S * 1e6
        geo = {"name": name, "q_heads": hq, "kv_heads": hkv, "pool": pool,
               "contexts": [int(c) for c in ctx], "kv_bytes": kv_bytes,
               "floor_us": floor_us if on_chip else None, "readings": []}
        for kv_block, group in configs:
            for reading in args.readings.split(","):
                with patched(reading):
                    run = chained(layers, kv_block, group, not on_chip,
                                  head_dim)
                    try:
                        dev_us, host_us = measure(run, inputs, calls,
                                                  layers, on_chip)
                        err = None
                    except Exception as e:  # a form Mosaic refuses
                        dev_us = host_us = None
                        err = f"{type(e).__name__}: {str(e)[:300]}"
                row = {"reading": reading, "kv_block": kv_block,
                       "group": group, "kernel_us": dev_us,
                       "host_us_per_call": host_us if on_chip else None,
                       "floor_share_pct": (100 * floor_us / dev_us
                                           if dev_us else None),
                       "error": err}
                geo["readings"].append(row)
                print(f"[ablation] {name} kv={kv_block} g={group} "
                      f"{reading}: {row}", flush=True)
        result["geometries"].append(geo)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"out": args.out, "ok": True}))


if __name__ == "__main__":
    main()
