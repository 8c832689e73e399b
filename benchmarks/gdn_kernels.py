"""The two Gated-DeltaNet kernels alone on the chip, in place in a slot
pool shaped as the tree shapes it (``ModelConfig.ssm_slot_shapes``), at
olmo-hybrid-7b.reason's shapes: 12 layers x 33 slots of 30 heads of 96 x
192, 32 decoding rows (``gdn_recurrent_step``), and one joining prompt's
chunks in the 512- and 1024-token buckets' layouts (``gdn_chunk_scan``).

    python benchmarks/gdn_kernels.py [--tree DIR] [--cpu-rehearsal]

through the chip tool (one chip, ~1 min a tree). ``--tree`` times another
checkout's kernels (a parent unpacked beside this one) with this script.
A program of ``--calls`` kernel calls, layer after layer as the step's
scan walks them, is timed on the host's clock around
``block_until_ready``; printed: microseconds a row and layer, the bytes
the pool stores for a state, and those bytes both ways as a share of the
chip's bandwidth. Results: ``chiprun_out/gdn_kernels.json`` (one entry a
tree).
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E_BYTES_PER_S = 819e9         # Google Cloud documentation, "TPU v5e"


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=ROOT)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--calls", type=int, default=120)
    ap.add_argument("--iters", type=int, default=7)
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    from gllm_tpu.models.config import from_hf_config
    from gllm_tpu.ops.gdn import gdn_chunk_slots
    from gllm_tpu.ops.pallas.gdn_recurrent import gdn_recurrent_step
    from gllm_tpu.ops.pallas.gdn_scan import gdn_chunk_scan
    from gllm_tpu.utils import tpu_compiler_options

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.cpu_rehearsal:
        sys.exit("no TPU here: run through the chip tool, or pass "
                 "--cpu-rehearsal")
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "olmo-hybrid-7b.json")) as f:
        hf = json.load(f)
    if not on_chip:
        hf.update(hf["rehearsal"]["model"])
        args.calls, args.iters = 4, 1
    cfg = from_hf_config(hf)
    H, Dk, Dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    Lg, S = cfg.num_linear_layers, 32
    n_slots = S + 1
    slot_shape = tuple(cfg.ssm_slot_shapes[1])
    P = Lg * n_slots
    key = jax.random.key(45)

    def rand(i, *shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32)

    def up(n, m):
        return -(-n // m) * m
    stored = 4 * slot_shape[0] * up(slot_shape[1], 8) * up(slot_shape[2],
                                                            128)
    result = {"tree": tree, "device": jax.devices()[0].device_kind,
              "slot_shape": slot_shape, "stored_bytes_a_state": stored,
              "element_bytes_a_state": 4 * H * Dk * Dv}

    def timed(fn, pool, *operands):
        fn = jax.jit(fn, donate_argnums=(0,),
                     compiler_options=tpu_compiler_options())
        pool = fn(pool, *operands)          # compiles
        jax.block_until_ready(pool)
        seconds = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            pool = fn(pool, *operands)
            jax.block_until_ready(pool)
            seconds.append(time.perf_counter() - t0)
        return statistics.median(seconds) / args.calls, pool

    # the decode step's calls: layer l's rows on its own slots 1..32
    q, k, v = rand(1, S, H, Dk), rand(2, S, H, Dk), rand(3, S, H, Dv)
    g = -jnp.abs(rand(4, S, H))
    beta = jax.nn.sigmoid(rand(5, S, H)) * 2.0
    rows = jnp.arange(1, S + 1, dtype=jnp.int32)

    def decode_calls(pool, q, k, v, g, beta):
        def one(i, pool):
            _, pool = gdn_recurrent_step(
                q, k, v, g, beta, pool, rows + (i % Lg) * n_slots,
                interpret=not on_chip)
            return pool
        return jax.lax.fori_loop(0, args.calls, one, pool)

    pool = jnp.zeros((P,) + slot_shape, jnp.float32)
    per_call, pool = timed(decode_calls, pool, q, k, v, g, beta)
    us = per_call / S * 1e6
    result["gdn_recurrent_step"] = {
        "us_a_row_and_layer": us, "ms_a_decode_step": per_call * Lg * 1e3,
        "stored_bytes_of_peak_pct":
            100 * 2 * stored / (us * 1e-6) / V5E_BYTES_PER_S,
        "element_bytes_of_peak_pct":
            100 * 2 * 4 * H * Dk * Dv / (us * 1e-6) / V5E_BYTES_PER_S}

    # a joining prompt's chunks: one sequence from the layout's first
    # chunk on, the dead chunks behind it on the layer's dummy slot
    for tokens in (512, 1024):
        N, C = gdn_chunk_slots(tokens, S)
        live = tokens // C
        ops = (rand(6, H, N, C, Dk), rand(7, H, N, Dk, C),
               rand(8, H, N, C, Dv), rand(9, H, N, C, Dk),
               rand(10, H, N, C, C) * 0.01,
               jnp.exp(-jnp.abs(rand(11, H, N, 1, Dv))))
        first = jnp.arange(N) == 0
        slot = jnp.where(jnp.arange(N) < live, 1, 0).astype(jnp.int32)

        def scan_calls(pool, *ops):
            def one(i, pool):
                _, pool = gdn_chunk_scan(
                    *ops, pool, slot + (i % Lg) * n_slots, first,
                    interpret=not on_chip)
                return pool
            return jax.lax.fori_loop(0, args.calls, one, pool)

        per_call, pool = timed(scan_calls, pool * 0.0, *ops)
        result[f"gdn_chunk_scan_{tokens}"] = {
            "chunks": N, "ms_a_layer": per_call * 1e3}
    if not on_chip:
        # a CPU run's clock says nothing of the chip: no time is printed
        print(json.dumps({k: v for k, v in result.items()
                          if not k.startswith("gdn_")}
                         | {"rehearsed": sorted(k for k in result
                                                if k.startswith("gdn_"))}))
        return
    print(json.dumps(result))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    path = os.path.join(out, "gdn_kernels.json")
    try:
        with open(path) as f:
            runs = json.load(f)
    except (OSError, ValueError):
        runs = []
    with open(path, "w") as f:
        json.dump(runs + [result], f, indent=1)


if __name__ == "__main__":
    main()
