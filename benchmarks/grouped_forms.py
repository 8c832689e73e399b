"""The grouped products over a layer's held experts on the chip, in the
two forms the tree has: XLA's ``ragged_dot`` and the Pallas kernel
(ops/pallas/grouped_matmul.py), as ``models/deepseek._held_experts`` calls
them (gate, up and down of the gated SiLU form over a run's whole stacks,
of which one layer's groups have rows).

    python benchmarks/grouped_forms.py [--cpu-rehearsal]

through the chip tool (one chip, ~2 min). At command-a-plus-05-2026's
widths (16 of 128 experts held, 4096 x 4096, 4 layers stacked, top 8) it
times a 16-row decode step's and a 512-token mixed step's expert part,
routed uniformly from a seed, and prints for each form the milliseconds a
layer, the experts touched, and the bytes of their matrices as a share of
the chip's bandwidth. The faster form is what
``models/cohere2_moe.grouped_impl`` gives where attention runs on Pallas.
Results:
``chiprun_out/grouped_forms.json``.
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
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    from gllm_tpu.models.config import from_hf_config
    from gllm_tpu.models.deepseek import _held_experts, deepseek_route
    from gllm_tpu.utils import tpu_compiler_options

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.cpu_rehearsal:
        sys.exit("no TPU here: run through the chip tool, or pass "
                 "--cpu-rehearsal")
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "command-a-plus-05-2026.json")) as f:
        hf = json.load(f)
    if not on_chip:
        hf.update(hf["rehearsal"]["model"])
    cfg = from_hf_config(hf)
    L, held = cfg.num_layers, cfg.experts_held
    H, I = cfg.hidden_size, cfg.moe_intermediate_size
    dtype = jnp.bfloat16 if on_chip else jnp.float32
    key = jax.random.key(44)
    stacks = tuple(
        jax.random.normal(jax.random.fold_in(key, i), shape, dtype) * 0.02
        for i, shape in enumerate(((L, held, H, I), (L, held, H, I),
                                   (L, held, I, H))))
    opts = tpu_compiler_options() if on_chip else None
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    bw = peaks.get(jax.devices()[0].device_kind, {}).get("bytes_per_s")
    results = []
    for T in (16, 512) if on_chip else (8, 32):
        x = jax.random.normal(jax.random.fold_in(key, 9), (T, H), dtype)
        logits = jax.random.normal(jax.random.fold_in(key, T),
                                   (T, cfg.num_experts), jnp.float32)
        weights, ids = deepseek_route(logits, None, cfg)
        valid = jnp.ones((T,), bool)
        outs = {}
        for form in ("xla", "pallas"):
            @functools.partial(jax.jit, compiler_options=opts)
            def run(x, stacks, form=form):
                def layer(x, li):
                    out, stats = _held_experts(None, x, weights, ids, valid,
                                               cfg, stacks, li, form)
                    return (x + out.astype(x.dtype) * 1e-3), stats
                return jax.lax.scan(layer, x, jnp.arange(L))
            out, stats = jax.block_until_ready(run(x, stacks))
            t0 = time.monotonic()
            for _ in range(args.iters):
                out, stats = run(x, stacks)
            jax.block_until_ready(out)
            ms = (time.monotonic() - t0) / args.iters / L * 1e3
            touched = float(stats[:, 2].mean())
            line = dict(form=form, tokens=T, ms_a_layer=round(ms, 4),
                        held_assignments=float(stats[:, 0].mean()),
                        experts_touched=touched)
            if bw and on_chip:
                nbytes = touched * 3 * H * I * 2
                line["bandwidth_pct"] = round(
                    100 * nbytes / bw / (ms * 1e-3), 1)
            outs[form] = out
            results.append(line)
            print(json.dumps(line), flush=True)
        diff = float(jnp.max(jnp.abs(outs["xla"].astype(jnp.float32)
                                     - outs["pallas"].astype(jnp.float32))))
        print(json.dumps(dict(tokens=T, forms_differ_by=diff)), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "grouped_forms.json"), "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "results": results}, f, indent=1)


if __name__ == "__main__":
    main()
