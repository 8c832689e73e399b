"""Are two trees' step programs the same programs?

    python benchmarks/step_hlo_hash.py <tree> <configuration> [--dump DIR]

lowers, for a described v5e (no chip, ~15 s), the decode step and the
mixed step that ``<tree>/gllm_tpu`` builds at a benchmark configuration's
own sizes (``perfbench/configs/<configuration>.json`` of THIS checkout: its
``--num-pages``, ``--max-num-seqs``, ``--max-model-len``, a full row bucket,
the widest page bucket) and prints a sha256 of each program's StableHLO
text with what names the checkout taken out: source locations, and the
file paths inside the Pallas kernels' serialized bodies. Run it on the
parent commit (``git archive <commit> | tar -x -C <dir>``) and on the
working tree: equal hashes say that a change left a cell's device work as
it was, so a pair on the chip has only the host's side to compare.
The batches and the capture are ``tests/test_tpu_compile.py``'s.
"""

import argparse
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Patch:
    """``monkeypatch.setattr`` for a process that ends with the run."""

    def setattr(self, obj, name, value):
        setattr(obj, name, value)


def flag(flags, name, default):
    return int(flags[flags.index(name) + 1]) if name in flags else default


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("tree")
    ap.add_argument("configuration")
    ap.add_argument("--dump", help="write the two texts into this directory")
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    sys.path.insert(1, os.path.join(ROOT, "tests"))
    import jax
    import gllm_tpu
    if not os.path.abspath(gllm_tpu.__file__).startswith(tree + os.sep):
        sys.exit(f"gllm_tpu came from {gllm_tpu.__file__}, not {tree}")
    import test_tpu_compile as T
    from gllm_tpu.models.config import from_hf_config
    from gllm_tpu.ops.pallas import tuning

    def capture_lower(runner, attr, default, params_sh=None, kv_sh=None):
        fn = getattr(runner, attr)

        def wrapper(params, kv, *rest, **static):
            raise T.Compiled(fn.lower(
                T._structs(params, params_sh or default),
                T._structs(kv, kv_sh or default),
                *T._structs(rest, default), **static), 0.0)
        setattr(runner, attr, wrapper)

    T.capture_compile = capture_lower
    jax.default_backend = lambda: "tpu"
    tuning.device_tag = lambda: "tpu_v5_lite"
    with open(os.path.join(ROOT, "perfbench", "configs",
                           args.configuration + ".json")) as f:
        config = json.load(f)
    flags = config["server_flags"]
    rows = flag(flags, "--max-num-seqs", 64)
    max_len = flag(flags, "--max-model-len", 4096)
    cfg = from_hf_config(config)
    runner = T.make_runner(cfg, T._topology(), monkeypatch=_Patch(),
                           num_pages=flag(flags, "--num-pages", 2048),
                           max_num_seqs=rows, max_model_len=max_len,
                           attention_impl="auto")
    pages = max_len // 16
    chunk = min(2048, max_len // 2)
    batches = {
        "decode": T.decode_batch(runner, rows, pages),
        "mixed": T.prefill_batch(runner, chunk, ndecode=rows - 1,
                                 npages=pages, table_pages=pages)}
    for what, batch in batches.items():
        if getattr(cfg, "use_seq_slots", False) or getattr(
                cfg, "use_hybrid", False):
            T._with_slots(batch)
        try:
            runner.step_async(batch)
            sys.exit(f"{what}: the step ran instead of being captured")
        except T.Compiled as c:
            text = c.compiled.as_text()
        text = re.sub(r"loc\(.*?\)", "", text)
        text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", text)
        print(args.configuration, what,
              hashlib.sha256(text.encode()).hexdigest()[:16], len(text))
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(
                    args.dump, f"{args.configuration}.{what}.txt"), "w") as f:
                f.write(text)


if __name__ == "__main__":
    main()
