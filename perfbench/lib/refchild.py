"""CPU-only child that answers the reference's questions.

    python perfbench/lib/refchild.py        (stdin: JSON lines, stdout: answers)

First line in: {"family", "model", "seed", "dtype", "stage_layers"}; the child makes
the seeded weights once. Every later line: {"id", "tokens", "want"} (and
"control": "int8" | "fp8" for the lower-precision control) and is answered with ``RESULT {"id", "logprobs", "seconds"}``. An empty line or EOF
ends it. It runs beside the serving child while that one sets up, and must
never touch the chip: the parent starts it with ``JAX_PLATFORMS=cpu``.
"""

import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_family(name):
    """The reference module ``perfbench/reference/<name>.py``."""
    path = os.path.join(HERE, "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("perfbench_ref_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit("refchild: JAX_PLATFORMS must be cpu (the chip is the "
                 "server's)")
    head = json.loads(sys.stdin.readline())
    ref = load_family(head["family"])
    t0 = time.monotonic()
    weights = ref.make_weights(head["model"], head["seed"],
                               dtype=head["dtype"],
                               stage_layers=head.get("stage_layers"))
    print(f"[refchild] weights in {time.monotonic() - t0:.1f}s",
          file=sys.stderr, flush=True)
    for line in sys.stdin:
        if not line.strip():
            break
        job = json.loads(line)
        t0 = time.monotonic()
        lp = ref.logprobs(head["model"], weights, job["tokens"], job["want"],
                          control=job.get("control"))
        print("RESULT " + json.dumps({
            "id": job["id"], "logprobs": lp,
            "seconds": time.monotonic() - t0}), flush=True)


if __name__ == "__main__":
    main()
