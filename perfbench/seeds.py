#!/usr/bin/env python3
"""Read, over many seeds, the two numbers a limit of ``correct`` is set
from: what sound runs of the program give, and what the control gives.

    python perfbench/seeds.py --workload <cell> --seeds 1,2,3 [--controls 2]
                              [--modes int8,fp8]

For each seed: the serving child with seeded weights, both probes, and the
plain reference on its own seeded weights (as run.py does it, without the
warm-up and the load). For the first ``--controls`` seeds also the control:
the reference itself, put in the program's place with its layer matrices
stored in the precision below bf16 (``int8``, then ``fp8``), compared by the
same arithmetic. The program's own ``--quantization int8`` path cannot
serve as the control at this size: it runs out of device memory while it
quantizes 8 GB of weights (PERF.md). Results go to
``perfbench/records/<configuration>.seeds.json`` (rehearsals to
chiprun_out/).
"""

import argparse
import json
import os
import sys

import run as bench
from lib import compare
from lib.serving import BenchFailure


def one_seed(workload, seed, controls, rehearsal):
    s = bench.Session(workload, seed, rehearsal)
    row = {"seed": seed}
    try:
        s.start()
        s.probe_served()
        for mode in controls:
            s.reference.ask("prefill." + mode, s.long_probe,
                            [[t] for t in s.long_probe[1:]] + [[]], mode)
        ref_prefill = s.reference.wait("prefill", 900)
        ref_decode = s.reference.wait("decode", 900)
        n_p = len(s.dec_prompt)
        ref_p = [v[0] for v in ref_prefill["logprobs"][:-1]]
        ref_d = ref_decode["logprobs"][n_p - 1:]
        v = compare.verdict(s.served_prefill, ref_p, s.dec_tops, ref_d,
                            s.config["correct"])
        row["program"] = v["numbers"]
        row["correct"] = v["correct"]
        for mode in controls:
            # the control in the program's place: its prompt logprobs, and
            # its logprobs for the decode probe's tokens
            want = [sorted(t) for t in s.dec_tops]
            s.reference.ask("decode." + mode, s.dec_full, s.dec_want, mode)
            cp = s.reference.wait("prefill." + mode, 900)
            cd = s.reference.wait("decode." + mode, 900)
            tops = [dict(zip(ids, lps)) for ids, lps in
                    zip(want, cd["logprobs"][n_p - 1:])]
            cv = compare.verdict([x[0] for x in cp["logprobs"][:-1]], ref_p,
                                 tops, ref_d, s.config["correct"])
            row["control." + mode] = cv["numbers"]
            row["control." + mode + ".correct"] = cv["correct"]
    finally:
        rc = s.close()
    row["server_rc"] = rc
    return row


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--modes", default="int8,fp8")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    seeds = [int(x) for x in args.seeds.split(",")]
    rows = []
    for i, seed in enumerate(seeds):
        modes = args.modes.split(",") if i < args.controls else []
        try:
            row = one_seed(args.workload, seed, modes, args.cpu_rehearsal)
        except BenchFailure as e:
            row = {"seed": seed, "error": str(e)}
        rows.append(row)
        print("[seeds] " + json.dumps(row), flush=True)
    cell = bench.load_json("cells", args.workload + ".json")
    path = os.path.join(bench.HERE, "records", cell["config"] + ".seeds.json")
    if args.cpu_rehearsal:
        path = os.path.join(bench.CHECKOUT, "chiprun_out", "perfbench",
                            "seeds.rehearsal.json")
    old = []
    if os.path.isfile(path):
        with open(path) as f:
            old = json.load(f)["rows"]
    # the chip tool brings back only chiprun_out/: keep a copy there
    mirror = os.path.join(bench.CHECKOUT, "chiprun_out", "perfbench",
                          os.path.basename(path))
    for target in {path, mirror}:
        with open(target, "w") as f:
            json.dump({"statistic": "lib/compare.py", "rows": old + rows}, f,
                      indent=1)
    return 0 if all("error" not in r for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
