#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip, and write it down.

    python perfbench/sweep.py --workload <cell> --seed <n> --start <rate>

One serving child for all rates. The rates are a geometric grid (the
traffic mix's ``knee.grid_step``) walked from ``--start``, up while rates
pass and down while they fail; at each, the mix is offered for
``knee.seconds`` after its ramp. A rate passes where at least
``share_meeting_both`` of the requests due in the window had a first token
within ``ttft_s`` of their due instant and a mean gap between tokens within
``mean_gap_s``, and at least ``share_started_by_end`` of them had started
streaming when the window ended (no growing backlog). The knee is the
highest rate that passes, next to one that fails; the cell's rate is
``load_factor`` x the knee. The result goes to
``perfbench/records/<cell>.sweep.json``; the rate is then written into the
cell's file by hand: the benchmark offers a fixed rate and never searches.
"""

import argparse
import json
import os
import sys
import time

import run as bench
from lib import stats
from lib.serving import BenchFailure, get_json


def attainment(records, seconds, knee):
    due = stats.due_in_window(records, seconds)
    meets = started = 0
    for r in due:
        t = r.times
        if len(t) and t[0] < seconds:
            started += 1
        if not len(t) or t[0] - r.due > knee["ttft_s"]:
            continue
        mean_gap = (t[-1] - t[0]) / (len(t) - 1) if len(t) > 1 else 0.0
        meets += mean_gap <= knee["mean_gap_s"]
    n = max(1, len(due))
    return {"due": len(due), "meeting_both": meets / n,
            "started_by_end": started / n}


def wait_idle(port, timeout=120):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        info = get_json(port, "/server_info")
        if not info["waiting"] and not info["running"]:
            return
        time.sleep(0.5)
    raise BenchFailure("the server did not drain between two rates")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--max-rates", type=int, default=8)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    session = bench.Session(args.workload, args.seed, args.cpu_rehearsal,
                            verify=False)
    knee = session.traffic["knee"]
    rows, rate, step = [], args.start, knee["grid_step"]
    try:
        session.start()
        session.warm()
        for i in range(args.max_rates):
            cell = dict(session.cell, rate_rps=rate)
            for attempt in range(6):
                # a step shape met for the first time stalls the server
                # while it compiles: such a reading says nothing about the
                # rate, so the rate is offered again (the shape is now there)
                before = session.compiled()
                records, _, _ = session.offer(knee["seconds"], cell=cell,
                                              seed=args.seed + i)
                wait_idle(session.port)
                if session.compiled() == before:
                    break
                print(f"[sweep] {rate} requests/s compiled "
                      f"{session.new_shapes}: offered again", flush=True)
            row = dict(rate_rps=rate,
                       **attainment(records, knee["seconds"], knee),
                       **stats.end_to_end(records, knee["seconds"]))
            row["new_shapes"] = session.new_shapes
            row["passes"] = (
                row["meeting_both"] >= knee["share_meeting_both"]
                and row["started_by_end"] >= knee["share_started_by_end"])
            rows.append(row)
            print("[sweep] " + json.dumps(row), flush=True)
            # up from a rate that passes, down from one that fails, until
            # the knee lies between two neighbours of the grid
            if {r["passes"] for r in rows} == {True, False}:
                break
            rate = rate * step if row["passes"] else rate / step
    except BenchFailure as e:
        session.server.tail()
        print(f"[sweep] FAILED: {e}", flush=True)
        return 1
    finally:
        session.close()
    rows.sort(key=lambda r: r["rate_rps"])
    passed = [r["rate_rps"] for r in rows if r["passes"]]
    out = {"cell": args.workload, "seed": args.seed, "device": session.dev,
           "limits": knee, "rates": rows,
           "knee_rps": max(passed) if passed else None,
           "bracketed": {r["passes"] for r in rows} == {True, False},
           "rate_chosen": (round(knee["load_factor"] * max(passed), 2)
                           if passed else None)}
    path = os.path.join(bench.HERE, "records", args.workload + ".sweep.json")
    if args.cpu_rehearsal:
        path = os.path.join(session.out_dir, "sweep.rehearsal.json")
    # the chip tool brings back only chiprun_out/: keep a copy there
    mirror = os.path.join(session.out_dir, os.path.basename(path))
    for target in {path, mirror}:
        with open(target, "w") as f:
            json.dump(out, f, indent=1)
    print(f"[sweep] knee {out['knee_rps']} requests/s, rate chosen "
          f"{out['rate_chosen']} -> {os.path.relpath(path, bench.CHECKOUT)}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
