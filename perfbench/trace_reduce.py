#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) to numbers. CPU-only.

    python perfbench/trace_reduce.py --trace-dir DIR --patterns JSON --out F
    python perfbench/trace_reduce.py --trace-dir DIR --dump F    (look first)

Reads the newest ``*.xplane.pb`` under DIR with ``jax.profiler.ProfileData``
and nothing else. Per device plane (``patterns["device_plane"]``, a regex):

- busy: the union of the intervals of the operations on the line
  ``patterns["ops_line"]``; the traced window is the span from the first
  operation to the last over all devices; idle share = 1 - busy / window;
- step programs: the events of the line ``patterns["modules_line"]`` whose
  name matches ``patterns["step_module"]``, each classed by the kernels
  that ran inside it (``patterns["step_classes"]``: class -> kernel names
  that must / must not occur);
- kernels: the operations whose name matches ``patterns["kernels"][k]``;
- the ten operations that took most time, and the ten longest idle gaps.
  The program writes no host span into the profiler's trace yet, so a gap
  is named by the step programs before and after it, not by what the host
  was doing.

The reduction is code of the benchmark, not of the program: a PR that
claims a gain cannot change how its numbers are computed.
"""

import argparse
import bisect
import glob
import json
import os
import re
import sys

DEFAULT_PATTERNS = {
    "device_plane": r"^/device:TPU:\d+$",
    "ops_line": "XLA Ops",
    "modules_line": "XLA Modules",
    "step_module": r"^jit_step",
    "kernels": {},
    "step_classes": {},
}


def newest_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    import jax
    return jax.profiler.ProfileData.from_file(path)


def union_length(intervals):
    """Total length covered by [(start, end), ...]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_of(intervals, lo, hi):
    """The idle gaps [(start, end)] of [lo, hi] not covered by intervals."""
    out, edge = [], lo
    for s, e in sorted(intervals):
        if s > edge:
            out.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        out.append((edge, hi))
    return out


def self_times(events):
    """Per event of one line, its duration minus what the events nested
    inside it cover (a ``while`` holds its body's operations): [(name,
    self ns)]. Events are (name, start, end)."""
    out, stack = [], []          # stack of [name, end, self]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((name, t) for name, _, t in stack)
    return out


def label(name, limit=110):
    """An operation's name for the breakdown: the HLO line without its
    layout annotations, cut short."""
    return re.sub(r"\{[^{}]*\}", "", name)[:limit]


def events_of(plane, line_name):
    evs = []
    for line in plane.lines:
        if line.name == line_name:
            evs.extend((e.name, float(e.start_ns),
                        float(e.start_ns) + float(e.duration_ns))
                       for e in line.events)
    return evs


def classify(kernels_inside, classes):
    """The first class whose ``has`` kernels all occur and whose ``lacks``
    kernels do not; None where no class fits."""
    for name, rule in classes.items():
        if (all(k in kernels_inside for k in rule.get("has", []))
                and not any(k in kernels_inside
                            for k in rule.get("lacks", []))):
            return name
    return None


def reduce_plane(plane, pat):
    ops = events_of(plane, pat["ops_line"])
    modules = [m for m in events_of(plane, pat["modules_line"])
               if re.search(pat["step_module"], m[0])]
    kernel_res = {k: re.compile(v) for k, v in pat["kernels"].items()}
    kernel_events = {k: [] for k in kernel_res}
    by_name = {}
    for name, t in self_times(ops):
        row = by_name.setdefault(label(name), [0, 0.0])
        row[0] += 1
        row[1] += t
    for name, s, e in ops:
        for k, rx in kernel_res.items():
            if rx.search(name):
                kernel_events[k].append((s, e))
    # class each step program by the kernels that ran inside it
    steps = []
    marks = sorted((s, k) for k, evs in kernel_events.items()
                   for s, _ in evs)
    starts = [m[0] for m in marks]
    for name, s, e in sorted(modules, key=lambda m: m[1]):
        inside = {marks[i][1] for i in
                  range(bisect.bisect_left(starts, s),
                        bisect.bisect_right(starts, e))}
        steps.append({"name": name, "start": s, "dur": e - s,
                      "class": classify(inside, pat["step_classes"])})
    return {"ops": ops, "by_name": by_name, "steps": steps,
            "kernel_events": kernel_events}


def reduce(pd, pat):
    plane_re = re.compile(pat["device_plane"])
    planes = [p for p in pd.planes if plane_re.search(p.name)]
    if not planes:
        raise SystemExit("no device plane matching "
                         f"{pat['device_plane']!r}; planes: "
                         f"{[p.name for p in pd.planes]}")
    per = {p.name: reduce_plane(p, pat) for p in planes}
    all_ops = [o for r in per.values() for o in r["ops"]]
    if not all_ops:
        raise SystemExit(f"no event on line {pat['ops_line']!r} of any "
                         "device plane: nothing ran on the device")
    lo = min(s for _, s, _ in all_ops)
    hi = max(e for _, _, e in all_ops)
    window = (hi - lo) / 1e9
    devices, busy_total = {}, 0.0
    op_time, gaps = {}, []
    for pname, r in per.items():
        spans = [(s, e) for _, s, e in r["ops"]]
        busy = union_length(spans) / 1e9
        busy_total += busy
        step_ms = {}
        for st in r["steps"]:
            step_ms.setdefault(st["class"], []).append(st["dur"] / 1e6)
        kernels = {k: {"seconds": sum(e - s for s, e in evs) / 1e9,
                       "calls": len(evs)}
                   for k, evs in r["kernel_events"].items()}
        devices[pname] = {
            "busy_s": busy, "idle_pct": 100.0 * (1.0 - busy / window),
            "step_ms": {str(k): v for k, v in step_ms.items()},
            "kernels": kernels}
        for name, (n, t) in r["by_name"].items():
            row = op_time.setdefault(name, [0, 0.0])
            row[0] += n
            row[1] += t
        ordered = sorted(r["steps"], key=lambda st: st["start"])
        starts = [st["start"] for st in ordered]
        for s, e in gaps_of(spans, lo, hi):
            i = bisect.bisect_right(starts, s) - 1
            before = ordered[i]["class"] if i >= 0 else None
            after = (ordered[i + 1]["class"]
                     if i + 1 < len(ordered) else None)
            gaps.append((f"{pname.rsplit(':', 1)[-1]}: after "
                         f"{before} step, before {after} step",
                         (e - s) / 1e9))
    top_ops = sorted(((n, t / 1e9) for n, (_, t) in op_time.items()),
                     key=lambda x: -x[1])[:10]
    by_kind = {}
    for name, sec in gaps:
        by_kind[name] = by_kind.get(name, 0.0) + sec
    longest = sorted(by_kind.items(), key=lambda x: -x[1])[:10]
    return {
        "busy_s": busy_total / len(planes), "window_s": window,
        "devices": devices,
        "breakdown": {
            "device_ops": [[n, t] for n, t in top_ops],
            "idle_gaps": [[n, t] for n, t in longest],
            "idle_gaps_named_by": "the classes of the step programs before "
                                  "and after each gap, summed per pair: the "
                                  "program writes no host span into the "
                                  "profiler's trace yet"},
    }


def dump(pd, limit=40):
    """The trace's structure, for a first look by hand."""
    out = []
    for plane in pd.planes:
        prow = {"plane": plane.name, "lines": []}
        for line in plane.lines:
            names, n, first = {}, 0, []
            for e in line.events:
                n += 1
                row = names.setdefault(e.name, [0, 0.0])
                row[0] += 1
                row[1] += float(e.duration_ns)
                if len(first) < 3 or (
                        "custom" in e.name and len(first) < 12):
                    first.append({"name": e.name, "start_ns": e.start_ns,
                                  "dur_ns": e.duration_ns,
                                  "stats": {str(k): str(v)[:300]
                                            for k, v in e.stats}})
                if n > 400000:
                    break
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:limit]
            prow["lines"].append({
                "line": line.name, "events": n,
                "top": [[k, c, t / 1e6] for k, (c, t) in top],
                "samples": first})
        out.append(prow)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("--patterns", default="{}")
    ap.add_argument("--out")
    ap.add_argument("--dump")
    args = ap.parse_args()
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit("trace_reduce: JAX_PLATFORMS must be cpu")
    path = newest_xplane(args.trace_dir)
    pd = load(path)
    if args.dump:
        with open(args.dump, "w") as f:
            json.dump({"file": path, "bytes": os.path.getsize(path),
                       "planes": dump(pd)}, f, indent=1)
    if args.out:
        pat = dict(DEFAULT_PATTERNS, **json.loads(args.patterns))
        with open(args.out, "w") as f:
            json.dump(reduce(pd, pat), f)


if __name__ == "__main__":
    main()
