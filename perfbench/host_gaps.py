#!/usr/bin/env python3
"""Whose idle time is it: the device's idle intervals of a profiler trace
(``.xplane.pb``), named by what the engine thread was doing. CPU-only.

    python perfbench/host_gaps.py --trace-dir DIR [--patterns JSON]
                                  [--out F] [--table]

Beside ``trace_reduce.py`` and with its definitions: per device plane the
idle intervals are the complement, inside the traced window (first
operation to last, over all devices), of the union of the operations on
the ``ops_line``. New here: the program's engine thread writes a span
``gllm:<phase>`` for every phase of its loop while a capture runs
(gllm_tpu/obs/spans.py; docs/observability.md has the catalog), on the
host plane, whose clock the device planes share. Each idle interval is
cut along those spans and every piece goes to one bucket:

- ``schedule``, ``build``: idle under that span;
- ``dispatch``: idle under ``gllm:dispatch``, plus the launch latency:
  idle under ``gllm:wait`` AFTER the last dispatch span that ended inside
  the same idle interval (the jit call has returned, the program's first
  operation has not begun);
- ``output``: idle under ``readback``, ``output`` or ``deliver``;
- ``loop``: idle under ``intake``, or under no span of the engine thread
  at all (the seams between two spans, between two passes of the loop);
- ``unattributed``: what no span but ``wait`` / ``idle`` covers and is no
  launch latency: the device has finished and the host has not noticed
  yet, or there is nothing to do. Above 10 % of the idle time the
  vocabulary has a hole.

The six buckets partition the idle time, so they add up to the idle share
of the same slice: ``identity_error`` says how far off the sum is. The
per-step numbers divide by the step programs in the slice (events of the
``modules_line`` matching ``step_module``, on the first device plane).

The clock check: for every ``gllm:dispatch`` span, the distance from its
end to the start of the step program it launched (the first one to start
after the span began). Both planes on one clock give a small positive
median. The other side bounds the offset the other way: a program ends
before the ``gllm:wait`` span that waited for it does.

A trace without ``gllm:*`` spans (a program that writes none, a capture
with the host tracer off) has nothing to read: exit code 3 and a line on
stderr, no output file.
"""

import argparse
import bisect
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402  (its definitions, not copies of them)
from lib.stats import percentile  # noqa: E402

SPAN_PREFIX = "gllm:"
BUCKET_OF = {"schedule": "schedule", "build": "build",
             "dispatch": "dispatch", "first_use": "dispatch",
             "readback": "output", "output": "output", "deliver": "output",
             "intake": "loop"}
BUCKETS = ("schedule", "build", "dispatch", "output", "loop",
           "unattributed")
NO_SPANS = 3        # exit code: the trace holds no gllm:* span


def engine_spans(pd, host_plane):
    """[(phase, start, end)] of the engine thread, sorted by start: the
    line of the host plane with the most ``gllm:*`` events (no other
    thread opens a phase). Nested spans (``first_use`` in ``dispatch``)
    are dropped: the outer one covers them."""
    plane_re = re.compile(host_plane)
    best = []
    for plane in pd.planes:
        if not plane_re.search(plane.name):
            continue
        for line in plane.lines:
            spans = [(e.name[len(SPAN_PREFIX):], float(e.start_ns),
                      float(e.start_ns) + float(e.duration_ns))
                     for e in line.events
                     if e.name.startswith(SPAN_PREFIX)]
            if len(spans) > len(best):
                best = spans
    best.sort(key=lambda s: (s[1], -s[2]))
    flat, edge = [], float("-inf")
    for name, s, e in best:
        if s >= edge:
            flat.append((name, s, e))
            edge = e
    return flat


def cut(gap, spans, starts):
    """One idle interval cut along the engine thread's spans:
    [(phase or None, start, end)], covering the interval exactly."""
    a, b = gap
    pieces, edge = [], a
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(spans) and spans[i][1] < b:
        name, s, e = spans[i]
        s, e = max(s, a), min(e, b)
        if e > s:
            if s > edge:
                pieces.append((None, edge, s))
            pieces.append((name, s, e))
            edge = e
        i += 1
    if b > edge:
        pieces.append((None, edge, b))
    return pieces


def attribute(gap, spans, starts):
    """{bucket: ns} of one idle interval."""
    a, b = gap
    # the last dispatch span that ENDED inside this interval: idle under
    # ``wait`` after it is the launch latency of the program it launched
    launched = max((e for name, s, e in spans[
        max(0, bisect.bisect_right(starts, a) - 1):
        bisect.bisect_left(starts, b)]
        if name == "dispatch" and a <= e <= b), default=None)
    out = dict.fromkeys(BUCKETS, 0.0)
    for name, s, e in cut(gap, spans, starts):
        if name is None:
            bucket = "loop"
        elif name == "wait" and launched is not None and s >= launched:
            bucket = "dispatch"
        else:
            bucket = BUCKET_OF.get(name, "unattributed")
        out[bucket] += e - s
    return out


def clock_check(spans, modules):
    """Distances in microseconds between the host plane's spans and the
    device plane's step programs (``modules``: [(start, end)] sorted).
    A dispatch is paired with the first program that began after the
    dispatch span began and before the next dispatch span did (a loop
    that runs ahead queues programs behind one another: no pair), and
    with the first ``wait`` span that began after the dispatch ended."""
    mod_starts = [m[0] for m in modules]
    dispatches = [(s, e) for name, s, e in spans if name == "dispatch"]
    waits = [(s, e) for name, s, e in spans if name == "wait"]
    wait_starts = [w[0] for w in waits]
    end_to_start, end_to_wait_end = [], []
    for k, (s, e) in enumerate(dispatches):
        nxt = (dispatches[k + 1][0] if k + 1 < len(dispatches)
               else float("inf"))
        i = bisect.bisect_left(mod_starts, s)
        if i >= len(modules) or mod_starts[i] >= nxt:
            continue
        m_start, m_end = modules[i]
        end_to_start.append((m_start - e) / 1e3)
        j = bisect.bisect_left(wait_starts, e)
        if j < len(waits) and waits[j][0] < nxt:
            end_to_wait_end.append((waits[j][1] - m_end) / 1e3)
    if not end_to_start:
        return None
    return {
        "pairs": len(end_to_start),
        "dispatch_end_to_program_start_us": {
            "median": percentile(end_to_start, 50),
            "p99": percentile(end_to_start, 99),
            "min": min(end_to_start),
            "negative": sum(d < 0 for d in end_to_start)},
        # the other side: the host cannot see a program end before it
        # has ended. A device clock AHEAD of the host's stretches the
        # distance above and pushes this one below zero; one BEHIND it
        # does the opposite. Both small and positive bound the offset.
        "program_end_to_wait_end_us": {
            "median": percentile(end_to_wait_end, 50),
            "min": min(end_to_wait_end, default=None),
            "negative": sum(d < 0 for d in end_to_wait_end)},
    }


def reduce(pd, pat):
    plane_re = re.compile(pat["device_plane"])
    planes = [p for p in pd.planes if plane_re.search(p.name)]
    if not planes:
        raise SystemExit("no device plane matching "
                         f"{pat['device_plane']!r}; planes: "
                         f"{[p.name for p in pd.planes]}")
    spans = engine_spans(pd, pat.get("host_plane", r"^/host:CPU$"))
    if not spans:
        print("host_gaps: no gllm:* span on any line of the host plane: "
              "nothing to read", file=sys.stderr)
        raise SystemExit(NO_SPANS)
    starts = [s[1] for s in spans]
    ops = {p.name: [(s, e) for _, s, e in
                    trace_reduce.events_of(p, pat["ops_line"])]
           for p in planes}
    every = [iv for ivs in ops.values() for iv in ivs]
    if not every:
        raise SystemExit(f"no event on line {pat['ops_line']!r} of any "
                         "device plane: nothing ran on the device")
    lo = min(s for s, _ in every)
    hi = max(e for _, e in every)
    modules = sorted(
        (s, e) for name, s, e in
        trace_reduce.events_of(planes[0], pat["modules_line"])
        if re.search(pat["step_module"], name))
    steps = len(modules)
    idle = dict.fromkeys(BUCKETS, 0.0)
    by_cover, idle_total, busy_total = {}, 0.0, 0.0
    for pname, ivs in ops.items():
        busy_total += trace_reduce.union_length(ivs)
        for gap in trace_reduce.gaps_of(ivs, lo, hi):
            parts = attribute(gap, spans, starts)
            for bucket, ns in parts.items():
                idle[bucket] += ns
            idle_total += gap[1] - gap[0]
            top = max(parts, key=parts.get)
            key = f"{pname.rsplit(':', 1)[-1]}: {top}"
            by_cover[key] = by_cover.get(key, 0.0) + (gap[1] - gap[0])
    n = len(planes)
    window = hi - lo
    idle_pct = 100.0 * idle_total / n / window
    # the same number as trace_reduce's: 1 - busy / window, per device
    reduce_pct = 100.0 * (1.0 - busy_total / n / window)
    per_step = ({b: idle[b] / n / steps / 1e6 for b in BUCKETS}
                if steps else {})
    shares = {b: 100.0 * idle[b] / n / window for b in BUCKETS}
    named = sorted(by_cover.items(), key=lambda kv: -kv[1])[:10]
    return {
        "steps": steps, "window_s": window / 1e9,
        "idle_s": idle_total / n / 1e9, "idle_pct": idle_pct,
        "idle_s_by_phase": {b: idle[b] / n / 1e9 for b in BUCKETS},
        "idle_ms_per_step": per_step,
        "idle_pct_by_phase": shares,
        "unattributed_pct": (100.0 * idle["unattributed"] / idle_total
                             if idle_total else 0.0),
        # the buckets against trace_reduce's idle share of the same slice
        "identity_error_pct": (abs(sum(shares.values()) - reduce_pct)
                               / reduce_pct * 100.0 if reduce_pct
                               else 0.0),
        "clock": clock_check(spans, modules),
        "idle_gaps": [[k, v / 1e9] for k, v in named],
        "idle_gaps_named_by": "the bucket of engine-thread spans that "
                              "covers most of each gap's idle time, gap "
                              "lengths summed per bucket",
    }


def table(r):
    lines = [f"{r['steps']} step programs in a slice of "
             f"{r['window_s']:.3f} s; device idle {r['idle_pct']:.2f} % "
             f"({r['idle_s']:.3f} s)",
             f"{'bucket':<14}{'idle ms/step':>14}{'% of slice':>12}"]
    for b in BUCKETS:
        lines.append(f"{b:<14}{r['idle_ms_per_step'].get(b, 0.0):>14.3f}"
                     f"{r['idle_pct_by_phase'][b]:>12.2f}")
    lines.append(f"unattributed share of the idle time: "
                 f"{r['unattributed_pct']:.2f} %; the buckets add up to "
                 f"the idle share within {r['identity_error_pct']:.3f} %")
    c = r["clock"]
    if c:
        d = c["dispatch_end_to_program_start_us"]
        lines.append(
            f"clock check over {c['pairs']} dispatches: dispatch end -> "
            f"program start median {d['median']:.1f} us, p99 "
            f"{d['p99']:.1f}, min {d['min']:.1f} ({d['negative']} "
            f"negative); program end -> wait end median "
            f"{c['program_end_to_wait_end_us']['median']} us, min "
            f"{c['program_end_to_wait_end_us']['min']} "
            f"({c['program_end_to_wait_end_us']['negative']} negative)")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("--patterns", default="{}")
    ap.add_argument("--out")
    ap.add_argument("--table", action="store_true")
    args = ap.parse_args()
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit("host_gaps: JAX_PLATFORMS must be cpu")
    pd = trace_reduce.load(trace_reduce.newest_xplane(args.trace_dir))
    pat = dict(trace_reduce.DEFAULT_PATTERNS, **json.loads(args.patterns))
    result = reduce(pd, pat)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f)
    if args.table or not args.out:
        print(table(result))


if __name__ == "__main__":
    main()
