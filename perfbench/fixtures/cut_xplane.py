#!/usr/bin/env python3
"""Cut a few step programs out of a profiler trace, for a test fixture.

    python perfbench/fixtures/cut_xplane.py SRC.xplane.pb DST.xplane.pb
                                            [--skip 20] [--steps 6]

Keeps, on every line of every plane, the events that begin between the
end of step program number ``skip`` of the first device plane and the end
of program ``skip + steps`` (so the slice begins with an idle gap and holds
``steps`` whole programs with the host spans that launched them), and
drops the event and stat metadata nothing kept refers to. A tool for
whoever records a fixture (``run.py --trace 2 --keep-trace`` leaves the
trace under chiprun_out/perfbench/<cell>/trace); no test and no run of the
benchmark imports it. It reads the trace as a protocol buffer, with the
bindings that ship with the installed tensorflow.
"""

import argparse
import re
import sys


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--skip", type=int, default=20)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--device-plane", default=r"^/device:TPU:\d+$")
    ap.add_argument("--modules-line", default="XLA Modules")
    ap.add_argument("--step-module", default=r"^jit_step")
    args = ap.parse_args()
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(args.src, "rb") as f:
        space.ParseFromString(f.read())

    def begins(line, event):            # picoseconds
        return line.timestamp_ns * 1000 + event.offset_ps

    device = next(p for p in space.planes
                  if re.search(args.device_plane, p.name))
    modules = sorted(
        (begins(line, e), begins(line, e) + e.duration_ps)
        for line in device.lines if line.name == args.modules_line
        for e in line.events
        if re.search(args.step_module,
                     device.event_metadata[e.metadata_id].name))
    if len(modules) < args.skip + args.steps + 1:
        sys.exit(f"only {len(modules)} step programs in the trace")
    lo = modules[args.skip][1]
    hi = modules[args.skip + args.steps][1] + 1
    kept = 0
    for plane in space.planes:
        used_events, used_stats = set(), set()
        for line in plane.lines:
            keep = [e for e in line.events if lo <= begins(line, e) < hi]
            del line.events[:]
            line.events.extend(keep)
            kept += len(keep)
            for e in keep:
                used_events.add(e.metadata_id)
                used_stats.update(s.metadata_id for s in e.stats)
        for mid in used_events:
            used_stats.update(s.metadata_id
                              for s in plane.event_metadata[mid].stats)
        used_stats.update(s.metadata_id for s in plane.stats)
        for mid in [m for m in plane.event_metadata
                    if m not in used_events]:
            del plane.event_metadata[mid]
        for mid in [m for m in plane.stat_metadata if m not in used_stats]:
            del plane.stat_metadata[mid]
    with open(args.dst, "wb") as f:
        f.write(space.SerializeToString())
    print(f"kept {kept} events of {args.steps} step programs, "
          f"{(hi - lo) / 1e9:.3f} ms, in {args.dst}")


if __name__ == "__main__":
    main()
