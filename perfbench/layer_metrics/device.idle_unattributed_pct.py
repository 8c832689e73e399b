"""Share of the device's idle time in the traced slice that no span of the
engine thread but ``gllm:wait`` / ``gllm:idle`` covers (%): the host was
blocked on a device that had already finished, or had nothing to do. Above
10 the phase vocabulary has a hole. Source: the profiler's trace, the
device plane's idle intervals cut along the ``gllm:*`` spans
(perfbench/host_gaps.py; run.py --trace 2 puts its output under
``host_gaps``). Layer: device."""


def read(run):
    gaps = run.get("host_gaps")
    return None if not gaps else gaps["unattributed_pct"]
