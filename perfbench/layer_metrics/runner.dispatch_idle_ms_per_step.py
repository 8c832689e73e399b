"""Device-idle time under ``gllm:dispatch`` (the jit call and the start of the host copy), per step program of the traced slice (ms).
With it the launch latency: idle under ``gllm:wait`` after the dispatch
span has ended and before the program's first operation. Source: the profiler's trace, the device plane's idle intervals cut
along the engine thread's ``gllm:*`` spans (perfbench/host_gaps.py bucket
``dispatch``; run.py --trace 2 puts its output under ``host_gaps``). With
the four other ``*_idle_ms_per_step`` and ``device.idle_unattributed_pct``
it adds up to ``device.idle_pct`` of the same slice. Layer: runner."""


def read(run):
    gaps = run.get("host_gaps")
    if not gaps or not gaps["steps"]:
        return None
    return gaps["idle_ms_per_step"]["dispatch"]
