"""Device-idle time under ``gllm:intake`` and under no span at all (the seams between two phases, between two passes of the loop), per step program of the traced slice (ms).
Source: the profiler's trace, the device plane's idle intervals cut
along the engine thread's ``gllm:*`` spans (perfbench/host_gaps.py bucket
``loop``; run.py --trace 2 puts its output under ``host_gaps``). With
the four other ``*_idle_ms_per_step`` and ``device.idle_unattributed_pct``
it adds up to ``device.idle_pct`` of the same slice. Layer: engine loop."""


def read(run):
    gaps = run.get("host_gaps")
    if not gaps or not gaps["steps"]:
        return None
    return gaps["idle_ms_per_step"]["loop"]
