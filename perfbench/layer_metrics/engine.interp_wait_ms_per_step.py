"""Milliseconds per step dispatch that the engine thread spent inside its
host phases without running: wall time minus the thread's own CPU time
over ``intake``, ``schedule``, ``build``, ``dispatch``, ``output`` and
``deliver`` (not ``wait``, ``readback`` or ``idle``, where blocking is the
point). What is left is the thread queuing for the interpreter behind the
handler threads that send the streams' chunks (or descheduled by the host):
work nobody does, with the device idle whenever it falls between two step
programs. Source: /metrics ``gllm_engine_phase_wall_seconds_total`` minus
``gllm_engine_phase_cpu_seconds_total`` (one label, ``phase``) over
``gllm_sampler_program_total`` (every label: one per dispatch), their
growth. Layer: engine loop."""

from lib import sources

PHASES = ("intake", "schedule", "build", "dispatch", "output", "deliver")


def read(run):
    def grown(clock):
        return sum(sources.counter_delta(
            run, f"gllm_engine_phase_{clock}_seconds_total",
            f'{{phase="{name}"}}') for name in PHASES)

    steps = sources.counter_delta(run, "gllm_sampler_program_total")
    # a program that lacks the counters (before PR 30) reads no growth
    if not steps or not sources.counter_delta(
            run, "gllm_engine_phase_wall_seconds_total"):
        return None
    return (grown("wall") - grown("cpu")) * 1e3 / steps
