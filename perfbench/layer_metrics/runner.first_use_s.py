"""Seconds the jit calls that FIRST used a step signature took (trace +
lower + compile, or the persistent-cache read) inside the measured window
and the tail; should read 0: every stream waits for each of them, and
``runner.compiles_in_window`` does not see a program that was cached.
Source: the steptrace ``compile`` events' ``first_use_ms`` (what
``gllm_step_first_use_seconds_total`` counts), of the measured window
(``window_steps``) and of the tail (``steps``). None where the program
records no ``first_use_ms``. Layer: runner."""


def read(run):
    if "window_steps" not in run:
        return None
    events = {e["seq"]: e for e in run["window_steps"] + run["steps"]}
    return sum(e.get("first_use_ms", 0.0) for e in events.values()
               if e.get("kind") == "compile") / 1e3
