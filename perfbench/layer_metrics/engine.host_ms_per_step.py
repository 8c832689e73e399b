"""Host time per engine step (ms): the loop's phases other than the wait
for the device (schedule, build, dispatch), summed, over the steps of the
window. A host time on the host's clock. Source: /steptrace ``ph``. Layer:
engine loop."""


def read(run):
    host = [sum(ms for name, ms in e["ph"].items() if name != "collect")
            for e in run["steps"] if isinstance(e.get("ph"), dict)]
    return sum(host) / len(host) if host else None
