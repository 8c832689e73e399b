"""Share of the traced slice with no operation running on the device (%),
mean over the cell's devices. Source: device trace, 1 - union of the
operation intervals over the slice. Layer: device."""


def read(run):
    if not run["trace"]:
        return None
    idle = [d["idle_pct"] for d in run["trace"]["devices"].values()]
    return sum(idle) / len(idle)
