"""Median device time of the step programs that carry a prefill chunk
(ms). Source: device trace, step programs classed by the kernels inside
them. Layer: runner."""

from lib import sources, stats


def read(run):
    return stats.percentile(sources.step_ms(run, "prefill"), 50)
