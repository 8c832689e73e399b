"""Median device time of the step programs that carry only decode rows
(ms). Source: device trace, step programs classed by the kernels inside
them (the configuration's ``trace_patterns``). Layer: runner."""

from lib import sources, stats


def read(run):
    return stats.percentile(sources.step_ms(run, "decode"), 50)
