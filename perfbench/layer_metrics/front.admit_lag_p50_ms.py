"""How long a parsed request waits for the scheduler (median, ms): from
the request body read to ``llm.add_seq`` returning in the engine loop's
intake drain (parse, validation, tokenisation, the intake queue). Source:
/metrics ``gllm_http_admit_lag_seconds`` histogram, its growth over the
tail of a --trace 2 run. Layer: HTTP front."""

from lib import sources


def read(run):
    q = sources.histogram_quantile(run, "gllm_http_admit_lag_seconds", 0.5)
    return None if q is None else 1e3 * q
