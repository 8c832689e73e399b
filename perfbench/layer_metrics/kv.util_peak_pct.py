"""Largest share of the KV pool in use (%), polled once a second in the
traced run. Source: /metrics ``gllm_sched_kv_util``. Layer: KV manager."""


def read(run):
    return 100.0 * max(run["kv_util"]) if run["kv_util"] else None
