"""How full the Mamba-2 chunked rule's packed layout was in a
parallel-hybrid decoder (%): the real tokens of the rows that prefilled
over the token slots the layout computed (chunks of 128), their growth over
the window. The quantity ``runner.mamba_chunk_fill_pct`` reads, read by its
reader (counters alone), under a name of this cell's own (the accepted
metric's list of cells is pinned by the accepted benchmark's tests).
Source: /metrics ``gllm_mamba_chunk_tokens_total`` over
``gllm_mamba_chunk_slots_total``. Layer: runner."""


def read(run):
    if run["model"].get("model_type") != "falcon_h1":
        return None
    return run["load_module"](
        "layer_metrics", "runner.mamba_chunk_fill_pct").read(run)
