"""Latent attention over the chosen rows in the full layers: least time
over device time in the traced slice (%). Least time from
kernels/sparse_mla.py: min(context, 2048) rows a token and full layer, the
rows' bytes against the absorbed products' FLOPs (2 x 128 heads x (576 +
512) a row), the larger bound: at 128 heads they are within a hundredth
of each other on a v5e. Tokens: those decoded in the slice and every
position of the prompts prefilled in it. Device time: the operations the
configuration's ``trace_patterns`` name ``sparse_mla`` (the gather of the
chosen rows, scores, softmax, weighted sum). Source: device trace. Layer:
kernels."""

from lib import latent_trace


def read(run):
    sec = latent_trace.seconds(run, "sparse_mla")
    ctx, prompts = latent_trace.work(run)
    if not sec or not (ctx or prompts):
        return None
    k = latent_trace.modules(run)
    contexts = ctx + k["sparse_mla"].prompt_contexts(prompts)
    least, _ = k["sparse_mla"].least_seconds(run["model"], contexts,
                                             run["peaks"],
                                             k["latent_common"])
    return 100.0 * least / sec
