"""Windowed latent attention: least time over device time in the traced
slice (%). Least time from kernels/swa_mla.py: min(context, 513) rows a
token and windowed layer; a decoded token reads its window's rows, a
prompt's tokens share theirs; FLOPs 2 x 64 heads x (1088 + 1024) a row;
the larger bound. Device time: the operations the configuration's
``trace_patterns`` name ``swa_mla`` (ring reads, scores, softmax, weighted
sum, ring writes). Source: device trace. Layer: kernels."""

from lib import latent_trace


def read(run):
    sec = latent_trace.seconds(run, "swa_mla")
    ctx, prompts = latent_trace.work(run)
    if not sec or not (ctx or prompts):
        return None
    k = latent_trace.modules(run)
    least, _ = k["swa_mla"].least_seconds(run["model"], ctx, prompts,
                                          run["peaks"], k["latent_common"])
    return 100.0 * least / sec
