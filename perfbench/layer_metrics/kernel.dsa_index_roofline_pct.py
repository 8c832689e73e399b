"""The DSA indexer's scoring and selection: least time over device time in
the traced slice (%). Least time from kernels/dsa_index.py: every
(query, visible position) pair of the tokens decoded and the prompts
prefilled in the slice, 2 x 64 x 128 FLOPs a pair and full layer, against
the index keys read (a decoded token its whole context's; a prompt's once
a chunk), the larger bound. Device time: the operations the
configuration's ``trace_patterns`` name ``dsa_index`` (scores, ReLU sum,
top-k). None where the trace shows no such operation. Source: device
trace. Layer: kernels."""

from lib import latent_trace


def read(run):
    sec = latent_trace.seconds(run, "dsa_index")
    ctx, prompts = latent_trace.work(run)
    if not sec or not (ctx or prompts):
        return None
    k = latent_trace.modules(run)
    least, _ = k["dsa_index"].least_seconds(run["model"], ctx, prompts,
                                            run["peaks"],
                                            k["latent_common"])
    return 100.0 * least / sec
