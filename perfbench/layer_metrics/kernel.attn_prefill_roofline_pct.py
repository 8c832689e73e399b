"""Ragged prefill attention kernel: least time over device time in the
traced slice (%). The kernel serves a whole mixed step: the prefill chunk
and the decode rows that ride with it. Least time is the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s, where the FLOPs are those of
the prompts whose first token arrived in the slice (kernels/attn_prefill.py,
4 x Hq x D per causal pair) and the bytes add, to the prompts' own, the KV
that the decode rows of the mixed steps read (kernels/attn_decode.py, the
contexts of the tokens decoded in the slice times the mixed steps' share of
all steps). Source: device trace, kernel ``attn_prefill`` of the
configuration's ``trace_patterns``. Layer: kernels."""

from lib import sources


def read(run):
    if run["peaks"] is None or run["slice"] is None:
        return None
    seconds, calls = sources.kernel_seconds(run, "attn_prefill")
    prompts = sources.prefills_in_slice(run)
    if not calls or not prompts:
        return None
    pre = run["load_module"]("kernels", "attn_prefill")
    dec = run["load_module"]("kernels", "attn_decode")
    n_mixed = len(sources.step_ms(run, "prefill"))
    n_decode = len(sources.step_ms(run, "decode"))
    riding = n_mixed / (n_mixed + n_decode)
    ctx = sources.decode_contexts(run)
    model, peaks = run["model"], run["peaks"]
    flops = (pre.flops_needed(model, prompts)
             + riding * dec.flops_needed(model, ctx))
    nbytes = (pre.bytes_needed(model, prompts)
              + riding * dec.bytes_needed(model, ctx))
    least = max(flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"])
    return 100.0 * least / seconds
