"""Share of the decode-only steps' device time that the 261120-wide head
takes with the sampler behind it (%): the operations whose result is as
wide as the vocabulary (the ``head`` operations of the configuration's
``trace_patterns``: the logits' product and what the sampler computes over
them; the reductions that leave one value a row are not in it). Their time
over all steps is scaled by the decode-only steps' share of all steps (a
mixed step computes the head for the same rows). The cut in depth colours
it: six layers stand beside a whole head, where the published model has
72. Source: device trace. Layer: runner."""

from lib import mla_trace, par_trace


def read(run):
    share = par_trace.is_family(run) and par_trace.decode_share(run)
    seconds = mla_trace.seconds(run, "head") if share else None
    if not seconds:
        return None
    dec, of_all = share
    return 100.0 * seconds * of_all / (sum(dec) / 1e3)
