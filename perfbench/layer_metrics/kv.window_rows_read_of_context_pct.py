"""KV rows the windowed layers have to read over the rows of the contexts
they belong to (%): the growth of
``gllm_attn_rows_read_total{kind="sliding"}`` (min(context, window) a
sequence, layer and step) over that of ``{kind="full"}`` (the whole
context a sequence, layer and step) brought to the same number of layers.
100 says the window never binds (every context inside it); at contexts of
8-17 k under a window of 4096 it reads ~33. The counters are the
program's, from the batch's ``kv_lens`` at dispatch; no device value is
read. Source: /metrics. Layer: KV manager."""

from lib import swa_trace
from lib.serving import prom_samples

COUNTER = "gllm_attn_rows_read_total"


def growth(run, kind):
    """The counter's growth over the window for one kind of layer, both
    kinds of step."""
    def total(text):
        return sum(v for labels, v in prom_samples(text, COUNTER).items()
                   if 'kind="%s"' % kind in labels)
    return total(run["prom1"]) - total(run["prom0"])


def read(run):
    if not swa_trace.is_family(run) or run["prom0"] is None or (
            run["prom1"] is None):
        return None
    step = swa_trace.step_module(run)
    model = run["model"]
    n_s, n_f = (step.layers(model, k) for k in (swa_trace.SLIDING,
                                                swa_trace.FULL))
    full = growth(run, "full")
    if not full or not n_f or not n_s:
        return None
    return 100.0 * (growth(run, "sliding") / n_s) / (full / n_f)
