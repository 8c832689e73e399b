"""What the readers of a latent-attention cell (dots3_note) share: the
kernels' modules, the work of the traced slice, the program's counters,
and the checks that keep a shape pattern from going blind in silence.

The cell's device operations are XLA's, found in the trace by the shapes
in their HLO lines (the configuration's ``trace_patterns``), but for the
grouped expert product, which XLA gives a name of its own
(``ragged-dot``). ``seconds`` raises where a pattern matches nothing while
the step programs it belongs to ran: a refusion or a change of widths has
then moved the operations out of the pattern's sight, and a number would
be wrong, not missing.
"""

from lib import sources


class PatternBlind(RuntimeError):
    pass


def modules(run):
    load = run["load_module"]
    return {name: load("kernels", name)
            for name in ("latent_common", "dsa_index", "sparse_mla",
                         "swa_mla", "moe_expert", "latent_moe_decode_step")}


def configured(run, kernel):
    return kernel in run["config"].get("trace_patterns", {}).get("kernels",
                                                                 {})


def steps(run):
    """(decode-only step programs, mixed ones) of the traced slice."""
    return (len(sources.step_ms(run, "decode")),
            len(sources.step_ms(run, "prefill")))


def seconds(run, kernel, mixed_only=False):
    """Device seconds of ``kernel`` in the slice; None where the trace or
    the pattern is not there (a parent without the program's part)."""
    if run["peaks"] is None or run["slice"] is None or not run["trace"]:
        return None
    if not configured(run, kernel):
        return None
    n_dec, n_mixed = steps(run)
    sec, calls = sources.kernel_seconds(run, kernel)
    ran = n_mixed if mixed_only else n_dec + n_mixed
    if ran and not calls:
        raise PatternBlind(
            f"{ran} step programs ran in the slice and the {kernel!r} "
            "pattern matched no operation (configs/<name>.json "
            "trace_patterns)")
    return sec or None


def work(run):
    """(contexts of the tokens decoded in the slice, prompts prefilled in
    it)."""
    return sources.decode_contexts(run), sources.prefills_in_slice(run)


def per_layer_step(run, kind):
    """Held experts touched per expert layer and step of ``kind`` (decode |
    mixed), from the counters' growth over the tail; None without them."""
    label = '{step="%s"}' % kind
    touched = sources.counter_delta(run, "gllm_moe_experts_touched_total",
                                    label)
    layers = sources.counter_delta(run, "gllm_moe_layer_steps_total", label)
    if not layers or touched is None:
        return None
    return touched / layers


def held_per_layer_step(run):
    """Assignments to held experts per expert layer and step, all kinds."""
    held = sources.counter_delta(run, "gllm_moe_assignments_total",
                                 '{where="held"}')
    layers = sources.counter_delta(run, "gllm_moe_layer_steps_total")
    if not layers or held is None:
        return None
    return held / layers
