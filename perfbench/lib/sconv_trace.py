"""What the readers of the short-convolution cell (LFM2-24B-A2B) share: the
family's test, the operator's device time in the decode-only steps of a
traced slice, and the check that keeps its pattern from going blind in
silence.

The operator is XLA's: fusions, gathers and a scatter, which the v5e trace
shows as bare HLO lines with their operands' types. The configuration's
``trace_patterns`` find them by the shapes only the operator has:
``sconv_in_decode`` is the in-projection's product (its RESULT is [128, 3
x hidden]: one an operator call, the witness), ``sconv_decode`` every
operation that produces or reads that array, a window ([128, 1 | 2,
hidden] float32) or the window pool: in-projection to out-projection, the
two gates and the taps fused where XLA fuses them. Both name the 128 rows
only a decode-only step has. A decode-only step calls the operator once a
"conv" layer: where the witness matched fewer times, a change of widths
or a refusion has moved the operations out of the pattern's sight, and a
number would be wrong, not missing: the reader raises. A reader reads
nothing (None) where the model is another family's or the trace is not
there: a parent without the program's part.
"""

from lib import mla_trace, sources

SLACK = 0.75    # a slice's edges cut a step: counts may differ a little


class PatternBlind(RuntimeError):
    pass


def is_family(run):
    return (run["model"].get("model_type") == "lfm2_moe"
            and run["peaks"] is not None and run["slice"] is not None
            and bool(run["trace"])
            and "sconv_in_decode" in run["config"].get(
                "trace_patterns", {}).get("kernels", {}))


def decode_share(run):
    """(device ms of the decode-only steps, their share of all traced
    steps), or None where the slice holds no decode-only step."""
    dec = sources.step_ms(run, "decode")
    return (dec, 1.0 - mla_trace.mixed_share(run)) if dec else None


def operator_seconds(run, sconv):
    """Device seconds of the operator's operations in the decode-only
    steps of the slice, or None where none ran. ``sconv``: the module
    kernels/sconv.py."""
    n_dec = len(sources.step_ms(run, "decode"))
    if not n_dec:
        return None
    layers = sconv.conv_layers(run["model"])
    _, calls = sources.kernel_seconds(run, "sconv_in_decode")
    if calls < SLACK * layers * n_dec:
        raise PatternBlind(
            f"{n_dec} decode-only steps ran in the slice, {layers} conv "
            f"layers each, and the 'sconv_in_decode' pattern matched "
            f"{calls} operations: fewer operator calls a step than layers "
            "(configs/<name>.json trace_patterns)")
    sec, _ = sources.kernel_seconds(run, "sconv_decode")
    return sec or None
