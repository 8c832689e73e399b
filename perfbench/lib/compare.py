"""The comparison that decides ``correct``.

Two numbers, each the root-mean-square difference between the served
logprobs and the plain reference's, as a share of the SPREAD of the
reference's logprobs over the long probe (their standard deviation): the
configurations differ 40-fold in logit scale (a head tied to a
unit-variance embedding gives logits of std ~ sqrt(hidden); a head of its
own gives ~1), and the spread is that scale. The arithmetic is
``chip_smoke.py``'s (PR 21), with the root mean square over some thousand
positions in place of the maximum: it is steady from seed to seed, which a
maximum is not, and it separates bf16 from the precision below.

- ``prefill_rel_rms``: the logprob of each token of the long probe given
  the tokens before it (the probe is longer than --maxp: chunked prefill).
- ``decode_rel_rms``: at each decoded step, the logprobs of the server's
  top tokens, against the reference's for the same tokens on prompt +
  output (decode through the cache). Tokens are not compared: with random
  weights the largest logit changes on rounding.

The limits are the configuration's (``correct`` in its file), set from
sound runs and from the lower-precision control as PERF.md records.
"""

import math
import statistics


def rel_rms(served, ref, spread):
    diffs = [a - b for a, b in zip(served, ref)]
    return math.sqrt(sum(d * d for d in diffs) / len(diffs)) / spread


def verdict(served_prefill, ref_prefill, served_tops, ref_tops, limits):
    """``served_tops``: per decode step {token: logprob}; ``ref_tops``: per
    step the reference's logprobs for those tokens, in sorted token
    order."""
    lines, ok = [], True
    finite = all(v is not None and math.isfinite(v) for v in served_prefill)
    finite = finite and all(math.isfinite(v) for step in served_tops
                            for v in step.values())
    lines.append(f"served logprobs finite: {finite} (limit: all)")
    spread = statistics.pstdev(ref_prefill)
    numbers = {"spread": spread}
    if finite:
        dec_s = [step[t] for step in served_tops for t in sorted(step)]
        dec_r = [v for step in ref_tops for v in step]
        worst = max(abs(a - b) for a, b in zip(served_prefill, ref_prefill))
        numbers.update(
            prefill_rel_rms=rel_rms(served_prefill, ref_prefill, spread),
            decode_rel_rms=rel_rms(dec_s, dec_r, spread),
            prefill_rel_max=worst / spread)
        for name in ("prefill_rel_rms", "decode_rel_rms"):
            limit = limits[name + "_max"]
            good = numbers[name] <= limit
            ok = ok and good
            lines.append(f"{name} = {numbers[name]:.6f} (limit {limit}) "
                         f"{'ok' if good else 'NOT CORRECT'}")
        lines.append(f"reference logprob spread = {spread:.4f}; "
                     f"prefill_rel_max = {numbers['prefill_rel_max']:.6f} "
                     f"(not limited); positions: {len(ref_prefill)} "
                     f"prefill, {len(dec_r)} decode")
    return {"correct": ok and finite, "numbers": numbers, "lines": lines}
