"""The grouped product over the experts held here: the least a chip must do
for the routed part of the expert layers.

Bytes: an expert that has a token in a step is read once in that step, its
three matrices of ``hidden x moe_intermediate_size`` (47.2 MB in bf16 at
dots3_note's 5120 x 1536); an expert without a token is not read.
``touched`` is the number of (expert, layer, step) triples with a token,
counted by the program (``gllm_moe_experts_touched_total``). FLOPs: each
assignment of a token to a held expert is three products, ``6 x hidden x
moe_intermediate_size``; ``held`` counts them (``gllm_moe_assignments_total
{where="held"}``). Assignments to absent experts are no work here.

A decode step of 64 rows gives an expert 2 tokens: 94 MFLOP for 47 MB, 2
FLOP/B: bytes bind 100-fold. A mixed step of 2112 tokens gives it 66: 66
FLOP/B, still bytes. The tokens' own rows (hidden values in, out) are left
out: a thousandth of the weights at 2 tokens an expert.
"""


def expert_bytes(model, weight_bytes=2):
    return (3 * model["hidden_size"] * model["moe_intermediate_size"]
            * weight_bytes)


def bytes_needed(model, touched):
    return expert_bytes(model) * touched


def flops_needed(model, held):
    return 6 * model["hidden_size"] * model["moe_intermediate_size"] * held


def least_seconds(model, touched, held, peaks):
    by_bytes = bytes_needed(model, touched) / peaks["bytes_per_s"]
    by_flops = flops_needed(model, held) / peaks["flops_per_s"]
    return max(by_bytes, by_flops), ("bytes" if by_bytes >= by_flops
                                     else "flops")
