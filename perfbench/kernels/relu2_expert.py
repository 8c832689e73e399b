"""The grouped product over the held experts of the relu^2 form (two
matrices, ``relu(u W_up)^2 W_down``): the least a chip must do for the
routed part of the expert layers. kernels/moe_expert.py's count at two
matrices in place of the gated form's three.

Bytes: an expert that has a token in a step is read once in that step, its
two matrices of ``hidden x moe_intermediate_size`` (19.96 MB in bf16 at
Nemotron 3 Nano's 2688 x 1856); an expert without a token is not read.
``touched`` is the number of (expert, layer, step) triples with a token,
counted by the program (``gllm_moe_experts_touched_total``). FLOPs: each
assignment of a token to a held expert is two products, ``4 x hidden x
moe_intermediate_size``; ``held`` counts them
(``gllm_moe_assignments_total{where="held"}``).

A decode step of 64 rows gives an expert 3 tokens: 60 MFLOP for 20 MB, 3
FLOP/B: bytes bind. The tokens' own rows are left out.
"""


def expert_bytes(model, weight_bytes=2):
    return (2 * model["hidden_size"] * model["moe_intermediate_size"]
            * weight_bytes)


def bytes_needed(model, touched):
    return expert_bytes(model) * touched


def flops_needed(model, held):
    return 4 * model["hidden_size"] * model["moe_intermediate_size"] * held


def least_seconds(model, touched, held, peaks):
    by_bytes = bytes_needed(model, touched) / peaks["bytes_per_s"]
    by_flops = flops_needed(model, held) / peaks["flops_per_s"]
    return max(by_bytes, by_flops), ("bytes" if by_bytes >= by_flops
                                     else "flops")
