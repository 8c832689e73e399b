"""A whole decode step of a windowed-GQA decoder that holds a share of its
experts (command-a-plus-05-2026: a parallel block, 3 windowed layers of 4,
16 of 128 routed experts held beside four shared ones): every weight
matrix that every step uses is read once, the held experts that have a
token are read once (kernels/moe_expert.py at this model's one width), and
each row reads, in every layer, the KV rows its attention sees: its whole
context in a full layer, the last ``sliding_window`` positions of it in a
windowed one (kernels/attn_decode.py over one layer of the kind).

Weight parameters that every step reads, from the published sizes: a
layer's attention (q and o: 2 h heads d; k and v: 2 h kv_heads d), the
router (h x published experts) and the ``num_shared_experts`` shared
experts (3 h intermediate each), and the tied embedding once as the head
(vocab x h; the step's input rows are gathered from the same matrix).
Norm vectors left out. At 4 layers: 4 x 344.5 M + 134.2 M = 1512.0 M
parameters, 3.02 GB in bf16, beside 4 x 16 x 50.3 M = 3.22 G parameters of
held experts, of which a step reads the touched ones (~10.1 of 16 a layer
at 16 rows under a uniform choice: 4.07 GB). The count is the program's
counter, never this reckoning.
"""

SLIDING, FULL = "sliding_attention", "full_attention"


def layers(model, kind):
    return sum(1 for t in model["layer_types"] if t == kind)


def expert_model(model):
    """``model`` as kernels/moe_expert.py and the accepted expert readers
    have to see it: this family's one width under their key."""
    return dict(model, moe_intermediate_size=model["intermediate_size"])


def one_layer(model):
    """``model`` as kernels/attn_decode.py and attn_prefill.py have to see
    it for ONE layer of a kind (the callers multiply by the kind's
    count)."""
    return dict(model, num_hidden_layers=1)


def fixed_weight_params(model):
    """Parameters every decode step reads, whatever the router does."""
    h, d = model["hidden_size"], model["head_dim"]
    attn = (2 * h * model["num_attention_heads"] * d
            + 2 * h * model["num_key_value_heads"] * d)
    published = (model.get("ep_share") or {}).get(
        "num_experts", model["num_experts"])
    moe = (h * published
           + 3 * h * model["intermediate_size"] * model["num_shared_experts"])
    return (model["num_hidden_layers"] * (attn + moe)
            + model["vocab_size"] * h)


def rows_read(model, contexts):
    """(rows the windowed layers read, rows the full layers read) for one
    query token at each of ``contexts`` (tokens attended), summed over the
    layers of the kind."""
    w = model["sliding_window"]
    return (layers(model, SLIDING) * sum(min(c, w) for c in contexts),
            layers(model, FULL) * sum(contexts))


def kv_bytes(model, contexts, decode):
    """K and V of the rows ``rows_read`` counts. ``decode``: the module
    kernels/attn_decode.py."""
    per_row = decode.kv_bytes_per_token(one_layer(model))
    return per_row * sum(rows_read(model, contexts))


def bytes_needed(model, steps, touched_per_layer_step, contexts, expert,
                 decode, weight_bytes=2):
    """``steps`` decode-only steps that touched ``touched_per_layer_step``
    held experts a layer each and decoded rows at ``contexts`` (one entry
    a row and step). ``expert``, ``decode``: the modules
    kernels/moe_expert.py and kernels/attn_decode.py."""
    return (steps * (fixed_weight_params(model) * weight_bytes
                     + expert.bytes_needed(
                         expert_model(model),
                         touched_per_layer_step * model["num_hidden_layers"]))
            + kv_bytes(model, contexts, decode))
