"""Operations one chip's share of a Keye-VL-2.0 language-model stack
requires per trained token, from its sizes alone (``model_params`` of
model_zoo/transformer_lm/hybrid_moe_lm.py with ``s`` layers, softmax
routing and an untied head). Imports nothing: the readers run it in a
process that never starts a backend.

The expert products are counted at their EXPECTATION under even
routing: a token makes ``num_experts_per_tok`` assignments, of which
the share ``experts_held / num_experts`` falls on experts held here
(what a run really routed here is in its ``train_window`` events,
``moe_rows_here`` over ``moe_rows_routed``). Attention is counted over
the pairs the selection KEEPS, which is what the model's equations
require: a kernel that computes every causal pair and masks does more
and is credited with no more. The indexer is counted forward only (it
takes no gradient under the LM loss), over every causal pair: a key
cannot be left out before it has been scored."""


def matmul_params(model_params):
    """Parameters every token meets in a matrix multiplication with a
    gradient: the attention projections and the router of every layer,
    and the untied head's slice once (the embedding LOOKUP is a gather
    and multiplies nothing). Norm weights, the experts and the
    indexers are not here."""
    d = model_params["embed_dim"]
    q = model_params["num_heads"] * model_params["head_dim"]
    kv = model_params["num_kv_heads"] * model_params["head_dim"]
    attention = d * q + 2 * d * kv + q * d
    layers = len(model_params["layer_pattern"])
    return (
        layers * (attention + d * model_params["num_experts"])
        + model_params["vocab_size"] * d
    )


def expert_params_per_token(model_params):
    """Expert parameters a token meets HERE, in expectation: one
    expert's three matrices, times the assignments a token makes, times
    the share of the experts held, for each layer."""
    one_expert = 3 * model_params["embed_dim"] * model_params["expert_dim"]
    return (
        len(model_params["layer_pattern"])
        * model_params["num_experts_per_tok"]
        * model_params["experts_held"]
        / model_params["num_experts"]
        * one_expert
    )


def pairs_kept(seq_len, topk):
    """(query, key) pairs a sequence's selection keeps, and the causal
    pairs it chooses among: query t reads min(t + 1, topk) keys."""
    topk = min(topk, seq_len)
    kept = topk * (topk + 1) // 2 + (seq_len - topk) * topk
    return kept, seq_len * (seq_len + 1) // 2


def indexer_forward_flops_per_token(model_params, seq_len):
    """The indexer's forward in one layer, per token of a sequence of
    ``seq_len``: its three projections (2 FLOPs a parameter) and one
    product of ``indexer_dim`` a head for each causal pair."""
    heads, dim = model_params["indexer_heads"], model_params["indexer_dim"]
    projections = model_params["embed_dim"] * (heads * dim + dim + heads)
    _, causal = pairs_kept(seq_len, model_params["select_topk"])
    return 2 * projections + 2 * heads * dim * causal / seq_len


def train_flops_per_token(model_params, seq_len):
    """Forward + backward of one token at context ``seq_len``: 6 FLOPs
    per matmul parameter it meets (2 forward, 4 backward); attention's
    two products over the KEPT pairs, forward 2 * 2 * head_dim a pair
    and head, backward twice that; the indexers' forward."""
    layers = len(model_params["layer_pattern"])
    kept, _ = pairs_kept(seq_len, model_params["select_topk"])
    attention = (
        12
        * kept
        / seq_len
        * model_params["num_heads"]
        * model_params["head_dim"]
        * layers
    )
    return (
        6 * (matmul_params(model_params) + expert_params_per_token(model_params))
        + attention
        + layers * indexer_forward_flops_per_token(model_params, seq_len)
    )
