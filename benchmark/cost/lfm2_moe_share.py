"""Operations one chip's share of an LFM2-MoE stack requires per trained
token, from its sizes alone (``model_params`` of
model_zoo/transformer_lm/hybrid_moe_lm.py). Imports nothing: the
readers run it in a process that never starts a backend.

The expert products are counted at their EXPECTATION under even
routing: a token makes ``num_experts_per_tok`` assignments, of which
the share ``experts_held / num_experts`` falls on experts held here.
What a run really routed here is in its ``train_window`` events
(``moe_rows_here`` over ``moe_rows_routed``); the selection bias keeps
it near this share."""


def matmul_params(model_params):
    """Parameters every token meets in a matrix multiplication: the
    convolution and attention projections, the dense MLP of the leading
    layers, each expert layer's router, and the tied head's slice once
    (the embedding LOOKUP is a gather and multiplies nothing). The
    depthwise taps, the norm weights and the experts are not here."""
    d = model_params["embed_dim"]
    q = model_params["num_heads"] * model_params["head_dim"]
    kv = model_params["num_kv_heads"] * model_params["head_dim"]
    pattern = model_params["layer_pattern"]
    dense = model_params["num_dense_layers"]
    conv = 3 * d * d + d * d
    attention = d * q + 2 * d * kv + q * d
    return (
        pattern.count("c") * conv
        + pattern.count("a") * attention
        + dense * 3 * d * model_params["mlp_dim"]
        + (len(pattern) - dense) * d * model_params["num_experts"]
        + model_params["vocab_size"] * d
    )


def expert_params_per_token(model_params):
    """Expert parameters a token meets HERE, in expectation: one
    expert's three matrices, times the assignments a token makes, times
    the share of the experts held, for each expert layer."""
    one_expert = 3 * model_params["embed_dim"] * model_params["expert_dim"]
    layers = len(model_params["layer_pattern"]) - model_params["num_dense_layers"]
    return (
        layers
        * model_params["num_experts_per_tok"]
        * model_params["experts_held"]
        / model_params["num_experts"]
        * one_expert
    )


def train_flops_per_token(model_params, seq_len):
    """Forward + backward of one token at context ``seq_len``: 6 FLOPs
    per matmul parameter it meets (2 forward, 4 backward), plus causal
    attention's two products in each attention layer, 6 * L * heads *
    head_dim (the causal half; the KV heads' repetition multiplies
    nothing)."""
    attention = (
        6
        * seq_len
        * model_params["num_heads"]
        * model_params["head_dim"]
        * model_params["layer_pattern"].count("a")
    )
    return (
        6 * (matmul_params(model_params) + expert_params_per_token(model_params))
        + attention
    )
