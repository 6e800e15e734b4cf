"""Operations one chip's share of a SmallThinker stack (global and
windowed attention layers, an expert layer behind each, routed from the
attention's input) requires per trained token, and the parameters it
holds, from its sizes alone (``model_params`` of
model_zoo/transformer_lm/hybrid_moe_lm.py with ``a`` and ``w`` layers,
softmax routing and an untied head). Imports nothing: the readers run
it in a process that never starts a backend.

The expert products are counted at their EXPECTATION under even
routing: a token makes ``num_experts_per_tok`` assignments, of which
the share ``experts_held / num_experts`` falls on experts held here
(what a run really routed here is in its ``train_window`` events,
``moe_rows_here`` over ``moe_rows_routed``). Attention is counted over
the pairs the model's equations read: every causal pair in a global
layer, the pairs inside the band in a window layer. No recompute: what
``remat_layers`` runs a second time is the program's business."""


def _attention_params(model_params):
    d = model_params["embed_dim"]
    q = model_params["num_heads"] * model_params["head_dim"]
    kv = model_params["num_kv_heads"] * model_params["head_dim"]
    return d * q + 2 * d * kv + q * d


def matmul_params(model_params):
    """Parameters every token meets in a matrix multiplication: the
    attention projections and the router of every layer, and the untied
    head's slice once (the embedding LOOKUP is a gather and multiplies
    nothing). Norm weights and the experts are not here."""
    pattern = model_params["layer_pattern"]
    if set(pattern) - {"a", "w"} or model_params["num_dense_layers"]:
        raise ValueError(
            "this count knows layers a and w with the expert layer behind each"
        )
    d = model_params["embed_dim"]
    return (
        len(pattern)
        * (_attention_params(model_params) + d * model_params["num_experts"])
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


def parameters_held(model_params):
    """Every parameter this chip holds: what its state is 12 bytes of
    (f32 parameter and both AdamW moments)."""
    d = model_params["embed_dim"]
    one_expert = 3 * d * model_params["expert_dim"]
    layer = (
        _attention_params(model_params)
        + d * model_params["num_experts"]
        + model_params["experts_held"] * one_expert
        + 2 * d  # the two norms
    )
    return (
        len(model_params["layer_pattern"]) * layer
        + 2 * model_params["vocab_size"] * d  # embedding, untied head
        + d  # the final norm
    )


def pairs_read(seq_len, window):
    """(query, key) pairs one sequence reads in a window layer, query t
    reading min(t + 1, window) keys, and in a global layer (the causal
    pairs)."""
    window = min(window, seq_len)
    return (
        window * (window + 1) // 2 + (seq_len - window) * window,
        seq_len * (seq_len + 1) // 2,
    )


def train_flops_per_token(model_params, seq_len):
    """Forward + backward of one token at context ``seq_len``: 6 FLOPs
    per matmul parameter it meets (2 forward, 4 backward); attention's
    two products over the pairs its layer reads, forward 2 * 2 *
    head_dim a pair and head, backward twice that."""
    pattern = model_params["layer_pattern"]
    band, causal = pairs_read(seq_len, model_params["attention_window"])
    pairs = pattern.count("w") * band + pattern.count("a") * causal
    attention = (
        12
        * pairs
        / seq_len
        * model_params["num_heads"]
        * model_params["head_dim"]
    )
    return (
        6 * (matmul_params(model_params) + expert_params_per_token(model_params))
        + attention
    )
