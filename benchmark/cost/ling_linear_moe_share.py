"""Operations one chip's share of a Ling-3.0-flash stack (Kimi-Delta-
Attention layers and a latent-attention layer, a dense SwiGLU behind
the leading layers and a grouped router's expert layer with a shared
expert behind the others) requires per trained token, and the
parameters it holds, from its sizes alone (``model_params`` of
model_zoo/transformer_lm/hybrid_moe_lm.py with ``k`` and ``l`` layers,
``sigmoid_bias`` routing and an untied head). Imports nothing: the
readers run it in a process that never starts a backend.

The routed experts are counted at their EXPECTATION under even
routing: a token makes ``num_experts_per_tok`` assignments, of which
the share ``experts_held / num_experts`` falls on experts held here
(8 * 8 / 512 an expert layer at the published sizes; what a run really
routed here is in its ``train_window`` events, ``moe_rows_here`` over
``moe_rows_routed``). The shared expert is every token's, whole. The
recurrence is counted as the recurrence, position by position, not as
the chunked form the program runs (whose decay matrices, inverse and
products are the program's way of computing it, and cost more).
Attention over the causal pairs at the two head sizes. No recompute:
what ``remat_layers`` runs a second time is the program's business."""

KDA, MLA = "k", "l"


def _checked(model_params):
    pattern = model_params["layer_pattern"]
    if set(pattern) - {KDA, MLA}:
        raise ValueError("this count knows layers k and l")
    return pattern


def kda_params(model_params):
    """(matmul parameters, other parameters) of one KDA mixer: W_q,
    W_k, W_v, W_f and W_o, and the two projections a head (beta and
    the output gate); three convolutions' taps, ``A_log`` a head,
    ``dt_bias`` a channel, one head's norm weight."""
    d = model_params["embed_dim"]
    heads, width = model_params["kda_heads"], model_params["kda_head_dim"]
    inner = heads * width
    return (
        5 * d * inner + 2 * d * heads,
        3 * inner * model_params["kda_conv_kernel"] + heads + inner + width,
    )


def mla_params(model_params):
    """(matmul parameters, other parameters) of one MLA mixer: W_q over
    [nope | rope], W_kva down to [latent | rope], W_kvb up to [nope |
    v], W_o; the latent's norm weight."""
    d, heads = model_params["embed_dim"], model_params["num_heads"]
    rank = model_params["mla_kv_rank"]
    nope, rope = model_params["mla_nope_dim"], model_params["mla_rope_dim"]
    v = model_params["mla_v_dim"]
    return (
        d * heads * (nope + rope)
        + d * (rank + rope)
        + rank * heads * (nope + v)
        + heads * v * d,
        rank,
    )


def _one_expert(model_params):
    return 3 * model_params["embed_dim"] * model_params["expert_dim"]


def _shared_expert(model_params):
    return 3 * model_params["embed_dim"] * model_params.get(
        "shared_expert_dim", 0
    )


def _layers(model_params):
    pattern = _checked(model_params)
    dense = model_params["num_dense_layers"]
    return pattern, dense, len(pattern) - dense


def matmul_params(model_params):
    """Parameters every token meets in a matrix multiplication: the
    mixers' projections, the dense FF, each expert layer's router and
    shared expert, and the untied head's slice once (the embedding
    LOOKUP is a gather and multiplies nothing). The routed experts are
    not here."""
    pattern, dense, sparse = _layers(model_params)
    d = model_params["embed_dim"]
    return (
        pattern.count(KDA) * kda_params(model_params)[0]
        + pattern.count(MLA) * mla_params(model_params)[0]
        + dense * 3 * d * model_params["mlp_dim"]
        + sparse * (d * model_params["num_experts"] + _shared_expert(model_params))
        + model_params["vocab_size"] * d
    )


def expert_params_per_token(model_params):
    """Routed-expert parameters a token meets HERE, in expectation: one
    expert's three matrices, times the assignments a token makes, times
    the share of the experts held, for each expert layer."""
    _, _, sparse = _layers(model_params)
    return (
        sparse
        * model_params["num_experts_per_tok"]
        * model_params["experts_held"]
        / model_params["num_experts"]
        * _one_expert(model_params)
    )


def parameters_held(model_params):
    """Every parameter this chip holds: what its state is 12 bytes of
    (f32 parameter and both AdamW moments)."""
    pattern, dense, sparse = _layers(model_params)
    d = model_params["embed_dim"]
    return (
        pattern.count(KDA) * sum(kda_params(model_params))
        + pattern.count(MLA) * sum(mla_params(model_params))
        + dense * 3 * d * model_params["mlp_dim"]
        + sparse
        * (
            d * model_params["num_experts"]
            + _shared_expert(model_params)
            + model_params["experts_held"] * _one_expert(model_params)
        )
        + len(pattern) * 2 * d  # the two norms of a layer
        + 2 * model_params["vocab_size"] * d  # embedding, untied head
        + d  # the final norm
    )


def recurrence_forward_flops_per_token(model_params):
    """What ONE KDA layer's recurrence does for a position, forward, a
    head: the state (d_k x d_v) decayed a channel (1 a state element),
    read with k (2), written with k (x) w (2) and read with q (2): 7
    d_k d_v. The gates' exponentials, the norms and the convolutions'
    taps are not counted."""
    width = model_params["kda_head_dim"]
    return 7 * width * width * model_params["kda_heads"]


def train_flops_per_token(model_params, seq_len):
    """Forward + backward of one token at context ``seq_len``: 6 FLOPs
    per matmul parameter it meets (2 forward, 4 backward); three times
    the recurrence's forward in each KDA layer; causal attention in each
    MLA layer, forward ``q k^T`` at ``nope + rope`` and ``p v`` at
    ``v_dim`` over the (L + 1) / 2 pairs a token reads on average,
    backward twice that."""
    pattern = _checked(model_params)
    attention = (
        pattern.count(MLA)
        * 6
        * (seq_len + 1)
        / 2
        * model_params["num_heads"]
        * (
            model_params["mla_nope_dim"]
            + model_params["mla_rope_dim"]
            + model_params["mla_v_dim"]
        )
    )
    recurrence = (
        3 * pattern.count(KDA) * recurrence_forward_flops_per_token(model_params)
    )
    return (
        6 * (matmul_params(model_params) + expert_params_per_token(model_params))
        + attention
        + recurrence
    )
