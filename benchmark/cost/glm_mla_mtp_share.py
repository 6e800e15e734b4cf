"""Operations one chip's share of a GLM-4.7-Flash stack (latent
attention with a low-rank query in every layer, a dense SwiGLU behind
the leading layers, a sigmoid router's expert layer with a shared
expert behind the others, and one multi-token-prediction module behind
the last layer) requires per trained token, and the parameters it
holds, from its sizes alone (``model_params`` of
model_zoo/transformer_lm/hybrid_moe_lm.py with ``l`` layers,
``mla_q_rank``, ``mtp_layers`` and an untied head). Imports nothing: the
readers run it in a process that never starts a backend.

The prediction module is counted as what it is: one more layer of the
pattern's last kind (its mixer, its router, its shared expert and its
routed share), the projection ``W_M`` of ``2 embed_dim x embed_dim``,
and a SECOND product against the head's slice: a trained token meets
the head twice. The routed experts are counted at their EXPECTATION
under even routing: a token makes ``num_experts_per_tok`` assignments,
of which the share ``experts_held / num_experts`` falls on experts held
here (4 * 8 / 64 an expert layer at the published sizes; what a run
really routed here is in its ``train_window`` events, ``moe_rows_here``
over ``moe_rows_routed``): the experts a token is computed by, not the
experts held. The shared expert is every token's, whole. Attention over
the causal pairs, q and k at ``mla_nope_dim + mla_rope_dim`` and v at
``mla_v_dim``. No recompute: what ``remat_layers`` runs a second time,
and the kernels' second pass over ``q k^T``, are the program's
business."""

MLA = "l"


def _layers(model_params):
    """(latent-attention layers, dense halves, expert halves), the
    prediction module's layer among them: it is of the pattern's last
    kind, feed-forward half included."""
    pattern = model_params["layer_pattern"]
    if set(pattern) - {MLA}:
        raise ValueError("this count knows layers of kind l")
    dense = model_params["num_dense_layers"]
    module = model_params.get("mtp_layers", 0)
    module_is_dense = dense == len(pattern)
    return (
        len(pattern) + module,
        dense + module * module_is_dense,
        len(pattern) - dense + module * (not module_is_dense),
    )


def mla_params(model_params):
    """(matmul parameters, other parameters) of one mixer: W_qa down to
    the query's latent, W_qb up to [nope | rope] a head, W_kva down to
    [latent | rope], W_kvb up to [nope | v] a head, W_o; the two
    latents' norm weights."""
    d, heads = model_params["embed_dim"], model_params["num_heads"]
    q_rank, rank = model_params["mla_q_rank"], model_params["mla_kv_rank"]
    nope, rope = model_params["mla_nope_dim"], model_params["mla_rope_dim"]
    v = model_params["mla_v_dim"]
    return (
        d * q_rank
        + q_rank * heads * (nope + rope)
        + d * (rank + rope)
        + rank * heads * (nope + v)
        + heads * v * d,
        q_rank + rank,
    )


def _one_expert(model_params):
    return 3 * model_params["embed_dim"] * model_params["expert_dim"]


def _shared_expert(model_params):
    return 3 * model_params["embed_dim"] * model_params.get(
        "shared_expert_dim", 0
    )


def _module_projection(model_params):
    d = model_params["embed_dim"]
    return model_params.get("mtp_layers", 0) * 2 * d * d


def matmul_params(model_params):
    """Parameters every token meets in a matrix multiplication: the
    mixers' projections, the dense FF, each expert layer's router and
    shared expert, the module's projection, and the untied head's slice
    once for the trunk and once more for the module (the embedding
    LOOKUPs are gathers and multiply nothing). The routed experts are
    not here."""
    mixers, dense, sparse = _layers(model_params)
    d = model_params["embed_dim"]
    return (
        mixers * mla_params(model_params)[0]
        + dense * 3 * d * model_params["mlp_dim"]
        + sparse * (d * model_params["num_experts"] + _shared_expert(model_params))
        + _module_projection(model_params)
        + (1 + model_params.get("mtp_layers", 0)) * model_params["vocab_size"] * d
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
    mixers, dense, sparse = _layers(model_params)
    d = model_params["embed_dim"]
    return (
        mixers * sum(mla_params(model_params))
        + dense * 3 * d * model_params["mlp_dim"]
        + sparse
        * (
            d * model_params["num_experts"]
            + _shared_expert(model_params)
            + model_params["experts_held"] * _one_expert(model_params)
        )
        + mixers * 2 * d  # the two norms of a layer
        + _module_projection(model_params)
        # the module's two input norms and its output norm
        + model_params.get("mtp_layers", 0) * 3 * d
        + 2 * model_params["vocab_size"] * d  # embedding, untied head
        + d  # the final norm
    )


def attention_flops_per_token(model_params, seq_len):
    """Forward + backward of ONE layer's causal attention for a token
    at context ``seq_len``: forward ``q k^T`` at ``nope + rope`` and
    ``p v`` at ``v_dim`` over the (L + 1) / 2 pairs a token reads on
    average, 2 FLOPs a pair and a channel; backward twice that."""
    return (
        6
        * (seq_len + 1)
        / 2
        * model_params["num_heads"]
        * (
            model_params["mla_nope_dim"]
            + model_params["mla_rope_dim"]
            + model_params["mla_v_dim"]
        )
    )


def train_flops_per_token(model_params, seq_len):
    """Forward + backward of one token at context ``seq_len``: 6 FLOPs
    per matmul parameter it meets (2 forward, 4 backward), and causal
    attention in every layer, the module's among them."""
    mixers, _, _ = _layers(model_params)
    return 6 * (
        matmul_params(model_params) + expert_params_per_token(model_params)
    ) + mixers * attention_flops_per_token(model_params, seq_len)
