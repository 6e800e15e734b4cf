"""Operations one pipeline stage of a Granite 4.0-H stack (Mamba-2 and
attention layers, a dense SwiGLU behind each) requires per trained
token over its slice of the vocabulary, and the parameters it holds,
from its sizes alone (``model_params`` of
model_zoo/transformer_lm/hybrid_moe_lm.py). Imports nothing: the
readers run it in a process that never starts a backend."""


def _layer_parameters(model_params):
    """(matmul parameters, other parameters) of a Mamba layer's mixer,
    of an attention layer's mixer, and of the MLP with the two norms
    every layer has."""
    d = model_params["embed_dim"]
    inner = model_params["ssm_heads"] * model_params["ssm_head_dim"]
    shared = model_params["ssm_groups"] * model_params["ssm_state"]
    heads = model_params["ssm_heads"]
    conv_width = inner + 2 * shared
    q = model_params["num_heads"] * model_params["head_dim"]
    kv = model_params["num_kv_heads"] * model_params["head_dim"]
    mamba = (
        d * (inner + conv_width + heads) + inner * d,
        # taps and bias a channel; dt_bias, A_log, D a head; gated norm
        conv_width * (model_params["ssm_conv_kernel"] + 1) + 3 * heads + inner,
    )
    attention = (d * q + 2 * d * kv + q * d, 0)
    behind = (3 * d * model_params["mlp_dim"], 2 * d)
    return mamba, attention, behind


def _counted(model_params, which):
    pattern = model_params["layer_pattern"]
    if set(pattern) - {"m", "a"} or model_params["num_dense_layers"] != len(pattern):
        raise ValueError(
            "this count knows layers m and a with the dense MLP behind each"
        )
    mamba, attention, behind = _layer_parameters(model_params)
    return (
        pattern.count("m") * mamba[which]
        + pattern.count("a") * attention[which]
        + len(pattern) * behind[which]
    )


def matmul_params(model_params):
    """Parameters every token meets in a matrix multiplication: the
    mixers' projections, the MLPs, and the tied head's slice once (the
    embedding LOOKUP is a gather and multiplies nothing)."""
    return (
        _counted(model_params, 0)
        + model_params["vocab_size"] * model_params["embed_dim"]
    )


def parameters(model_params):
    """Every parameter held here: the matmul parameters (the tied
    table once), the depthwise taps and biases, the per-head scalars
    and the norms, the final norm among them."""
    return (
        matmul_params(model_params)
        + _counted(model_params, 1)
        + model_params["embed_dim"]
    )


def scan_forward_flops_per_token(model_params, seq_len):
    """What the chunked scan of ONE Mamba layer multiplies for a token,
    forward: for each earlier position of its chunk (itself included)
    ``c_t . b_s`` once a group and the pair's weight times ``x_s`` once
    a head; the state read against ``c_t`` and ``x_t (x) b_t`` written
    into it, once a head each. The decays' exponentials are not
    counted."""
    heads, p = model_params["ssm_heads"], model_params["ssm_head_dim"]
    groups, n = model_params["ssm_groups"], model_params["ssm_state"]
    chunk = min(model_params["ssm_chunk"], seq_len)
    pairs = (chunk + 1) / 2
    return pairs * (2 * n * groups + 2 * p * heads) + 4 * n * p * heads


def train_flops_per_token(model_params, seq_len):
    """Forward + backward of one token at context ``seq_len``: 6 FLOPs
    per matmul parameter it meets (2 forward, 4 backward); causal
    attention's two products in each attention layer, 6 * L * heads *
    head_dim (the causal half; the KV heads' repetition multiplies
    nothing); three times the scan's forward products in each Mamba
    layer. No recompute, though the configuration recomputes each
    layer in its backward pass."""
    pattern = model_params["layer_pattern"]
    attention = (
        6
        * seq_len
        * model_params["num_heads"]
        * model_params["head_dim"]
        * pattern.count("a")
    )
    scan = 3 * pattern.count("m") * scan_forward_flops_per_token(
        model_params, seq_len
    )
    return 6 * matmul_params(model_params) + attention + scan
