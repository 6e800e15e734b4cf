"""Operations and bytes the algorithm requires, from shapes alone.

The yardstick's arithmetic: nothing here reads the program. A model's
FLOPs per token count what forward and backward REQUIRE and no
recompute (the flash backward's second pass over QK^T is the kernel's
business, not the model's), so a kernel that recomputes more does not
look more useful. A kernel's roofline counts what that kernel call has
to do given its inputs.
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The published peaks of ``device_kind`` (benchmark/peaks.json).
    A device that is not in the table is an error, not a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            "no published peaks for device kind %r in benchmark/peaks.json"
            % device_kind
        )
    return table[device_kind]


def matmul_params(model_params):
    """Parameters that sit in a matrix multiplication: the four
    attention projections and the two MLP matrices of every layer, and
    the tied head's V x d once (the embedding LOOKUP is a gather and
    multiplies nothing). Norm scales and biases are not matmuls."""
    d = model_params["embed_dim"]
    inner = model_params["num_heads"] * model_params["head_dim"]
    per_layer = 4 * d * inner + 2 * d * model_params["mlp_dim"]
    return (
        model_params["num_layers"] * per_layer
        + model_params["vocab_size"] * d
    )


def train_flops_per_token(model_params, seq_len):
    """Forward + backward of one token at context ``seq_len``:
    6 FLOPs per matmul parameter (2 forward, 4 backward), plus causal
    attention's two products, QK^T and PV: forward 2 * 2 * L * hd / 2
    per head (the causal half), backward twice that, so
    6 * L * heads * head_dim a layer."""
    attention = (
        6
        * seq_len
        * model_params["num_heads"]
        * model_params["head_dim"]
        * model_params["num_layers"]
    )
    return 6 * matmul_params(model_params) + attention


def mfu_percent(tokens_per_s_per_chip, model_params, seq_len, device_kind):
    return (
        100.0
        * tokens_per_s_per_chip
        * train_flops_per_token(model_params, seq_len)
        / peaks(device_kind)["bf16_flops_per_s"]
    )


# matmuls of L x L x D a causal flash kernel call performs per (batch,
# head), each 2 * L^2 * D / 2 FLOPs: forward QK^T and PV; dq recomputes
# QK^T, then dO V^T and dS K; dkv recomputes QK^T, then dO V^T, P^T dO
# and dS^T Q
_FLASH_MATMULS = {
    "edl_flash_fwd": 2,
    "edl_flash_bwd_dq": 3,
    "edl_flash_bwd_dkv": 4,
}
# (b, l, h, d)-sized bf16 operands read or written, and f32 rows of
# length L (logsumexp, delta): fwd reads q k v, writes o and lse; dq
# reads q k v do lse delta, writes dq; dkv reads the same, writes dk dv
_FLASH_TENSORS = {
    "edl_flash_fwd": (4, 1),
    "edl_flash_bwd_dq": (5, 2),
    "edl_flash_bwd_dkv": (6, 2),
}


def flash_kernel_cost(kernel, batch_heads, seq_len, head_dim, itemsize=2):
    """(FLOPs, bytes) one call of ``kernel`` has to do."""
    flops = _FLASH_MATMULS[kernel] * batch_heads * seq_len**2 * head_dim
    tensors, rows = _FLASH_TENSORS[kernel]
    nbytes = batch_heads * seq_len * (
        tensors * head_dim * itemsize + rows * 4
    )
    return flops, nbytes


def roofline(flops, nbytes, device_kind):
    """The least seconds the chip could take, and which peak sets it."""
    peak = peaks(device_kind)
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
