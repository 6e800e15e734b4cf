"""Operations and bytes one call of the flash kernels at UNEQUAL head
sizes (``edl_flash_mla_*``, elasticdl_tpu/ops/flash_attention.py
``flash_attention(q, k, v)`` with ``v.shape[-1] != q.shape[-1]``: a
latent attention in its expanded form) has to do, from shapes alone;
``flops.roofline`` turns them into the chip's least time.

q and k are ``d_qk`` wide (192: 128 without positions and 64 rotated),
v, o and dO ``d_v`` (128). A product with k or q (``q k^T`` and its
transposes ``dS K`` and ``dS^T Q``) costs 2 * d_qk FLOPs a causal
(query, key) pair and head, one with v or dO (``P V``, ``dO V^T``,
``P^T dO``) 2 * d_v. The pairs are the causal ones exactly, L (L + 1) /
2 a sequence and head. 192 is one and a half lane tiles: whatever the
kernel pads it to is overhead and is not counted here, so a share of
100% is out of reach by that much."""

# products a causal pair and head, (with q or k, with v or dO): forward
# QK^T | PV; dq recomputes QK^T, then dO V^T and dS K; dkv recomputes
# QK^T, then dO V^T, P^T dO and dS^T Q
MATMULS = {
    "edl_flash_mla_fwd": (1, 1),
    "edl_flash_mla_bwd_dq": (2, 1),
    "edl_flash_mla_bwd_dkv": (2, 2),
}
# (b, l, h, .)-sized operands read or written, (d_qk wide, d_v wide),
# and f32 rows of length L (logsumexp, delta): fwd reads q k | v,
# writes o and lse; dq reads q k | v dO and lse delta, writes dq; dkv
# reads the same, writes dk | dv. Each once
TENSORS = {
    "edl_flash_mla_fwd": ((2, 2), 1),
    "edl_flash_mla_bwd_dq": ((3, 2), 2),
    "edl_flash_mla_bwd_dkv": ((3, 3), 2),
}


def causal_pairs(seq_len):
    return seq_len * (seq_len + 1) // 2


def unequal_kernel_cost(kernel, batch_heads, seq_len, d_qk, d_v, itemsize=2):
    """(FLOPs, bytes) one call of ``kernel`` has to do: its products
    over the causal pairs of every (sequence, head), each at its own
    width; its tensors once."""
    wide, narrow = MATMULS[kernel]
    flops = (
        2 * batch_heads * causal_pairs(seq_len) * (wide * d_qk + narrow * d_v)
    )
    (wide, narrow), rows = TENSORS[kernel]
    nbytes = batch_heads * seq_len * (
        (wide * d_qk + narrow * d_v) * itemsize + rows * 4
    )
    return flops, nbytes
