"""Operations and bytes one call of the flash kernels UNDER A SELECTION
(``edl_flash_sel_*``, elasticdl_tpu/ops/flash_attention.py
``flash_attention_selected``) has to do, from shapes alone;
``flops.roofline`` turns them into the chip's least time.

What a call has to do is attention over the pairs its selection KEEPS:
query t of a sequence reads min(t + 1, topk) keys, so the count is
static, from the length and the top-k alone. A kernel that computes
every causal pair and masks most of them away does 2.29 times that at
L = 8,192 and topk = 2,048 (33.6M causal pairs a sequence, 14.7M kept)
and is credited with no more: it reads at most 44% of what the dense
kernel reads on the same shapes, and no kernel can pass 100%."""

# matmuls per kept (query, key) pair and head, each 2 * head_dim FLOPs:
# forward QK^T and PV; dq recomputes QK^T, then dO V^T and dS K; dkv
# recomputes QK^T, then dO V^T, P^T dO and dS^T Q
MATMULS = {
    "edl_flash_sel_fwd": 2,
    "edl_flash_sel_bwd_dq": 3,
    "edl_flash_sel_bwd_dkv": 4,
}
# (b, l, h, d)-sized operands read or written, and f32 rows of length L
# (logsumexp, delta): fwd reads q k v, writes o and lse; dq reads q k v
# do lse delta, writes dq; dkv reads the same, writes dk dv
TENSORS = {
    "edl_flash_sel_fwd": (4, 1),
    "edl_flash_sel_bwd_dq": (5, 2),
    "edl_flash_sel_bwd_dkv": (6, 2),
}


def pairs_kept(seq_len, topk):
    """Pairs one sequence's selection keeps."""
    topk = min(topk, seq_len)
    return topk * (topk + 1) // 2 + (seq_len - topk) * topk


def selected_kernel_cost(
    kernel, batch_heads, seq_len, head_dim, topk, heads, itemsize=2
):
    """(FLOPs, bytes) one call of ``kernel`` has to do: its matmuls
    over the kept pairs of every (sequence, head); its tensors once,
    and the selection, one int8 (L, L) a sequence (``batch_heads //
    heads`` of them), once."""
    flops = (
        MATMULS[kernel] * 2 * batch_heads * pairs_kept(seq_len, topk) * head_dim
    )
    tensors, rows = TENSORS[kernel]
    nbytes = (
        batch_heads * seq_len * (tensors * head_dim * itemsize + rows * 4)
        + (batch_heads // heads) * seq_len**2
    )
    return flops, nbytes
