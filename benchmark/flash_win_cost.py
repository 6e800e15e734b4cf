"""Operations and bytes one call of the flash kernels UNDER A WINDOW
(``edl_flash_win_*``, elasticdl_tpu/ops/flash_attention.py
``flash_attention(..., window=W)``) has to do, from shapes alone;
``flops.roofline`` turns them into the chip's least time.

What a call has to do is attention over the pairs INSIDE the band:
query t of a sequence reads min(t + 1, window) keys, so the count is
static, from the length and the window alone: 58,722,304 of the
134,225,920 causal pairs at L = 16,384 and a window of 4,096 (43.7%).
A kernel that computes the whole causal triangle and masks is credited
with no more than that share; one that computes the band by whole
sub-blocks (1.06 times the band at these shapes) reads that much under
100% before anything else is lost."""

# matmuls per (query, key) pair inside the band and head, each 2 *
# head_dim FLOPs: forward QK^T and PV; dq recomputes QK^T, then dO V^T
# and dS K; dkv recomputes QK^T, then dO V^T, P^T dO and dS^T Q
MATMULS = {
    "edl_flash_win_fwd": 2,
    "edl_flash_win_bwd_dq": 3,
    "edl_flash_win_bwd_dkv": 4,
}
# (b, l, h, d)-sized operands read or written, and f32 rows of length L
# (logsumexp, delta): fwd reads q k v, writes o and lse; dq reads q k v
# do lse delta, writes dq; dkv reads the same, writes dk dv. Each once:
# a key tile that five query tiles read is counted one time
TENSORS = {
    "edl_flash_win_fwd": (4, 1),
    "edl_flash_win_bwd_dq": (5, 2),
    "edl_flash_win_bwd_dkv": (6, 2),
}


def pairs_in_band(seq_len, window):
    """Pairs one sequence keeps under a window."""
    window = min(window, seq_len)
    return window * (window + 1) // 2 + (seq_len - window) * window


def windowed_kernel_cost(
    kernel, batch_heads, seq_len, head_dim, window, itemsize=2
):
    """(FLOPs, bytes) one call of ``kernel`` has to do: its matmuls
    over the band's pairs of every (sequence, head); its tensors once."""
    flops = (
        MATMULS[kernel]
        * 2
        * batch_heads
        * pairs_in_band(seq_len, window)
        * head_dim
    )
    tensors, rows = TENSORS[kernel]
    nbytes = batch_heads * seq_len * (tensors * head_dim * itemsize + rows * 4)
    return flops, nbytes
