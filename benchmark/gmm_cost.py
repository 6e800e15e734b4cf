"""Operations and bytes one call of the grouped-matmul kernels
(elasticdl_tpu/ops/grouped_matmul.py) has to do, from shapes alone;
``flops.roofline`` turns them into the chip's least time.

``rows`` is the number of rows that belong to a group, NOT the length
of the buffer the call is handed: an expert layer that holds a share of
its experts hands the kernel a buffer as long as every assignment, of
which only the held experts' rows are multiplied. Counting the buffer
would count work the kernel is built to skip, and the share of the
roofline would read over 100%.
"""


def grouped_product_cost(rows, k, n, groups, itemsize=2):
    """(FLOPs, bytes) of one grouped product over ``rows`` rows and
    ``groups`` (k, n) matrices: ``edl_gmm_*`` (``out[rows, n] =
    lhs[rows, k] @ rhs[g]`` by groups, forward or against ``rhs``
    transposed) and ``edl_tgmm`` (``out[g] = lhs[rows of g]^T @
    rhs[rows of g]``) alike: each row once through its group's matrix;
    the two row-sized operands move once, and each of the ``groups``
    matrices once (read by the first kernel, written by the second)."""
    return (
        2 * rows * k * n,
        itemsize * (rows * k + groups * k * n + rows * n),
    )
