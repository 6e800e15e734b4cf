"""Learned key selection in front of attention: an indexer scores every
earlier key for every query and the ``topk`` best are what the query's
attention reads (the DeepSeek-Sparse-Attention indexer, its equation 1).

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])        (J heads, ONE key head)
    S_t     = the s <= t with the topk largest I[t, s]

``S_t`` is every ``s <= t`` while ``t < topk``; ties go to the lower
index, ``lax.top_k``'s rule. Ties are not rare: with no position inside
the indexer, two positions that hold the same token give the first
layer the same key, so the rule decides whole runs of keys there.

Everything here is float32 with matrix products at ``HIGHEST``
precision (the selection is discrete: a bf16 product would change
which keys a query reads), and nothing here takes a gradient: the
result is a mask. The ``J x L x L`` products are never whole in memory:
queries go through in blocks of ``block`` rows (``lax.map``), and a
block's ``(J, block, keys)`` products, its ``(block, keys)`` scores and
its threshold search live and die inside one iteration. What leaves is the
selection as ``(B, L, L)`` int8, the operand
:func:`elasticdl_tpu.ops.flash_attention.flash_attention_selected`
takes.

What a block computes follows what can change its answer
(:func:`block_runs`): blocks whose every row is under ``topk`` are the
causal triangle and are written down as that, with no product, no
search and no loop; the others go through in short runs, and a run
scores the keys up to its own end. :func:`select_work_ratio` counts
the scores computed over the pairs whose score can matter.

The k-th largest score of a row is found without sorting it: the
scores are mapped to unsigned integers of the same order and the
threshold's bits are fixed two at a time, most significant first, by
counting the row's scores at or over each of 3 candidates (16 passes
over the block, each one fused compare-and-count). Of the keys AT the
threshold the lowest indices make up the count, and the last of them
is found by the same search over the keys' positions. On the chip
``lax.top_k`` sorts for a ``k`` this large.

Named scopes: ``edl/sparse_select/scores`` (the block's products, ReLU
and weighted sum) and ``edl/sparse_select/topk`` (threshold, ties,
mask), docs/observability.md; the rows under ``topk`` enter neither.
"""

import functools

import jax
import jax.numpy as jnp

SCORES_SCOPE = "edl/sparse_select/scores"
TOPK_SCOPE = "edl/sparse_select/topk"
# Bits of the threshold fixed a pass: a pass of b bits costs 2**b - 1
# compare-and-counts a score, 32 bits in passes of 2 cost 48 where
# passes of 4 cost 120 and passes of 1 cost 32 in twice the passes,
# and the search is bound by the vector unit while the block stays in
# the fast memory. In the cell's step on the chip the selection read
# 27.1 ms with 2 bits a pass, 30.7 with 1 and 36.0 with 4 (PERF.md,
# PR 33, which also has 3 bits and mixed widths, kernel-only).
_RADIX_BITS = 2
# A run is one ``lax.map`` loop to compile, in every layer.
MAX_RUNS = 6


def _ordered_bits(x):
    """float32 -> uint32 with the same order (``-inf`` lowest); ``-0.0``
    and ``0.0`` are one value first, as they are one score."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.uint32)
    negative = bits >> 31 == 1
    return jnp.where(negative, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest(keys, k, bits=32):
    """(..., n, rows) uint32 under ``2**bits`` -> (..., rows): the k-th
    largest of each COLUMN, ``k >= 1`` (one for all columns or one
    each), duplicates counted: the largest ``v`` with ``count(column >=
    v) >= k``. Radix search from the top bits down; no sort. The
    columns are the rows of queries: a block's scores leave their
    product with the keys along the major axis, and a count down that
    axis needs no transposed copy of the block."""
    k = jnp.asarray(k)
    if k.ndim:
        k = k[..., None, :]
    prefix = jnp.zeros(keys.shape[:-2] + keys.shape[-1:], jnp.uint32)
    digits = jnp.arange(1, 1 << _RADIX_BITS, dtype=jnp.uint32)[:, None]
    top = -(-bits // _RADIX_BITS) * _RADIX_BITS
    for shift in range(top - _RADIX_BITS, -1, -_RADIX_BITS):
        candidates = prefix[..., None, :] | (digits << shift)  # ascending
        counts = jnp.sum(
            keys[..., None, :, :] >= candidates[..., None, :],
            axis=-2,
            dtype=jnp.int32,
        )
        # the counts fall as the candidates rise: how many still hold
        # k of the column is the digit
        digit = jnp.sum(counts >= k, axis=-2).astype(jnp.uint32)
        prefix = prefix | (digit << shift)
    return prefix


def block_scores(q_block, keys, weights_block):
    """``I`` for one block of queries: (B, n, J, D), (B, L, D),
    (B, n, J) -> (B, L, n) float32, keys by queries."""
    products = jnp.einsum(
        "bqjd,bsd->bjsq",
        q_block,
        keys,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return jnp.einsum("bjsq,bqj->bsq", jax.nn.relu(products), weights_block)


def select_block(scores, first_row, topk):
    """(B, L, n) scores of queries ``first_row ..`` -> (B, L, n) bool:
    each query's ``topk`` best keys among those not after it, ties to
    the lower index."""
    length, n = scores.shape[-2:]
    rows = first_row + jnp.arange(n)[None, :]
    causal = jnp.arange(length)[:, None] <= rows
    keys = _ordered_bits(jnp.where(causal, scores, -jnp.inf))
    threshold = kth_largest(keys, topk)[..., None, :]
    over = keys > threshold
    # of the keys AT the threshold the first ones make up the count
    # (one at least: the threshold is the topk-th largest). Counted
    # from the sequence's end a key's place is larger the earlier it
    # is, so the last key that still counts holds the left-th largest
    # place among them.
    left = topk - jnp.sum(over, axis=-2, dtype=jnp.int32)
    place = jnp.uint32(length) - jnp.arange(length, dtype=jnp.uint32)
    place = jnp.where(keys == threshold, place[:, None], jnp.uint32(0))
    last = kth_largest(place, left, bits=length.bit_length())[..., None, :]
    # a query with fewer than topk keys before it finds its threshold
    # at -inf, among the keys after it: the causal mask has the last word
    return (over | (place >= last)) & causal


def block_runs(length, topk, block):
    """How ``length // block`` blocks of queries go through: ``(free,
    runs)``. The first ``free`` blocks end at or under ``topk``: every
    row of them reads every key not after it, and nothing is scored.
    ``runs`` are the ``(lo, hi)`` block ranges of the others, each one
    loop that scores the keys up to ``hi * block``: as many runs as
    ``MAX_RUNS`` allows, of two blocks or more where there are two
    (XLA unrolls a loop of one), near equal, the longer first."""
    blocks = length // block
    free = min(topk // block, blocks)
    scored = blocks - free
    if not scored:
        return free, []
    count = max(1, min(MAX_RUNS, scored // 2))
    short, longer = divmod(scored, count)
    runs, lo = [], free
    for i in range(count):
        hi = lo + short + (i < longer)
        runs.append((lo, hi))
        lo = hi
    return free, runs


def select_work_ratio(length, topk, block, runs=None):
    """Scores computed over the pairs whose score can change a row's
    answer (``t >= topk``, ``s <= t``), static from shapes, walking
    ``runs`` (:func:`block_runs`'s unless given): 1.0 when every row
    scores its own keys and no other."""
    if runs is None:
        runs = block_runs(length, topk, block)[1]
    computed = sum((hi - lo) * block * hi * block for lo, hi in runs)
    open_rows = max(length - topk, 0)
    needed = open_rows * (topk + 1 + length) // 2
    return computed / needed if needed else 1.0


# jitted here so that the layers of a model, which call it with the same
# shapes, share one trace of the loops
@functools.partial(jax.jit, static_argnames=("topk", "block"))
def select_keys(queries, keys, weights, topk, block=512):
    """The selection for every query: ``queries`` (B, L, J, D), ``keys``
    (B, L, D), ``weights`` (B, L, J), all float32 -> (B, L, L) int8, 1
    where query ``t`` reads key ``s``. Inside the causal triangle, and
    ``min(t + 1, topk)`` keys a query exactly.

    The blocks of ``block`` queries go through as :func:`block_runs`
    lays them out: inside a run one after another, each against the
    keys up to the run's end (a static slice)."""
    batch, length = keys.shape[:2]
    block = min(block, length)
    if length % block:
        raise ValueError(
            "sequence length %d is not a multiple of the selection's "
            "block of %d queries" % (length, block)
        )
    topk = min(topk, length)
    queries = jax.lax.stop_gradient(queries.astype(jnp.float32))
    keys = jax.lax.stop_gradient(keys.astype(jnp.float32))
    weights = jax.lax.stop_gradient(weights.astype(jnp.float32))

    def one_block(q_block, weights_block, first, visible):
        with jax.named_scope(SCORES_SCOPE):
            scores = block_scores(q_block, keys[:, :visible], weights_block)
        with jax.named_scope(TOPK_SCOPE):
            chosen = select_block(scores, first, topk)
            return jnp.swapaxes(chosen, -1, -2).astype(jnp.int8)

    def in_blocks(x, lo, hi):
        """Rows ``lo * block .. hi * block`` of (B, L, ...) as (blocks,
        B, block, ...): a loop is handed its own run's rows only."""
        x = x[:, lo * block : hi * block]
        x = x.reshape((batch, hi - lo, block) + x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    free, runs = block_runs(length, topk, block)
    parts = []
    if free:
        rows = jnp.arange(free * block)[:, None]
        triangle = (jnp.arange(length)[None, :] <= rows).astype(jnp.int8)
        parts.append(jnp.broadcast_to(triangle, (batch,) + triangle.shape))
    for lo, hi in runs:
        run = jax.lax.map(
            lambda xs: one_block(*xs, hi * block),
            (
                in_blocks(queries, lo, hi),
                in_blocks(weights, lo, hi),
                jnp.arange(lo, hi) * block,
            ),
        )
        # (blocks of the run, B, block, visible) -> (B, rows, L)
        run = run.transpose(1, 0, 2, 3).reshape(batch, -1, hi * block)
        parts.append(jnp.pad(run, ((0, 0), (0, 0), (0, length - hi * block))))
    return jnp.concatenate(parts, axis=1)
