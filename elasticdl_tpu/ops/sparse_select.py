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

The k-th largest score of a row is found without sorting it: the
scores are mapped to unsigned integers of the same order and the
threshold's bits are fixed four at a time, most significant first, by
counting the row's scores at or over each of 15 candidates (8 passes
over the block, each one fused compare-and-count). On the chip
``lax.top_k`` sorts for a ``k`` this large.

Named scopes: ``edl/sparse_select/scores`` (the block's products, ReLU
and weighted sum) and ``edl/sparse_select/topk`` (threshold, ties,
mask), docs/observability.md.
"""

import jax
import jax.numpy as jnp

SCORES_SCOPE = "edl/sparse_select/scores"
TOPK_SCOPE = "edl/sparse_select/topk"
_RADIX_BITS = 4


def _ordered_bits(x):
    """float32 -> uint32 with the same order (``-inf`` lowest); ``-0.0``
    and ``0.0`` are one value first, as they are one score."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.uint32)
    negative = bits >> 31 == 1
    return jnp.where(negative, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest(keys, k):
    """(..., n) uint32 -> (...,): the k-th largest of each row, k >= 1,
    duplicates counted: the largest ``v`` with ``count(row >= v) >= k``.
    Radix search from the top bits down; no sort."""
    prefix = jnp.zeros(keys.shape[:-1], jnp.uint32)
    digits = jnp.arange(1, 1 << _RADIX_BITS, dtype=jnp.uint32)
    for shift in range(32 - _RADIX_BITS, -1, -_RADIX_BITS):
        candidates = prefix[..., None] | (digits << shift)  # ascending
        counts = jnp.sum(
            keys[..., None, :] >= candidates[..., None],
            axis=-1,
            dtype=jnp.int32,
        )
        # the counts fall as the candidates rise: how many still hold
        # k of the row is the digit
        digit = jnp.sum(counts >= k, axis=-1).astype(jnp.uint32)
        prefix = prefix | (digit << shift)
    return prefix


def block_scores(q_block, keys, weights_block):
    """``I`` for one block of queries: (B, n, J, D), (B, L, D),
    (B, n, J) -> (B, n, L) float32."""
    products = jnp.einsum(
        "bqjd,bsd->bjqs",
        q_block,
        keys,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return jnp.einsum("bjqs,bqj->bqs", jax.nn.relu(products), weights_block)


def select_block(scores, first_row, topk):
    """(B, n, L) scores of queries ``first_row ..`` -> (B, n, L) bool:
    each query's ``topk`` best keys among those not after it, ties to
    the lower index."""
    n, length = scores.shape[-2:]
    rows = first_row + jnp.arange(n)[:, None]
    causal = jnp.arange(length)[None, :] <= rows
    keys = _ordered_bits(jnp.where(causal, scores, -jnp.inf))
    threshold = kth_largest(keys, topk)[..., None]
    over = keys > threshold
    at = keys == threshold
    # of the keys AT the threshold, the first ones make up the count
    left = topk - jnp.sum(over, axis=-1, keepdims=True, dtype=jnp.int32)
    first_at = jnp.cumsum(at, axis=-1, dtype=jnp.int32) <= left
    # a query with fewer than topk keys before it finds its threshold
    # at -inf, among the keys after it: the causal mask has the last word
    return (over | (at & first_at)) & causal


def select_keys(queries, keys, weights, topk, block=512, spans=4):
    """The selection for every query: ``queries`` (B, L, J, D), ``keys``
    (B, L, D), ``weights`` (B, L, J), all float32 -> (B, L, L) int8, 1
    where query ``t`` reads key ``s``. Inside the causal triangle, and
    ``min(t + 1, topk)`` keys a query exactly.

    The queries are cut into ``spans`` runs, and a run scores only the
    keys up to its own end (a static slice: with 4 runs 10 of 16
    quarter-squares, the causal triangle holds 8.5); inside a run the
    blocks of ``block`` queries go through one after another."""
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

    def one_block(first, visible):
        with jax.named_scope(SCORES_SCOPE):
            scores = block_scores(
                jax.lax.dynamic_slice_in_dim(queries, first, block, axis=1),
                keys[:, :visible],
                jax.lax.dynamic_slice_in_dim(weights, first, block, axis=1),
            )
        with jax.named_scope(TOPK_SCOPE):
            return select_block(scores, first, topk).astype(jnp.int8)

    blocks = length // block
    per_span = -(-blocks // spans)
    runs = []
    for lo in range(0, blocks, per_span):
        hi = min(lo + per_span, blocks)
        run = jax.lax.map(
            lambda i: one_block(i * block, hi * block), jnp.arange(lo, hi)
        )
        # (blocks of the run, B, block, visible) -> (B, rows, L)
        run = run.transpose(1, 0, 2, 3).reshape(batch, -1, hi * block)
        runs.append(jnp.pad(run, ((0, 0), (0, 0), (0, length - hi * block))))
    return jnp.concatenate(runs, axis=1)
