"""Kimi Delta Attention's recurrence, in chunks, with a backward: a gated
delta rule whose decay is one number a CHANNEL of the key and a
position (Kimi Linear, arXiv:2510.26692 section 3; Gated DeltaNet,
arXiv:2412.06464, with the scalar decay made a vector over ``d_k``),

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T     (d_k x d_v, S_{-1} = 0)
    o_t = d_k ** -0.5 * S_t^T q_t

for every head, ``alpha_t = exp(g_t)`` with ``g_t <= 0`` a vector over
``d_k``, ``beta_t`` a scalar. The convolutions, the normalisation of q
and k, the gates' own form and the gated norm behind are the model's
(model_zoo/transformer_lm/hybrid_moe_lm.py).

``ops/ssd.py`` cannot compute it. The decay is a vector, so it does not
come out of ``q_t . k_s`` as one scalar a pair of positions; and the
update is a delta rule: each position reads the state before it writes
it, ``S_t = Diag(alpha_t) S_{t-1} + k_t w_t^T`` with ``w_t = beta_t (v_t
- S_{t-1}^T (alpha_t * k_t))``, so that inside a chunk the ``w`` depend
on one another. With ``G_r`` the sum of ``g`` from the chunk's first
position to ``r`` (itself included) and ``S`` the state the chunk was
handed:

    A[r, i] = sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])     (i <  r, else 0)
    B[r, i] = sum_c q_r[c] k_i[c] exp(G_r[c] - G_i[c])     (i <= r, else 0)
    (I + Diag(beta) A) [U | Y] = Diag(beta) [V | exp(G) * K]     (unit lower triangular, C x C)
    W = U - Y S
    O = d_k ** -0.5 * ((exp(G) * Q) S + B W)
    S_out = Diag(exp(G_last)) S + (exp(G_last - G) * K)^T W

(the WY / UT form of arXiv:2412.06464 section 3 and arXiv:2510.26692
section 3.2). ``A``, ``B``, ``U`` and ``Y`` do not read the
state: they are computed for :data:`GROUP_CHUNKS` chunks at once (every
chunk at once held two dozen arrays of the sequence's size in float32,
1.5 GB at 2 x 4,096 positions of 32 heads of 128, through a layer's
backward pass), and the loop over a group's chunks (``lax.scan``) is
four products a chunk.

The exponents need care. Every ``exp`` above has an exponent of at most
0 but the one inside ``A`` and ``B``, which a matrix product can only
form as ``(k_r exp(G_r - R)) . (k_i exp(R - G_i))`` about some
reference ``R``, and at a decay of ``exp(-5)`` a position a chunk of 64
accumulates a log-decay of -320: about the chunk's start the second
factor overflows float32. So the rows go in sub-blocks of
:data:`SUB_BLOCK` (the paper's secondary chunking), each about the
``G`` of its own middle row: both factors of a pair the product keeps
lie within ``exp(+-SUB_BLOCK / 2 * max|g|)`` (``exp(40)`` at 16 rows
and -5), a column behind the sub-block only decays further, and a
column past it, which the mask drops, gets the exponent 0. ``U`` and
``Y`` come out of one triangular solve a chunk
(``lax.linalg.triangular_solve`` of the identity, then ``M^-1 R`` as a
float32 product at ``highest``): back-substitution stays accurate where
keys repeat, which an explicit inverse by powers of the strictly lower
part does not (its terms grow like binomial coefficients and cancel),
and an inverse by blocks in ``highest`` precision took three quarters
of this op's compile time. Inverse times right-hand side is what the
chip did with the solve of ``R`` itself: XLA's TPU lowering of a
triangular solve is the custom call ``InvertDiagBlocksLowerTriangular``
(an explicit inversion of diagonal blocks of up to 128 rows: the whole
``C x C`` matrix here) and a ``dot`` at ``highest`` with the right-hand
side, and the inversion was the largest single device operation of a
step (PERF.md section 6, PR 44). So the inverse is made once, in the
forward pass, and kept: the backward pass needs no other solve
(:func:`_solved_by`: ``X = M^-1 R`` to rebuild, ``dR = M^-T dX``,
``dM = -dR X^T`` under the diagonal), where differentiating the solve
inverted the same matrix twice more.

Gates, cumulative sums and the solve are float32; the products take
operands of ``v``'s dtype and accumulate in float32; the carried state
is float32. The loop over the groups has a backward pass of its own
(``jax.custom_vjp``): the forward emits beside each group's ``o`` the
state the group was handed and its chunks' inverses and keeps those and
the grouped operands; the backward is a loop over the groups last to
first that carries the state's cotangent, rebuilds a group from its
kept state and inverses and differentiates it (``jax.vjp`` of the
group's ``jax.checkpoint``ed step), so nothing else inside a group
outlives it: what ``lax.scan`` of a ``jax.checkpoint``ed step gives,
bit for bit. The kept states, the kept inverses and ``kda``'s result go
through ``checkpoint_name`` as :data:`KEPT_STATES`,
:data:`KEPT_INVERSES` and :data:`KEPT_OUTPUT`: a layer rematerialised
under ``save_only_these_names`` that lists them (``remat_layers`` of
the model) keeps all three from its forward pass (at 2 x 4,096
positions of 32 heads of 128 in chunks of 64: 34 MB of states, 67 MB
of output in bf16, 67 MB of inverses, which the TPU's tiles of 128
lanes lay out as 134), and its recomputation does not run the
recurrence; one that does not list them recomputes it, as any
other op.

Named scope: ``edl/kda`` (docs/observability.md). A device trace
carries no scope; there the recurrence is the ``while`` loops that
carry the ``(batch, heads, d_k, d_v)`` float32 state (forward) or its
cotangent (backward): the loops over the groups, and nested in them
the loops over a group's chunks.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

SCOPE = "edl/kda"
# what a rematerialised layer keeps of the recurrence (the names of a
# ``save_only_these_names`` policy): ``kda``'s result, the states the
# loop over the groups handed its groups, and every chunk's inverse
KEPT_OUTPUT = "kda_output"
KEPT_STATES = "kda_group_states"
KEPT_INVERSES = "kda_chunk_inverses"
KEPT_NAMES = (KEPT_OUTPUT, KEPT_STATES, KEPT_INVERSES)
# rows of a sub-block of the chunk's two decay matrices; with
# ``SUB_BLOCK / 2 * max|g|`` under 80 nothing overflows float32
SUB_BLOCK = 16
# chunks whose decay matrices, solve and right-hand sides are alive at
# a time: the loop over the chunks is nested in groups of this many
GROUP_CHUNKS = 8


def _decay_matrices(q, k, cum, sub, dtype):
    """``(A, B)`` of the module's text for every chunk, (..., C, C)
    float32, ``A`` masked under the diagonal and ``B`` on and under it.
    ``q``, ``k`` (..., C, d_k) float32; ``cum`` their ``G``."""
    chunk, d_k = k.shape[-2:]
    blocks = chunk // sub
    lead = k.shape[:-2]
    by_block = lambda t: t.reshape(lead + (blocks, sub, d_k))
    # a sub-block's reference: the G of its middle row
    middle = by_block(cum)[..., sub // 2, :]  # (..., blocks, d_k)
    rows = jnp.exp(by_block(cum) - middle[..., None, :])
    # every column against every sub-block's reference; a column past
    # the sub-block is not read, and its exponent (positive, and
    # growing with the distance) is set to 0 before the exp
    column = jnp.arange(chunk)
    read = column[None, :] < (jnp.arange(blocks)[:, None] + 1) * sub
    columns = jnp.exp(
        jnp.where(
            read[..., None],
            middle[..., :, None, :] - cum[..., None, :, :],
            0.0,
        )
    )  # (..., blocks, C, d_k)
    k_columns = (k[..., None, :, :] * columns).astype(dtype)

    def against_the_keys(x):
        found = jnp.einsum(
            "...ard,...aid->...ari",
            (by_block(x) * rows).astype(dtype),
            k_columns,
            preferred_element_type=jnp.float32,
        )
        return found.reshape(lead + (chunk, chunk))

    under = column[:, None] > column[None, :]
    on_or_under = column[:, None] >= column[None, :]
    return (
        jnp.where(under, against_the_keys(k), 0.0),
        jnp.where(on_or_under, against_the_keys(q), 0.0),
    )


@jax.custom_vjp
def _solved_by(inverse, matrix, rhs):
    """``X`` of ``matrix X = rhs`` from the INVERSE of the unit lower
    triangular ``matrix``, (..., C, C) float32 both: a float32 product
    at ``highest``. ``matrix`` is here for its cotangent; ``inverse``
    is a function of it and gets none."""
    return jnp.matmul(inverse, rhs, precision=jax.lax.Precision.HIGHEST)


def _solved_by_fwd(inverse, matrix, rhs):
    solved = _solved_by(inverse, matrix, rhs)
    return solved, (inverse, solved)


def _solved_by_bwd(kept, d_solved):
    """``d_rhs = M^-T dX``; ``d_matrix = -d_rhs X^T`` strictly under
    the diagonal (a unit triangular solve reads nothing else)."""
    inverse, solved = kept
    highest = jax.lax.Precision.HIGHEST
    d_rhs = jnp.einsum("...ji,...jv->...iv", inverse, d_solved, precision=highest)
    d_matrix = -jnp.tril(
        jnp.einsum("...iv,...jv->...ij", d_rhs, solved, precision=highest), -1
    )
    return None, d_matrix, d_rhs


_solved_by.defvjp(_solved_by_fwd, _solved_by_bwd)


def _chunk_step(state, inputs):
    """One chunk: (B, H, d_k, d_v) float32 state in, the chunk's ``o``
    (B, H, C, d_v) float32 and the state after it out. ``inputs``: U
    (B, H, C, d_v); Y, exp(G) * q (scaled) and exp(G_last - G) * k
    (B, H, C, d_k); B (B, H, C, C); exp(G_last) (B, H, d_k) float32."""
    u, y, q_decayed, k_left, b_matrix, last = inputs
    dtype = u.dtype
    held = state.astype(dtype)
    w = u.astype(jnp.float32) - jnp.einsum(
        "bhck,bhkv->bhcv", y, held, preferred_element_type=jnp.float32
    )
    out = jnp.einsum(
        "bhck,bhkv->bhcv", q_decayed, held, preferred_element_type=jnp.float32
    ) + jnp.einsum(
        "bhci,bhiv->bhcv",
        b_matrix,
        w.astype(dtype),
        preferred_element_type=jnp.float32,
    )
    after = last[..., None] * state + jnp.einsum(
        "bhck,bhcv->bhkv",
        k_left,
        w.astype(dtype),
        preferred_element_type=jnp.float32,
    )
    return after, out


def _group_step(state, inputs, sub, inverse=None):
    """A group of chunks: what does not read the state (the decay
    matrices, ``U`` and ``Y``) for all of them at once,
    then the loop over them. ``inputs``: q, k (B, H, n, C, d_k); v
    (B, H, n, C, d_v); g (B, H, n, C, d_k) float32; beta (B, H, n, C,
    1) float32. ``inverse``: ``(I + Diag(beta) A)^-1`` of every chunk,
    (B, H, n, C, C) float32, where an earlier pass over the group kept
    it; computed here where not. Returns the state after the group and
    its ``o``, (B, H, n, C, d_v) float32, and beside them the inverse."""
    q, k, v, g, beta = inputs
    dtype, f32 = v.dtype, jnp.float32
    q, k = q.astype(f32) * q.shape[-1] ** -0.5, k.astype(f32)
    cum = jnp.cumsum(g, axis=-2)
    last = cum[..., -1:, :]
    a_matrix, b_matrix = _decay_matrices(q, k, cum, sub, dtype)
    decay = jnp.exp(cum)
    # U and Y at once: (I + Diag(beta) A) [U | Y] = Diag(beta) [V | exp(G) K]
    eye = jnp.eye(k.shape[-2], dtype=f32)
    matrix = eye + beta * a_matrix
    if inverse is None:
        inverse = jax.lax.linalg.triangular_solve(
            matrix,
            jnp.broadcast_to(eye, matrix.shape),
            left_side=True,
            lower=True,
            unit_diagonal=True,
        )
    solved = _solved_by(
        inverse,
        matrix,
        beta * jnp.concatenate([v.astype(f32), decay * k], axis=-1),
    ).astype(dtype)
    by_chunk = (
        solved[..., : v.shape[-1]],
        solved[..., v.shape[-1] :],
        (decay * q).astype(dtype),
        (jnp.exp(last - cum) * k).astype(dtype),
        b_matrix.astype(dtype),
        jnp.exp(last[..., 0, :]),
    )
    state, out = jax.lax.scan(
        _chunk_step,
        state,
        jax.tree_util.tree_map(lambda t: jnp.moveaxis(t, 2, 0), by_chunk),
    )
    return (state, jnp.moveaxis(out, 0, 2)), inverse


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _over_the_groups(inputs, sub):
    """The loop over the groups, from a state of zeros: ``o`` of every
    group, (groups, B, H, n, C, d_v) float32. ``inputs``: what
    :func:`_group_step` takes, a leading axis over the groups."""
    return _over_the_groups_fwd(inputs, sub)[0]


def _over_the_groups_fwd(inputs, sub):
    """The same loop, emitting beside a group's ``o`` the state the
    group was HANDED (the first is zeros) and its chunks' inverses:
    the backward pass starts each group from the one and solves with
    the other."""
    k, v = inputs[1:3]

    def step(state, group):
        (after, out), inverse = _group_step(state, group, sub)
        return after, (out, state, inverse)

    _, (out, handed, inverses) = jax.lax.scan(
        step,
        jnp.zeros(v.shape[1:3] + (k.shape[-1], v.shape[-1]), jnp.float32),
        inputs,
    )
    kept = (
        inputs,
        checkpoint_name(handed, KEPT_STATES),
        checkpoint_name(inverses, KEPT_INVERSES),
    )
    return out, kept


def _over_the_groups_bwd(sub, kept, d_out):
    """The groups last to first, the carry the state's cotangent: a
    group is rebuilt from the state it was handed and its kept
    inverses and differentiated, nothing of it kept for the next."""
    inputs, handed, inverses = kept

    # checkpointed, so that the group's forward runs inside ``back``
    # under jax's name for a recomputation (``rematted_computation``:
    # utils/step_ops.py counts it as one) and ``jax.vjp``'s own is dead
    rebuilt = jax.checkpoint(
        functools.partial(_group_step, sub=sub), prevent_cse=False
    )

    def step(d_state, group):
        group_inputs, state, inverse, d_group_out = group
        _, back, _ = jax.vjp(
            functools.partial(rebuilt, inverse=inverse),
            state,
            group_inputs,
            has_aux=True,
        )
        return back((d_state, d_group_out))

    _, d_inputs = jax.lax.scan(
        step,
        jnp.zeros_like(handed[0]),
        (inputs, handed, inverses, d_out),
        reverse=True,
    )
    return (d_inputs,)


_over_the_groups.defvjp(_over_the_groups_fwd, _over_the_groups_bwd)


def kda(q, k, v, g, beta, chunk=64):
    """``o`` of the recurrence above, (B, L, H, d_v) in ``v``'s dtype.

    ``q``, ``k``: (B, L, H, d_k); ``v``: (B, L, H, d_v); ``g``:
    (B, L, H, d_k), the log-decay a channel, at most 0 (taken as
    float32); ``beta``: (B, L, H). ``chunk`` is :data:`SUB_BLOCK` rows
    or less, or a multiple of them. A length that is no multiple of
    ``chunk`` is padded behind with positions that decay nothing and
    write nothing."""
    batch, length, heads, d_k = k.shape
    d_v = v.shape[-1]
    dtype = v.dtype
    chunk = min(chunk, length)
    sub = SUB_BLOCK if chunk % SUB_BLOCK == 0 else chunk
    if sub > SUB_BLOCK:
        raise ValueError(
            "a chunk of %d positions is neither %d or less nor a multiple "
            "of them" % (chunk, SUB_BLOCK)
        )
    padded = -length % chunk
    if padded:
        pad = lambda t: jnp.pad(
            t, ((0, 0), (0, padded)) + ((0, 0),) * (t.ndim - 2)
        )
        q, k, v, g, beta = pad(q), pad(k), pad(v), pad(g), pad(beta)
    chunks = (length + padded) // chunk
    group = next(n for n in range(GROUP_CHUNKS, 0, -1) if chunks % n == 0)

    def grouped(t):
        """(B, L, H, ...) -> (groups, B, H, chunks a group, C, ...)"""
        split = t.reshape(
            (batch, chunks // group, group, chunk, heads) + t.shape[3:]
        )
        return jnp.moveaxis(split, (1, 4), (0, 2))

    with jax.named_scope(SCOPE):
        f32 = jnp.float32
        out = _over_the_groups(
            (
                grouped(q),
                grouped(k),
                grouped(v),
                grouped(g.astype(f32)),
                grouped(beta.astype(f32))[..., None],
            ),
            sub,
        )  # (groups, B, H, n, C, d_v)
        out = jnp.moveaxis(out, (0, 2), (1, 4)).reshape(
            batch, length + padded, heads, d_v
        )
        return checkpoint_name(out[:, :length].astype(dtype), KEPT_OUTPUT)
