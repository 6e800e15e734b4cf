"""ops/kda.py: the chunked gated delta rule against the recurrence
written out position by position, in float32 on the CPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import kda

LOWER_BOUND = -5.0


def recurrence(q, k, v, g, beta):
    """``o`` position by position: the (d_k, d_v) state of every
    (sequence, head) decayed a channel, read, then written."""

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at  # (B, H, ...)
        state = jnp.exp(g_t)[..., None] * state
        read = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - read)
        )
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    b, _, h, d_k = k.shape
    with jax.default_matmul_precision("highest"):
        _, out = jax.lax.scan(
            step,
            jnp.zeros((b, h, d_k, v.shape[-1]), jnp.float32),
            tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)),
        )
    return jnp.moveaxis(out, 0, 1) * d_k**-0.5


def operands(length, gates="random", floor=(0, 0), seed=0, heads=2, d_k=8, d_v=4):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (2, length, heads)
    normed = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = normed(jax.random.normal(keys[0], shape + (d_k,)))
    k = normed(jax.random.normal(keys[1], shape + (d_k,)))
    v = jax.random.normal(keys[2], shape + (d_v,))
    g = LOWER_BOUND * jax.nn.sigmoid(
        2.0 * jax.random.normal(keys[3], shape + (d_k,))
    )
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape))
    if gates == "floor":
        # every gate at the bound for a whole chunk: exp(-5 * 64) is 0
        # in float32
        g = g.at[:, slice(*floor)].set(LOWER_BOUND)
    elif gates == "no_write":
        beta = jnp.zeros_like(beta)
    elif gates == "plain_delta_rule":
        g, beta = jnp.zeros_like(g), jnp.ones_like(beta)
    return q, k, v, g, beta


def weighted(fn, weights):
    return lambda *args: jnp.sum(fn(*args) * weights)


@pytest.mark.parametrize("gates", ["random", "floor"])
# 1, 4 and 5 chunks are one group; 10 are two groups of five, 16 two of
# eight, 24 three of eight, 13 thirteen of one
@pytest.mark.parametrize("chunks", [1, 4, 5, 10, 13, 16, 24])
@pytest.mark.parametrize("chunk", [16, 64])
def test_chunks_agree_with_the_recurrence(chunk, chunks, gates):
    """Forward and every gradient (q, k, v, g, beta; ``kda``'s own
    backward pass against plain differentiation of the recurrence),
    with random gates and with every gate at -5 for a whole chunk:
    nothing overflows, and the tolerance is the same."""
    length = chunk * chunks
    # the second chunk where there is one, else the only one
    floor = (chunk, 2 * chunk) if chunks > 1 else (0, chunk)
    args = operands(length, gates, floor)
    weights = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    want, want_grads = jax.value_and_grad(
        weighted(recurrence, weights), argnums=(0, 1, 2, 3, 4)
    )(*args)
    got, got_grads = jax.value_and_grad(
        weighted(lambda *a: kda.kda(*a, chunk=chunk), weights),
        argnums=(0, 1, 2, 3, 4),
    )(*args)
    assert np.isfinite(got)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        kda.kda(*args, chunk=chunk), recurrence(*args), rtol=1e-4, atol=1e-5
    )
    for name, g_got, g_want in zip("qkvgb", got_grads, want_grads):
        assert np.all(np.isfinite(g_got)), name
        np.testing.assert_allclose(
            g_got, g_want, rtol=2e-4, atol=2e-5, err_msg="d" + name
        )


def test_a_length_that_is_no_multiple_of_the_chunk_is_padded_behind():
    args = operands(40)
    np.testing.assert_allclose(
        kda.kda(*args, chunk=16), recurrence(*args), rtol=1e-4, atol=1e-5
    )


@pytest.mark.parametrize("length", [40, 136, 200])
def test_a_padded_lengths_gradients_agree_with_the_recurrence(length):
    """3, 9 and 13 chunks of 16 with the last one part padding: one
    group, three groups of three, thirteen of one."""
    args = operands(length, seed=3)
    weights = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    want = jax.grad(weighted(recurrence, weights), argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.grad(
        weighted(lambda *a: kda.kda(*a, chunk=16), weights),
        argnums=(0, 1, 2, 3, 4),
    )(*args)
    for name, g_got, g_want in zip("qkvgb", got, want):
        assert g_got.shape == g_want.shape
        np.testing.assert_allclose(
            g_got, g_want, rtol=2e-4, atol=2e-5, err_msg="d" + name
        )


def scan_of_checkpoint(q, k, v, g, beta, chunk):
    """``kda`` as it was before it had a backward pass of its own (PR
    42): ``lax.scan`` over the groups of ``jax.checkpoint`` of the
    group's step, differentiated by jax. A length that is a multiple of
    the chunk."""
    batch, length, heads, d_k = k.shape
    chunks = length // chunk
    group = next(n for n in range(kda.GROUP_CHUNKS, 0, -1) if chunks % n == 0)

    def grouped(t):
        split = t.reshape(
            (batch, chunks // group, group, chunk, heads) + t.shape[3:]
        )
        return jnp.moveaxis(split, (1, 4), (0, 2))

    f32 = jnp.float32
    sub = min(chunk, kda.SUB_BLOCK)
    _, out = jax.lax.scan(
        # [0]: the state and the group's ``o``, without the inverse
        jax.checkpoint(
            lambda state, group: kda._group_step(state, group, sub)[0],
            prevent_cse=False,
        ),
        jnp.zeros((batch, heads, d_k, v.shape[-1]), f32),
        (
            grouped(q),
            grouped(k),
            grouped(v),
            grouped(g.astype(f32)),
            grouped(beta.astype(f32))[..., None],
        ),
    )
    out = jnp.moveaxis(out, (0, 2), (1, 4)).reshape(v.shape)
    return out.astype(v.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunk, chunks", [(16, 4), (16, 16), (64, 24), (16, 13)])
def test_the_backward_pass_is_what_differentiating_the_loop_gave(chunk, chunks, dtype):
    """The op's own backward pass (a reverse loop over the groups, each
    rebuilt from the state it was handed) against jax's differentiation
    of the loop of checkpointed groups it replaced: the same
    arithmetic group by group, so the result and the five gradients
    are equal bit for bit, in the dtypes they had (``g`` and ``beta``
    float32 under operands of bfloat16)."""
    args = operands(chunk * chunks, seed=5)
    args = tuple(a.astype(dtype) for a in args[:3]) + args[3:]
    weights = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    both = [
        jax.jit(
            jax.value_and_grad(
                lambda *a: jnp.sum(fn(*a, chunk).astype(jnp.float32) * weights),
                argnums=(0, 1, 2, 3, 4),
            )
        )(*args)
        for fn in (kda.kda, scan_of_checkpoint)
    ]
    got, want = map(jax.tree_util.tree_leaves, both)
    for name, a, b in zip(("loss",) + tuple("qkvgb"), got, want):
        assert a.dtype == b.dtype, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    assert [a.dtype for a in got[1:]] == [jnp.dtype(dtype)] * 3 + [jnp.float32] * 2


@pytest.mark.parametrize("matrix", ["random", "keys_that_repeat"])
def test_the_applied_inverses_backward_rule_is_the_solves(matrix):
    """``_solved_by``'s own backward rule (``d_rhs = M^-T dX``,
    ``d_matrix = -d_rhs X^T`` under the diagonal) against jax's
    differentiation of ``lax.linalg.triangular_solve``, which groups
    the second as ``M^-T (dX X^T)``: the result and both gradients
    within float32 rounding, on random unit lower matrices of the size
    of ``Diag(beta) A``'s entries and on the matrix of
    ``test_keys_that_repeat_are_solved_as_accurately_as_any`` (one plus
    the strictly lower triangle of ones)."""
    size, width = 64, 24
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    under = {
        "random": 0.1 * jax.random.normal(keys[0], (2, 3, size, size)),
        "keys_that_repeat": jnp.ones((2, 3, size, size)),
    }[matrix]
    unit_lower = jnp.eye(size) + jnp.tril(under, -1)
    rhs = jax.random.normal(keys[1], (2, 3, size, width))
    weights = jax.random.normal(keys[2], rhs.shape)
    solve = functools.partial(
        jax.lax.linalg.triangular_solve,
        left_side=True,
        lower=True,
        unit_diagonal=True,
    )

    def applied(m, r):
        inverse = solve(m, jnp.broadcast_to(jnp.eye(size), m.shape))
        return kda._solved_by(inverse, m, r)

    want, want_grads = jax.value_and_grad(weighted(solve, weights), (0, 1))(
        unit_lower, rhs
    )
    got, got_grads = jax.value_and_grad(weighted(applied, weights), (0, 1))(
        unit_lower, rhs
    )
    # float32's 6e-8 through 64 rows: the worst of them read 3e-7
    np.testing.assert_allclose(got, want, rtol=2e-6)
    for name, g_got, g_want in zip(("d_matrix", "d_rhs"), got_grads, want_grads):
        np.testing.assert_allclose(
            g_got,
            g_want,
            rtol=2e-6,
            atol=2e-6 * float(jnp.max(jnp.abs(g_want))),
            err_msg=name,
        )
    # a unit triangular solve reads nothing on or over the diagonal
    np.testing.assert_array_equal(jnp.triu(got_grads[0]), 0.0)


# 4 chunks are one group; 16 two groups of eight, 13 thirteen of one
@pytest.mark.parametrize("chunks", [4, 13, 16])
def test_the_backward_pass_solves_nothing(chunks):
    """The lowered gradient holds the ONE ``triangular_solve`` of the
    lowered forward: the backward pass applies the inverses the forward
    kept (PR 43's gradient held four: the rebuilt group's, and one for
    either cotangent of the solve). Lowered for the TPU, where the op
    keeps XLA's name (on the CPU it is LAPACK's ``trsm``)."""
    args = operands(16 * chunks)

    def solves(fn):
        lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
        return lowered.as_text().count("stablehlo.triangular_solve")

    objective = lambda *a: jnp.sum(kda.kda(*a, chunk=16))
    assert solves(objective) == 1
    assert solves(jax.value_and_grad(objective, argnums=(0, 1, 2, 3, 4))) == 1


def test_nothing_written_leaves_nothing_to_read():
    """``beta = 0``: the state only decays, and it starts at zero."""
    args = operands(64, "no_write")
    np.testing.assert_array_equal(kda.kda(*args, chunk=16), 0.0)


def test_no_write_into_a_state_that_holds_something_is_a_pure_decay():
    """``beta = 0`` from position 16 on: ``o_t = d_k ** -0.5 * q_t .
    (prod of alpha after 15) S_15``, whatever k and v say there."""
    q, k, v, g, beta = operands(48)
    beta = beta.at[:, 16:].set(0.0)
    got = kda.kda(q, k, v, g, beta, chunk=16)
    other = kda.kda(
        q, k.at[:, 16:].multiply(-3.0), v.at[:, 16:].add(7.0), g, beta, chunk=16
    )
    np.testing.assert_allclose(got[:, 16:], other[:, 16:], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        got, recurrence(q, k, v, g, beta), rtol=1e-4, atol=1e-5
    )


def test_alpha_one_and_beta_one_is_the_plain_delta_rule():
    """``S_t = (I - k_t k_t^T) S_{t-1} + k_t v_t^T``: with unit keys,
    reading the state with the key just written gives back its value."""
    q, k, v, g, beta = operands(32, "plain_delta_rule")
    got = kda.kda(k, k, v, g, beta, chunk=16)
    np.testing.assert_allclose(
        got, v * k.shape[-1] ** -0.5, rtol=1e-4, atol=1e-5
    )


def test_a_chunk_between_sixteen_and_its_multiples_is_refused():
    with pytest.raises(ValueError, match="multiple"):
        kda.kda(*operands(48), chunk=24)


def test_keys_that_repeat_are_solved_as_accurately_as_any():
    """Sixty-four equal unit keys written at full strength with no
    decay: ``I + Diag(beta) A`` is one plus the strictly lower triangle
    of ones, whose inverse by powers cancels numbers of seventeen
    digits; a back-substitution does not notice."""
    q, k, v, g, beta = operands(64, "plain_delta_rule")
    k = jnp.broadcast_to(k[:, :1], k.shape)
    np.testing.assert_allclose(
        kda.kda(q, k, v, g, beta, chunk=64),
        recurrence(q, k, v, g, beta),
        rtol=1e-4,
        atol=1e-5,
    )


def test_operands_of_bfloat16_keep_a_float32_state():
    """bf16 operands: the result is bf16 and within bf16's reach of the
    float32 recurrence."""
    args = operands(128)
    low = [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])
    got = kda.kda(*low, chunk=64)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*[a.astype(jnp.float32) for a in low])
    np.testing.assert_allclose(
        got.astype(jnp.float32), want, rtol=0.05, atol=0.02
    )


@pytest.mark.parametrize("chunks", [2, 16])
def test_gradients_under_operands_of_bfloat16(chunks):
    """The cotangents of q, k and v come back bf16, those of ``g`` and
    ``beta`` float32, each within bf16's reach of the float32
    recurrence's (one group of two chunks; two groups of eight)."""
    args = operands(64 * chunks, seed=7)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    weights = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = jax.grad(
        lambda *a: jnp.sum(kda.kda(*a, chunk=64).astype(jnp.float32) * weights),
        argnums=(0, 1, 2, 3, 4),
    )(*low)
    want = jax.grad(weighted(recurrence, weights), argnums=(0, 1, 2, 3, 4))(
        *[a.astype(jnp.float32) for a in low]
    )
    assert [a.dtype for a in got] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    for name, g_got, g_want in zip("qkvgb", got, want):
        error = jnp.linalg.norm((g_got.astype(jnp.float32) - g_want).ravel())
        assert error < 0.03 * jnp.linalg.norm(g_want.ravel()), name
