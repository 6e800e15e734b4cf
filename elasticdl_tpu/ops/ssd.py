"""The Mamba-2 selective scan (state-space duality), in chunks, with a
backward: the recurrence of a state-space layer whose decay is one
scalar a head and a position,

    S_t = exp(dt_t * a) * S_{t-1} + dt_t * x_t (x) b_t     (head_dim x state, S_{-1} = 0)
    y_t = S_t c_t

for every head; ``b`` and ``c`` are shared by the heads of a group.
The skip ``D * x``, the convolution in front and the gated norm behind
are the model's (model_zoo/transformer_lm/hybrid_moe_lm.py).

Position by position that is ``L`` dependent steps. Here the sequence
goes through in chunks of ``chunk`` positions (``lax.scan``), and a
chunk needs three products and the state it was handed. With ``cum_t``
the sum of ``dt * a`` from the chunk's first position to ``t``:

    y_t  = sum_{s <= t} exp(cum_t - cum_s) * dt_s * (c_t . b_s) * x_s     (inside the chunk)
         + exp(cum_t) * S_in c_t                                          (what came before it)
    S_out = exp(cum_last) * S_in + sum_s exp(cum_last - cum_s) * dt_s * x_s (x) b_s

``dt``, the decays and their sums are float32 and every exponent is at
most 0; the products take operands of ``x``'s dtype and accumulate in
float32; the carried state is float32. A chunk's ``(batch, heads,
chunk, chunk)`` decay matrix lives and dies inside one iteration: the
loop keeps the state it handed each chunk and recomputes the rest in
the backward pass (``jax.checkpoint`` of the body), so what the scan
holds for its backward is ``chunks x (batch, heads, head_dim, state)``
float32 and its own inputs.

Named scope: ``edl/ssd`` (docs/observability.md). A device trace
carries no scope; there the scan is the ``while`` loops that carry the
``(batch, heads, head_dim, state)`` float32 state (forward) or its
cotangent (backward).
"""

import jax
import jax.numpy as jnp

SCOPE = "edl/ssd"


def _chunked(array, chunk):
    """(B, L, ...) -> (L / chunk, B, chunk, ...): the scan's axis first."""
    b, length = array.shape[:2]
    split = array.reshape((b, length // chunk, chunk) + array.shape[2:])
    return jnp.moveaxis(split, 1, 0)


def _chunk_step(state, inputs):
    """One chunk: (B, H, P, N) float32 state in, the chunk's ``y``
    (B, Q, H, P) float32 and the state after it out. ``inputs``: x
    (B, Q, G, J, P), dt and cum (B, G, J, Q) float32, b and c
    (B, Q, G, N); H = G * J."""
    x, dt, cum, b, c = inputs
    dtype = x.dtype
    batch, q, groups, per_group, p = x.shape
    last = cum[..., -1:]
    # (c_t . b_s), once a group
    scores = jnp.einsum(
        "bqgn,bsgn->bgqs", c, b, preferred_element_type=jnp.float32
    )
    # exp(cum_t - cum_s) where s < t, 1 on the diagonal, 0 above it.
    # The exponent is masked before the exp: above the diagonal it
    # would be positive and overflow, and on it it is the constant 0,
    # which keeps the diagonal's terms, the largest, out of the
    # gradient of ``cum``, where they would enter twice with opposite
    # signs and leave their rounding behind
    below = jnp.tril(jnp.ones((q, q), bool), -1)
    decay = jnp.where(
        jnp.tril(jnp.ones((q, q), bool)),
        jnp.exp(jnp.where(below, cum[..., :, None] - cum[..., None, :], 0.0)),
        0.0,
    )
    mixing = scores[:, :, None] * decay * dt[..., None, :]
    within = jnp.einsum(
        "bgjqs,bsgjp->bqgjp",
        mixing.astype(dtype),
        x,
        preferred_element_type=jnp.float32,
    )
    held = state.reshape(batch, groups, per_group, p, -1)
    before = jnp.einsum(
        "bqgn,bgjpn->bqgjp",
        c,
        held.astype(dtype),
        preferred_element_type=jnp.float32,
    ) * jnp.moveaxis(jnp.exp(cum), -1, 1)[..., None]
    # what each position leaves in the state at the chunk's end
    left = jnp.moveaxis(jnp.exp(last - cum) * dt, -1, 1)[..., None]
    added = jnp.einsum(
        "bqgjp,bqgn->bgjpn",
        (x * left).astype(dtype),
        b,
        preferred_element_type=jnp.float32,
    )
    after = jnp.exp(last)[..., None] * held + added
    return after.reshape(state.shape), (within + before).reshape(
        batch, q, groups * per_group, p
    )


def ssd_scan(x, dt, a, b, c, chunk):
    """``y`` of the recurrence above, (B, L, H, P) in ``x``'s dtype.

    ``x``: (B, L, H, P); ``dt``: (B, L, H), positive (after its
    softplus); ``a``: (H,), negative; ``b``, ``c``: (B, L, G, N) with G
    dividing H, group ``g`` serving heads ``g * H/G .. (g + 1) * H/G -
    1``. A length that is no multiple of ``chunk`` is padded behind
    with positions that decay nothing and add nothing."""
    batch, length, heads, p = x.shape
    groups, n = b.shape[2:]
    if heads % groups:
        raise ValueError(
            "%d heads are not a multiple of %d groups" % (heads, groups)
        )
    chunk = min(chunk, length)
    padded = -length % chunk
    if padded:
        pad = lambda t: jnp.pad(
            t, ((0, 0), (0, padded)) + ((0, 0),) * (t.ndim - 2)
        )
        x, dt, b, c = pad(x), pad(dt), pad(b), pad(c)
    with jax.named_scope(SCOPE):
        dt = dt.astype(jnp.float32)
        by_group = (groups, heads // groups)

        def scalars(t):
            """(B, L, H) -> (chunks, B, G, J, Q): the chunk's positions
            along the lanes"""
            return jnp.moveaxis(
                _chunked(t, chunk).reshape((-1, batch, chunk) + by_group),
                2,
                -1,
            )

        steps = scalars(dt * a.astype(jnp.float32))
        inputs = (
            _chunked(x, chunk).reshape((-1, batch, chunk) + by_group + (p,)),
            scalars(dt),
            jnp.cumsum(steps, axis=-1),
            _chunked(b.astype(x.dtype), chunk),
            _chunked(c.astype(x.dtype), chunk),
        )
        state = jnp.zeros((batch, heads, p, n), jnp.float32)
        _, y = jax.lax.scan(
            jax.checkpoint(_chunk_step, prevent_cse=False), state, inputs
        )
        y = jnp.moveaxis(y, 0, 1).reshape(batch, length + padded, heads, p)
    return y[:, :length].astype(x.dtype)
