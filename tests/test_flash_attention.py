"""Pallas flash-attention kernel vs the XLA reference (interpret mode)."""

import jax
import numpy as np
import pytest

from elasticdl_tpu.ops.flash_attention import flash_attention
from elasticdl_tpu.parallel.ring_attention import reference_attention


def _qkv(b=2, l=64, h=2, d=16, seed=0):
    rng = np.random.default_rng(seed)
    shape = (b, l, h, d)
    return tuple(
        rng.standard_normal(shape).astype(np.float32) for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    got = np.asarray(
        jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, causal, 16, 16
            )
        )(q, k, v)
    )
    want = np.asarray(reference_attention(q, k, v, causal=causal))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_flash_gradients():
    q, k, v = _qkv(l=32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, True, 16, 16) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-4
        )


def test_flash_rejects_nondivisible():
    q, k, v = _qkv(l=60)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, False, 16, 16)


def test_flash_backward_never_materializes_dense_scores():
    """The round-1 advisor finding: the old backward re-ran dense
    reference attention, materializing (L, L). The blockwise backward's
    jaxpr must contain no intermediate with two sequence-length dims
    (only (block, block) tiles inside the kernels)."""
    L = 64
    q, k, v = _qkv(l=L)

    def loss(q, k, v):
        return (flash_attention(q, k, v, True, 16, 16) ** 2).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    def no_dense(jx):
        for eqn in jx.eqns:
            for var in eqn.outvars:
                shape = getattr(var.aval, "shape", ())
                assert shape.count(L) < 2, (eqn.primitive, shape)
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    no_dense(sub.jaxpr)

    no_dense(jaxpr.jaxpr)


def test_flash_gradients_bfloat16():
    import jax.numpy as jnp

    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(l=32))

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, True, 16, 16).astype(jnp.float32) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (
            reference_attention(q, k, v, causal=True).astype(jnp.float32)
            ** 2
        ).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32),
            np.asarray(b, dtype=np.float32),
            rtol=0.1,
            atol=0.1,
        )


def test_flash_with_lse_merges_like_ring():
    """(out, lse) pairs from two K/V halves merged with the logsumexp
    rule must equal attention over the full K/V — the property ring
    attention's per-block fused path relies on."""
    import jax.numpy as jnp

    from elasticdl_tpu.ops.flash_attention import flash_attention_with_lse

    q, k, v = _qkv(l=32)
    half = 16
    o1, l1 = flash_attention_with_lse(q, k[:, :half], v[:, :half], False, 16, 16)
    o2, l2 = flash_attention_with_lse(q, k[:, half:], v[:, half:], False, 16, 16)
    lse = jnp.logaddexp(l1, l2)  # (B, H, L)
    w1 = jnp.exp(l1 - lse).transpose(0, 2, 1)[..., None]
    w2 = jnp.exp(l2 - lse).transpose(0, 2, 1)[..., None]
    merged = o1 * w1 + o2 * w2
    want = reference_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(merged), np.asarray(want), rtol=2e-4, atol=2e-5
    )


def test_flash_lse_cotangent_propagates():
    """A loss that uses the lse output (e.g. a z-loss) must produce the
    same gradients as the dense logsumexp."""
    import jax.numpy as jnp

    from elasticdl_tpu.ops.flash_attention import flash_attention_with_lse

    q, k, v = _qkv(l=32)
    scale = q.shape[-1] ** -0.5

    def loss_flash(q, k, v):
        out, lse = flash_attention_with_lse(q, k, v, False, 16, 16)
        return (out ** 2).sum() + 0.1 * (lse ** 2).sum()

    def loss_ref(q, k, v):
        out = reference_attention(q, k, v)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        return (out ** 2).sum() + 0.1 * (lse ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-4
        )


# ---------------------------------------------------------------------------
# sub-blocks inside the tile (PR 29): the bodies cut a tile into sub-blocks of
# width w and skip, trim or mask each by where the diagonal passes. The public
# functions derive w from the head size; the private ones take it, so that a
# test can force several sub-blocks into a toy tile.
# ---------------------------------------------------------------------------

# lq, lk, block_q, block_k, w
GEOMETRIES = [
    pytest.param(64, 64, 32, 32, 8, id="four-sub-blocks-a-tile"),
    pytest.param(64, 64, 64, 64, 16, id="one-tile-a-head"),
    pytest.param(64, 64, 32, 16, 8, id="block_q-over-block_k"),
    pytest.param(64, 64, 16, 32, 8, id="block_k-over-block_q"),
    pytest.param(64, 64, 32, 32, 12, id="width-dividing-nothing"),
    pytest.param(256, 256, 256, 256, 128, id="sub-blocks-of-whole-lanes"),
]
# both ways, and one call whose lengths differ (a ring block's shape; the
# models' causal calls have lq == lk)
CASES = [
    pytest.param(*g.values, causal, id="%s-%s" % (g.id, name))
    for g in GEOMETRIES
    for causal, name in ((False, "full"), (True, "causal"))
] + [pytest.param(64, 32, 32, 16, 8, False, id="lq-over-lk-full")]


def _dense(q, k, v, causal):
    """(out, lse) the long way, in f32."""
    import jax.numpy as jnp

    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    if causal:
        keep = jnp.arange(q.shape[1])[:, None] >= jnp.arange(k.shape[1])
        s = jnp.where(keep, s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v)
    return out, lse


def _geometry_inputs(lq, lk):
    rng = np.random.default_rng(lq + lk)
    q, k, v = (
        rng.standard_normal((2, l, 2, 16)).astype(np.float32)
        for l in (lq, lk, lk)
    )
    return q, k, v


@pytest.mark.parametrize("lq, lk, block_q, block_k, w, causal", CASES)
def test_sub_blocked_forward_matches_dense(
    lq, lk, block_q, block_k, w, causal
):
    from elasticdl_tpu.ops.flash_attention import _flash_fwd

    q, k, v = _geometry_inputs(lq, lk)
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k, True, w=w)
    want_out, want_lse = _dense(q, k, v, causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want_out), rtol=2e-4, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(want_lse), rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize("lq, lk, block_q, block_k, w, causal", CASES)
def test_sub_blocked_gradients_match_dense(
    lq, lk, block_q, block_k, w, causal
):
    """dq, dk, dv under a cotangent on out AND on lse."""
    from elasticdl_tpu.ops.flash_attention import _flash_bwd, _flash_fwd

    q, k, v = _geometry_inputs(lq, lk)
    rng = np.random.default_rng(7)
    g = rng.standard_normal(q.shape).astype(np.float32)
    g_lse = rng.standard_normal((2, 2, lq)).astype(np.float32)

    def loss(q, k, v):
        out, lse = _dense(q, k, v, causal)
        return (out * g).sum() + (lse * g_lse).sum()

    want = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k, True, w=w)
    got = _flash_bwd(
        q, k, v, out, lse, g, causal, block_q, block_k, True,
        g_lse=g_lse, w=w,
    )
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-4
        )


@pytest.mark.parametrize(
    "length, w, performed_tiles, kept",
    [
        # L = 2,048 in 1,024-tiles: two tiles on the diagonal, one below
        (2048, 1024, 3.0, 2048 * 2049 // 2),
        (2048, 512, 2 * 0.75 + 1, 2048 * 2049 // 2),
        (2048, 256, 2 * 0.625 + 1, 2048 * 2049 // 2),
        (2048, 128, 2 * 0.5625 + 1, 2048 * 2049 // 2),
        # L = 1,024: one tile a head, all of it on the diagonal
        (1024, 1024, 1.0, 1024 * 1025 // 2),
        (1024, 256, 0.625, 1024 * 1025 // 2),
        # L = 4,096: four on the diagonal, six below
        (4096, 256, 4 * 0.625 + 6, 4096 * 4097 // 2),
    ],
)
def test_causal_work_ratio_against_hand_counts(
    length, w, performed_tiles, kept
):
    from elasticdl_tpu.ops.flash_attention import causal_work_ratio

    got = causal_work_ratio(length, length, 1024, 1024, w)
    assert got == pytest.approx(performed_tiles * 1024 * 1024 / kept)
    # against L^2 / 2, which is what the benchmark's flops count
    assert got == pytest.approx(
        performed_tiles * 1024 * 1024 / (length * length / 2), rel=1e-3
    )


def test_causal_work_ratio_where_nothing_is_trimmed():
    from elasticdl_tpu.ops.flash_attention import causal_work_ratio

    # nothing masked, nothing skipped
    assert causal_work_ratio(2048, 2048, 1024, 1024, 256, causal=False) == 1.0
    assert causal_work_ratio(64, 32, 32, 16, 8, causal=False) == 1.0
    # tiles that are not square are skipped or kept whole: of the eight
    # 32 x 16 tiles of a 64 x 64 call two lie above the diagonal
    assert causal_work_ratio(64, 64, 32, 16, 8) == pytest.approx(
        6 * 32 * 16 / (64 * 65 / 2)
    )


def test_the_width_is_derived_and_no_caller_can_set_it():
    import inspect

    from elasticdl_tpu.ops import flash_attention as fa

    assert fa.sub_block(64) == fa.sub_block(128) == 256  # both swept
    for fn in (fa.flash_attention, fa.flash_attention_with_lse):
        assert list(inspect.signature(fn).parameters) == [
            "q", "k", "v", "causal", "block_q", "block_k",
        ]


def test_three_kernels_a_layer_under_their_names():
    """The benchmark counts the Mosaic calls of a built step and reads the
    kernels by name: sub-blocking must not split or rename them."""
    q, k, v = _qkv(l=32)

    def loss(q, k, v):
        return (flash_attention(q, k, v, True, 16, 16) ** 2).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    assert text.count("pallas_call") == 3
    for name in ("edl_flash_fwd", "edl_flash_bwd_dq", "edl_flash_bwd_dkv"):
        assert text.count("name=%s" % name) == 1, name
