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
    for aval in _every_aval(jaxpr.jaxpr):
        shape = getattr(aval, "shape", ())
        assert shape.count(L) < 2, shape


def _every_aval(jaxpr):
    """Every value of a jaxpr and of the jaxprs nested in it (a
    pallas_call's kernel among them)."""
    for var in list(jaxpr.invars) + [
        v for eqn in jaxpr.eqns for v in eqn.outvars
    ]:
        yield var.aval
    for eqn in jaxpr.eqns:
        for sub in eqn.params.values():
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield from _every_aval(sub)


@pytest.mark.parametrize("block", [16, 64], ids=["four-tiles", "one-tile"])
def test_flash_gradient_carries_no_statistic_across_128_lanes(block):
    """lse and delta are one f32 a row. Until PR 30 they left the forward
    and entered both backward kernels as (batch*heads, L, 128), every lane
    the same number: no value of that shape is left, outside the kernels
    (the arrays) or inside them (a block of a whole length)."""
    L = 64
    q, k, v = _qkv(l=L)

    def loss(q, k, v):
        return (flash_attention(q, k, v, True, block, block) ** 2).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    shapes = {
        tuple(aval.shape)
        for aval in _every_aval(jaxpr.jaxpr)
        if hasattr(aval, "shape")
    }
    assert (4, 1, L) in shapes  # the statistics as they travel now
    wide = [s for s in shapes if len(s) == 3 and s[1:] == (L, 128)]
    assert not wide, wide


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


@pytest.mark.parametrize("block", [16, 8], ids=["one-tile-a-half", "two"])
def test_flash_with_lse_merges_like_ring(block):
    """(out, lse) pairs from two K/V halves merged with the logsumexp
    rule must equal attention over the full K/V — the property ring
    attention's per-block fused path relies on."""
    import jax.numpy as jnp

    from elasticdl_tpu.ops.flash_attention import flash_attention_with_lse

    q, k, v = _qkv(l=32)
    half = 16
    o1, l1 = flash_attention_with_lse(
        q, k[:, :half], v[:, :half], False, block, block
    )
    o2, l2 = flash_attention_with_lse(
        q, k[:, half:], v[:, half:], False, block, block
    )
    assert l1.shape == l2.shape == (2, 2, 32)  # (B, H, L), as ring merges it
    lse = jnp.logaddexp(l1, l2)  # (B, H, L)
    w1 = jnp.exp(l1 - lse).transpose(0, 2, 1)[..., None]
    w2 = jnp.exp(l2 - lse).transpose(0, 2, 1)[..., None]
    merged = o1 * w1 + o2 * w2
    want = reference_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(merged), np.asarray(want), rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize("block", [16, 32], ids=["two-tiles", "one-tile"])
def test_flash_lse_cotangent_propagates(block):
    """A loss that uses the lse output (e.g. a z-loss) must produce the
    same gradients as the dense logsumexp."""
    import jax.numpy as jnp

    from elasticdl_tpu.ops.flash_attention import flash_attention_with_lse

    q, k, v = _qkv(l=32)
    scale = q.shape[-1] ** -0.5

    def loss_flash(q, k, v):
        out, lse = flash_attention_with_lse(q, k, v, False, block, block)
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
    # PR 31: four tiles each way, so that under the causal mask six grid
    # steps a head are skipped and their blocks clamped to a neighbour's
    pytest.param(64, 64, 16, 16, 8, id="four-tiles-a-head"),
    pytest.param(96, 96, 32, 48, 8, id="tiles-that-share-no-edge"),
]
# both ways, and one call whose lengths differ (a ring block's shape; the
# models' causal calls have lq == lk); bf16 on a square and on a rectangular
# tiling (PR 31: the statistics stay f32 whatever the operands are)
CASES = (
    [
        pytest.param(*g.values, causal, "float32", id="%s-%s" % (g.id, name))
        for g in GEOMETRIES
        for causal, name in ((False, "full"), (True, "causal"))
    ]
    + [pytest.param(64, 32, 32, 16, 8, False, "float32", id="lq-over-lk-full")]
    + [
        pytest.param(
            *g.values, causal, "bfloat16", id="%s-%s-bf16" % (g.id, name)
        )
        for g in (GEOMETRIES[0], GEOMETRIES[2], GEOMETRIES[6])
        for causal, name in ((False, "full"), (True, "causal"))
    ]
)
# against the dense computation in f32 on the same (rounded) inputs: the
# kernels' own arithmetic is f32, so bf16 only rounds what they return
TOLERANCE = {
    "float32": dict(rtol=2e-4, atol=2e-5),
    "bfloat16": dict(rtol=2e-2, atol=2e-2),
}
GRAD_TOLERANCE = {
    "float32": dict(rtol=3e-4, atol=3e-4),
    "bfloat16": dict(rtol=5e-2, atol=5e-2),
}


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


def _geometry_inputs(lq, lk, dtype="float32"):
    """(q, k, v) in ``dtype`` and the same numbers in f32 for the dense
    computation."""
    import jax.numpy as jnp

    rng = np.random.default_rng(lq + lk)
    given = tuple(
        jnp.asarray(rng.standard_normal((2, l, 2, 16)), dtype)
        for l in (lq, lk, lk)
    )
    return given, tuple(np.asarray(x, np.float32) for x in given)


@pytest.mark.parametrize("lq, lk, block_q, block_k, w, causal, dtype", CASES)
def test_sub_blocked_forward_matches_dense(
    lq, lk, block_q, block_k, w, causal, dtype
):
    from elasticdl_tpu.ops.flash_attention import _flash_fwd

    (q, k, v), exact = _geometry_inputs(lq, lk, dtype)
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k, True, w=w)
    want_out, want_lse = _dense(*exact, causal)
    assert out.dtype == q.dtype and lse.dtype == np.float32
    assert lse.shape == (2, 2, lq)  # the residual, as every caller has it
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want_out), **TOLERANCE[dtype]
    )
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(want_lse), rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize("lq, lk, block_q, block_k, w, causal, dtype", CASES)
def test_sub_blocked_gradients_match_dense(
    lq, lk, block_q, block_k, w, causal, dtype
):
    """dq, dk, dv under a cotangent on out AND on lse."""
    from elasticdl_tpu.ops.flash_attention import _flash_bwd, _flash_fwd

    (q, k, v), exact = _geometry_inputs(lq, lk, dtype)
    rng = np.random.default_rng(7)
    g = np.asarray(rng.standard_normal(q.shape), dtype).astype(np.float32)
    g_lse = rng.standard_normal((2, 2, lq)).astype(np.float32)

    def loss(q, k, v):
        out, lse = _dense(q, k, v, causal)
        return (out * g).sum() + (lse * g_lse).sum()

    want = jax.grad(loss, argnums=(0, 1, 2))(*exact)
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k, True, w=w)
    got = _flash_bwd(
        q, k, v, out, lse, g, causal, block_q, block_k, True,
        g_lse=g_lse, w=w,
    )
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b), **GRAD_TOLERANCE[dtype]
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


# ---------------------------------------------------------------------------
# what moves through HBM (PR 31): the statistics travel as (batch*heads, 1, L)
# and, under the causal mask, the blocks that follow a grid's inner axis are
# clamped to the nearest tile with work, so a skipped step copies nothing
# ---------------------------------------------------------------------------

from elasticdl_tpu.ops.flash_attention import (  # noqa: E402
    BWD_DKV_KERNEL as DKV,
    BWD_DQ_KERNEL as DQ,
    FWD_KERNEL as FWD,
)


@pytest.mark.parametrize(
    "causal, inner_blocks_a_head", [(True, 2), (False, 4)],
    ids=["causal-clamped", "full-every-step"],
)
def test_grid_walk_counts_fetches_at_2048(causal, inner_blocks_a_head):
    """L = 2,048 in 1,024-tiles, four grid steps a head. Under the mask one
    of them is skipped: it used to fetch its blocks all the same (k and v in
    the forward and dq, q, dO and both statistics in dkv: 4 a head, what the
    unmasked call rightly still does) and now names its neighbour's (2)."""
    from elasticdl_tpu.ops.flash_attention import hbm_traffic

    heads = 96
    blocks = {
        kernel: moved["blocks"]
        for kernel, moved in hbm_traffic(
            heads, 2048, 2048, 64, 1024, 1024, causal=causal
        ).items()
    }
    for kernel in (FWD, DQ):
        assert blocks[kernel]["k"] == blocks[kernel]["v"] == (
            inner_blocks_a_head * heads
        )
        assert blocks[kernel]["q"] == 2 * heads  # follows the outer axis
    for name in ("q", "dO", "lse", "delta"):
        assert blocks[DKV][name] == inner_blocks_a_head * heads, name
    assert blocks[DKV]["k"] == blocks[DKV]["v"] == 2 * heads
    # every result is written once a tile, clamped or not
    assert blocks[FWD]["o"] == blocks[FWD]["lse"] == 2 * heads
    assert blocks[DQ]["dq"] == blocks[DKV]["dk"] == blocks[DKV]["dv"] == (
        2 * heads
    )


@pytest.mark.parametrize("heads", [96, 32], ids=["lm125m", "lm350m"])
def test_hbm_traffic_against_hand_counts(heads):
    """bf16, causal, heads x 2,048 x 64 in 1,024-tiles. A tensor tile is
    1,024 x 64 x 2 B = 128 KiB, a statistic's 1,024 x 4 B = 4 KiB; two
    tiles of each a head."""
    from elasticdl_tpu.ops.flash_attention import hbm_traffic

    tile, stat = 1024 * 64 * 2, 1024 * 4
    got = hbm_traffic(heads, 2048, 2048, 64, 1024, 1024)
    want = {
        # q k v o | lse
        FWD: (4 * 2 * tile, 1 * 2 * stat),
        # q k v dO dq | lse delta
        DQ: (5 * 2 * tile, 2 * 2 * stat),
        # q k v dO dk dv | lse delta
        DKV: (6 * 2 * tile, 2 * 2 * stat),
    }
    for kernel, (tensors, statistics) in want.items():
        assert got[kernel]["tensors"] == heads * tensors, kernel
        assert got[kernel]["statistics"] == heads * statistics, kernel
    if heads == 96:
        # the figures the module's docstring and PERF.md quote
        total = lambda kind: sum(m[kind] for m in got.values()) / 1e6
        assert total("tensors") == pytest.approx(377.5, abs=0.05)
        assert total("statistics") == pytest.approx(3.93, abs=0.01)
        # the same walk with nothing clamped (not causal) is what the
        # tensors cost before: 12 + 14 + 16 tiles a head
        full = hbm_traffic(heads, 2048, 2048, 64, 1024, 1024, causal=False)
        assert sum(m["tensors"] for m in full.values()) == (
            heads * 42 * tile
        )


@pytest.mark.parametrize(
    "lq, lk, block_q, block_k, w",
    GEOMETRIES
    + [
        # k tiles that no q row sees: their first q tile lies past the end
        pytest.param(32, 64, 16, 16, 8, id="lk-over-lq"),
        pytest.param(64, 32, 16, 16, 8, id="lq-over-lk"),
    ],
)
def test_a_tile_with_work_is_handed_its_own_blocks(
    lq, lk, block_q, block_k, w
):
    """The clamp may only touch steps that do nothing: wherever the walk of
    a tile performs a sub-block, every operand's block is the tile's own;
    wherever it performs none, the blocks named are inside the arrays."""
    from elasticdl_tpu.ops import flash_attention as fa

    nq, nk = lq // block_q, lk // block_k
    for kernel in (FWD, DQ, DKV):
        grid, inputs, outputs = fa._plan(
            kernel, 2, lq, lk, 16, block_q, block_k, True
        )
        for i, a, b in np.ndindex(*grid):
            qi, kj = (b, a) if kernel == DKV else (a, b)
            worked = []
            fa._walk_tile(
                qi, kj, block_q, block_k, w, True, worked.append,
                when=fa._run_if,
            )
            for name, spec in inputs + outputs:
                index = spec.index_map(int(i), int(a), int(b))
                along = index[2] if name in ("lse", "delta") else index[1]
                own = kj if name in ("k", "v", "dk", "dv") else qi
                limit = nk if name in ("k", "v", "dk", "dv") else nq
                assert index[0] == i and 0 <= along < limit
                if worked:
                    assert along == own, (kernel, name, qi, kj)
    # and the clamp does engage: some skipped step names another tile
    assert fa.hbm_traffic(2, lq, lk, 16, block_q, block_k)[DKV]["blocks"][
        "q"
    ] <= 2 * nq * nk


@pytest.mark.parametrize(
    "lq, lk, block_q, block_k, ok",
    [
        (2048, 2048, 1024, 1024, True),
        (1024, 1024, 128, 128, True),  # ring attention's default tiles
        (1024, 1024, 128, 8, True),  # block_k is only ever a sublane dim
        (1024, 1024, 64, 128, False),  # block_q is the statistics' lane dim
        (1024, 1024, 8, 8, False),
        (100, 100, 128, 128, True),  # a whole length suits any dimension
        (100, 36, 1024, 1024, True),
        (1000, 1000, 500, 1000, False),
        (2048, 2048, 768, 1024, False),  # does not divide the length
    ],
)
def test_divisible_on_a_tpu_wants_block_q_in_whole_lanes(
    monkeypatch, lq, lk, block_q, block_k, ok
):
    from elasticdl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "kernel_interpret_mode", lambda: False)
    assert fa.divisible(lq, lk, block_q, block_k) is ok
    if lq % min(block_q, lq) == 0 and lk % min(block_k, lk) == 0:
        # interpret mode keeps no constraint but division
        monkeypatch.setattr(fa, "kernel_interpret_mode", lambda: True)
        assert fa.divisible(lq, lk, block_q, block_k)
