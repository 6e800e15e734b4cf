"""Pallas flash-attention kernel vs the XLA reference (interpret mode)."""

import functools

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
    plain = ["q", "k", "v", "causal", "block_q", "block_k"]
    # PR 39: a caller may name a window, which is the model's; not a width
    for fn, extra in (
        (fa.flash_attention, ["window"]),
        (fa.flash_attention_with_lse, []),
    ):
        assert list(inspect.signature(fn).parameters) == plain + extra


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
    """Every tile in which the walk of a tile performs a sub-block is a
    step of the grid, once, and every operand's block there is the
    tile's own; a step that performs none (an outer tile past the other
    sequence's end keeps one, to write its zeros) names blocks inside
    the arrays."""
    _check_the_walk(lq, lk, block_q, block_k, w)


def _check_the_walk(lq, lk, block_q, block_k, w, window=None):
    from elasticdl_tpu.ops import flash_attention as fa

    nq, nk = lq // block_q, lk // block_k
    every_empty = 0
    for kernel in (FWD, DQ, DKV):
        grid, tables, inputs, outputs = fa._plan(
            kernel, 2, lq, lk, 16, block_q, block_k, True, window=window
        )
        q_tiles, k_tiles = fa._walk(
            kernel, lq, lk, block_q, block_k, True, window
        )
        assert tables == (q_tiles, k_tiles) and grid == (2, len(q_tiles))
        # dkv runs q tiles inside a k tile, the other two the other way
        outer, inner = (k_tiles, q_tiles) if kernel == DKV else tables
        tile_of = lambda a, b: (b, a) if kernel == DKV else (a, b)
        with_work = set()
        for a, b in np.ndindex(*((nk, nq) if kernel == DKV else (nq, nk))):
            qi, kj = tile_of(a, b)
            worked = []
            fa._walk_tile(
                qi, kj, block_q, block_k, w, True, worked.append,
                when=fa._run_if, window=window,
            )  # fmt: skip
            if window is not None:
                # a tile does work exactly where the band passes through
                behind = (
                    qi * block_q + np.arange(block_q)[:, None]
                    - kj * block_k - np.arange(block_k)
                )  # fmt: skip
                assert bool(worked) == bool(
                    ((behind >= 0) & (behind < window)).any()
                ), (qi, kj)
            assert bool(worked) == fa._has_work(
                qi, kj, block_q, block_k, True, window
            )
            if worked:
                with_work.add((a, b))
        listed = list(zip(outer, inner))
        # each tile with work once; an outer tile's steps consecutive,
        # its inner tiles ascending; every outer tile writes its result
        assert len(set(listed)) == len(listed) and with_work <= set(listed)
        assert listed == sorted(listed)
        assert set(outer) == set(range(nk if kernel == DKV else nq))
        # a step without work is the only step of its outer tile
        idle = set(listed) - with_work
        assert all(outer.count(a) == 1 for a, _ in idle)
        every_empty += len(idle)
        for i, s in np.ndindex(*grid):
            qi, kj = q_tiles[s], k_tiles[s]
            for name, spec in inputs + outputs:
                index = spec.index_map(int(i), int(s), *tables)
                along = index[2] if name in ("lse", "delta") else index[1]
                own = kj if name in ("k", "v", "dk", "dv") else qi
                limit = nk if name in ("k", "v", "dk", "dv") else nq
                assert index[0] == i and 0 <= along < limit
                assert along == own, (kernel, name, qi, kj)
    # the masks do leave tiles out: fewer steps than the rectangle has
    if nq > 1 and nk > 1:
        assert len(listed) < nq * nk
    if lq == lk:
        assert every_empty == 0
    return every_empty


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


# ---------------------------------------------------------------------------
# under a window (PR 39): query t reads keys t - W < s <= t. Tiles wholly
# outside the band do nothing and fetch nothing, tiles wholly inside run
# unmasked, the diagonal's and the lower edge's are trimmed and masked
# ---------------------------------------------------------------------------

WINDOWS = [
    # lq, lk, block_q, block_k, w, window
    pytest.param(64, 64, 16, 16, 4, 20, id="w-over-a-tile-not-a-multiple"),
    pytest.param(64, 64, 16, 16, 4, 16, id="w-one-tile"),
    pytest.param(64, 64, 16, 16, 4, 32, id="w-two-tiles"),
    pytest.param(64, 64, 16, 16, 4, 5, id="w-under-a-tile"),
    pytest.param(64, 64, 16, 16, 8, 1, id="w-the-key-itself"),
    pytest.param(64, 64, 16, 16, 16, 24, id="tile-left-whole"),
    pytest.param(64, 64, 16, 16, 4, 63, id="w-all-but-one-key"),
    pytest.param(128, 128, 32, 32, 8, 50, id="larger-tiles"),
    pytest.param(64, 64, 32, 16, 8, 20, id="tiles-32x16"),
    pytest.param(64, 64, 16, 32, 8, 20, id="tiles-16x32"),
    pytest.param(64, 64, 64, 64, 16, 20, id="one-tile-a-head"),
]


def _banded(q, k, v, window):
    """(out, lse) the long way, in f32, over t - window < s <= t."""
    import jax.numpy as jnp

    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    behind = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])
    s = jnp.where((behind >= 0) & (behind < window), s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v)
    return out, lse


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("lq, lk, block_q, block_k, w, window", WINDOWS)
def test_windowed_forward_matches_a_masked_softmax(
    lq, lk, block_q, block_k, w, window, dtype
):
    from elasticdl_tpu.ops.flash_attention import _flash_fwd

    (q, k, v), exact = _geometry_inputs(lq, lk, dtype)
    out, lse = _flash_fwd(
        q, k, v, True, block_q, block_k, True, w=w, window=window
    )
    want_out, want_lse = _banded(*exact, window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want_out), **TOLERANCE[dtype]
    )
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(want_lse), rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize("lq, lk, block_q, block_k, w, window", WINDOWS)
def test_windowed_gradients_match_a_masked_softmax(
    lq, lk, block_q, block_k, w, window
):
    """dq, dk and dv, each against the dense computation's."""
    from elasticdl_tpu.ops.flash_attention import _flash_bwd, _flash_fwd

    (q, k, v), exact = _geometry_inputs(lq, lk)
    g = np.random.default_rng(7).standard_normal(q.shape).astype(np.float32)
    want = jax.grad(
        lambda q, k, v: (_banded(q, k, v, window)[0] * g).sum(),
        argnums=(0, 1, 2),
    )(*exact)
    out, lse = _flash_fwd(
        q, k, v, True, block_q, block_k, True, w=w, window=window
    )
    got = _flash_bwd(
        q, k, v, out, lse, g, True, block_q, block_k, True, w=w, window=window
    )
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), err_msg=name,
            **GRAD_TOLERANCE["float32"],
        )  # fmt: skip


@pytest.mark.parametrize("window", [64, 65, 1000])
def test_a_window_that_reaches_every_key_is_causal_attention(window):
    """W >= L: built as the plain causal call, under the plain names,
    and so the same numbers bit for bit."""
    from elasticdl_tpu.ops import flash_attention as fa

    q, k, v = _qkv()
    plain = jax.make_jaxpr(lambda *a: fa.flash_attention(*a, True, 16, 16))
    windowed = jax.make_jaxpr(
        lambda *a: fa.flash_attention(*a, True, 16, 16, window=window)
    )
    assert str(plain(q, k, v)) == str(windowed(q, k, v))
    assert "edl_flash_win" not in str(windowed(q, k, v))
    np.testing.assert_array_equal(
        np.asarray(fa.flash_attention(q, k, v, True, 16, 16, window=window)),
        np.asarray(fa.flash_attention(q, k, v, True, 16, 16)),
    )


def test_a_window_goes_under_names_of_its_own_and_trains():
    """Three calls a layer, none of which a reader of the plain
    kernels' prefix catches; a call with no window is built as before."""
    from elasticdl_tpu.ops import flash_attention as fa

    q, k, v = _qkv()

    def loss(window):
        return lambda q, k, v: (
            fa.flash_attention(q, k, v, True, 16, 16, window=window) ** 2
        ).sum()

    text = str(jax.make_jaxpr(jax.grad(loss(20), argnums=(0, 1, 2)))(q, k, v))
    assert text.count("pallas_call") == 3
    for plain, name in fa.WINDOWED.items():
        assert text.count("name=%s\n" % name) == 1, name
        assert "name=%s\n" % plain not in text
        assert not name.startswith(plain + "_")
    plain = str(jax.make_jaxpr(jax.grad(loss(None), argnums=(0, 1, 2)))(q, k, v))
    assert "edl_flash_win" not in plain and "window" not in plain
    got = jax.grad(loss(20), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(
        lambda q, k, v: (fa.windowed_reference_attention(q, k, v, 20) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-4
        )
    for bad in (dict(causal=False, window=8), dict(causal=True, window=0)):
        with pytest.raises(ValueError, match="a window of"):
            fa.flash_attention(q, k, v, block_q=16, block_k=16, **bad)


def test_the_policy_hands_short_lengths_the_window_in_xla():
    from elasticdl_tpu.ops import flash_attention as fa

    q, k, v = _qkv()
    short = fa.pick_causal_attention(64, window=20)
    np.testing.assert_allclose(
        np.asarray(short(q, k, v)),
        np.asarray(_banded(q, k, v, 20)[0]),
        rtol=2e-4, atol=2e-5,
    )  # fmt: skip
    # a window that reaches every key is the plain causal reference
    whole = fa.pick_causal_attention(64, window=64)
    np.testing.assert_array_equal(
        np.asarray(whole(q, k, v)),
        np.asarray(reference_attention(q, k, v, causal=True)),
    )
    long = fa.pick_causal_attention(1024, window=256)
    text = str(
        jax.make_jaxpr(long)(*(np.zeros((1, 1024, 1, 16), np.float32),) * 3)
    )
    assert "name=edl_flash_win_fwd" in text


def test_work_and_traffic_under_the_cells_window():
    """L = 16,384 in 1,024-tiles under a window of 4,096: a q tile has
    work in 5 of its 16 k tiles (three whole, the diagonal's and the
    lower edge's, each 0.625 of a tile at w = 256), the pairs are
    :func:`window_pairs`, and a skipped step fetches nothing."""
    from elasticdl_tpu.ops import flash_attention as fa

    length, window, tile = 16384, 4096, 1024
    kept, causal = fa.window_pairs(length, window)
    assert (kept, causal) == (58_722_304, 134_225_920)
    assert kept == window * (window + 1) // 2 + (length - window) * window
    worked = {}
    for qi in range(16):
        for kj in range(16):
            done = []
            fa._walk_tile(
                qi, kj, tile, tile, 256, True, done.append,
                when=fa._run_if, window=window,
            )  # fmt: skip
            if done:
                worked[qi, kj] = sum(
                    (r.stop - r.start) * (c.stop - c.start)
                    for r, c, _ in done[0]
                ) / tile**2
    for qi in range(16):
        mine = {kj: area for (q, kj), area in worked.items() if q == qi}
        assert sorted(mine) == list(range(max(qi - 4, 0), qi + 1))
        assert mine[qi] == 0.625  # the diagonal's tile
        if qi >= 4:
            assert len(mine) == 5 and mine[qi - 4] == 0.625  # the lower edge's
        assert all(mine[kj] == 1.0 for kj in mine if qi - 4 < kj < qi)
    performed = sum(worked.values())
    assert performed == 16 * 0.625 + 12 * 0.625 + (1 + 2 + 3 * 13)
    assert fa.causal_work_ratio(
        length, length, tile, tile, 256, window=window
    ) == pytest.approx(performed * tile**2 / kept)
    # the static account of blocks moved: a q tile moves the k tiles it
    # works on and no other (the first step of a q tile names the tile
    # the last step of the one before left in place)
    steps = sum(min(qi, 4) + 1 for qi in range(16))
    assert steps == len(worked) == 70
    blocks = {
        kernel: moved["blocks"]
        for kernel, moved in fa.hbm_traffic(
            1, length, length, 128, tile, tile, window=window
        ).items()
    }
    for kernel in (FWD, DQ):
        assert blocks[kernel]["k"] == blocks[kernel]["v"] == steps - 1
        assert blocks[kernel]["q"] == 16
    for name in ("q", "dO", "lse", "delta"):
        assert blocks[DKV][name] == steps - 1
    assert blocks[DKV]["k"] == blocks[DKV]["dk"] == 16
    causal_blocks = fa.hbm_traffic(1, length, length, 128, tile, tile)
    assert causal_blocks[FWD]["blocks"]["k"] == 16 * 17 // 2 - 1


@pytest.mark.parametrize("lq, lk, block_q, block_k, w, window", WINDOWS)
def test_under_a_window_a_tile_with_work_is_handed_its_own_blocks(
    lq, lk, block_q, block_k, w, window
):
    """The band from both sides: a tile is a step of the grid exactly
    where the band passes through it."""
    assert _check_the_walk(lq, lk, block_q, block_k, w, window) == 0


# ---------------------------------------------------------------------------
# a grid step with no tile to compute costs nothing (PR 41): the grid lists
# only tiles with work, handed to the call as two scalar-prefetch tables
# ---------------------------------------------------------------------------

# the three ways a call leaves tiles without work, at the cell's
# proportions in toy tiles: W = 4 tiles, as `smallthinker-ep8-l16384`
MASKS = ["causal", "windowed", "selected"]
TOY_LENGTH, TOY_TILE, TOY_W, TOY_WINDOW = 128, 16, 4, 64


def _toy_selection(length, seed=3):
    """(1, L, L) int8 inside the causal triangle, the diagonal kept."""
    rng = np.random.default_rng(seed)
    kept = rng.random((1, length, length)) < 0.4
    kept |= np.eye(length, dtype=bool)
    return (kept & np.tril(np.ones((length, length), bool))).astype(np.int8)


def _toy_call(
    fa, mask, dtype, length=TOY_LENGTH, tile=TOY_TILE, seed=41,
    sizes=(16, 16), w=TOY_W, window=TOY_WINDOW,
):  # fmt: skip
    """(out, lse, dq, dk, dv) of one call of ``fa``'s kernels under
    ``mask``: eight tiles of ``tile`` each way in sub-blocks of 4, one
    sequence of two heads of ``sizes`` (q and k's, v's), inputs from
    ``seed``."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    q, k, v, g = (
        jnp.asarray(rng.standard_normal((1, length, 2, d)), dtype)
        for d in (sizes[0], sizes[0], sizes[1], sizes[1])
    )
    how = {}
    if mask == "windowed":
        how = {"window": window}
    selection = _toy_selection(length) if mask == "selected" else None
    out, lse = fa._flash_fwd(
        q, k, v, mask != "full", tile, tile, True, w=w,
        selection=selection, **how,
    )  # fmt: skip
    if selection is not None:
        how = {"selection_t": selection.transpose(0, 2, 1)}
    grads = fa._flash_bwd(
        q, k, v, out, lse, g, mask != "full", tile, tile, True, w=w, **how
    )
    return (q, k, v, g), (out, lse) + tuple(grads)


def _digest(fa, mask, dtype, d="16"):
    import hashlib

    _, results = _toy_call(fa, mask, dtype, sizes=(int(d), int(d)))
    sha = hashlib.sha256()
    for x in results:
        sha.update(np.asarray(x).tobytes())
    return sha.hexdigest()[:16]


# out, lse, dq, dk and dv of `_toy_call` as the PARENT's kernels (commit
# 909f9a5, the 3-axis grid with its prologue on every step) computed them
# here on the CPU's interpreter. Recorded anew by
# `PYTHONPATH=. python tests/test_flash_attention.py <a flash_attention.py>`.
BIT_FOR_BIT = {
    "causal-float32": "adfdfb1ccf81b0a2",
    "causal-bfloat16": "c2b11eeaa57cb270",
    "windowed-float32": "9e1ce5c3c27fff8f",
    "windowed-bfloat16": "162f925f8be37582",
    "selected-float32": "992ee4becaa9c301",
    "selected-bfloat16": "6443fa57784f0188",
    "full-float32": "a06c2fec720642fc",
    "full-bfloat16": "107d748b23b31609",
    # head size 64, every cell's whose v leaves lanes spare: the forward
    # PR 47 left as it was, recorded from ITS parent (commit f6a1c34)
    "causal-float32-64": "015a1756b50fb7f3",
    "causal-bfloat16-64": "08c077b0d527bdf5",
    "windowed-float32-64": "3fc559d316d0e79f",
    "windowed-bfloat16-64": "208aab076997c6ee",
    "selected-float32-64": "db7c87d3d497f57d",
    "selected-bfloat16-64": "325cf58eef962190",
    "full-float32-64": "6be03b909ba0abba",
    "full-bfloat16-64": "9f3093a1f016f279",
}


@pytest.mark.parametrize("case", sorted(BIT_FOR_BIT))
def test_a_tile_with_work_computes_what_it_computed(case):
    """Neither the prologue under the tile's ``when`` nor the grid of
    tiles with work changes a product, a mask or their order: bit for
    bit the parent's results."""
    from elasticdl_tpu.ops import flash_attention as fa

    assert _digest(fa, *case.split("-")) == BIT_FOR_BIT[case]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mask", MASKS)
def test_every_tile_of_eight_each_way_against_the_reference(mask, dtype):
    """Forward and the three gradients where the rectangle had 28 (36
    under the window) of 64 steps a head without work."""
    import jax.numpy as jnp

    from elasticdl_tpu.ops import flash_attention as fa

    (q, k, v, g), (out, _, *grads) = _toy_call(fa, mask, dtype)
    if mask == "windowed":
        reference = lambda q, k, v: fa.windowed_reference_attention(
            q, k, v, TOY_WINDOW
        )
    elif mask == "selected":
        selection = jnp.asarray(_toy_selection(TOY_LENGTH))
        reference = lambda q, k, v: fa.selected_reference_attention(
            q, k, v, selection
        )
    else:
        reference = lambda q, k, v: reference_attention(q, k, v, causal=True)
    exact = [x.astype(jnp.float32) for x in (q, k, v)]
    want, vjp = jax.vjp(reference, *exact)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want), **TOLERANCE[dtype]
    )
    for name, a, b in zip(("dq", "dk", "dv"), grads, vjp(g.astype(jnp.float32))):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b), err_msg=name,
            **GRAD_TOLERANCE[dtype],
        )  # fmt: skip


@pytest.mark.parametrize("kernel", [FWD, DQ, DKV])
@pytest.mark.parametrize("mask", MASKS)
def test_the_grid_lists_the_tiles_with_work_and_no_other(mask, kernel):
    """`smallthinker-ep8-l16384`'s calls, 16 x 16 tiles of 1,024 a head
    (and `keyevl2-ep8-l8192`'s selection at the same length): a step
    for each tile with work, once, an outer tile's steps consecutive,
    and none without work, where the rectangle had 120 (186 under the
    window of four tiles) of 256."""
    from elasticdl_tpu.ops import flash_attention as fa

    length, tile, n = 16384, 1024, 16
    window = 4096 if mask == "windowed" else None
    grid, (q_tiles, k_tiles), inputs, _ = fa._plan(
        kernel, 28, length, length, 128, tile, tile, True,
        heads=28 if mask == "selected" else None, window=window,
    )  # fmt: skip
    outer, inner = (k_tiles, q_tiles) if kernel == DKV else (q_tiles, k_tiles)
    band = 5 if window else n  # tiles the band crosses, the diagonal's too
    want = [
        (a, b)
        for a in range(n)
        for b in (
            range(a, min(a + band, n))
            if kernel == DKV
            else range(max(a - band + 1, 0), a + 1)
        )
    ]
    assert list(zip(outer, inner)) == want
    assert grid == (28, 70 if window else 136)
    for a, b in want:
        worked = []
        fa._walk_tile(
            *((b, a) if kernel == DKV else (a, b)), tile, tile, 256, True,
            worked.append, when=fa._run_if, window=window,
        )  # fmt: skip
        assert worked
    # a selection's tile follows both tables: the backward kernels read
    # the transposed selection
    if mask == "selected":
        name, spec = inputs[-1]
        s = 7
        at = (q_tiles[s], k_tiles[s])
        assert spec.index_map(30, s, q_tiles, k_tiles) == (
            1, *(at if kernel == FWD else at[::-1])
        ), name  # fmt: skip


def test_a_call_that_is_not_causal_lists_every_pair():
    from elasticdl_tpu.ops import flash_attention as fa

    for kernel in (FWD, DQ, DKV):
        q_tiles, k_tiles = fa._walk(kernel, 64, 32, 16, 8, False)
        outer, inner = (k_tiles, q_tiles) if kernel == DKV else (q_tiles, k_tiles)
        assert list(zip(outer, inner)) == list(np.ndindex(4, 4))


# what the parent's rectangle moved, whose steps without work named a
# neighbour's block and copied nothing (PR 31, PR 39): bytes a kernel as
# (tensors, statistics) and blocks as (inner-axis operands, the others)
TRAFFIC = {
    "lm125m-l2048": (
        (96, 2048, 64, None),
        {FWD: (100663296, 786432, 192, 192),
         DQ: (125829120, 1572864, 192, 192),
         DKV: (150994944, 1572864, 192, 192)},
    ),
    "smallthinker-window": (
        (28, 16384, 128, 4096),
        {FWD: (1247805440, 1835008, 1932, 448),
         DQ: (1365245952, 3670016, 1932, 448),
         DKV: (1482686464, 15826944, 1932, 448)},
    ),
    "smallthinker-global": (
        (28, 16384, 128, None),
        {FWD: (2216689664, 1835008, 3780, 448),
         DQ: (2334130176, 3670016, 3780, 448),
         DKV: (2451570688, 30965760, 3780, 448)},
    ),
}  # fmt: skip


@pytest.mark.parametrize("cell", sorted(TRAFFIC))
def test_the_listed_grid_moves_what_the_rectangle_moved(cell):
    from elasticdl_tpu.ops import flash_attention as fa

    (heads, length, d, window), want = TRAFFIC[cell]
    got = fa.hbm_traffic(
        heads, length, length, d, 1024, 1024, window=window
    )
    for kernel, (tensors, statistics, inner, others) in want.items():
        moved = got[kernel]
        assert (moved["tensors"], moved["statistics"]) == (tensors, statistics)
        follows_inner = (
            ("q", "dO", "lse", "delta") if kernel == DKV else ("k", "v")
        )
        for name, blocks in moved["blocks"].items():
            assert blocks == (inner if name in follows_inner else others), name
    if cell == "lm125m-l2048":
        total = lambda kind: sum(m[kind] for m in got.values()) / 1e6
        assert total("tensors") == pytest.approx(377.5, abs=0.05)
        assert total("statistics") == pytest.approx(3.93, abs=0.01)


def test_a_call_says_how_many_steps_its_grid_takes():
    """`grid_steps_in` reads the flash calls' grids off a traced program:
    what `step_built` reports as `flash_grid_steps` and
    `flash_grid_steps_empty`."""
    from elasticdl_tpu.ops import flash_attention as fa

    q, k, v = _qkv()  # 2 sequences x 2 heads, 64 positions

    def loss(window):
        return lambda q, k, v: (
            fa.flash_attention(q, k, v, True, 16, 16, window=window) ** 2
        ).sum()

    both = lambda q, k, v: loss(None)(q, k, v) + loss(20)(q, k, v)
    jaxpr = jax.make_jaxpr(jax.grad(both, argnums=(0, 1, 2)))(q, k, v)
    # four tiles each way: ten with work under the diagonal, nine of
    # them under a window of 20 (a query reads into the tile before
    # the last one); three kernels each, four heads
    assert fa.grid_steps_in(jaxpr) == {
        "flash_grid_steps": 4 * 3 * (10 + 9),
        "flash_grid_steps_empty": 0,
        "flash_fwd_lane_sums": 0,  # head size 16: ones in the spare lanes
    }
    # lengths that differ leave k tiles that no query reads: one step
    # each, to write their zeros
    q = q[:, :32]
    jaxpr = jax.make_jaxpr(jax.grad(loss(None), argnums=(0, 1, 2)))(q, k, v)
    assert fa.grid_steps_in(jaxpr) == {
        "flash_grid_steps": 4 * (3 + 3 + (2 + 1 + 2)),
        "flash_grid_steps_empty": 4 * 2,
        "flash_fwd_lane_sums": 0,
    }
    assert fa.grid_steps_in(jax.make_jaxpr(lambda x: x * 2)(1.0)) == {}


# ---------------------------------------------------------------------------
# a value head size that is not the query's and the key's (PR 42): a
# latent attention trained in its expanded form reads 192 and writes 128
# ---------------------------------------------------------------------------

UNEQUAL_SIZES = [(192, 128), (128, 64)]


def _unequal_qkv(d_qk, d_v, dtype="float32", b=2, l=64, h=2, seed=5):
    rng = np.random.default_rng(seed)
    draw = lambda d: rng.standard_normal((b, l, h, d)).astype(dtype)
    return draw(d_qk), draw(d_qk), draw(d_v), draw(d_v)


@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("d_qk, d_v", UNEQUAL_SIZES)
def test_unequal_head_sizes_match_the_reference(d_qk, d_v, causal, what):
    """``softmax(q k^T / sqrt(d_qk)) v`` with q and k of one head size
    and v of another, four tiles each way: the result and each gradient
    against ``reference_attention``."""
    q, k, v, weights = _unequal_qkv(d_qk, d_v)
    if what == "forward":
        got = flash_attention(q, k, v, causal, 16, 16)
        assert got.shape == v.shape
        np.testing.assert_allclose(
            got,
            reference_attention(q, k, v, causal=causal),
            rtol=2e-4,
            atol=2e-5,
        )
        return
    argnum = ["dq", "dk", "dv"].index(what)
    grad = lambda fn: jax.grad(
        lambda *qkv: (fn(*qkv) * weights).sum(), argnums=argnum
    )(q, k, v)
    got = grad(lambda q, k, v: flash_attention(q, k, v, causal, 16, 16))
    want = grad(lambda q, k, v: reference_attention(q, k, v, causal=causal))
    assert got.shape == (q, k, v)[argnum].shape
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-5)


def test_unequal_head_sizes_go_under_names_of_their_own_and_nothing_else_does():
    from elasticdl_tpu.ops import flash_attention as fa

    def names(q, k, v):
        loss = lambda q, k, v: (fa.flash_attention(q, k, v, True, 16, 16) ** 2).sum()
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        return sorted(
            eqn.params["name"]
            for eqn in _every_eqn(jaxpr.jaxpr)
            if eqn.primitive.name == "pallas_call"
        )

    q, k, v, _ = _unequal_qkv(24, 16)
    assert names(q, k, v) == sorted(fa.UNEQUAL.values())
    assert names(q, k, v[..., :8]) == sorted(fa.UNEQUAL.values())
    assert names(q, k, k) == sorted(fa.UNEQUAL)
    assert fa.attention_in_step(
        {"mosaic_kernels": sorted(fa.UNEQUAL.values()), "pallas_kernels": []}
    ) == "pallas"
    with pytest.raises(ValueError, match="not built"):
        fa.flash_attention(q, k, v, True, 16, 16, window=20)


def _every_eqn(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _every_eqn(getattr(inner, "jaxpr", inner))


@pytest.mark.parametrize("d_qk, d_v", UNEQUAL_SIZES + [(64, 64)])
def test_grid_steps_and_traffic_at_both_head_sizes(d_qk, d_v):
    """The walk does not read a head size, so the steps are those of
    equal sizes; the traffic counts q, k, dq and dk at one width and v,
    o, dO and dv at the other. bf16, causal, 64 x 4,096 in 1,024-tiles
    (`ling3flash-ep64-l4096` at (192, 128)): 4 q tiles and 10 steps a
    head in the forward and dq, the mirror image in dkv."""
    from elasticdl_tpu.ops import flash_attention as fa

    q, k, v, _ = _unequal_qkv(d_qk, d_v)
    loss = lambda q, k, v: (fa.flash_attention(q, k, v, True, 16, 16) ** 2).sum()
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert fa.grid_steps_in(jaxpr) == {
        "flash_grid_steps": 4 * 3 * 10,
        "flash_grid_steps_empty": 0,
        # the one forward: v of 128 fills its lanes, v of 64 does not
        "flash_fwd_lane_sums": int(d_v == 128),
    }

    heads, tile = 64, 1024 * 2
    got = fa.hbm_traffic(heads, 4096, 4096, d_qk, 1024, 1024, d_v=d_v)
    # blocks a head: an outer tile's operand once a tile (4), an inner
    # tile's once a step that changes it (9 of the 10 steps: an outer
    # tile's first inner tile is its neighbour's first, or its last)
    want = {
        FWD: 4 * d_qk + 9 * d_qk + 9 * d_v + 4 * d_v,  # q k v o
        DQ: (4 + 9 + 4) * d_qk + (9 + 4) * d_v,  # q k dq | v dO
        DKV: (9 + 4 + 4) * d_qk + (4 + 9 + 4) * d_v,  # q k dk | v dO dv
    }
    for kernel, columns in want.items():
        assert got[kernel]["tensors"] == heads * tile * columns, kernel
    if d_qk == d_v:
        assert got == fa.hbm_traffic(heads, 4096, 4096, d_qk, 1024, 1024)
    assert fa.causal_work_ratio(
        4096, 4096, 1024, 1024, fa.sub_block(d_qk)
    ) == pytest.approx(1.0615, abs=1e-3)


def test_unequal_head_sizes_in_bfloat16():
    q, k, v, weights = _unequal_qkv(192, 128, "float32", l=32)
    low = [x.astype(jax.numpy.bfloat16) for x in (q, k, v)]
    got = flash_attention(*low, True, 16, 16)
    assert got.dtype == jax.numpy.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        reference_attention(*[np.asarray(x, np.float32) for x in low], causal=True),
        rtol=0.03,
        atol=0.03,
    )


def test_the_policy_hands_short_lengths_unequal_head_sizes_in_xla():
    from elasticdl_tpu.ops.flash_attention import pick_causal_attention

    q, k, v, _ = _unequal_qkv(24, 16)
    got = pick_causal_attention(q.shape[1])(q, k, v)
    np.testing.assert_allclose(
        got, flash_attention(q, k, v, True, 16, 16), rtol=2e-4, atol=2e-5
    )


# head size 256: q, k and v of a latent attention equally wide
# (glm-4.7-flash-ep8), so the call is the plain bodies', at a size no
# cell ran before PR 46
WIDE = 256
WIDE_LENGTH = 2048


@pytest.fixture(scope="module")
def wide_both_ways():
    """Forward and the three gradients at head size 256, causal, at the
    tiles the policy picks for the length (``auto_blocks``: two 1,024
    tiles each way, three with work, the diagonal's cut into sub-blocks
    of ``sub_block(256)``), interpreted, and the same of XLA's path."""
    rng = np.random.default_rng(17)
    draw = lambda: rng.standard_normal((1, WIDE_LENGTH, 2, WIDE)).astype("float32")
    q, k, v, weights = draw(), draw(), draw(), draw()

    def both(fn):
        out, pull = jax.vjp(fn, q, k, v)
        return (out,) + pull(weights)

    return (
        both(lambda q, k, v: flash_attention(q, k, v, True)),
        both(lambda q, k, v: reference_attention(q, k, v, causal=True)),
    )


@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv"])
def test_head_size_256_matches_the_reference(wide_both_ways, what):
    at = ["forward", "dq", "dk", "dv"].index(what)
    got, want = wide_both_ways[0][at], wide_both_ways[1][at]
    assert got.shape == (1, WIDE_LENGTH, 2, WIDE)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-5)


def test_head_size_256_takes_the_plain_bodies_at_the_policys_tiles():
    """Equal head sizes go under the plain names (the unequal bodies'
    are a latent attention's with q and k wider than v), in tiles of
    1,024 cut into sub-blocks of 256, as every head size measured."""
    from elasticdl_tpu.ops import flash_attention as fa

    assert fa.auto_blocks(WIDE_LENGTH, WIDE_LENGTH) == (1024, 1024)
    assert fa.sub_block(WIDE) == 256
    q = jax.ShapeDtypeStruct((1, WIDE_LENGTH, 2, WIDE), jax.numpy.bfloat16)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda q, k, v: flash_attention(q, k, v, True).sum(), argnums=(0, 1, 2))
    )(q, q, q)
    names = [
        eqn.params["name"] for eqn in _every_eqn(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call"
    ]  # fmt: skip
    assert sorted(names) == sorted([FWD, DQ, DKV])


# ---------------------------------------------------------------------------
# the normalizer's row sums (PR 47): where the value's head size fills its
# lanes (128, 256) the forward sums p lane by lane and reduces across lanes
# once a finished q tile; where it leaves lanes spare (64, 16) the ones ride
# beside v through the matrix unit, as before
# ---------------------------------------------------------------------------

# three tiles of 256 each way in sub-blocks of 128: a whole sub-block is
# two groups of 128 columns (the lane sums add across groups), a q tile has
# up to three k tiles (they are rescaled across tiles), and the window's
# lower edge passes through the middle of a tile
LANE_LENGTH, LANE_TILE, LANE_W, LANE_WINDOW = 768, 256, 128, 384
# q and k's head size, v's: the cells' whose v fills its lanes
# (`smallthinker-ep8-l16384` and `keyevl2-ep8-l8192`,
# `glm47flash-ep8-l8192`, `ling3flash-ep64-l4096`)
LANE_SIZES = [(128, 128), (256, 256), (192, 128)]
LANE_CASES = [
    pytest.param(mask, *sizes, id="%s-%d-%d" % (mask, *sizes))
    for sizes in LANE_SIZES
    for mask in MASKS + ["full"]
    # unequal sizes under a selection or a window are not built
    if sizes[0] == sizes[1] or mask in ("causal", "full")
]
RESULTS = ["forward", "lse", "dq", "dk", "dv"]


# one call serves its five results, which run one after the other
@functools.lru_cache(maxsize=1)
def _lane_sums_both_ways(mask, d_qk, d_v, dtype):
    """``{result: (got, want)}`` of one call whose row sums are kept by
    lanes: the kernels' ``(out, lse, dq, dk, dv)`` beside the plain
    reference's in f32 on the same (rounded) inputs, ``lse`` beside the
    ``logsumexp`` of the reference's masked scores."""
    import jax.numpy as jnp

    from elasticdl_tpu.ops import flash_attention as fa

    (q, k, v, g), got = _toy_call(
        fa, mask, dtype, LANE_LENGTH, LANE_TILE, seed=47, sizes=(d_qk, d_v),
        w=LANE_W, window=LANE_WINDOW,
    )  # fmt: skip
    distance = np.arange(LANE_LENGTH)[:, None] - np.arange(LANE_LENGTH)
    if mask == "windowed":
        keep = ((distance >= 0) & (distance < LANE_WINDOW))[None]
        reference = lambda q, k, v: fa.windowed_reference_attention(
            q, k, v, LANE_WINDOW
        )
    elif mask == "selected":
        keep = _toy_selection(LANE_LENGTH) != 0
        reference = lambda q, k, v: fa.selected_reference_attention(
            q, k, v, jnp.asarray(keep)
        )
    else:
        keep = (distance >= 0 if mask == "causal" else distance < np.inf)[None]
        reference = lambda q, k, v: reference_attention(
            q, k, v, causal=mask == "causal"
        )
    exact = [x.astype(jnp.float32) for x in (q, k, v)]
    want, vjp = jax.vjp(reference, *exact)
    scores = jnp.einsum("bqhd,bkhd->bhqk", *exact[:2]) * d_qk ** -0.5
    lse = jax.scipy.special.logsumexp(
        jnp.where(keep[:, None], scores, -jnp.inf), axis=-1
    )
    wanted = (want, lse) + tuple(vjp(g.astype(jnp.float32)))
    return {
        name: (np.asarray(a, np.float32), np.asarray(b))
        for name, a, b in zip(RESULTS, got, wanted)
    }


@pytest.mark.parametrize("what", RESULTS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mask, d_qk, d_v", LANE_CASES)
def test_row_sums_by_lanes_match_the_reference(mask, d_qk, d_v, dtype, what):
    """Forward, logsumexp and the three gradients at the head sizes
    whose row sums are kept by lanes, under every mask. The statistics
    are f32 whatever the operands are, so ``lse`` is held to f32's
    tolerance in bf16 too."""
    got, want = _lane_sums_both_ways(mask, d_qk, d_v, dtype)[what]
    assert got.shape == want.shape
    if what == "lse":
        tolerance = dict(rtol=2e-5, atol=2e-5)
    else:
        tolerance = (TOLERANCE if what == "forward" else GRAD_TOLERANCE)[dtype]
    np.testing.assert_allclose(got, want, **tolerance)


def _forward_calls(jaxpr):
    """``(name, scratch shapes)`` of each flash forward in ``jaxpr``."""
    from elasticdl_tpu.ops import flash_attention as fa

    return [
        (
            eqn.params["name"],
            [a.shape for a in eqn.params["grid_mapping"].scratch_avals],
        )
        for eqn in _every_eqn(getattr(jaxpr, "jaxpr", jaxpr))
        if eqn.primitive.name == "pallas_call"
        and eqn.params["name"] in fa._FORWARDS
    ]


# q and k's head size, v's, the lanes v takes, whether the row sums are
# kept by lanes
ARMS = [
    pytest.param(*arm, mask, id="%d-%d-%s" % (*arm[:2], mask))
    for arm in [
        (16, 16, 128, False),  # the tests'
        (64, 64, 128, False),  # the dense cells', granite's, lfm2moe's
        (128, 64, 128, False),
        (192, 192, 256, False),
        (128, 128, 128, True),
        (192, 128, 128, True),  # a latent attention's
        (256, 256, 256, True),
    ]
    for mask in MASKS
    # unequal sizes under a selection or a window are not built
    if arm[0] == arm[1] or mask == "causal"
]


@pytest.mark.parametrize("d_qk, d_v, lanes, by_lanes, mask", ARMS)
def test_the_row_sums_live_where_the_values_head_size_puts_them(
    d_qk, d_v, lanes, by_lanes, mask
):
    """Which body a forward gets is read off ``v.shape[-1]`` and
    nothing else: its scratch says which, and `grid_steps_in` counts the
    calls that keep their row sums by lanes (``step_built``'s
    ``flash_fwd_lane_sums``). Tiles of 1,024 x 512 tell the two third
    scratches apart: ``[v | 1]`` has a k tile's rows, ``l`` a q tile's.
    Where v leaves lanes spare the scratch is the parent's, shape for
    shape."""
    import jax.numpy as jnp

    from elasticdl_tpu.ops import flash_attention as fa

    length, block_q, block_k = 2048, 1024, 512
    q, v = (
        jax.ShapeDtypeStruct((1, length, 2, d), jnp.bfloat16)
        for d in (d_qk, d_v)
    )
    how = {"window": 700} if mask == "windowed" else {}
    if mask == "selected":
        how = {"selection": jnp.asarray(_toy_selection(length))}
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: fa._flash_fwd(
            q, k, v, True, block_q, block_k, True, **how
        )
    )(q, q, v)
    ((name, scratch),) = _forward_calls(jaxpr)
    side = (block_q, 128) if by_lanes else (block_k, lanes)
    assert scratch == [(block_q, lanes), (block_q, 128), side], name
    assert fa.grid_steps_in(jaxpr)["flash_fwd_lane_sums"] == int(by_lanes)
    if not by_lanes:  # the parent's: room for v and a column of ones
        parents = -(-(d_v + 1) // 128) * 128
        assert scratch == [(block_q, parents), (block_q, 128), (block_k, parents)]


@pytest.mark.parametrize("d, forwards_by_lanes", [(128, 3), (16, 0)])
def test_a_recomputed_forward_counts_once_more(d, forwards_by_lanes):
    """`flash_fwd_lane_sums` counts a call as often as the program holds
    it: a layer under ``jax.checkpoint`` runs its forward twice, and the
    backward kernels, which read ``(out, lse)`` alone, never count."""
    from elasticdl_tpu.ops import flash_attention as fa

    q, k, v = _qkv(d=d)
    layer = lambda q, k, v: fa.flash_attention(q, k, v, True, 16, 16)

    def loss(q, k, v):
        once = layer(q, k, v)
        return (jax.checkpoint(layer)(once, k, v) ** 2).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert len(_forward_calls(jaxpr)) == 3
    assert fa.grid_steps_in(jaxpr) == {
        "flash_grid_steps": 4 * 10 * (3 + 2 + 2),
        "flash_grid_steps_empty": 0,
        "flash_fwd_lane_sums": forwards_by_lanes,
    }


@pytest.mark.parametrize("cols", [16, 100, 128, 384])
def test_lane_sum_is_a_row_sum_once_reduced_across_lanes(cols):
    """`_lane_sum` keeps (rows, 128) whose cross-lane sum is the row
    sum: groups of 128 columns added lane by lane, and a narrow
    sub-block's whole sum in lane 0."""
    from elasticdl_tpu.ops import flash_attention as fa

    x = np.random.default_rng(cols).random((8, cols)).astype("float32")
    by_lanes = np.asarray(fa._lane_sum(jax.numpy.asarray(x)))
    assert by_lanes.shape == (8, 128)
    np.testing.assert_allclose(by_lanes.sum(axis=1), x.sum(axis=1), rtol=1e-6)
    if cols % 128 == 0:
        np.testing.assert_allclose(
            by_lanes, x.reshape(8, -1, 128).sum(axis=1), rtol=1e-6
        )
    else:
        assert not by_lanes[:, 1:].any()


if __name__ == "__main__":
    import importlib.util
    import json
    import sys

    spec = importlib.util.spec_from_file_location("fa_recorded", sys.argv[1])
    recorded = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(recorded)
    print(
        json.dumps(
            {
                case: _digest(recorded, *case.split("-"))
                for case in BIT_FOR_BIT
            },
            indent=4,
        )
    )
