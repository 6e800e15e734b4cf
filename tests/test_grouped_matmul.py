"""ops/grouped_matmul.py, interpreted on the CPU as the flash kernels'
tests are: the product and its gradients against a per-group einsum,
the visit scheme, and the kernels' build for the chip at the widths the
benchmark's expert layer hands them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import grouped_matmul as gmm

ROWS, K, N, TILING = 24, 16, 32, (8, 8, 16)
# group sizes over 24 rows in tiles of 8
CASES = {
    "an_empty_group_and_rows_past_the_end": [5, 0, 9, 3],
    "a_group_that_crosses_two_tiles": [2, 19, 1, 0],
    "groups_on_tile_edges": [8, 8, 8, 0],
    "every_group_empty": [0, 0, 0, 0],
    "one_group_owns_every_row": [0, 24, 0, 0],
    "most_rows_past_the_last_group": [1, 1, 1, 1],
}


def _operands(seed=0):
    a, b, c = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(a, (ROWS, K)),
        jax.random.normal(b, (len(CASES["groups_on_tile_edges"]), K, N)),
        jax.random.normal(c, (ROWS, N)),
    )


def _per_group(lhs, rhs, sizes):
    """The same product as one masked einsum a group."""
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    row, start = jnp.arange(lhs.shape[0]), 0
    for g, size in enumerate(sizes):
        mine = (row >= start) & (row < start + size)
        product = jnp.einsum("rk,kn->rn", lhs, rhs[g], precision="highest")
        out = out + jnp.where(mine[:, None], product, 0.0)
        start += size
    return out


def _owned(x, sizes):
    """``x`` with the rows past the last group's end, which the kernel
    leaves undefined, masked as a caller has to mask them."""
    return jnp.where(jnp.arange(x.shape[0])[:, None] < sum(sizes), x, 0.0)


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_matches_a_per_group_einsum(case):
    lhs, rhs, _ = _operands()
    sizes = CASES[case]
    got = gmm.grouped_matmul(lhs, rhs, jnp.array(sizes, jnp.int32), TILING)
    np.testing.assert_allclose(
        _owned(got, sizes), _per_group(lhs, rhs, sizes), atol=1e-5
    )


@pytest.mark.parametrize("argument", ["lhs", "rhs"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_gradient_matches_a_per_group_einsum(case, argument):
    lhs, rhs, weight = _operands(1)
    sizes = CASES[case]
    which = ("lhs", "rhs").index(argument)

    def through_kernel(lhs, rhs):
        out = gmm.grouped_matmul(lhs, rhs, jnp.array(sizes, jnp.int32), TILING)
        return jnp.sum(_owned(out, sizes) * weight)

    got = jax.grad(through_kernel, which)(lhs, rhs)
    if argument == "lhs":
        got = _owned(got, sizes)
    want = jax.grad(
        lambda lhs, rhs: jnp.sum(_per_group(lhs, rhs, sizes) * weight), which
    )(lhs, rhs)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_group_sizes_take_no_gradient_and_jit_picks_its_own_tiles():
    lhs, rhs, _ = _operands(2)
    sizes = jnp.array(CASES["an_empty_group_and_rows_past_the_end"], jnp.int32)
    got = jax.jit(gmm.grouped_matmul)(lhs, rhs, sizes)
    sizes = [int(s) for s in sizes]
    np.testing.assert_allclose(
        _owned(got, sizes), _per_group(lhs, rhs, sizes), atol=1e-5
    )
    assert gmm.auto_tiles(32768, 2048, 3072) == (512, 1024, 1024)
    assert gmm.auto_tiles(32768, 1536, 1536) == (512, 768, 768)


def test_nothing_a_row_past_the_end_holds_reaches_a_result():
    """The rows past the last group's end are undefined on the way out,
    so a chained product gets them undefined on the way in: not finite
    there, in ``lhs`` and in the cotangent, and the rows the groups own
    and ``rhs``'s gradient are what they were."""
    lhs, rhs, weight = _operands(3)
    sizes = CASES["an_empty_group_and_rows_past_the_end"]
    past = jnp.arange(ROWS)[:, None] >= sum(sizes)

    def through_kernel(lhs, rhs, weight):
        out = gmm.grouped_matmul(lhs, rhs, jnp.array(sizes, jnp.int32), TILING)
        # the cotangent of the rows past the end is ``weight``'s there
        return jnp.sum(jnp.where(past, 0.0, out) * weight) + jnp.sum(
            jnp.where(past, out * 0.0, 0.0) * weight
        )

    clean = jax.value_and_grad(through_kernel, (0, 1))(lhs, rhs, weight)
    dirty = jax.value_and_grad(through_kernel, (0, 1))(
        jnp.where(past[:, :1], jnp.nan, lhs),
        rhs,
        jnp.where(past[:, :1], jnp.nan, weight),
    )
    np.testing.assert_allclose(
        _owned(dirty[1][0], sizes), _owned(clean[1][0], sizes), atol=1e-6
    )
    np.testing.assert_allclose(dirty[1][1], clean[1][1], atol=1e-6)
    assert np.isfinite(np.asarray(dirty[1][1])).all()


def test_visits_walk_each_tile_of_each_group_once():
    sizes = jnp.array([5, 0, 9, 3], jnp.int32)
    (offsets, groups, tiles), count = gmm.visits(sizes, 24, 8, False)
    assert list(offsets) == [0, 5, 5, 14, 17]
    # group 0 in tile 0; group 2 in tiles 0 and 1; group 3 in tiles 1, 2
    assert int(count) == 5
    assert list(zip(groups[:5].tolist(), tiles[:5].tolist())) == [
        (0, 0), (2, 0), (2, 1), (3, 1), (3, 2),
    ]  # fmt: skip
    # the transposed product also visits the empty group, to zero it
    (_, groups, _), count = gmm.visits(sizes, 24, 8, True)
    assert int(count) == 6 and groups[:6].tolist() == [0, 1, 2, 2, 3, 3]


def test_shapes_that_do_not_tile_are_refused():
    lhs, rhs, _ = _operands()
    with pytest.raises(ValueError, match="does not divide"):
        gmm.grouped_matmul(lhs, rhs, jnp.zeros((4,), jnp.int32), (16, 8, 16))
    with pytest.raises(ValueError, match="group_sizes"):
        gmm.grouped_matmul(lhs, rhs, jnp.zeros((3,), jnp.int32))


# ---------------------------------------------------------------------------
# the kernels build for the chip at the benchmark's widths (no chip: the
# TPU's compiler is installed here and compiles for a described v5e)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


# an expert layer of the benchmark's LFM2 share: 8,192 tokens x 4
# assignments, 8 experts held, hidden 2048, expert width 1536
BUFFER_ROWS, HELD, HIDDEN, WIDTH = 32768, 8, 2048, 1536


@pytest.mark.parametrize(
    "kernel, k, n",
    [
        ("fwd", HIDDEN, 2 * WIDTH),
        ("fwd", WIDTH, HIDDEN),
        ("dlhs", 2 * WIDTH, HIDDEN),
        ("dlhs", HIDDEN, WIDTH),
        ("tgmm", HIDDEN, 2 * WIDTH),
        ("tgmm", WIDTH, HIDDEN),
    ],
)
def test_kernel_compiles_for_the_v5e_at_the_benchmarks_widths(
    one_chip, kernel, k, n
):
    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    sizes = shape(HELD, dtype=jnp.int32)
    if kernel == "tgmm":
        fn = lambda lhs, rhs, s: gmm._tgmm(lhs, rhs, s, jnp.bfloat16, None, False)
        args = (shape(BUFFER_ROWS, k), shape(BUFFER_ROWS, n), sizes)
    else:
        transposed = kernel == "dlhs"
        fn = lambda lhs, rhs, s: gmm._gmm(lhs, rhs, s, transposed, None, False)
        rhs = shape(HELD, n, k) if transposed else shape(HELD, k, n)
        args = (shape(BUFFER_ROWS, k), rhs, sizes)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "tpu_custom_call" in text
    name = gmm.TGMM_KERNEL if kernel == "tgmm" else "%s_k%d_%s" % (
        gmm.GMM_KERNEL, k, kernel
    )
    assert name in text


# The flash kernels' real-size compile lives here and not beside their
# parity tests: one process may describe the chip, so every such test
# shares this file's fixture (and with it one xdist worker).
@pytest.mark.parametrize(
    "batch, heads, length, causal, tiles",
    [
        pytest.param(8, 12, 2048, True, None, id="lm125m-l2048"),
        pytest.param(2, 16, 2048, True, None, id="lm350m-l2048"),
        pytest.param(4, 32, 2048, True, None, id="lfm2moe-ep8-l2048"),
        pytest.param(16, 12, 1024, True, None, id="one-tile-a-head"),
        pytest.param(8, 12, 2048, False, None, id="not-causal"),
        # PR 31: block_q is the lane dimension of a statistic's block, and
        # tiles that are not square are clamped by positions
        pytest.param(2, 16, 2048, True, (512, 1024), id="tiles-512x1024"),
        pytest.param(2, 16, 2048, True, (1024, 512), id="tiles-1024x512"),
        pytest.param(2, 4, 1024, False, (128, 128), id="ring-default-tiles"),
        pytest.param(2, 4, 100, True, (100, 100), id="a-whole-odd-length"),
    ],
)
def test_flash_kernels_compile_for_the_v5e_at_the_benchmarks_shapes(
    one_chip, batch, heads, length, causal, tiles
):
    """Sub-blocks of 256 inside 1,024-tiles at head size 64: the static
    slices, the widened accumulator and the scoped VMEM are Mosaic's to
    refuse, and interpret mode refuses none of them. So are (PR 31) the
    statistics' (1, 1, block_q) blocks with L along the lanes, the
    forward's transpose of its (rows, 128) logsumexp and the backward
    kernels' transposed scores; and what XLA compiles around the kernels
    holds no (batch*heads, L, 128) f32 array any more."""
    from elasticdl_tpu.ops import flash_attention as fa

    x = jax.ShapeDtypeStruct(
        (batch, length, heads, 64), jnp.bfloat16, sharding=one_chip
    )
    tiles = tiles or fa.auto_blocks(length, length)

    def fwd_and_bwd(q, k, v, g):
        out, lse = fa._flash_fwd(q, k, v, causal, *tiles, False)
        return out, fa._flash_bwd(q, k, v, out, lse, g, causal, *tiles, False)

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(fwd_and_bwd).lower(x, x, x, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert text.count("tpu_custom_call") >= 3
    for name in (fa.FWD_KERNEL, fa.BWD_DQ_KERNEL, fa.BWD_DKV_KERNEL):
        assert name in text
    assert "f32[%d,%d,128]" % (batch * heads, length) not in text
    assert "f32[%d,1,%d]" % (batch * heads, length) in text


def test_flash_kernels_under_a_selection_compile_for_the_v5e_at_the_cells_shape(
    one_chip,
):
    """`keyevl2-ep8-l8192`: one sequence of 8,192, 32 heads of 128, the
    selection as (1, 8192, 8192) int8 in (1, 1024, 1024) tiles beside
    the 1,024 x 1,024 tile of scores. An int8 tile, its widening and
    the scoped VMEM with it are Mosaic's to refuse; interpret mode
    refuses none of them. The calls go under their own names."""
    from elasticdl_tpu.ops import flash_attention as fa

    length, heads = 8192, 32
    x = jax.ShapeDtypeStruct(
        (1, length, heads, 128), jnp.bfloat16, sharding=one_chip
    )
    selection = jax.ShapeDtypeStruct(
        (1, length, length), jnp.int8, sharding=one_chip
    )
    tiles = fa.auto_blocks(length, length)
    assert tiles == (1024, 1024)

    def fwd_and_bwd(q, k, v, g, selection):
        out, lse = fa._flash_fwd(
            q, k, v, True, *tiles, False, selection=selection
        )
        return out, fa._flash_bwd(
            q, k, v, out, lse, g, True, *tiles, False,
            selection_t=selection.transpose(0, 2, 1),
        )  # fmt: skip

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = (
            jax.jit(fwd_and_bwd).lower(x, x, x, x, selection).compile().as_text()
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert text.count("tpu_custom_call") >= 3
    for name in fa.SELECTED.values():
        assert name in text
    for name in fa.SELECTED:
        assert name + '"' not in text


@pytest.mark.parametrize(
    "batch, tiles",
    [
        pytest.param(1, None, id="glm47flash-ep8-l8192"),
        pytest.param(2, None, id="its-comparison"),
        pytest.param(1, (512, 512), id="tiles-512x512"),
    ],
)
def test_flash_kernels_at_head_size_256_compile_for_the_v5e_at_the_cells_shape(
    one_chip, batch, tiles
):
    """`glm47flash-ep8-l8192`'s latent attention: 8,192 positions of 20
    heads whose q, k and v are all 256 wide, so the call is the plain
    bodies' at a head size none had run before PR 46: a q tile of 1,024
    x 256, dkv's two f32 (1,024, 256) accumulators and the forward's
    scratch (since PR 47 a (1,024, 256) accumulator beside two (1,024,
    128) statistics, the running maximum and the row sums kept by lanes;
    until then `[v | 1]` and the accumulator at 384 lanes) are Mosaic's
    to refuse (scoped VMEM), and interpret mode refuses none of them."""
    from elasticdl_tpu.ops import flash_attention as fa

    x = jax.ShapeDtypeStruct((batch, 8192, 20, 256), jnp.bfloat16, sharding=one_chip)
    tiles = tiles or fa.auto_blocks(8192, 8192)
    assert fa.sub_block(256) == 256

    def fwd_and_bwd(q, k, v, g):
        out, lse = fa._flash_fwd(q, k, v, True, *tiles, False)
        return out, fa._flash_bwd(q, k, v, out, lse, g, True, *tiles, False)

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(fwd_and_bwd).lower(x, x, x, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    from elasticdl_tpu.utils import step_ops

    ops = step_ops.op_classes(text)
    assert sum(name.startswith("edl_flash") for name in ops) == 3, sorted(ops)
    for name in (fa.FWD_KERNEL, fa.BWD_DQ_KERNEL, fa.BWD_DKV_KERNEL):
        assert name in text
    for name in fa.UNEQUAL.values():
        assert name not in text


@pytest.mark.parametrize(
    "length, d, tiles",
    [
        pytest.param(100, 128, (100, 100), id="a-whole-odd-length"),
        pytest.param(72, 256, (72, 72), id="a-whole-odd-length-at-256"),
        pytest.param(2048, 128, (1024, 512), id="tiles-1024x512"),
        pytest.param(2048, 128, (512, 1024), id="tiles-512x1024"),
    ],
)
def test_the_forward_by_lanes_compiles_for_the_v5e_off_the_policys_tiles(
    one_chip, length, d, tiles
):
    """Where v fills its lanes the forward sums p lane by lane (PR 47):
    a sub-block that is no whole number of 128 columns (a short whole
    length) puts its row sum in lane 0 by an iota's test, and tiles that
    are not square are never cut, so a sub-block is a whole tile, eight
    groups of 128 columns at once. Both are Mosaic's to refuse."""
    from elasticdl_tpu.ops import flash_attention as fa

    x = jax.ShapeDtypeStruct((2, length, 4, d), jnp.bfloat16, sharding=one_chip)
    forward = lambda q, k, v: fa._flash_fwd(q, k, v, True, *tiles, False)
    assert fa.grid_steps_in(jax.make_jaxpr(forward)(x, x, x)) == {
        "flash_grid_steps": 8 * len(fa._walk(fa.FWD_KERNEL, length, length, *tiles, True)[0]),
        "flash_grid_steps_empty": 0,
        "flash_fwd_lane_sums": 1,
    }
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(forward).lower(x, x, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert fa.FWD_KERNEL in text


@pytest.mark.parametrize(
    "batch, length, heads, d_qk, d_v, causal",
    [
        pytest.param(2, 4096, 32, 192, 128, True, id="ling3flash-ep64-l4096"),
        pytest.param(2, 2048, 4, 128, 64, True, id="a-value-half-the-key"),
        pytest.param(2, 2048, 4, 192, 128, False, id="not-causal"),
    ],
)
def test_flash_kernels_of_unequal_head_sizes_compile_for_the_v5e_at_the_cells_shape(
    one_chip, batch, length, heads, d_qk, d_v, causal
):
    """`ling3flash-ep64-l4096`'s latent attention in its expanded form:
    2 sequences of 4,096, 32 heads whose q and k are 192 wide (128
    without positions, 64 rotated) and whose v is 128. A block whose
    last dimension is one and a half lane tiles, a contraction over 192
    and accumulators of two widths are Mosaic's to refuse; interpret
    mode refuses none of them. The calls go under their own names."""
    from elasticdl_tpu.ops import flash_attention as fa

    def shape(d):
        return jax.ShapeDtypeStruct(
            (batch, length, heads, d), jnp.bfloat16, sharding=one_chip
        )

    tiles = fa.auto_blocks(length, length)

    def fwd_and_bwd(q, k, v, g):
        out, lse = fa._flash_fwd(q, k, v, causal, *tiles, False)
        return out, fa._flash_bwd(q, k, v, out, lse, g, causal, *tiles, False)

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = (
            jax.jit(fwd_and_bwd)
            .lower(shape(d_qk), shape(d_qk), shape(d_v), shape(d_v))
            .compile()
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = compiled.as_text()
    from elasticdl_tpu.utils import step_ops

    ops = step_ops.op_classes(text)
    assert sum(name.startswith("edl_flash") for name in ops) == 3, sorted(ops)
    for name in fa.UNEQUAL.values():
        assert name in text
    for name in fa.UNEQUAL:
        assert name + '"' not in text
    out, (dq, dk, dv) = compiled.out_info
    assert out.shape == dv.shape == (batch, length, heads, d_v)
    assert dq.shape == dk.shape == (batch, length, heads, d_qk)


@pytest.mark.parametrize(
    "length, heads, window, tiles",
    [
        pytest.param(16384, 28, 4096, None, id="smallthinker-ep8-l16384"),
        # the cell's global layer: the plain kernels at 16 x 16 tiles of
        # head size 128, 136 steps a head read off the grid's two tables
        pytest.param(16384, 28, None, None, id="smallthinker-ep8-l16384-global"),
        # an edge that is no multiple of the tile, nor of the sub-block
        pytest.param(4096, 4, 1000, None, id="a-ragged-window"),
        pytest.param(2048, 4, 300, (512, 1024), id="tiles-512x1024"),
    ],
)
def test_flash_kernels_under_a_window_compile_for_the_v5e_at_the_cells_shape(
    one_chip, length, heads, window, tiles
):
    """`smallthinker-ep8-l16384`: one sequence of 16,384, 28 heads of 128,
    a window of 4,096. A third variant of each body (the lower edge's
    trimmed sub-blocks, with a mask of their own) and the index maps
    clamped from both sides are Mosaic's to refuse; interpret mode
    refuses none of them. The calls go under their own names. Since
    PR 41 the grid is (heads, tiles with work) and a step reads its
    tiles from two int32 tables in scalar memory: those too."""
    from elasticdl_tpu.ops import flash_attention as fa

    x = jax.ShapeDtypeStruct(
        (1, length, heads, 128), jnp.bfloat16, sharding=one_chip
    )
    tiles = tiles or fa.auto_blocks(length, length)

    def fwd_and_bwd(q, k, v, g):
        out, lse = fa._flash_fwd(q, k, v, True, *tiles, False, window=window)
        return out, fa._flash_bwd(
            q, k, v, out, lse, g, True, *tiles, False, window=window
        )

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(fwd_and_bwd).lower(x, x, x, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert text.count("tpu_custom_call") >= 3
    # the compiled text stays a line an instruction, which is how
    # utils/step_ops.py reads a step (a call's `metadata=` is written
    # as JSON over several lines and ended that: PERF.md section 6, PR 41)
    from elasticdl_tpu.utils import step_ops

    ops = step_ops.op_classes(text)
    assert sum(name.startswith("edl_flash") for name in ops) == 3, sorted(ops)
    if window is None:
        for name in fa.WINDOWED:
            assert name + "/pallas_call" in text
        assert "edl_flash_win" not in text
        return
    for name in fa.WINDOWED.values():
        assert name in text
    for name in fa.WINDOWED:
        assert name + '"' not in text


def test_the_key_selection_compiles_for_the_v5e_as_loops_that_carry_int8_blocks(
    one_chip,
):
    """The cell's selection (16 heads of 64, 2,048 of 8,192 keys): the
    radix search's unsigned compares and the block loops are XLA's to
    build for the chip, and `select_ms_per_step` finds the selection in
    a trace by exactly this: `while` ops whose carried tuple holds a
    4-d int8 array, one a run of queries. The rows under the top-k
    (queries 0..2,047) are the causal triangle and have no loop; the
    other twelve blocks go in six runs of two, each against the keys up
    to its own end."""
    import re

    from elasticdl_tpu.ops import sparse_select

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = (
            jax.jit(lambda q, k, w: sparse_select.select_keys(q, k, w, 2048))
            .lower(shape(1, 8192, 16, 64), shape(1, 8192, 64), shape(1, 8192, 16))
            .compile()
            .as_text()
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    loops = [
        line
        for line in text.splitlines()
        if re.match(r"^\s*%?while[.\d]* = \(.*?\bs8\[\d+,\d+,\d+,\d+\]", line)
    ]
    runs = sparse_select.block_runs(8192, 2048, 512)[1]
    assert len(loops) == len(runs) == 6
    for lo, hi in runs:
        assert any("s8[%d,1,512,%d]" % (hi - lo, hi * 512) in line for line in loops)
