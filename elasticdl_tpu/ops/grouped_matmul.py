"""Grouped matrix multiplication (Pallas/TPU): forward AND backward.

The product of an expert layer that holds many experts on one device
(parallel/expert.py ``held_experts_apply``): the rows of ``lhs`` are
sorted so that the rows of one group (one expert) lie together, and
group ``g`` multiplies its rows by its own matrix ``rhs[g]``:

    out[start_g : start_g + group_sizes[g]] =
        lhs[start_g : start_g + group_sizes[g]] @ rhs[g]

Groups have any size, an empty one included, and need not end on a tile;
the rows past the last group's end belong to no group (the assignments
of experts this device does not hold): no tile made only of them is
computed or written, and what they hold in the result is UNDEFINED
(whatever the buffer held; it may not be finite). Nothing they hold on
the way in reaches a row a group owns or a gradient of ``rhs``, and
since PR 38 the caller hands in nothing there either: it gathers and
computes the chunks that hold a group's row and leaves the rest of its
buffers as the memory was (parallel/expert.py ``held_experts_apply``),
and where a result leaves it picks the rows under the last group's end
and no other. Zeroing them here instead, a pass over the whole buffer
after every product, cost 1.4 ms of an expert layer's 14.5 ms forward
and backward at the benchmark's sizes, where seven rows of eight
belong to no group (PERF.md section 6, PR 28); the layer alone is
12.2 ms that way and 4.3 ms with only the held rows moved, 2.7 of it
these kernels (PERF.md section 6, PR 38).

Two kernels, in the pattern of ops/flash_attention.py (named custom
calls, f32 accumulation over operands that may be bfloat16, interpreted
only in a process put on the CPU by name):

- ``edl_gmm_k<K>_fwd`` / ``edl_gmm_k<K>_dlhs``: one kernel body. The
  grid is (n tiles, VISITS, k tiles); a visit is one (row tile, group)
  pair with at least one row in common, found from ``group_sizes`` on
  the device and handed to the kernel as scalar prefetch, so the grid's
  middle dimension is as long as the held rows need and no longer. A
  row tile two groups share is visited by both, consecutively, each
  storing only its own rows. ``dlhs`` is the same product against
  ``rhs`` transposed: ``dout[rows of g] @ rhs[g]^T``.
- ``edl_tgmm``: ``drhs[g] = lhs[rows of g]^T @ dout[rows of g]``, the
  grid (n tiles, k tiles, visits) accumulating over a group's visits;
  an empty group is visited once so that its block is written (zero).

The contraction width ``K`` is in the gmm kernels' names because a
trace names an op by its result's shape, which does not hold it
(benchmark/layer_metrics reads the name); the name ends in a letter
because trailing digits are the compiler's numbering.

jax's own ``jax.experimental.pallas.ops.tpu.megablox`` has this visit
scheme; its calls carry no name, so a lowered step or a trace could not
find them, and it is written here on the repo's own terms.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.flash_attention import kernel_interpret_mode

GMM_KERNEL = "edl_gmm"
TGMM_KERNEL = "edl_tgmm"

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary")
)


def _largest_divisor(x, candidates):
    return next((c for c in candidates if x % c == 0), x)


def auto_tiles(rows, k, n):
    """(tm, tk, tn): row, contraction and column tile. 512-row tiles
    keep a visit compute-bound on the v5e against columns of 1024 (a
    visit re-reads its ``rhs`` tile: 2 * 512 FLOPs a byte of it); a
    width that no candidate divides is taken whole."""
    widths = (1024, 768, 512, 384, 256, 128)
    return (
        _largest_divisor(rows, (512, 256, 128, 64, 32, 16, 8)),
        _largest_divisor(k, widths),
        _largest_divisor(n, widths),
    )


def visits(group_sizes, rows, tm, visit_empty_groups):
    """The (row tile, group) pairs a kernel walks, in order.

    Returns ``(offsets [G+1], group_ids [V], tile_ids [V]), count``:
    group ``g`` owns rows ``offsets[g]:offsets[g+1]``; visit ``i`` works
    on row tile ``tile_ids[i]`` for group ``group_ids[i]``; only the
    first ``count`` of the ``V = rows/tm + G - 1`` entries are real
    (``count`` is a device value: it depends on where the groups fall).
    With ``visit_empty_groups`` an empty group gets one visit."""
    n_groups = group_sizes.shape[0]
    tiles_m = rows // tm
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends]).astype(
        jnp.int32
    )
    starts = offsets[:-1]
    first_tile = starts // tm
    tiles_of = jnp.where(
        group_sizes == 0,
        1 if visit_empty_groups else 0,
        (ends + tm - 1) // tm - first_tile,
    ).astype(jnp.int32)
    n_visits = tiles_m + n_groups - 1
    group_ids = jnp.repeat(
        jnp.arange(n_groups, dtype=jnp.int32),
        tiles_of,
        total_repeat_length=n_visits,
    )
    first_visit = jnp.cumsum(tiles_of) - tiles_of
    tile_ids = (
        first_tile[group_ids]
        + jnp.arange(n_visits, dtype=jnp.int32)
        - first_visit[group_ids]
    )
    # an empty group at the very end starts at ``rows``; entries past
    # ``count`` are never walked: both only have to name a real tile
    tile_ids = jnp.clip(tile_ids, 0, tiles_m - 1).astype(jnp.int32)
    return (offsets, group_ids, tile_ids), jnp.sum(tiles_of)


def _rows_of_group(offsets_ref, group, tile, tm, width):
    """(tm, width) mask: the rows of row tile ``tile`` that ``group``
    owns."""
    row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0)
    return jnp.logical_and(
        row >= offsets_ref[group], row < offsets_ref[group + 1]
    )


def _gmm_kernel(
    offsets_ref,
    group_ids_ref,
    tile_ids_ref,
    lhs_ref,
    rhs_ref,
    out_ref,
    acc_ref,
    *,
    tm,
    tn,
    tiles_k,
    transpose_rhs,
):
    visit = pl.program_id(1)
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    contract_rhs = 1 if transpose_rhs else 0
    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...],
        rhs_ref[...],
        (((1,), (contract_rhs,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k_i == tiles_k - 1)
    def _store():
        # a tile two groups share stays resident between their
        # (consecutive) visits: each stores its own rows only
        mine = _rows_of_group(
            offsets_ref, group_ids_ref[visit], tile_ids_ref[visit], tm, tn
        )
        out_ref[...] = jnp.where(
            mine, acc_ref[...], out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


def _gmm(lhs, rhs, group_sizes, transpose_rhs, tiling, interpret):
    rows, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiling or auto_tiles(rows, k, n)
    if rows % tm or k % tk or n % tn:
        raise ValueError(
            "grouped matmul of (%d, %d) x (%d, %d) does not divide into "
            "tiles (%d, %d, %d)" % (rows, k, k, n, tm, tk, tn)
        )
    tiles_k = k // tk
    metadata, count = visits(group_sizes, rows, tm, visit_empty_groups=False)

    def lhs_index(n_i, visit, k_i, offsets, group_ids, tile_ids):
        return tile_ids[visit], k_i

    def rhs_index(n_i, visit, k_i, offsets, group_ids, tile_ids):
        if transpose_rhs:
            return group_ids[visit], n_i, k_i
        return group_ids[visit], k_i, n_i

    def out_index(n_i, visit, k_i, offsets, group_ids, tile_ids):
        return tile_ids[visit], n_i

    return pl.pallas_call(
        functools.partial(
            _gmm_kernel,
            tm=tm,
            tn=tn,
            tiles_k=tiles_k,
            transpose_rhs=transpose_rhs,
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_index),
                pl.BlockSpec(
                    (None, tn, tk) if transpose_rhs else (None, tk, tn),
                    rhs_index,
                ),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(n // tn, count, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="%s_k%d_%s"
        % (GMM_KERNEL, k, "dlhs" if transpose_rhs else "fwd"),
    )(*metadata, lhs, rhs)


def _tgmm_kernel(
    offsets_ref,
    group_ids_ref,
    tile_ids_ref,
    lhs_ref,
    rhs_ref,
    out_ref,
    acc_ref,
    *,
    tm,
    tk,
    tn,
):
    visit = pl.program_id(2)
    last = pl.num_programs(2) - 1
    group = group_ids_ref[visit]
    tile = tile_ids_ref[visit]

    @pl.when(
        jnp.logical_or(
            visit == 0,
            group_ids_ref[jnp.maximum(visit - 1, 0)] != group,
        )
    )
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets_ref[group + 1] > offsets_ref[group])
    def _accumulate():
        # rows of the tile another group owns (or none) count for
        # nothing: masked on both sides, so that nothing a row past the
        # end holds can reach the sum
        lhs = jnp.where(
            _rows_of_group(offsets_ref, group, tile, tm, tk),
            lhs_ref[...].astype(jnp.float32),
            0.0,
        ).astype(lhs_ref.dtype)
        rhs = jnp.where(
            _rows_of_group(offsets_ref, group, tile, tm, tn),
            rhs_ref[...].astype(jnp.float32),
            0.0,
        ).astype(rhs_ref.dtype)
        acc_ref[...] += jax.lax.dot_general(
            lhs,
            rhs,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(
        jnp.logical_or(
            visit == last,
            group_ids_ref[jnp.minimum(visit + 1, last)] != group,
        )
    )
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tgmm(lhs, rhs, group_sizes, out_dtype, tiling, interpret):
    """``out[g] = lhs[rows of g]^T @ rhs[rows of g]``: (G, K, N)."""
    rows, k = lhs.shape
    n = rhs.shape[1]
    tm, tk, tn = tiling or auto_tiles(rows, k, n)
    if rows % tm or k % tk or n % tn:
        raise ValueError(
            "transposed grouped matmul of (%d, %d)^T x (%d, %d) does not "
            "divide into tiles (%d, %d, %d)" % (rows, k, rows, n, tm, tk, tn)
        )
    metadata, count = visits(group_sizes, rows, tm, visit_empty_groups=True)

    def lhs_index(n_i, k_i, visit, offsets, group_ids, tile_ids):
        return tile_ids[visit], k_i

    def rhs_index(n_i, k_i, visit, offsets, group_ids, tile_ids):
        return tile_ids[visit], n_i

    def out_index(n_i, k_i, visit, offsets, group_ids, tile_ids):
        return group_ids[visit], k_i, n_i

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, tk=tk, tn=tn),
        out_shape=jax.ShapeDtypeStruct(
            (group_sizes.shape[0], k, n), out_dtype
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lhs_index),
                pl.BlockSpec((tm, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            grid=(n // tn, k // tk, count),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=TGMM_KERNEL,
    )(*metadata, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(lhs, rhs, group_sizes, tiling):
    return _gmm(lhs, rhs, group_sizes, False, tiling, kernel_interpret_mode())


def _fwd_rule(lhs, rhs, group_sizes, tiling):
    out = _gmm(lhs, rhs, group_sizes, False, tiling, kernel_interpret_mode())
    return out, (lhs, rhs, group_sizes)


def _bwd_rule(tiling, residuals, g):
    lhs, rhs, group_sizes = residuals
    interpret = kernel_interpret_mode()
    g = g.astype(lhs.dtype)
    dlhs = _gmm(g, rhs, group_sizes, True, tiling, interpret)
    drhs = _tgmm(lhs, g, group_sizes, rhs.dtype, tiling, interpret)
    return dlhs, drhs, None


_grouped_matmul.defvjp(_fwd_rule, _bwd_rule)


def grouped_matmul(lhs, rhs, group_sizes, tiling=None):
    """``lhs`` (R, K) times ``rhs`` (G, K, N) by groups of rows:
    (R, N) in ``lhs``'s dtype, accumulated in float32.

    ``group_sizes`` (G,) int32: group ``g`` owns the ``group_sizes[g]``
    rows after those of the groups before it. Rows past the last
    group's end are UNDEFINED in the result and in the ``lhs``
    gradient (never written: where a result leaves the buffer read
    none of them, or mask them before anything multiplies them); they
    give ``rhs`` no
    gradient, whatever they or their cotangent hold. ``tiling``
    (tm, tk, tn) overrides :func:`auto_tiles`; every size has to divide
    its dimension, and on a TPU ``K`` and ``N`` are multiples of 128
    and ``R`` of 8."""
    if lhs.ndim != 2 or rhs.ndim != 3 or rhs.shape[1] != lhs.shape[1]:
        raise ValueError(
            "grouped_matmul wants lhs (R, K) and rhs (G, K, N); got %s "
            "and %s" % (lhs.shape, rhs.shape)
        )
    if group_sizes.shape != (rhs.shape[0],):
        raise ValueError(
            "group_sizes %s does not count rhs's %d groups"
            % (group_sizes.shape, rhs.shape[0])
        )
    return _grouped_matmul(
        lhs,
        rhs.astype(lhs.dtype),
        group_sizes.astype(jnp.int32),
        tiling and tuple(tiling),
    )
