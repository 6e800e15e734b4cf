"""Fused flash attention (Pallas/TPU): forward AND blockwise backward.

The hot op of the transformer family: softmax(QK^T)V computed blockwise
with the online-softmax recurrence, so neither the (L, L) score matrix nor
full-length K/V ever sit in VMEM. The grid is (batch*heads, q_blocks,
k_blocks): Pallas streams one (block_k, D) K/V tile from HBM per step
while the running max / normalizer / accumulator persist in VMEM scratch
across the innermost k axis — the standard TPU flash pipeline.
Accumulation is float32 while inputs may be bfloat16 (MXU native).

Inside a grid step the tile is not computed whole (PR 29). The body
cuts it into sub-blocks of ``w`` rows (:func:`sub_block`: 256) and,
under the causal mask, does per sub-block only what the diagonal
leaves (:func:`_walk_tile`): a tile above the diagonal does
nothing, a tile below it runs every sub-block with no mask at all, and
a tile the diagonal crosses runs each sub-block trimmed to the columns
its rows can see, masked there and only there. At L = 2,048 in
1,024-tiles the kernels perform 2.25 tiles of scores a head where the
whole-tile bodies performed 3 and the mask keeps 2
(:func:`causal_work_ratio` 1.125, from 1.5). The big tiles stay: they
amortise the DMA and the grid steps. All three kernels cut along q: a
sub-block is ``w`` rows of the tile against every column those rows can
see (cutting dkv along k instead read 1% faster, cutting dq along k 2%
slower: not worth a second order).

The forward keeps ONE online-softmax step a tile however many
sub-blocks it has: the row maximum is collected lane by lane across the
sub-blocks and reduced across lanes once, and the normalizer's row sum
is taken by the matrix unit (``p @ [v | 1]``: at head size 64 half of
the 128 result lanes are idle). Per-sub-block recurrences doubled the
forward's time on the chip; cross-lane reductions and (rows, 128)
statistics are what a tile's forward is made of, not its area. The
softmax scale is folded into q once a tile. Operands reach the matrix
unit as float32 (Mosaic contracts them in one bf16 pass on v5e);
handing them over in bf16 measured 1-3% SLOWER in all three kernels
(chip runs, PR 29) and was left out.

Training path: the forward saves only (out, logsumexp) per row — O(L)
extra — and the backward runs two more blockwise kernels that recompute
``p = exp(qk^T - lse)`` per tile:

- q-major pass: ``dq += (p * (dO V^T - delta)) K`` accumulated over k
  blocks,
- k-major pass: ``dv += p^T dO`` and ``dk += (p * (dO V^T - delta))^T Q``
  accumulated over q blocks,

with ``delta = rowsum(dO * O)``. Peak memory in backward is O(block^2)
per core — no (L, L) materialization anywhere (round-1 advisor finding:
the previous backward re-ran dense reference attention).

On a TPU backend the kernels compile through Mosaic. They run in Pallas
interpret mode only in a process that was explicitly put on the CPU
(tests, rehearsals); a CPU backend JAX fell back to, or any other
platform, raises (:func:`kernel_interpret_mode`).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # stats are broadcast across a full lane register

# stable kernel names: the Mosaic custom call carries them as
# ``kernel_name``, which is how a lowered step (and a profiler trace)
# shows that the fused kernels are in it
FWD_KERNEL = "edl_flash_fwd"
BWD_DQ_KERNEL = "edl_flash_bwd_dq"
BWD_DKV_KERNEL = "edl_flash_bwd_dkv"

# every grid is (batch*heads, outer tile, inner tile) and accumulates
# over the innermost axis only. No vmem_limit_bytes: on v5e / libtpu
# 0.0.34 the default 1024x1024 tiles compile under Mosaic's default
# scoped-VMEM limit (chip runs, PR 21 and PR 29; 2048x2048 tiles do
# not: the dq kernel runs out of VMEM, PR 29).
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _run_if(pred):
    """``pl.when`` for Python booleans: what :func:`causal_work_ratio`
    walks a tile with."""
    return lambda fn: fn() if pred else None


def _walk_tile(qi, kj, block_q, block_k, w, causal, step, when=pl.when):
    """Call ``step(strips)`` with the sub-blocks of tile ``(qi, kj)`` in
    which the causal mask leaves work: ``strips`` is a static list of
    ``(rows, cols, off)``.

    ``rows`` and ``cols`` are static slices of the tile. ``off`` is None
    where every score of the sub-block is kept (no mask is built), else
    the sub-block's first q position minus its first k position: the
    score at local ``(r, c)`` is kept when ``r + off >= c``.

    The tile is cut along q into ``block_q // w`` sub-blocks of ``w``
    rows, in ascending order; a ``w`` that does not divide ``block_q``
    leaves the tile whole.

    - not causal: every sub-block whole, ``off`` None;
    - causal, square tiles: the diagonal passes only through tiles with
      ``qi == kj``, corner to corner. Tiles above it do nothing, tiles
      below it step as a non-causal tile does, and a tile on it steps
      with each sub-block trimmed to the columns its rows can see: a
      static slice, and a static ``off``;
    - causal, other tiles (explicit sizes): nothing is cut. The tile
      is skipped, masked or whole by a predicate on the program ids.
    """
    if block_q % w:
        w = block_q
    all_rows, all_cols = slice(0, block_q), slice(0, block_k)

    def strips(trimmed):
        for lo in range(0, block_q, w):
            cols = slice(0, lo + w) if trimmed else all_cols
            yield slice(lo, lo + w), cols, lo if trimmed else None

    whole = functools.partial(step, list(strips(False)))
    if not causal:
        whole()
    elif block_q == block_k:
        when(kj < qi)(whole)
        when(kj == qi)(functools.partial(step, list(strips(True))))
    else:
        q_lo, k_lo = qi * block_q, kj * block_k
        q_hi, k_hi = q_lo + block_q - 1, k_lo + block_k - 1
        when(q_lo >= k_hi)(
            functools.partial(step, [(all_rows, all_cols, None)])
        )
        when((q_hi >= k_lo) & (q_lo < k_hi))(
            functools.partial(step, [(all_rows, all_cols, q_lo - k_lo)])
        )


def _each(strip):
    """A step that treats its sub-blocks one by one."""

    def step(strips):
        for s in strips:
            strip(*s)

    return step


def _scaled_q(q_ref, scale):
    """The q tile with the softmax scale folded in: once an element of
    q, not once a score."""
    return q_ref[0].astype(jnp.float32) * scale


def _scores(q, k, off):
    """q k^T for one sub-block (q carries the softmax scale), masked to
    NEG_INF above the diagonal where ``off`` says it passes."""
    s = jax.lax.dot_general(
        q,
        k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if off is None:
        return s
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(row + off >= col, s, NEG_INF)


def _lane_max(x):
    """(rows, cols) -> (rows, 128): the maximum over the groups of 128
    columns, lane by lane. The elementwise half of a row maximum; the
    cross-lane half is taken once a tile, over all rows."""
    cols = x.shape[1]
    if cols % _LANES:  # narrow sub-block (a short length, the tests')
        return jnp.broadcast_to(
            jnp.max(x, axis=1, keepdims=True), (x.shape[0], _LANES)
        )
    return functools.reduce(
        jnp.maximum, [x[:, c:c + _LANES] for c in range(0, cols, _LANES)]
    )


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    v1_ref,
    *,
    causal,
    scale,
    w,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    d = q_ref.shape[2]

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)

    q = _scaled_q(q_ref, scale)
    # v beside columns of ones: p @ [v | 1] leaves the row sums of p in
    # the lanes the head size leaves empty, so the matrix unit takes
    # the normalizer's row reduction and acc_ref[:, d:] carries l
    # through the same rescale as the accumulator
    v1_ref[:, :d] = v_ref[0].astype(jnp.float32)
    v1_ref[:, d:] = jnp.ones((v1_ref.shape[0], v1_ref.shape[1] - d))

    def step(strips):
        # ONE step of the online-softmax recurrence for the tile, its
        # products and exponentials taken sub-block by sub-block.
        # m_ref holds the running maximum in every lane; between the
        # sub-blocks it collects the per-lane part of the new one, and
        # the cross-lane reduction and the rescale run once a tile.
        m_prev = m_ref[:, :1]
        scores = []
        for rows, cols, off in strips:
            s = _scores(q[rows], k_ref[0, cols, :], off)
            m_ref[rows, :] = jnp.maximum(m_ref[rows, :], _lane_max(s))
            scores.append(s)
        m_new = jnp.max(m_ref[:], axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * jnp.exp(m_prev - m_new)
        for (rows, cols, _), s in zip(strips, scores):
            acc_ref[rows, :] += jax.lax.dot(
                jnp.exp(s - m_new[rows]),
                v1_ref[cols, :],
                preferred_element_type=jnp.float32,
            )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    _walk_tile(qi, kj, q_ref.shape[1], k_ref.shape[1], w, causal, step)

    @pl.when(kj == nk - 1)
    def _finish():
        l_fin = acc_ref[:, d:d + 1]
        o_ref[0] = (acc_ref[:, :d] / l_fin).astype(o_ref.dtype)
        lse_ref[:] = jnp.broadcast_to(
            m_ref[:, :1] + jnp.log(l_fin), lse_ref.shape
        )


def _p_and_ds(q, k_ref, v_ref, do, lse_ref, delta_ref, rows, cols, off):
    """One sub-block of both backward passes: ``p = exp(q k^T - lse)``
    recomputed, and ``ds = p * (dO V^T - delta)``."""
    p = jnp.exp(
        _scores(q[rows], k_ref[0, cols, :], off) - lse_ref[0, rows, :1]
    )
    dp = jax.lax.dot_general(
        do[rows],
        v_ref[0, cols, :].astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p, p * (dp - delta_ref[0, rows, :1])


def _bwd_dq_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dq_ref,
    dq_acc,
    *,
    causal,
    scale,
    w,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q = _scaled_q(q_ref, scale)
    do = do_ref[0].astype(jnp.float32)

    def strip(rows, cols, off):
        _, ds = _p_and_ds(
            q, k_ref, v_ref, do, lse_ref, delta_ref, rows, cols, off
        )
        dq_acc[rows, :] += jax.lax.dot(
            ds,
            k_ref[0, cols, :].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    _walk_tile(
        qi, kj, q_ref.shape[1], k_ref.shape[1], w, causal, _each(strip)
    )

    @pl.when(kj == nk - 1)
    def _finish():
        # ds k is the gradient of (q scale): the scale comes back once
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dk_ref,
    dv_ref,
    dk_acc,
    dv_acc,
    *,
    causal,
    scale,
    w,
):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # the scaled q serves both products: s = (q scale) k^T, and
    # dk = ds^T (q scale) needs no scale of its own
    q = _scaled_q(q_ref, scale)
    do = do_ref[0].astype(jnp.float32)
    lhs_t = (((0,), (0,)), ((), ()))

    def strip(rows, cols, off):
        p, ds = _p_and_ds(
            q, k_ref, v_ref, do, lse_ref, delta_ref, rows, cols, off
        )
        dv_acc[cols, :] += jax.lax.dot_general(
            p, do[rows], lhs_t, preferred_element_type=jnp.float32
        )  # p^T dO
        dk_acc[cols, :] += jax.lax.dot_general(
            ds, q[rows], lhs_t, preferred_element_type=jnp.float32
        )  # ds^T (q scale)

    _walk_tile(
        qi, kj, q_ref.shape[1], k_ref.shape[1], w, causal, _each(strip)
    )

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _fold_heads(x):
    x = jnp.asarray(x)
    b, l, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, l, d)


def _unfold_heads(x, b, h):
    bh, l, d = x.shape
    return x.reshape(b, h, l, d).transpose(0, 2, 1, 3)


def divisible(lq, lk, block_q, block_k):
    """True when the fused kernels can tile these lengths.

    On a TPU the Pallas lowering additionally wants each (possibly
    clamped) block size along the sequence divisible by 8, or equal to
    the whole length, whatever the dtype; interpret mode has no such
    constraint. (v5e / libtpu 0.0.34, chip run, PR 21: bf16 and f32
    alike, 8x8 up to 1000x1000 and whole odd lengths 7, 36, 100 build;
    4, 12, 20 or 50 rows out of a longer sequence are refused.)
    """
    bq, bk = min(block_q, lq), min(block_k, lk)
    if lq % bq or lk % bk:
        return False
    if kernel_interpret_mode():
        return True
    return (bq % 8 == 0 or bq == lq) and (bk % 8 == 0 or bk == lk)


def sub_block(d):
    """Rows of the sub-blocks a tile body cuts its tile into: 256 at
    both head sizes measured, so ``d`` does not enter yet.

    TPU v5e, libtpu 0.0.34, jax 0.9.0, bf16, causal, tiles 1,024 x
    1,024, fwd + dq + dkv in ms a call (chip runs, PR 29). d = 64 (every
    cell's head size), 96 x 2,048 x 64, each kernel cut along its best
    axis: the tile whole 1.18 + 1.62 + 2.13 = 4.93; w = 512: 1.05 +
    1.42 + 1.87 = 4.35; **w = 256: 0.99 + 1.32 + 1.74 = 4.04**; w =
    128: 0.95 + 1.31 + 1.93 = 4.20; as committed (all three along q,
    w = 256) 1.00 + 1.32 + 1.75 = 4.07, against the whole-tile bodies'
    1.43 + 1.56 + 2.17 = 5.16. d = 128, 32 x 2,048 x 128: w = 1,024
    1.63, 512 1.40, **256 1.30** (whole-tile bodies 1.55). Narrower
    sub-blocks skip more of the masked area (0.75, 0.625, 0.5625 of a
    tile on the diagonal) but make more and smaller products, and dkv
    passes over its (columns, d) accumulators once a sub-block: at 256
    the two meet. A tile that 256 does not divide (the tests' 16-row
    tiles, a short whole length) is left whole."""
    del d
    return 256


def causal_work_ratio(lq, lk, block_q, block_k, w, causal=True):
    """Score elements the kernels perform over score elements the mask
    keeps: 1.0 means no score is computed only to be masked away.

    Static, from shapes alone: it walks every tile with the kernels' own
    :func:`_walk_tile` and adds up the sub-blocks' areas, so one number
    serves all three kernels. L = 2,048 in 1,024-tiles:
    1.5 with the tile whole, 1.125 at w = 256; L = 1,024: 2.0 and 1.25;
    L = 4,096: 1.25 and 1.06."""
    block_q, block_k = _block_sizes(lq, lk, block_q, block_k)
    kept = sum(min(r + 1, lk) for r in range(lq)) if causal else lq * lk
    done = []

    def step(strips):
        done.extend(
            (rows.stop - rows.start) * (cols.stop - cols.start)
            for rows, cols, _ in strips
        )

    for qi in range(lq // block_q):
        for kj in range(lk // block_k):
            _walk_tile(
                qi, kj, block_q, block_k, w, causal, step, when=_run_if
            )
    return sum(done) / kept


def _block_sizes(lq, lk, block_q, block_k):
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    if lq % block_q or lk % block_k:
        raise ValueError(
            "sequence lengths (%d, %d) must divide block sizes (%d, %d)"
            % (lq, lk, block_q, block_k)
        )
    return block_q, block_k


# Both halves are jitted INLINE with everything but the arrays static:
# jax then traces a kernel body once for each distinct call (shapes,
# tile sizes, causal) and not once a layer, and hands every layer the
# same kernel jaxpr, which lets the step's lowering build each Mosaic
# module once and copy it in place: the lowered step still holds three
# custom calls a layer. The sub-blocked bodies are four times the
# equations of the whole-tile ones; traced and lowered per layer they
# cost `lm125m-l2048` 9 s of set-up on the chip (PR 29).
_STATIC = ("causal", "block_q", "block_k", "interpret", "w")


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, w=None):
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    block_q, block_k = _block_sizes(lq, lk, block_q, block_k)
    w = w or sub_block(d)
    scale = d ** -0.5
    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)

    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, w=w
    )
    # room for v and at least one column of ones, in whole lanes
    d_ones = -(-(d + 1) // _LANES) * _LANES
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct(qf.shape, q.dtype),
            jax.ShapeDtypeStruct((b * h, lq, _LANES), jnp.float32),
        ],
        grid=(b * h, lq // block_q, lk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, qi, kj: (i, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, qi, kj: (i, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, qi, kj: (i, kj, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, qi, kj: (i, qi, 0)),
            pl.BlockSpec(
                (1, block_q, _LANES),
                lambda i, qi, kj: (i, qi, 0),
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d_ones), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_k, d_ones), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=FWD_KERNEL,
    )(qf, kf, vf)
    return (
        _unfold_heads(out, b, h),
        lse[:, :, 0].reshape(b, h, lq),
    )


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _flash_bwd(
    q,
    k,
    v,
    out,
    lse,
    g,
    causal,
    block_q,
    block_k,
    interpret,
    g_lse=None,
    w=None,
):
    b, lq, h, d = q.shape
    lk = k.shape[1]
    block_q, block_k = _block_sizes(lq, lk, block_q, block_k)
    w = w or sub_block(d)
    scale = d ** -0.5
    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    dof = _fold_heads(g.astype(q.dtype))
    outf = _fold_heads(out)
    # delta = rowsum(dO * O): tiny elementwise reduce, plain XLA.
    # An lse cotangent folds in exactly here: d lse/d s = p, so
    # ds = p * (dp - delta + g_lse) — pass delta_eff = delta - g_lse.
    delta = jnp.sum(
        dof.astype(jnp.float32) * outf.astype(jnp.float32), axis=-1
    )  # (b*h, lq)
    if g_lse is not None:
        delta = delta - jnp.asarray(g_lse, jnp.float32).reshape(
            b * h, lq
        )
    lse_l = jnp.broadcast_to(
        lse.reshape(b * h, lq, 1), (b * h, lq, _LANES)
    )
    delta_l = jnp.broadcast_to(
        delta[..., None], (b * h, lq, _LANES)
    )

    stat_spec_q = pl.BlockSpec(
        (1, block_q, _LANES), lambda i, qi, kj: (i, qi, 0)
    )
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, scale=scale, w=w
        ),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        grid=(b * h, lq // block_q, lk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, qi, kj: (i, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, qi, kj: (i, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, qi, kj: (i, kj, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, qi, kj: (i, qi, 0)),
            stat_spec_q,
            stat_spec_q,
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, d), lambda i, qi, kj: (i, qi, 0)
        ),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=BWD_DQ_KERNEL,
    )(qf, kf, vf, dof, lse_l, delta_l)

    stat_spec_kmajor = pl.BlockSpec(
        (1, block_q, _LANES), lambda i, kj, qi: (i, qi, 0)
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, scale=scale, w=w
        ),
        out_shape=[
            jax.ShapeDtypeStruct(kf.shape, k.dtype),
            jax.ShapeDtypeStruct(vf.shape, v.dtype),
        ],
        grid=(b * h, lk // block_k, lq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, kj, qi: (i, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kj, qi: (i, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kj, qi: (i, kj, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, kj, qi: (i, qi, 0)),
            stat_spec_kmajor,
            stat_spec_kmajor,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, kj, qi: (i, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, kj, qi: (i, kj, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=BWD_DKV_KERNEL,
    )(qf, kf, vf, dof, lse_l, delta_l)
    return (
        _unfold_heads(dq, b, h),
        _unfold_heads(dk, b, h),
        _unfold_heads(dv, b, h),
    )


def kernel_interpret_mode():
    """False on a TPU backend (the kernels compile through Mosaic); True
    only when this process was explicitly put on the CPU, i.e. ``cpu``
    leads ``jax_platforms`` (``JAX_PLATFORMS=cpu``, or the
    ``EDL_DIST_PLATFORM=cpu`` world bring-up, which sets that config).

    Anything else raises: with the platform left open, a TPU whose
    start-up failed (held by another process, say) leaves JAX on the
    CPU, and interpreting the kernels there would train at a crawl
    while looking like a device run."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    requested = (jax.config.jax_platforms or "").split(",")[0].strip()
    if backend == "cpu" and requested == "cpu":
        return True
    raise RuntimeError(
        "flash attention: the JAX backend is %r but jax_platforms is %r; "
        "the Pallas kernels compile on TPU and are interpreted only "
        "where the CPU was asked for by name (JAX_PLATFORMS=cpu). A TPU "
        "that failed to initialise, or is held by another process, "
        "lands here." % (backend, jax.config.jax_platforms)
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_with_lse(q, k, v, causal, block_q, block_k):
    return _flash_fwd(q, k, v, causal, block_q, block_k, kernel_interpret_mode())


def _fwd_rule(q, k, v, causal, block_q, block_k):
    out, lse = _flash_fwd(
        q, k, v, causal, block_q, block_k, kernel_interpret_mode()
    )
    return (out, lse), (q, k, v, out, lse)


def _bwd_rule(causal, block_q, block_k, residuals, cotangents):
    q, k, v, out, lse = residuals
    g, g_lse = cotangents
    return _flash_bwd(
        q,
        k,
        v,
        out,
        lse,
        g,
        causal,
        block_q,
        block_k,
        kernel_interpret_mode(),
        g_lse=g_lse,
    )


_flash_with_lse.defvjp(_fwd_rule, _bwd_rule)


def auto_blocks(lq, lk, block_q=None, block_k=None):
    """Resolve tile sizes for the fused kernel.

    Measured on TPU v5e (L=2048, b4 h8 d64, fwd+bwd): the original
    128x128 tiles ran 10.2 ms — SLOWER than XLA's unfused attention
    (8.8 ms) because tiny tiles re-read Q/dO from HBM once per k-block
    and leave the MXU under-filled. 512x1024 runs 3.71 ms (2.4x the XLA
    path). Larger q-tiles amortize the streamed K/V; an r4 re-sweep
    found 1024-row q-tiles a further win everywhere measured (L=1024
    b16 h12: 6.92 vs 7.14 ms; L=4096 b4 h8: 14.45 vs 15.68 ms; L=2048
    tied). The k-tile caps at 1024 because nothing wider was swept, not
    because it cannot be built: 1024x2048 compiles on v5e / libtpu
    0.0.34 (chip run, PR 21). Explicit sizes always win; None picks the
    largest measured-good divisor of the sequence length.

    Re-swept with the sub-blocked bodies (v5e, libtpu 0.0.34, jax 0.9.0,
    bf16, causal, 96 x 2,048 x 64, fwd + dq + dkv a call; chip runs,
    PR 29): 1,024 x 1,024 tiles 4.07 ms (w = 256), 512 x 512 tiles 6.79
    ms (w = 256) and 7.12 (w = 128), 2,048 x 2,048 tiles refused (dq
    out of VMEM). The sub-blocks did not move the optimum: 1,024 stays.
    Tiles that are not square (explicit sizes) are never cut: 512 x
    1,024 runs 5.47 ms (the whole-tile bodies 5.56), 1,024 x 512 5.72
    (6.63).
    """
    if block_q is None:
        block_q = next(
            (b for b in (1024, 512, 256, 128) if lq % b == 0), 128
        )
    if block_k is None:
        block_k = next(
            (b for b in (1024, 512, 256, 128) if lk % b == 0), 128
        )
    return block_q, block_k


def flash_attention_with_lse(
    q, k, v, causal=False, block_q=None, block_k=None
):
    """(B, L, H, D) fused attention returning (out, lse).

    ``lse`` is the per-row logsumexp (B, H, L) — the flash statistic that
    makes partial attentions mergeable (ring attention combines per-block
    (out, lse) pairs) and the only residual the blockwise backward needs.
    ``block_q``/``block_k`` default to measured-good tile sizes
    (:func:`auto_blocks`).
    """
    block_q, block_k = auto_blocks(
        q.shape[1], k.shape[1], block_q, block_k
    )
    return _flash_with_lse(q, k, v, causal, block_q, block_k)


def flash_attention(q, k, v, causal=False, block_q=None, block_k=None):
    """(B, L, H, D) fused attention; trains with the blockwise backward."""
    out, _ = flash_attention_with_lse(q, k, v, causal, block_q, block_k)
    return out


def attention_in_step(step_facts):
    """Name the attention a built step runs, from the facts
    ``ElasticDPTrainer.describe_step`` reads off that step: ``"pallas"``
    (the fused kernels are Mosaic custom calls in the lowered module),
    ``"pallas-interpret"`` (the kernels are in the jaxpr, interpreted:
    a process put on the CPU by request) or ``"xla"`` (the reference
    attention :func:`pick_causal_attention` hands short or untileable
    lengths)."""
    flash = {FWD_KERNEL, BWD_DQ_KERNEL, BWD_DKV_KERNEL}
    if flash <= set(step_facts["mosaic_kernels"]):
        return "pallas"
    if flash <= set(step_facts["pallas_kernels"]):
        return "pallas-interpret"
    return "xla"


def pick_causal_attention(seq_len, use_flash=True, min_flash_len=1024):
    """Causal attention fn for a model at this sequence length.

    One home for the policy: 1,024 is the length from which the cells
    use the kernels; ``lm125m-l512`` is the cell on the other side; the
    crossover between them is not measured on the chip (ROADMAP S10).
    The kernels need 128-divisible lengths to tile. Both transformer
    builds call this so the threshold lives in exactly one place."""
    if (
        use_flash
        and seq_len >= min_flash_len
        and divisible(seq_len, seq_len, 128, 128)
    ):
        return lambda q, k, v: flash_attention(q, k, v, True)
    from elasticdl_tpu.parallel.ring_attention import reference_attention

    return functools.partial(reference_attention, causal=True)
