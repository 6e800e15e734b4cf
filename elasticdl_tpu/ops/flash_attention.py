"""Fused flash attention (Pallas/TPU): forward AND blockwise backward.

The hot op of the transformer family: softmax(QK^T)V computed blockwise
with the online-softmax recurrence, so neither the (L, L) score matrix nor
full-length K/V ever sit in VMEM. The grid is (batch*heads, steps), a
step for each (q tile, k tile) pair the mask leaves work in, the k tiles
of a q tile one after the other (:func:`_walk`): Pallas streams one
(block_k, D) K/V tile from HBM per step while the running max /
normalizer / accumulator persist in VMEM scratch across a q tile's
steps — the standard TPU flash pipeline, over a list of tiles.
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
sub-blocks and reduced across lanes once. The normalizer's row sum goes
one of two ways, by the value's head size alone (:func:`_fwd_scratch`).
Where v leaves lanes spare in its last group of 128 (head size 64: half
of the 128 result lanes are idle) it is taken by the matrix unit,
``p @ [v | 1]``, ones in the spare lanes, in a pass the unit makes
anyway. Where v fills its lanes (128, 256) a column of ones would cost
one more 128-wide pass for ONE useful column (384 lanes of matrix work
a tile where the roofline counts 256 at head size 128, 640 for 512 at
256), so there the sums are kept lane by lane as the maximum is
(:func:`_lane_sum`: elementwise adds over a sub-block's groups of 128
columns, rescaled with the accumulator) and reduced across lanes once a
FINISHED q tile, and the product is ``p @ v``, v read at the product
(chip runs, PR 47, the forward alone in ms a call, ones column -> lane
sums: (28, 16384, 128) under a window of 4,096 8.195 -> 6.519, causal
17.117 -> 13.518; (32, 8192, 128) under a selection 5.210 -> 4.191;
(20, 8192, 256) 5.095 -> 4.342; (32, 4096, 192 / 128) 1.728 -> 1.429;
(96, 2048, 64) 0.903 and 0.903, bit for bit the same results; an f32
copy of v in a scratch of its own, filled once a tile, read 1.5-2.3%
SLOWER than v converted at each sub-block's product: 6.655, 13.774,
4.290, 4.443, 1.451). The sums are f32 adds of p where the matrix unit
contracted p in one bf16 pass, so the normalizer is the more exact:
against f32 attention at head size 256 the logsumexp is off by 6e-6
where the ones column's was off by 1e-3. Per-sub-block recurrences
doubled the forward's time on the chip; cross-lane reductions and (rows, 128)
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

What moves through HBM besides q, k, v, o, dO and the gradients
(PR 31). The two per-row statistics, ``lse`` and ``delta``, are one f32
a row and travel as ``(batch*heads, 1, L)``, L along the lanes, in
blocks of ``(1, 1, block_q)``: 4 KiB a tile where the ``(rows, 128)``
blocks they rode in until PR 30, every lane the same number, were
512 KiB (100.7 MB an array a layer at 96 x 2,048, 704.6 MB over the
three kernels, and two XLA broadcasts a layer to write them).
``_flash_fwd`` hands its callers ``(b, h, L)`` as ever; XLA computes
``delta`` (and folds an lse cotangent in) at ``(batch*heads, L)`` and
broadcasts nothing. Each kernel reads or writes them its own way:

- the forward keeps its orientation, scores as (q rows, k columns):
  transposed, ``p v`` would stream 64 rows past a weight matrix of
  scores. Its running maximum fills ``(rows, 128)`` as before, and once
  a FINISHED q tile one transpose of the logsumexp puts the rows on the
  lanes to write them;
- both backward kernels form the TRANSPOSED scores of a sub-block,
  ``s^T = k q^T`` and ``dp^T = v dO^T`` (both the NT product the forward
  uses), so that ``lse`` and ``delta`` are row vectors that broadcast
  down the sublanes with no relayout. ``dv += p^T dO`` and
  ``dk += ds^T q`` are then plain products, where until PR 30 both
  contracted over the left operand's rows; ``dq += (ds^T)^T k`` is now
  the one that does.

And a tile the mask leaves no work in is no grid step at all (PR 41).
Which pairs hold work is known from the shapes, so :func:`_walk` lists
them, an outer tile's one after the other (q tiles outside in the
forward and dq, k tiles in dkv), and the call takes the list as two
int32 tables in scalar memory (``PrefetchScalarGridSpec``, as
``ops/grouped_matmul.py`` takes its visits): the index maps and the
body read a step's tiles from them (:func:`_here`), and an accumulator
starts and is written out where the outer tile changes. Until then the
grid was the rectangle (batch*heads, outer tiles, inner tiles). A step
without work fetched nothing since PR 31 (its index maps named the
block its neighbour computed with), but it was still a step, and it
still ran the body's prologue (the forward's scaled q and its
``[v | 1]`` scratch, the backward kernels' q and dO tiles converted
whole): 0.40-0.44 us each at head size 128 (chip runs, PR 41), 186 of
256 steps a head under `smallthinker-ep8-l16384`'s window, 120 under
its causal mask, one in four at L = 2,048. The prologue now sits under
the tile's ``when``, a sub-block's rows at a time, which a step WITH
work gains from too (0.12-0.20 us). Kernel-only, fwd + dq + dkv in ms a
call, parent -> as committed: (28, 16384, 128) under a window of 4,096
33.55 -> 26.02, causal 60.09 -> 55.09; (32, 8192, 128) under a
selection 18.89 -> 16.41; (96, 2048, 64) 3.490 -> 3.280.
:func:`hbm_traffic` walks the grids with the kernels' own index maps and
counts the bytes: what the rectangle moved, block for block.

Under a SELECTION (PR 32, :func:`flash_attention_selected`): the same
three bodies take one more input, a per-sequence ``(L, L)`` int8 mask
shared by a sequence's heads, whose tile follows both tile axes and
whose test takes the diagonal's place in :func:`_scores` and
:func:`_scores_t`; the calls carry names of their own
(:data:`SELECTED`). A call without a selection is built exactly as
before: same names, operands and block specs.

Under a WINDOW (PR 39, ``flash_attention(..., causal=True, window=W)``):
query t reads keys ``t - W < s <= t``, and the same three bodies compute
exactly the band, with no input more. What a square tile holds follows
from its distance under the diagonal alone, so :func:`_walk_tile` tells
the tiles apart statically (:func:`_band_strips`): a tile wholly outside
the band does nothing, a tile wholly inside runs every sub-block with no
mask, and the diagonal's tile and the one or two the window's lower edge
crosses run their sub-blocks trimmed to the columns their rows can see,
masked where an edge passes and only there (the lower edge's kept corner
is the complement of the diagonal's). :func:`_walk` lists the band's
tiles and no other, so nothing is stepped through, before the band or
after it. At L = 16,384 in
1,024-tiles under W = 4,096 a q tile has work in 5 of its 16 k tiles
(three whole and two of 0.625: :func:`causal_work_ratio` 1.062 over the
58.7M pairs :func:`window_pairs` counts of the 134.2M causal ones). A
row whose keys in a tile are all masked (the lower edge's tile holds
such rows) runs on a maximum of ``NEG_INF`` and what it adds there is
rescaled to nothing by the first tile that holds a key of its own: the
diagonal's always does. The calls carry names of their own
(:data:`WINDOWED`); ``W >= L`` is causal attention and is built as that,
and a call with no window is built exactly as before.

On a TPU backend the kernels compile through Mosaic. They run in Pallas
interpret mode only in a process that was explicitly put on the CPU
(tests, rehearsals); a CPU backend JAX fell back to, or any other
platform, raises (:func:`kernel_interpret_mode`).
"""

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # the forward's running statistics fill a lane register

# stable kernel names: the Mosaic custom call carries them as
# ``kernel_name``, which is how a lowered step (and a profiler trace)
# shows that the fused kernels are in it
FWD_KERNEL = "edl_flash_fwd"
BWD_DQ_KERNEL = "edl_flash_bwd_dq"
BWD_DKV_KERNEL = "edl_flash_bwd_dkv"
# the same three bodies under a selection (:func:`flash_attention_selected`)
# are calls of their own, so that a trace tells them apart
SELECTED = {
    FWD_KERNEL: "edl_flash_sel_fwd",
    BWD_DQ_KERNEL: "edl_flash_sel_bwd_dq",
    BWD_DKV_KERNEL: "edl_flash_sel_bwd_dkv",
}

# and under a window (``flash_attention(..., window=W)``): the global
# layers of a model that mixes both keep the plain names
WINDOWED = {
    FWD_KERNEL: "edl_flash_win_fwd",
    BWD_DQ_KERNEL: "edl_flash_win_bwd_dq",
    BWD_DKV_KERNEL: "edl_flash_win_bwd_dkv",
}

# and with a value head size that differs from the query's and the key's
# (``v.shape[-1] != q.shape[-1]``: a latent attention trained in its
# expanded form reads 192 and writes 128), causal or not
UNEQUAL = {
    FWD_KERNEL: "edl_flash_mla_fwd",
    BWD_DQ_KERNEL: "edl_flash_mla_bwd_dq",
    BWD_DKV_KERNEL: "edl_flash_mla_bwd_dkv",
}
# every name a flash call goes under, a set a kind of call
_KINDS = (SELECTED, WINDOWED, UNEQUAL)
_NAMES = (frozenset(SELECTED),) + tuple(
    frozenset(names.values()) for names in _KINDS
)
# and those of them that are forwards
_FORWARDS = frozenset([FWD_KERNEL] + [names[FWD_KERNEL] for names in _KINDS])

# what ``step_built`` says of a step's flash calls (:func:`grid_steps_in`)
STEP_BUILT_FIELDS = (
    "flash_grid_steps",
    "flash_grid_steps_empty",
    "flash_fwd_lane_sums",
)
# the grid steps with no tile to compute of each call built so far
# (:func:`_call`), by what a jaxpr shows of a call: its name, its grid and
# its two lengths. Not in the call's ``metadata``: jax writes that into
# the compiled module's text as JSON over several lines, which
# ``utils/step_ops.py`` and every other reader of that text a line an
# instruction would have to learn
_EMPTY_STEPS = {}

# every grid is (batch*heads, steps of :func:`_walk`) and accumulates
# over the steps of one outer tile, which are consecutive. No
# vmem_limit_bytes: on v5e / libtpu 0.0.34 the default 1024x1024 tiles
# compile under Mosaic's default scoped-VMEM limit (chip runs, PR 21 and
# PR 29; 2048x2048 tiles do not: the dq kernel runs out of VMEM, PR 29).
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary")
)


def _run_if(pred):
    """``pl.when`` for Python booleans: what :func:`causal_work_ratio`
    walks a tile with."""
    return lambda fn: fn() if pred else None


def _walk_tile(
    qi, kj, block_q, block_k, w, causal, step, when=pl.when, window=None
):
    """Call ``step(strips)`` with the sub-blocks of tile ``(qi, kj)`` in
    which the causal mask leaves work: ``strips`` is a static list of
    ``(rows, cols, off)``.

    ``rows`` and ``cols`` are static slices of the tile. ``off`` is None
    where every score of the sub-block is kept (no mask is built), else
    the sub-block's first q position minus its first k position: the
    score at local ``(r, c)`` is kept when ``r + off >= c``. Under a
    ``window`` it is a pair ``(off, low)`` instead, either of them None
    where that edge does not pass through the sub-block: the score is
    kept when ``r + off >= c`` (the diagonal) and ``c >= r + low`` (the
    window's lower edge, ``low = off - window + 1``).

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
      is skipped, masked or whole by a predicate on the program ids;
    - under a ``window`` (causal, query t reads keys ``t - window < s
      <= t``), square tiles: what a tile holds follows from ``qi - kj``
      alone, so the tiles are told apart by it, statically
      (:func:`_band_strips`): a run of distances whose tiles lie wholly
      inside the band and step whole, and at most three whose tile an
      edge crosses, each with its own trimmed and masked sub-blocks (the
      diagonal's, and the one or two the lower edge passes through:
      their kept corner is the complement of the diagonal's). Every
      other tile does nothing. Other tiles: skipped, masked or whole by
      a predicate, as above, with both edges in the mask.
    """
    if block_q % w:
        w = block_q
    all_rows, all_cols = slice(0, block_q), slice(0, block_k)

    def strips(trimmed):
        for lo in range(0, block_q, w):
            cols = slice(0, lo + w) if trimmed else all_cols
            yield slice(lo, lo + w), cols, lo if trimmed else None

    untrimmed = list(strips(False))
    whole = functools.partial(step, untrimmed)
    if not causal:
        whole()
    elif window is not None and block_q == block_k:
        distance, inside = qi - kj, []
        for d in range((window + block_q - 2) // block_q + 1):
            found = _band_strips(d, block_q, w, window)
            if found == untrimmed:
                inside.append(d)
            elif found:
                when(distance == d)(functools.partial(step, found))
        if inside:  # one run: the band is convex
            when((distance >= inside[0]) & (distance <= inside[-1]))(whole)
    elif window is not None:
        q_lo, k_lo = qi * block_q, kj * block_k
        q_hi, k_hi = q_lo + block_q - 1, k_lo + block_k - 1
        touched = (q_hi >= k_lo) & (q_lo - window < k_hi)
        when((q_lo >= k_hi) & (q_hi - window < k_lo))(
            functools.partial(step, [(all_rows, all_cols, None)])
        )
        off = q_lo - k_lo
        when(touched & ((q_lo < k_hi) | (q_hi - window >= k_lo)))(
            functools.partial(
                step, [(all_rows, all_cols, (off, off - window + 1))]
            )
        )
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


def _band_strips(distance, block, w, window):
    """The sub-blocks with work of a square tile ``distance`` tiles
    under the diagonal (0: on it), under a window: a list of ``(rows,
    cols, off)`` as :func:`_walk_tile` hands them out, ``off`` None or a
    pair. A sub-block is ``w`` rows against the columns between the
    lowest key its first row reads and the highest its last row reads,
    widened to whole multiples of ``w``; one no row of which reads a
    column of the tile is left out."""
    found = []
    for lo in range(0, block, w):
        # the strip's first row, counted from the tile's first key
        first = lo + distance * block
        c_min = max(first - window + 1, 0) // w * w
        c_max = min(first + w, block)
        if c_min >= c_max:
            continue
        off = first - c_min
        low = off - window + 1
        span = c_max - c_min
        # the diagonal passes where some column lies past some row's own;
        # the lower edge where some row's lowest key lies past column 0
        mask = (
            off if span - 1 > off else None,
            low if w - 1 + low > 0 else None,
        )
        found.append(
            (
                slice(lo, lo + w),
                slice(c_min, c_max),
                None if mask == (None, None) else mask,
            )
        )
    return found


def _here(outer_ref, inner_ref):
    """``(outer, inner, first, last)`` of this grid step: the tiles
    :func:`_walk`'s two tables name for it, and whether it is the first
    / the last step of its outer tile, whose steps are consecutive: an
    accumulator starts at the one and is written out at the other."""
    step, steps = pl.program_id(1), pl.num_programs(1)
    outer = outer_ref[step]
    first = (step == 0) | (outer_ref[jnp.maximum(step - 1, 0)] != outer)
    last = (step == steps - 1) | (
        outer_ref[jnp.minimum(step + 1, steps - 1)] != outer
    )
    return outer, inner_ref[step], first, last


def _each(strip):
    """A step that treats its sub-blocks one by one."""

    def step(strips):
        for s in strips:
            strip(*s)

    return step


def _scaled_q(q_ref, scale, rows):
    """``rows`` of the q tile with the softmax scale folded in: once an
    element of q, not once a score."""
    return q_ref[0, rows, :].astype(jnp.float32) * scale


def _kept(sel_ref, down, along):
    """The selection's sub-block as booleans, or None without one. The
    forward's block is (q rows, k columns), the backward kernels' the
    transpose; the caller names the two slices in the block's order."""
    if sel_ref is None:
        return None
    return sel_ref[0, down, along].astype(jnp.int32) != 0


def _inside(row, col, off):
    """Where q row ``row`` reads k column ``col`` (local indices of a
    sub-block): under the diagonal, ``row + off >= col``; under a
    window ``off`` is :func:`_walk_tile`'s pair and the lower edge,
    ``col >= row + low``, is tested too, each edge only where it
    passes."""
    off, low = off if isinstance(off, tuple) else (off, None)
    if low is None:
        return row + off >= col
    if off is None:
        return col >= row + low
    return (row + off >= col) & (col >= row + low)


def _scores(q, k, off, keep=None):
    """q k^T for one sub-block (q carries the softmax scale), masked to
    NEG_INF above the diagonal where ``off`` says it passes, or
    wherever ``keep`` is False: a selection lies inside the causal
    triangle, so it is the diagonal's mask too."""
    s = jax.lax.dot_general(
        q,
        k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if keep is not None:
        return jnp.where(keep, s, NEG_INF)
    if off is None:
        return s
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(_inside(row, col, off), s, NEG_INF)


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


def _lane_sum(x):
    """(rows, cols) -> (rows, 128): the sum over the groups of 128
    columns, lane by lane. The elementwise half of a row sum, as
    :func:`_lane_max` is of a row maximum; the cross-lane half is taken
    once a FINISHED q tile."""
    cols = x.shape[1]
    if cols % _LANES:  # narrow sub-block: the whole sum, in lane 0
        lane = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], _LANES), 1)
        return jnp.where(lane == 0, jnp.sum(x, axis=1, keepdims=True), 0.0)
    return functools.reduce(
        jnp.add, [x[:, c:c + _LANES] for c in range(0, cols, _LANES)]
    )


def _fwd_scratch(block_q, block_k, d_v):
    """The forward's scratch: the accumulator, the running maximum in
    every lane, and what the normalizer's row sums l take, which
    follows from the value's head size and nothing else.

    Where v leaves lanes spare in its last group of 128 (``d_v`` 64, the
    tests' 16) the third is ``[v | 1]``, (block_k, lanes of v): v beside
    columns of ones, so that ``p @ [v | 1]`` leaves l in the
    accumulator's spare lanes, a row reduction the matrix unit takes in
    a pass it makes anyway. Where v fills its lanes (128, 256) a column
    of ones would cost one more 128-wide pass for ONE useful column, and
    the third is l itself, (block_q, 128), summed lane by lane
    (:func:`_lane_sum`) beside an accumulator of ``d_v`` lanes."""
    lanes = -(-d_v // _LANES) * _LANES
    side = (block_q, _LANES) if lanes == d_v else (block_k, lanes)
    return [
        pltpu.VMEM((block_q, lanes), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
        pltpu.VMEM(side, jnp.float32),
    ]


def _fwd_kernel(
    q_tile_ref,
    k_tile_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    side_ref,
    *,
    causal,
    scale,
    w,
    sel_ref=None,
    window=None,
):
    qi, kj, first, last = _here(q_tile_ref, k_tile_ref)
    d = v_ref.shape[2]  # the value's head size: the result's
    # where the normalizer's row sums live (:func:`_fwd_scratch`): in
    # the accumulator's lanes past d, side_ref being [v | 1], or lane by
    # lane in side_ref
    v1_ref, l_ref = (side_ref, None) if acc_ref.shape[1] > d else (None, side_ref)

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        if l_ref is not None:
            l_ref[:] = jnp.zeros_like(l_ref)

    def step(strips):
        if v1_ref is not None:
            # v beside columns of ones: p @ [v | 1] leaves the row sums
            # of p in the lanes the head size leaves empty, so the
            # matrix unit takes the normalizer's row reduction and
            # acc_ref[:, d:] carries l through the same rescale as the
            # accumulator. Filled here and not above: a grid step with
            # no tile to compute never gets here
            v1_ref[:, :d] = v_ref[0].astype(jnp.float32)
            v1_ref[:, d:] = jnp.ones((v1_ref.shape[0], v1_ref.shape[1] - d))
        # ONE step of the online-softmax recurrence for the tile, its
        # products and exponentials taken sub-block by sub-block.
        # m_ref holds the running maximum in every lane; between the
        # sub-blocks it collects the per-lane part of the new one, and
        # the cross-lane reduction and the rescale run once a tile.
        m_prev = m_ref[:, :1]
        scores = []
        for rows, cols, off in strips:
            s = _scores(
                _scaled_q(q_ref, scale, rows),
                k_ref[0, cols, :],
                off,
                _kept(sel_ref, rows, cols),
            )
            m_ref[rows, :] = jnp.maximum(m_ref[rows, :], _lane_max(s))
            scores.append(s)
        m_new = jnp.max(m_ref[:], axis=1, keepdims=True)
        rescale = jnp.exp(m_prev - m_new)
        acc_ref[:] = acc_ref[:] * rescale
        if l_ref is not None:
            l_ref[:] = l_ref[:] * rescale
        for (rows, cols, _), s in zip(strips, scores):
            p = jnp.exp(s - m_new[rows])
            if l_ref is None:
                values = v1_ref[cols, :]
            else:
                values = v_ref[0, cols, :].astype(jnp.float32)
                l_ref[rows, :] += _lane_sum(p)
            acc_ref[rows, :] += jax.lax.dot(
                p, values, preferred_element_type=jnp.float32
            )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    _walk_tile(
        qi, kj, q_ref.shape[1], k_ref.shape[1], w, causal, step,
        window=window,
    )  # fmt: skip

    @pl.when(last)
    def _finish():
        if l_ref is None:
            l_fin = acc_ref[:, d:d + 1]
        else:  # the one cross-lane reduction of l, a finished q tile
            l_fin = jnp.sum(l_ref[:], axis=1, keepdims=True)
        o_ref[0] = (acc_ref[:, :d] / l_fin).astype(o_ref.dtype)
        # the statistics leave with L along the lanes: m_ref holds the
        # maximum in every lane, so one transpose of the (rows, 128)
        # logsumexp a finished q tile puts the rows on the lanes of its
        # first row
        lse_ref[0] = (m_ref[:] + jnp.log(l_fin)).T[:1]


def _scores_t(k, q, off, keep_t=None):
    """k q^T for one sub-block (q carries the softmax scale): the
    TRANSPOSED scores, k positions down the sublanes and q positions
    along the lanes, so that a statistic of the q rows is a row vector.
    Masked to NEG_INF above the diagonal where ``off`` says it passes:
    the score of q row ``r`` and k column ``c`` is kept when
    ``r + off >= c``; or wherever ``keep_t``, the transposed selection,
    is False."""
    s_t = jax.lax.dot_general(
        k.astype(jnp.float32),
        q,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if keep_t is not None:
        return jnp.where(keep_t, s_t, NEG_INF)
    if off is None:
        return s_t
    col = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 1)
    return jnp.where(_inside(row, col, off), s_t, NEG_INF)


def _p_and_ds_t(
    q, k_ref, v_ref, do, lse_ref, delta_ref, rows, cols, off, sel_ref=None
):
    """One sub-block of both backward passes, transposed: ``p^T =
    exp(k q^T - lse)`` recomputed and ``ds^T = p^T * (V dO^T - delta)``,
    both (columns, rows). ``q`` (scaled) and ``do`` are the sub-block's
    ``rows`` in f32. ``lse`` and ``delta`` arrive as (1, rows)
    and broadcast down the sublanes: no relayout. ``sel_ref`` is the
    TRANSPOSED selection's block, (columns, rows) as the scores."""
    p_t = jnp.exp(
        _scores_t(k_ref[0, cols, :], q, off, _kept(sel_ref, cols, rows))
        - lse_ref[0, :, rows]
    )
    dp_t = jax.lax.dot_general(
        v_ref[0, cols, :].astype(jnp.float32),
        do,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p_t, p_t * (dp_t - delta_ref[0, :, rows])


def _bwd_dq_kernel(
    q_tile_ref,
    k_tile_ref,
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
    sel_ref=None,
    window=None,
):
    qi, kj, first, last = _here(q_tile_ref, k_tile_ref)

    @pl.when(first)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    lhs_t = (((0,), (0,)), ((), ()))

    def strip(rows, cols, off):
        # q and dO are read here, a sub-block's rows at a time, and not
        # above: a grid step with no tile to compute reads nothing
        q = _scaled_q(q_ref, scale, rows)
        do = do_ref[0, rows, :].astype(jnp.float32)
        _, ds_t = _p_and_ds_t(
            q, k_ref, v_ref, do, lse_ref, delta_ref, rows, cols, off, sel_ref
        )
        dq_acc[rows, :] += jax.lax.dot_general(
            ds_t,
            k_ref[0, cols, :].astype(jnp.float32),
            lhs_t,
            preferred_element_type=jnp.float32,
        )  # (ds^T)^T k

    _walk_tile(
        qi, kj, q_ref.shape[1], k_ref.shape[1], w, causal, _each(strip),
        window=window,
    )  # fmt: skip

    @pl.when(last)
    def _finish():
        # ds k is the gradient of (q scale): the scale comes back once
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_tile_ref,
    k_tile_ref,
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
    sel_ref=None,
    window=None,
):
    kj, qi, first, last = _here(k_tile_ref, q_tile_ref)

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def strip(rows, cols, off):
        # the scaled q serves both products: s^T = k (q scale)^T, and
        # dk = ds^T (q scale) needs no scale of its own; read here, as
        # in dq
        q = _scaled_q(q_ref, scale, rows)
        do = do_ref[0, rows, :].astype(jnp.float32)
        p_t, ds_t = _p_and_ds_t(
            q, k_ref, v_ref, do, lse_ref, delta_ref, rows, cols, off, sel_ref
        )
        dv_acc[cols, :] += jax.lax.dot(
            p_t, do, preferred_element_type=jnp.float32
        )  # p^T dO
        dk_acc[cols, :] += jax.lax.dot(
            ds_t, q, preferred_element_type=jnp.float32
        )  # ds^T (q scale)

    _walk_tile(
        qi, kj, q_ref.shape[1], k_ref.shape[1], w, causal, _each(strip),
        window=window,
    )  # fmt: skip

    @pl.when(last)
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
    clamped) block size along the sequence to suit the dimension it
    lands on, or to equal the whole length, whatever the dtype:
    ``block_k`` is only ever a sublane dimension (of k, v, dk, dv) and
    has to divide by 8; ``block_q`` is that too (q, o, dO, dq) and,
    since the statistics travel as ``(batch*heads, 1, L)``, the LANE
    dimension of their ``(1, 1, block_q)`` block, so it has to divide by
    128. Interpret mode has no such constraint. (v5e / libtpu 0.0.34,
    chip run, PR 21: bf16 and f32 alike, 8x8 up to 1000x1000 and whole
    odd lengths 7, 36, 100 build; 4, 12, 20 or 50 rows out of a longer
    sequence are refused.) :func:`auto_blocks` and
    :func:`pick_causal_attention` only hand out multiples of 128.
    """
    bq, bk = min(block_q, lq), min(block_k, lk)
    if lq % bq or lk % bk:
        return False
    if kernel_interpret_mode():
        return True
    return (bq % _LANES == 0 or bq == lq) and (bk % 8 == 0 or bk == lk)


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
    tiles, a short whole length) is left whole.

    Since PR 31 a sub-block of the two backward kernels is TRANSPOSED,
    (the columns its rows can see, ``w``): ``w`` is its lane dimension
    and the statistics' ``(1, w)`` slices broadcast down its sublanes.
    Same chip and versions, same shapes and way of timing (chip run,
    PR 31; the parent's bodies in the same process 0.965 + 1.360 +
    1.687 = 4.012 on that machine), step by step: skipped grid steps
    clamped to a neighbour's blocks, bodies untouched, 0.964 + 1.294 +
    1.500 = 3.758; with that the statistics as ``(bh, 1, L)`` and each
    turned from a row into a column once a grid step (the parent's
    orientation) 0.960 + 1.295 + 1.693 = 3.949, so a relayout a step
    costs what the traffic gives back; dq in the parent's orientation
    off a ``(rows, 128)`` scratch filled once a q tile and dkv
    transposed 0.960 + 1.205 + 1.404 = 3.569; **both transposed, as
    committed, 0.960 + 1.125 + 1.404 = 3.490 (-13.0%)**: a statistic
    broadcast along the lanes was itself a cost, beside its bytes.
    ``w`` again, transposed: 128: 0.929 + 1.755 + 1.391 = 4.076; 512:
    1.026 + 1.211 + 1.534 = 3.772: 256 stays. 32 x 2,048 x 64:
    0.316 + 0.377 + 0.487 = 1.179 -> 0.316 + 0.356 + 0.457 = 1.129."""
    del d
    return 256


def causal_work_ratio(lq, lk, block_q, block_k, w, causal=True, window=None):
    """Score elements the kernels perform over score elements the mask
    keeps: 1.0 means no score is computed only to be masked away.

    Static, from shapes alone: it walks every tile with the kernels' own
    :func:`_walk_tile` and adds up the sub-blocks' areas, so one number
    serves all three kernels. L = 2,048 in 1,024-tiles:
    1.5 with the tile whole, 1.125 at w = 256; L = 1,024: 2.0 and 1.25;
    L = 4,096: 1.25 and 1.06. Under a ``window`` the mask keeps
    ``t - window < s <= t``: L = 16,384, window 4,096, w = 256: 1.062
    (a q tile from the fifth on performs 4.25 tiles for the 4 it keeps;
    :func:`window_pairs` has the pairs)."""
    block_q, block_k = _block_sizes(lq, lk, block_q, block_k)
    if not causal:
        kept = lq * lk
    elif window is None:
        kept = sum(min(r + 1, lk) for r in range(lq))
    else:
        kept = sum(
            max(min(r + 1, lk) - max(r - window + 1, 0), 0) for r in range(lq)
        )
    done = []

    def step(strips):
        done.extend(
            (rows.stop - rows.start) * (cols.stop - cols.start)
            for rows, cols, _ in strips
        )

    for qi in range(lq // block_q):
        for kj in range(lk // block_k):
            _walk_tile(
                qi, kj, block_q, block_k, w, causal, step, when=_run_if,
                window=window,
            )  # fmt: skip
    return sum(done) / kept


def window_pairs(length, window):
    """``(kept, causal)``: the (query, key) pairs a sequence of
    ``length`` keeps under a window, query t reading ``min(t + 1,
    window)`` keys, and the causal pairs. 16,384 under 4,096:
    58,722,304 of 134,225,920 (43.7%)."""
    window = min(window, length)
    return (
        window * (window + 1) // 2 + (length - window) * window,
        length * (length + 1) // 2,
    )


def _block_sizes(lq, lk, block_q, block_k):
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    if lq % block_q or lk % block_k:
        raise ValueError(
            "sequence lengths (%d, %d) must divide block sizes (%d, %d)"
            % (lq, lk, block_q, block_k)
        )
    return block_q, block_k


_STATISTICS = ("lse", "delta")


def _has_work(qi, kj, block_q, block_k, causal, window=None):
    """Whether the mask leaves tile ``(qi, kj)`` a pair to compute, by
    positions: some query of the tile reads some key of it. What
    :func:`_walk_tile` decides by its predicates, tile by tile."""
    if not causal:
        return True
    q_lo, k_lo = qi * block_q, kj * block_k
    q_hi, k_hi = q_lo + block_q - 1, k_lo + block_k - 1
    return q_hi >= k_lo and (window is None or q_lo - window < k_hi)


def _walk(kernel, lq, lk, block_q, block_k, causal, window=None):
    """``(q_tiles, k_tiles)``: the tiles a kernel's grid steps through,
    as two lists with an entry a step.

    The forward and dq run k tiles inside a q tile (the q tile is the
    OUTER one), dkv q tiles inside a k tile. An outer tile's steps are
    consecutive and list, in ascending order, the inner tiles in which
    the mask leaves work (:func:`_has_work`: by positions, so square
    and other tiles alike). At L = 16,384 in 1,024-tiles a q tile has
    ``qi + 1`` steps under the causal mask (136 a head) and at most 5
    under a window of 4,096 (70 a head, of the rectangle's 256), and so
    a k tile. Not causal, every pair is listed.

    Every outer tile has at least one step, where its result is
    written. One with no work at all (lengths that differ: a k tile no
    query reads, a q tile whose window begins past the last key) keeps
    ONE step, on the last inner tile, to zero its result and write it:
    the only steps without work a grid has."""
    nq, nk = lq // block_q, lk // block_k
    by_k = kernel == BWD_DKV_KERNEL
    q_tiles, k_tiles = [], []
    for a in range(nk if by_k else nq):
        tiles = [(b, a) if by_k else (a, b) for b in range(nq if by_k else nk)]
        found = [
            tile
            for tile in tiles
            if _has_work(*tile, block_q, block_k, causal, window)
        ]
        found = found or tiles[-1:]
        q_tiles += [qi for qi, _ in found]
        k_tiles += [kj for _, kj in found]
    return q_tiles, k_tiles


def _plan(
    kernel, bh, lq, lk, d, block_q, block_k, causal, heads=None, window=None,
    d_v=None,
):  # fmt: skip
    """``(grid, tables, inputs, outputs)`` of one kernel's
    ``pallas_call``, the last two as lists of ``(name, BlockSpec)``:
    what the call is built from, and what :func:`hbm_traffic` walks.

    The grid is ``(bh, steps)``, a step for each tile with work and no
    other, and ``tables`` are :func:`_walk`'s ``(q_tiles, k_tiles)``:
    the call's two scalar-prefetch operands, which every index map is
    handed after the grid's two indices and the body reads its tiles
    from (:func:`_here`).

    q, dO, o, dq and the statistics move in tiles of ``block_q`` rows,
    k, v, dk and dv in tiles of ``block_k``; a statistic is
    ``(bh, 1, L)`` f32 and its tile ``(1, 1, block_q)``, L along the
    lanes. ``d`` is the head size of q and k (and of dq and dk);
    ``d_v``, where given, that of v, o, dO and dv, else ``d`` too.

    ``heads``, where given, says the call has a selection, one for each
    run of ``heads`` rows of the grid's first axis (a sequence's
    heads): the last input, int8, in tiles that follow BOTH tile axes.
    The forward reads it as ``(bh // heads, lq, lk)`` in
    ``(1, block_q, block_k)`` tiles; both backward kernels form
    transposed scores and read its transpose, ``(bh // heads, lk, lq)``
    in ``(1, block_k, block_q)`` tiles."""
    tables = _walk(kernel, lq, lk, block_q, block_k, causal, window)
    grid = (bh, len(tables[0]))
    rows = lambda i, s, q_tiles, k_tiles: (i, q_tiles[s], 0)
    cols = lambda i, s, q_tiles, k_tiles: (i, k_tiles[s], 0)
    stat = lambda i, s, q_tiles, k_tiles: (i, 0, q_tiles[s])
    sel = lambda i, s, q_tiles, k_tiles: (i // heads, q_tiles[s], k_tiles[s])
    sel_t = lambda i, s, q_tiles, k_tiles: (
        i // heads, k_tiles[s], q_tiles[s]
    )
    by_rows = pl.BlockSpec((1, block_q, d), rows)
    by_cols = pl.BlockSpec((1, block_k, d), cols)
    if d_v is None or d_v == d:
        value_rows, value_cols = by_rows, by_cols
    else:
        value_rows = pl.BlockSpec((1, block_q, d_v), rows)
        value_cols = pl.BlockSpec((1, block_k, d_v), cols)
    statistic = pl.BlockSpec((1, 1, block_q), stat)
    qkv = [("q", by_rows), ("k", by_cols), ("v", value_cols)]
    if kernel == FWD_KERNEL:
        if heads:
            qkv.append(("sel", pl.BlockSpec((1, block_q, block_k), sel)))
        return grid, tables, qkv, [("o", value_rows), ("lse", statistic)]
    inputs = qkv + [
        ("dO", value_rows), ("lse", statistic), ("delta", statistic)
    ]
    if heads:
        inputs.append(("sel_t", pl.BlockSpec((1, block_k, block_q), sel_t)))
    if kernel == BWD_DQ_KERNEL:
        return grid, tables, inputs, [("dq", by_rows)]
    return grid, tables, inputs, [("dk", by_cols), ("dv", value_cols)]


def hbm_traffic(
    bh, lq, lk, d, block_q, block_k, causal=True, itemsize=2, window=None,
    d_v=None,
):  # fmt: skip
    """Bytes each kernel moves between HBM and VMEM in one call, split
    into ``tensors`` (q, k, v, o, dO and the gradients, ``itemsize``
    bytes an element) and ``statistics`` (lse and delta, f32), with the
    count of blocks moved under each operand's name (``blocks``).

    Static, from shapes alone: it walks each kernel's grid in the order
    the chip does, with the kernel's OWN index maps (:func:`_plan`),
    and adds a block's bytes whenever its index differs from the step
    before, which is when the pipeline copies it. At 96 x 2,048 x 64,
    bf16, causal, 1,024-tiles: statistics 3.9 MB over the three
    kernels (704.6 MB as ``(bh, L, 128)`` blocks that dkv fetched at
    every step, until PR 30), tensors 377.5 MB (528.5), k and v tiles a
    head in the forward and dq 2 (4), q, dO and statistics tiles a head
    in dkv 2 (4). Not causal nothing is clamped: 4 and 4. Under a
    ``window`` the clamp is from both sides: at 16,384 in 1,024-tiles
    under 4,096, k and v tiles a head in the forward and dq 69 of the
    135 the causal call moves (in 256 grid steps), and so q, dO and
    the statistics in dkv. ``d_v``, where given, is the head size of v,
    o, dO and dv beside ``d`` of q, k, dq and dk: at 192 and 128 a
    block of q is one and a half times a block of o."""
    block_q, block_k = _block_sizes(lq, lk, block_q, block_k)
    traffic = {}
    for kernel in (FWD_KERNEL, BWD_DQ_KERNEL, BWD_DKV_KERNEL):
        grid, tables, inputs, outputs = _plan(
            kernel, bh, lq, lk, d, block_q, block_k, causal, window=window,
            d_v=d_v,
        )  # fmt: skip
        moved = {"tensors": 0, "statistics": 0, "blocks": {}}
        at = {}
        for step in itertools.product(*map(range, grid)):
            for name, spec in inputs + outputs:
                index = spec.index_map(*step, *tables)
                if at.get(name) == index:
                    continue
                at[name] = index
                moved["blocks"][name] = moved["blocks"].get(name, 0) + 1
                kind, size = (
                    ("statistics", 4)
                    if name in _STATISTICS
                    else ("tensors", itemsize)
                )
                moved[kind] += size * math.prod(spec.block_shape)
        traffic[kernel] = moved
    return traffic


# Both halves are jitted INLINE with everything but the arrays static:
# jax then traces a kernel body once for each distinct call (shapes,
# tile sizes, causal) and not once a layer, and hands every layer the
# same kernel jaxpr, which lets the step's lowering build each Mosaic
# module once and copy it in place: the lowered step still holds three
# custom calls a layer. The sub-blocked bodies are four times the
# equations of the whole-tile ones; traced and lowered per layer they
# cost `lm125m-l2048` 9 s of set-up on the chip (PR 29).
_STATIC = ("causal", "block_q", "block_k", "interpret", "w", "window")


def _selecting(body, at):
    """``body`` for a call whose ref number ``at``, its last input, is
    the selection: the refs come as tables, inputs, outputs, scratch."""

    def kernel(*refs, **static):
        return body(*refs[:at], *refs[at + 1 :], sel_ref=refs[at], **static)

    return kernel


def _call(
    kernel,
    body,
    shapes,
    out_shape,
    scratch_shapes,
    interpret,
    heads=None,
    window=None,
    d_v=None,
    **static
):
    """The ``pallas_call`` of ``kernel``, as a function of its array
    operands: its grid, tables and block specs are :func:`_plan`'s for
    ``shapes`` (the tables go in front of the operands, as scalar
    prefetch), ``static`` are the body's keywords. With ``heads`` (a
    selection is the last input, :func:`_plan`) the call goes under its
    :data:`SELECTED` name, with ``window`` (one more keyword of the
    body) under its :data:`WINDOWED` name, with a value head size ``d_v``
    that is not the query's under its :data:`UNEQUAL` name. How many of
    the grid's steps
    have no tile to compute is noted for :func:`grid_steps_in`."""
    grid, tables, inputs, outputs = _plan(
        kernel, *shapes, heads=heads, window=window, d_v=d_v
    )
    bh, lq, lk, d, block_q, block_k, causal = shapes
    if d_v not in (None, d):
        if heads or window is not None:
            raise ValueError(
                "head sizes %d and %d under a selection or a window: not "
                "built" % (d, d_v)
            )
        kernel = UNEQUAL[kernel]
    if heads:
        body = _selecting(body, len(tables) + len(inputs) - 1)
        kernel = SELECTED[kernel]
    if window is not None:
        static["window"] = window
        kernel = WINDOWED[kernel]
    call = pl.pallas_call(
        functools.partial(body, **static),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=grid,
            in_specs=[spec for _, spec in inputs],
            out_specs=[spec for _, spec in outputs],
            scratch_shapes=scratch_shapes,
        ),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=kernel,
    )
    _EMPTY_STEPS[kernel, grid, lq, lk] = bh * sum(
        not _has_work(qi, kj, block_q, block_k, causal, window)
        for qi, kj in zip(*tables)
    )
    tables = [np.asarray(table, np.int32) for table in tables]
    return lambda *operands: call(*tables, *operands)


@functools.partial(jax.jit, static_argnames=_STATIC, inline=True)
def _flash_fwd(
    q, k, v, causal, block_q, block_k, interpret, w=None, selection=None,
    window=None,
):  # fmt: skip
    """``selection``, where given: (b, lq, lk) int8, non-zero where a
    query reads a key, the same for every head of a sequence.
    ``window``, where given (causal, no selection): query t reads keys
    ``t - window < s <= t``."""
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    b, lq, h, d = q.shape
    lk, d_v = k.shape[1], v.shape[3]
    block_q, block_k = _block_sizes(lq, lk, block_q, block_k)
    w = w or sub_block(d)
    scale = d ** -0.5
    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)

    out, lse = _call(
        FWD_KERNEL,
        _fwd_kernel,
        (b * h, lq, lk, d, block_q, block_k, causal),
        [
            jax.ShapeDtypeStruct((b * h, lq, d_v), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, lq), jnp.float32),
        ],
        _fwd_scratch(block_q, block_k, d_v),
        interpret,
        heads=None if selection is None else h,
        window=window,
        d_v=d_v,
        causal=causal,
        scale=scale,
        w=w,
    )(qf, kf, vf, *(() if selection is None else (selection,)))
    return _unfold_heads(out, b, h), lse.reshape(b, h, lq)


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
    selection_t=None,
    window=None,
):
    """``selection_t``, where given: the forward's selection
    transposed, (b, lk, lq) int8; ``window`` as the forward's."""
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
    # both statistics enter as they are, (b*h, 1, lq): no broadcast
    operands = (
        qf,
        kf,
        vf,
        dof,
        lse.reshape(b * h, 1, lq),
        delta.reshape(b * h, 1, lq),
    )
    shapes = b * h, lq, lk, d, block_q, block_k, causal
    static = dict(
        causal=causal, scale=scale, w=w, window=window, d_v=v.shape[3]
    )
    if selection_t is not None:
        operands += (selection_t,)
        static["heads"] = h

    (dq,) = _call(
        BWD_DQ_KERNEL,
        _bwd_dq_kernel,
        shapes,
        [jax.ShapeDtypeStruct(qf.shape, q.dtype)],
        [pltpu.VMEM((block_q, d), jnp.float32)],
        interpret,
        **static,
    )(*operands)
    dk, dv = _call(
        BWD_DKV_KERNEL,
        _bwd_dkv_kernel,
        shapes,
        [
            jax.ShapeDtypeStruct(kf.shape, k.dtype),
            jax.ShapeDtypeStruct(vf.shape, v.dtype),
        ],
        [
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, v.shape[3]), jnp.float32),
        ],
        interpret,
        **static,
    )(*operands)
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


def flash_attention(
    q, k, v, causal=False, block_q=None, block_k=None, window=None
):
    """(B, L, H, D) fused attention; trains with the blockwise backward.

    ``window`` (causal only): query t reads keys ``t - window < s <= t``,

        o_t = sum_{t - W < s <= t} softmax(q_t k_s / sqrt(D)) v_s

    by the same three bodies under names of their own
    (:data:`WINDOWED`). They COMPUTE the band and no more: a tile wholly
    outside it does nothing and fetches nothing, a tile wholly inside
    runs unmasked, and the diagonal's tile and the lower edge's are
    trimmed and masked by sub-block (:func:`_walk_tile`). A window that
    reaches every key (``window >= L``) is causal attention and is
    built as that."""
    if window is None or (causal and window >= k.shape[1]):
        out, _ = flash_attention_with_lse(q, k, v, causal, block_q, block_k)
        return out
    if not causal or window < 1:
        raise ValueError(
            "a window of %r keys: a positive number, under causal=True"
            % (window,)
        )
    block_q, block_k = auto_blocks(
        q.shape[1], k.shape[1], block_q, block_k
    )
    return _flash_windowed(q, k, v, int(window), block_q, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_windowed(q, k, v, window, block_q, block_k):
    return _windowed_fwd_rule(q, k, v, window, block_q, block_k)[0]


def _windowed_fwd_rule(q, k, v, window, block_q, block_k):
    out, lse = _flash_fwd(
        q, k, v, True, block_q, block_k, kernel_interpret_mode(),
        window=window,
    )  # fmt: skip
    return out, (q, k, v, out, lse)


def _windowed_bwd_rule(window, block_q, block_k, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_bwd(
        q, k, v, out, lse, g, True, block_q, block_k, kernel_interpret_mode(),
        window=window,
    )  # fmt: skip


_flash_windowed.defvjp(_windowed_fwd_rule, _windowed_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_selected(q, k, v, selection, block_q, block_k):
    return _selected_fwd_rule(q, k, v, selection, block_q, block_k)[0]


def _selected_fwd_rule(q, k, v, selection, block_q, block_k):
    out, lse = _flash_fwd(
        q, k, v, True, block_q, block_k, kernel_interpret_mode(),
        selection=selection,
    )  # fmt: skip
    return out, (q, k, v, out, lse, selection)


def _selected_bwd_rule(block_q, block_k, residuals, g):
    q, k, v, out, lse, selection = residuals
    grads = _flash_bwd(
        q, k, v, out, lse, g, True, block_q, block_k, kernel_interpret_mode(),
        # one transpose a call serves both backward kernels
        selection_t=selection.transpose(0, 2, 1),
    )  # fmt: skip
    # the selection is discrete: its cotangent has no value
    return grads + (np.zeros(selection.shape, jax.dtypes.float0),)


_flash_selected.defvjp(_selected_fwd_rule, _selected_bwd_rule)


def flash_attention_selected(
    q, k, v, selection, block_q=None, block_k=None
):
    """(B, L, H, D) fused causal attention in which query ``t`` of
    sequence ``b`` reads key ``s`` only where ``selection[b, t, s]`` is
    non-zero: (B, Lq, Lk) int8, the same for every head of a sequence,
    inside the causal triangle and at least one key a query (what
    :func:`elasticdl_tpu.ops.sparse_select.select_keys` returns).

        o_t = sum_{s in S_t} softmax_{s in S_t}(q_t k_s / sqrt(D)) v_s

    The three kernel bodies of :func:`flash_attention`, under names of
    their own (:data:`SELECTED`), with the selection's tile as one more
    input (1 MiB beside a 1,024 x 1,024 tile of scores) and its test in
    place of the diagonal's. They COMPUTE every causal score and mask:
    a tile is skipped where the causal mask skips it, not where the
    selection leaves it empty, so the time is that of causal attention
    however few keys are kept. The selection takes no gradient."""
    block_q, block_k = auto_blocks(
        q.shape[1], k.shape[1], block_q, block_k
    )
    return _flash_selected(
        q, k, v, jnp.asarray(selection, jnp.int8), block_q, block_k
    )


def attention_in_step(step_facts):
    """Name the attention a built step runs, from the facts
    ``ElasticDPTrainer.describe_step`` reads off that step: ``"pallas"``
    (the fused kernels are Mosaic custom calls in the lowered module),
    ``"pallas-interpret"`` (the kernels are in the jaxpr, interpreted:
    a process put on the CPU by request) or ``"xla"`` (the reference
    attention :func:`pick_causal_attention` hands short or untileable
    lengths)."""
    for flash in _NAMES:
        if flash <= set(step_facts["mosaic_kernels"]):
            return "pallas"
        if flash <= set(step_facts["pallas_kernels"]):
            return "pallas-interpret"
    return "xla"


def grid_steps_in(jaxpr):
    """:data:`STEP_BUILT_FIELDS` of a traced program: the grid steps of
    every flash call in ``jaxpr`` and in the jaxprs nested in it, how
    many of them have no tile to compute, and how many of the calls are
    forwards that keep the normalizer's row sums lane by lane (a
    forward whose accumulator is no wider than v: :func:`_fwd_scratch`),
    each call counted as often as the program holds it (a layer's
    three, a recomputed forward's once more); ``{}`` for a program
    without the kernels. Static: a count of the walk (:func:`_walk`)
    and of the calls' scratch, no device number."""
    steps = empty = lane_sums = 0
    flash = frozenset().union(*_NAMES)
    todo = [getattr(jaxpr, "jaxpr", jaxpr)]
    while todo:
        for eqn in todo.pop().eqns:
            name = eqn.params.get("name")
            if eqn.primitive.name == "pallas_call" and name in flash:
                grid = tuple(eqn.params["grid_mapping"].grid)
                # a flash call's operands: the two tables, q, k, ...
                lengths = [v.aval.shape[1] for v in eqn.invars[2:4]]
                steps += math.prod(grid)
                empty += _EMPTY_STEPS.get((name, grid, *lengths), 0)
                if name in _FORWARDS:
                    acc = eqn.params["grid_mapping"].scratch_avals[0]
                    lane_sums += acc.shape[1] == eqn.invars[4].aval.shape[2]
            todo.extend(jax.core.jaxprs_in_params(eqn.params))
    # a flash call has a step at least
    if not steps:
        return {}
    return dict(zip(STEP_BUILT_FIELDS, (steps, empty, lane_sums)))


def selected_reference_attention(q, k, v, selection):
    """:func:`flash_attention_selected` in plain XLA, the (L, L) scores
    whole: what short or untileable lengths get, and the tests'
    yardstick."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where(
        selection[:, None] != 0, scores.astype(jnp.float32), NEG_INF
    )
    return jnp.einsum(
        "bhqk,bkhd->bqhd",
        jax.nn.softmax(scores, axis=-1),
        v.astype(jnp.float32),
    ).astype(q.dtype)


def _takes_the_kernels(seq_len, use_flash, min_flash_len):
    return (
        use_flash
        and seq_len >= min_flash_len
        and divisible(seq_len, seq_len, 128, 128)
    )


def pick_selected_attention(seq_len, use_flash=True, min_flash_len=1024):
    """:func:`pick_causal_attention`'s policy for attention under a
    selection: ``fn(q, k, v, selection)``."""
    if _takes_the_kernels(seq_len, use_flash, min_flash_len):
        return flash_attention_selected
    return selected_reference_attention


def windowed_reference_attention(q, k, v, window):
    """``flash_attention(..., causal=True, window=window)`` in plain
    XLA, the (L, L) scores whole: what short or untileable lengths get,
    and the tests' yardstick."""
    lq, lk = q.shape[1], k.shape[1]
    distance = jnp.arange(lq)[:, None] - jnp.arange(lk)[None, :]
    return selected_reference_attention(
        q, k, v, ((distance >= 0) & (distance < window))[None]
    )


def pick_causal_attention(
    seq_len, use_flash=True, min_flash_len=1024, window=None
):
    """Causal attention fn for a model at this sequence length; with
    ``window``, over the ``window`` nearest keys (itself among them),
    by the same policy.

    One home for the policy: 1,024 is the length from which the cells
    use the kernels; ``lm125m-l512`` is the cell on the other side; the
    crossover between them is not measured on the chip (ROADMAP S10).
    The kernels need 128-divisible lengths to tile. Both transformer
    builds call this so the threshold lives in exactly one place."""
    if _takes_the_kernels(seq_len, use_flash, min_flash_len):
        return lambda q, k, v: flash_attention(q, k, v, True, window=window)
    if window is not None and window < seq_len:
        return functools.partial(windowed_reference_attention, window=window)
    from elasticdl_tpu.parallel.ring_attention import reference_attention

    return functools.partial(reference_attention, causal=True)
