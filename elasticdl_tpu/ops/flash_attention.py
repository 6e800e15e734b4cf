"""Fused flash attention (Pallas/TPU): forward AND blockwise backward.

The hot op of the transformer family: softmax(QK^T)V computed blockwise
with the online-softmax recurrence, so neither the (L, L) score matrix nor
full-length K/V ever sit in VMEM. The grid is (batch*heads, q_blocks,
k_blocks): Pallas streams one (block_k, D) K/V tile from HBM per step
while the running max / normalizer / accumulator persist in VMEM scratch
across the innermost k axis — the standard TPU flash pipeline.
Accumulation is float32 while inputs may be bfloat16 (MXU native).

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
# 0.0.34 the default 1024x1024 tiles (and 1024x2048 at L=2048) compile
# under Mosaic's default scoped-VMEM limit, with or without these
# parameters (chip run, PR 21).
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    causal,
    scale,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    def compute():
        q = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = (
            jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())))
            * scale
        )  # (block_q, block_k)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[:, :1]  # (block_q, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)  # (block_q, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(p, v_blk)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # blocks entirely above the diagonal contribute nothing
        @pl.when(kj * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()

    else:
        compute()

    @pl.when(kj == nk - 1)
    def _finish():
        l_fin = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / l_fin).astype(o_ref.dtype)
        lse_ref[:] = jnp.broadcast_to(
            m_ref[:, :1] + jnp.log(l_fin), lse_ref.shape
        )


def _recompute_p(q_ref, k_ref, lse_ref, qi, kj, causal, scale):
    """exp(qk^T * scale - lse) for one tile — shared by both bwd passes."""
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    q = q_ref[0].astype(jnp.float32)
    k_blk = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ()))) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return jnp.exp(s - lse_ref[0, :, :1])


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
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    def compute():
        p = _recompute_p(q_ref, k_ref, lse_ref, qi, kj, causal, scale)
        do = do_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ()))
        )  # (block_q, block_k)
        ds = p * (dp - delta_ref[0, :, :1])
        dq_acc[:] += jax.lax.dot(ds, k_ref[0].astype(jnp.float32)) * scale

    if causal:
        @pl.when(kj * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()

    else:
        compute()

    @pl.when(kj == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


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
):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    def compute():
        p = _recompute_p(q_ref, k_ref, lse_ref, qi, kj, causal, scale)
        do = do_ref[0].astype(jnp.float32)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ()))
        )  # p^T dO: (block_k, d)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ()))
        )
        ds = p * (dp - delta_ref[0, :, :1])
        dk_acc[:] += (
            jax.lax.dot_general(
                ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ()))
            )
            * scale
        )  # ds^T Q: (block_k, d)

    if causal:
        # q blocks entirely above the diagonal see this k block masked
        @pl.when(qi * block_q + block_q - 1 >= kj * block_k)
        def _():
            compute()

    else:
        compute()

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


def _block_sizes(lq, lk, block_q, block_k):
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    if lq % block_q or lk % block_k:
        raise ValueError(
            "sequence lengths (%d, %d) must divide block sizes (%d, %d)"
            % (lq, lk, block_q, block_k)
        )
    return block_q, block_k


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    block_q, block_k = _block_sizes(lq, lk, block_q, block_k)
    scale = d ** -0.5
    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)

    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale)
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
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=FWD_KERNEL,
    )(qf, kf, vf)
    return (
        _unfold_heads(out, b, h),
        lse[:, :, 0].reshape(b, h, lq),
    )


def _flash_bwd(
    q, k, v, out, lse, g, causal, block_q, block_k, interpret, g_lse=None
):
    b, lq, h, d = q.shape
    lk = k.shape[1]
    block_q, block_k = _block_sizes(lq, lk, block_q, block_k)
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
        functools.partial(_bwd_dq_kernel, causal=causal, scale=scale),
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
        functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale),
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

    One home for the measured policy (bench.py --flash on v5e): the
    fused kernel wins from L=1024 up (1.3-2.2x fwd+bwd) but loses to
    XLA's unfused path at short L, and needs 128-divisible lengths to
    tile. Both the plain and pipelined transformer builds call this so
    the threshold lives in exactly one place."""
    if (
        use_flash
        and seq_len >= min_flash_len
        and divisible(seq_len, seq_len, 128, 128)
    ):
        return lambda q, k, v: flash_attention(q, k, v, True)
    from elasticdl_tpu.parallel.ring_attention import reference_attention

    return functools.partial(reference_attention, causal=True)
