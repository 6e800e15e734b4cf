"""Ring attention: exact attention over sequence-sharded inputs.

Long-context training shards the sequence axis across devices (the mesh's
``seq`` axis). Each device keeps its Q shard resident and K/V shards rotate
around the ring via ``ppermute`` over ICI; partial attention outputs merge
with the online-softmax (flash) recurrence, so the full (L, L) score matrix
never materializes and memory stays O(L_local).

This is the blockwise ring attention of Liu et al. (Ring Attention with
Blockwise Transformers, 2023), built with shard_map + XLA collectives —
the per-device block kernel lowers to the MXU, and the K/V rotation
overlaps with compute via XLA's async collective scheduling.

No counterpart exists in the reference (no attention models, SURVEY.md
§5.7); this subsystem is the framework's long-context scaling axis.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _block_attend(q, k, v, bias=None):
    """Scores + flash statistics for one (Q_block, KV_block) pair.

    q: (B, Lq, H, D), k/v: (B, Lk, H, D). Returns (out_unnorm, row_max,
    row_sum) with out_unnorm = exp(s - row_max) @ v.
    """
    scale = q.shape[-1] ** -0.5
    # online-softmax statistics must form in f32 even for bf16 q/k/v —
    # bf16 s/m/p/l loses precision the f32 accumulators can't recover
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    m = jnp.max(s, axis=-1)  # (B, H, Lq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)  # (B, H, Lq)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", p, v, preferred_element_type=jnp.float32
    )
    return o, m, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Merge two flash partials (associative online-softmax combine)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    o = o1 * a1.transpose(0, 2, 1)[..., None] + o2 * a2.transpose(0, 2, 1)[
        ..., None
    ]
    l = l1 * a1 + l2 * a2
    return o, m, l


def _causal_bias(q_offset, k_offset, lq, lk, dtype):
    q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0)
    k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1)
    return jnp.where(q_pos >= k_pos, 0.0, jnp.finfo(dtype).min)


def ring_attention(q, k, v, axis_name, causal=False):
    """Exact attention with K/V rotating around ``axis_name``.

    Call *inside* shard_map with q/k/v already sequence-sharded:
    q, k, v: (B, L_local, H, D). Returns (B, L_local, H, D).
    """
    n = jax.lax.psum(1, axis_name)
    # axis_index only under causal: a dead axis_index lowers to a
    # partition_id instruction with no data dependence on the manual
    # region's operands, which XLA hoists out of it — and the SPMD
    # partitioner rejects PartitionId outside manual sharding
    # ("PartitionId instruction is not supported for SPMD
    # partitioning"). The non-causal ring needs no rank at all.
    my_idx = jax.lax.axis_index(axis_name) if causal else None
    l_local = q.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        o, m, l, kk, vv = carry
        if causal:
            # the K/V block now held came from device (my_idx - step) % n
            src = (my_idx - step) % n
            bias = _causal_bias(
                my_idx * l_local,
                src * l_local,
                l_local,
                kk.shape[1],
                q.dtype,
            )[None, None]
        else:
            bias = None
        bo, bm, bl = _block_attend(q, kk, vv, bias)
        o, m, l = _merge(o, m, l, bo, bm, bl)
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return o, m, l, kk, vv

    b, _, h, d = q.shape
    o0 = jnp.zeros((b, l_local, h, d), jnp.float32)
    m0 = jnp.full((b, h, l_local), jnp.finfo(jnp.float32).min, jnp.float32)
    l0 = jnp.zeros((b, h, l_local), jnp.float32)
    o, m, l, _, _ = jax.lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Fused-kernel ring: per-block Pallas flash attention with (out, lse)
# merging, and a custom VJP that re-rotates K/V around the ring in the
# backward — so training memory stays O(L_local x block) per device (the
# Ring Attention recipe), instead of saving every rotated K/V block as a
# scan residual.
# ---------------------------------------------------------------------------


def _merge_normalized(o1, lse1, o2, lse2):
    """Merge two *normalized* partial attentions by their logsumexps."""
    lse = jnp.logaddexp(lse1, lse2)
    # both sides empty (fully masked so far): weights 0, not NaN
    finite = jnp.isfinite(lse)
    w1 = jnp.where(finite, jnp.exp(lse1 - jnp.where(finite, lse, 0.0)), 0.0)
    w2 = jnp.where(finite, jnp.exp(lse2 - jnp.where(finite, lse, 0.0)), 0.0)
    o = (
        o1 * w1.transpose(0, 2, 1)[..., None]
        + o2 * w2.transpose(0, 2, 1)[..., None]
    )
    return o, lse


def _block_cases(src, my_idx, causal, diag_fn, full_fn, skip_fn):
    """Ring blocks see equal-size shards, so causal masking is all-or-
    nothing per block: diagonal (src == my), fully visible (src < my), or
    fully masked (src > my)."""
    if not causal:
        return full_fn(None)
    return jax.lax.cond(
        src == my_idx,
        diag_fn,
        lambda _: jax.lax.cond(src < my_idx, full_fn, skip_fn, None),
        None,
    )


def ring_flash_attention(
    q, k, v, axis_name, causal=False, block_q=None, block_k=None
):
    """Ring attention whose per-block compute is the fused Pallas kernel.

    Call inside shard_map with q/k/v sequence-sharded (B, L_local, H, D).
    Forward carries (normalized out, lse) and merges blocks by logsumexp;
    backward re-rotates K/V (and their gradient accumulators) around the
    ring, running the blockwise flash backward against the *global* lse —
    so neither pass materializes more than one K/V block beyond the
    residents, and no (L, L) score matrix exists anywhere.
    """
    from elasticdl_tpu.ops.flash_attention import auto_blocks

    # resolve here (not per inner call): the custom_vjp's nondiff args
    # must be concrete and identical across the fwd/bwd ring loops
    block_q, block_k = auto_blocks(
        q.shape[1], k.shape[1], block_q, block_k
    )
    return _ring_flash(q, k, v, axis_name, causal, block_q, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, axis_name, causal, block_q, block_k):
    out, _ = _ring_flash_fwd_loop(
        q, k, v, axis_name, causal, block_q, block_k
    )
    return out


def _ring_flash_fwd_loop(q, k, v, axis_name, causal, block_q, block_k):
    from elasticdl_tpu.ops.flash_attention import flash_attention_with_lse

    n = jax.lax.psum(1, axis_name)
    # rank only under causal — see ring_attention: a dead axis_index
    # becomes a hoisted PartitionId the SPMD partitioner rejects
    my_idx = jax.lax.axis_index(axis_name) if causal else None
    b, l_local, h, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def attend(kk, vv, block_causal):
        o, lse = flash_attention_with_lse(
            q, kk, vv, block_causal, block_q, block_k
        )
        return o.astype(jnp.float32), lse

    def body(step, carry):
        o, lse, kk, vv = carry
        src = (my_idx - step) % n if causal else None
        o_b, lse_b = _block_cases(
            src,
            my_idx,
            causal,
            diag_fn=lambda _: attend(kk, vv, True),
            full_fn=lambda _: attend(kk, vv, False),
            skip_fn=lambda _: (
                jnp.zeros((b, l_local, h, d), jnp.float32),
                jnp.full((b, h, l_local), -jnp.inf, jnp.float32),
            ),
        )
        o, lse = _merge_normalized(o, lse, o_b, lse_b)
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return o, lse, kk, vv

    o0 = jnp.zeros((b, l_local, h, d), jnp.float32)
    lse0 = jnp.full((b, h, l_local), -jnp.inf, jnp.float32)
    o, lse, _, _ = jax.lax.fori_loop(0, n, body, (o0, lse0, k, v))
    return o.astype(q.dtype), lse


def _ring_flash_fwd_rule(q, k, v, axis_name, causal, block_q, block_k):
    out, lse = _ring_flash_fwd_loop(
        q, k, v, axis_name, causal, block_q, block_k
    )
    return out, (q, k, v, out, lse)


def _ring_flash_bwd_rule(
    axis_name, causal, block_q, block_k, residuals, g
):
    from elasticdl_tpu.ops.flash_attention import (
        _flash_bwd,
        kernel_interpret_mode,
    )

    q, k, v, out, lse = residuals
    n = jax.lax.psum(1, axis_name)
    # rank only under causal — see ring_attention: a dead axis_index
    # becomes a hoisted PartitionId the SPMD partitioner rejects
    my_idx = jax.lax.axis_index(axis_name) if causal else None
    perm = [(i, (i + 1) % n) for i in range(n)]
    interpret = kernel_interpret_mode()

    def block_bwd(kk, vv, block_causal):
        return _flash_bwd(
            q,
            kk,
            vv,
            out,
            lse,
            g,
            block_causal,
            block_q,
            block_k,
            interpret,
        )

    def body(step, carry):
        dq, dkk, dvv, kk, vv = carry
        src = (my_idx - step) % n if causal else None
        dq_b, dk_b, dv_b = _block_cases(
            src,
            my_idx,
            causal,
            diag_fn=lambda _: block_bwd(kk, vv, True),
            full_fn=lambda _: block_bwd(kk, vv, False),
            skip_fn=lambda _: (
                jnp.zeros_like(q),
                jnp.zeros_like(k),
                jnp.zeros_like(v),
            ),
        )
        dq = dq + dq_b.astype(jnp.float32)
        dkk = dkk + dk_b.astype(jnp.float32)
        dvv = dvv + dv_b.astype(jnp.float32)
        # rotate the gradient accumulators WITH their K/V blocks: after n
        # steps each block (and its accumulated grad) is home again
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        dkk = jax.lax.ppermute(dkk, axis_name, perm)
        dvv = jax.lax.ppermute(dvv, axis_name, perm)
        return dq, dkk, dvv, kk, vv

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq, dk, dv, _, _ = jax.lax.fori_loop(
        0, n, body, (dq0, dk0, dv0, k, v)
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def make_ring_attention(
    mesh, seq_axis="seq", causal=False, use_flash=True, block_q=128,
    block_k=128,
):
    """shard_map-wrapped ring attention over ``mesh[seq_axis]``.

    Inputs/outputs are global (B, L, H, D) arrays sharded on L. The batch
    dim additionally shards over ``data`` and the head dim over ``model``
    when those axes exist in the mesh, so dp x tp replicas each attend
    over their own batch/head slice — the ring only rotates K/V along
    ``seq_axis``.

    ``use_flash`` (default) runs the fused Pallas kernel per block with
    the blockwise ring backward; the XLA fallback materializes per-block
    scores (O(L_local x L_block) memory) and differentiates through the
    scan.
    """
    axes = set(mesh.axis_names)
    batch_axis = "data" if "data" in axes and "data" != seq_axis else None
    head_axis = "model" if "model" in axes and "model" != seq_axis else None
    spec = P(batch_axis, seq_axis, head_axis, None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    def _ring(q, k, v):
        from elasticdl_tpu.ops.flash_attention import divisible

        if use_flash and divisible(
            q.shape[1], k.shape[1], block_q, block_k
        ):
            return ring_flash_attention(
                q,
                k,
                v,
                seq_axis,
                causal=causal,
                block_q=block_q,
                block_k=block_k,
            )
        # shard lengths the kernel can't tile keep the XLA path
        return ring_attention(q, k, v, seq_axis, causal=causal)

    return _ring


def reference_attention(q, k, v, causal=False):
    """Plain XLA attention (for tests and single-device fallback)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        s = s + _causal_bias(0, 0, lq, lk, q.dtype)[None, None]
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )
