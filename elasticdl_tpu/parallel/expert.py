"""Expert parallelism: MoE dispatch over an ``expert`` mesh axis.

The reference has no expert parallelism (SURVEY.md §2.2: absent). This
is the TPU-native form: each device along the ``expert`` axis owns one
(or more) experts' parameters; tokens are gated top-1, packed into
capacity-bounded per-expert buckets, shipped to their expert with
``lax.all_to_all``, transformed, and shipped back — the same explicit
routing fabric as the HBM embedding plane (nn/hbm_embedding.py), which
is exactly the point: on TPU, "expert parallel" and "vocab-sharded
lookup" are the same all_to_all pattern over ICI with different
per-shard compute.

Capacity semantics follow the standard MoE recipe: each expert accepts
at most ``capacity`` tokens per shard per step; overflow tokens bypass
the experts (identity/zero contribution), weighted out by their gate.
Gradients flow through dispatch, experts, combine, and the gate (via the
gate-probability scaling).
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def topk_gate(logits, k):
    """(T, E) gate logits -> (expert_idx (T, k), gate_probs (T, k)).

    For ``k > 1`` the selected probabilities renormalize to sum to 1
    per token (the GShard top-2 recipe)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    if k > 1:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return idx, gate


def top1_gate(logits):
    """(T, E) gate logits -> (expert_idx (T,), gate_prob (T,))."""
    idx, gate = topk_gate(logits, 1)
    return idx[:, 0], gate[:, 0]


def load_balancing_loss(gate_logits):
    """Switch-transformer auxiliary loss: ``E * sum_e f_e * P_e``.

    ``f_e`` = fraction of tokens whose top-1 expert is ``e``; ``P_e`` =
    mean router probability for ``e``. Equals 1.0 at perfect balance,
    grows as routing collapses onto few experts. Differentiable through
    ``P_e`` (the ``f_e`` factor is piecewise-constant, as in the paper).
    """
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    e = probs.shape[-1]
    top1 = jnp.argmax(probs, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    return e * jnp.sum(f * p)


def moe_apply(
    expert_fn,
    expert_params,
    x,
    gate_logits,
    axis_name,
    capacity,
    num_selected=1,
):
    """Route tokens to experts over ``axis_name``; call inside shard_map.

    - ``expert_fn(params, x) -> y``: one expert's computation (same
      in/out feature width).
    - ``expert_params``: this device's expert's parameter slice (leading
      dim 1, squeezed internally).
    - ``x``: (T, D) local tokens; ``gate_logits``: (T, E).
    - ``num_selected``: top-k routing. Each (token, choice) pair routes
      as a virtual token through one shared capacity budget, and a
      token's k expert outputs sum gate-weighted — so top-2 costs 2x
      the dispatch of top-1, not a separate code path.

    Returns (T, D): gate-weighted expert outputs, overflow tokens zero.
    """
    n_exp = jax.lax.psum(1, axis_name)
    params = jax.tree_util.tree_map(
        lambda p: jnp.squeeze(p, axis=0), expert_params
    )
    t_local, d = x.shape
    k = num_selected

    idx_tk, gate_tk = topk_gate(gate_logits, k)
    # choice-major virtual tokens: v[j*T + t] = (token t, choice j)
    expert_idx = idx_tk.T.reshape(-1)  # (k*T,)
    gate = gate_tk.T.reshape(-1)
    vx = jnp.tile(x, (k, 1))  # (k*T, D)
    t_virtual = k * t_local
    cap = min(capacity, t_virtual)

    # position of each virtual token within its expert's bucket
    order = jnp.argsort(expert_idx, stable=True)
    sorted_expert = expert_idx[order]
    counts = jnp.bincount(expert_idx, length=n_exp)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t_virtual) - starts[sorted_expert]
    ok = pos < cap
    slot = jnp.where(ok, pos, cap)  # overflow -> trash column

    # (E, cap+1, D) send buffer; row e = tokens for expert e
    send = jnp.zeros((n_exp, cap + 1, d), x.dtype)
    send = send.at[sorted_expert, slot].set(vx[order])[:, :cap]
    recv = jax.lax.all_to_all(
        send, axis_name, split_axis=0, concat_axis=0, tiled=True
    )  # (E, cap, D): row p = tokens shard p sent to THIS expert

    y = expert_fn(params, recv.reshape(n_exp * cap, d))
    y = y.reshape(n_exp, cap, d)
    back = jax.lax.all_to_all(
        y, axis_name, split_axis=0, concat_axis=0, tiled=True
    )  # (E, cap, D): row e = this shard's tokens back from expert e

    # un-permute; overflow tokens contribute zero
    gathered = jnp.where(
        ok[:, None],
        back[sorted_expert, jnp.where(ok, pos, 0)],
        0.0,
    )
    inv = jnp.argsort(order, stable=True)
    routed = gathered[inv] * gate[:, None].astype(x.dtype)
    return routed.reshape(k, t_local, d).sum(axis=0)


def make_moe_fn(
    mesh,
    expert_fn,
    expert_axis="expert",
    batch_axis=None,
    capacity_factor=2.0,
    num_selected=1,
):
    """Global wrapper: ``(stacked_expert_params, x, gate_logits) -> y``.

    ``stacked_expert_params`` leaves are (E, ...) sharded over
    ``expert_axis``; ``x`` is (T, D) tokens (optionally sharded over
    ``batch_axis``), ``gate_logits`` (T, E) likewise. Capacity per
    expert = ceil(T_local * num_selected / E) * capacity_factor.
    """

    def _capacity(t_local, n_exp):
        return max(
            1,
            int(
                -(-(t_local * num_selected) // n_exp) * capacity_factor
            ),
        )

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(expert_axis), P(batch_axis), P(batch_axis)),
        out_specs=P(batch_axis),
        check_vma=False,
    )
    def _moe(stacked_params, x, gate_logits):
        cap = _capacity(x.shape[0], int(mesh.shape[expert_axis]))
        return moe_apply(
            expert_fn,
            stacked_params,
            x,
            gate_logits,
            expert_axis,
            cap,
            num_selected=num_selected,
        )

    return _moe


def reference_moe(expert_fn, per_expert_params, x, gate_logits, num_selected=1):
    """Dense semantics the routed form must match (tests): every expert
    runs every token, outputs combined by the top-k gate."""
    idx, gate = topk_gate(gate_logits, num_selected)
    outs = jnp.stack(
        [expert_fn(p, x) for p in per_expert_params]
    )  # (E, T, D)
    t = jnp.arange(x.shape[0])
    picked = sum(
        outs[idx[:, j], t] * gate[:, j, None].astype(x.dtype)
        for j in range(num_selected)
    )
    return picked
