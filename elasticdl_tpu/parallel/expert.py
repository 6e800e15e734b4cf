"""Expert parallelism: two mixture-of-experts layers.

The reference has no expert parallelism (SURVEY.md section 2.2: absent).
Two layers live here, and they are different layers, not two settings
of one.

**The exchanged, capacity-bounded layer** (:func:`moe_apply`,
:func:`make_moe_fn`, :func:`reference_moe`; the zoo's ``MoEMlp``). Each
device along the ``expert`` mesh axis owns exactly ONE expert
(``moe_apply`` squeezes the leading dim of its parameter slice, so the
axis has as many devices as the layer has experts). Tokens are gated by
a softmax over the experts, top-k with the selected probabilities
renormalised (:func:`topk_gate`), packed into per-expert buckets of at
most ``capacity`` rows, shipped to their expert with
``lax.all_to_all``, transformed, and shipped back: the same routing
fabric as the HBM embedding plane (nn/hbm_embedding.py). A token past
an expert's capacity is DROPPED (zero contribution). On a mesh without
an ``expert`` axis ``reference_moe`` runs every expert on every token.
:func:`load_balancing_loss` is the Switch auxiliary loss that keeps its
routing even.

**The held-share, dropless layer** (:func:`sigmoid_topk_route`,
:func:`softmax_topk_route`, :func:`held_experts_apply`,
:func:`held_experts_apply_masked`, :func:`shared_expert_apply`,
:func:`expert_bias_update`; the zoo's ``hybrid_moe_lm``). A device is
told which experts it holds
(``first_expert_held``, ``experts_held``: MANY a device), routes over
all ``num_experts`` of the layer, and computes the part of the result
its own experts give. Scores are sigmoids, selection adds a bias that
takes no gradient and is steered by the load (no auxiliary loss), or
a softmax with no bias at all; either way the
gates are normalised over all the selected experts, held or not. The
``T * k`` assignments are sorted so that those of held experts come
first, grouped by expert, and go through ops/grouped_matmul.py: a
static buffer of ``T * k`` rows, no capacity, no dropped token, of
which only the rows routed here are moved and computed, by loops
whose length is read on the device
(:func:`held_experts_apply_masked` gives the same share with no
dispatch at all, every held expert over every token, in a time the
routing cannot move). With
``experts_held == num_experts`` it is the whole layer. It has NO
exchange yet: it is one chip's share of an expert-parallel deployment
whose other chips are absent, and what their experts would have added
is left out. Its state (the bias, the assignment counters) is that of
ONE device's tokens: nothing sums the counts over a data axis, so the
elastic trainer refuses a model that keeps it on a mesh of more than
one device (parallel/elastic.py ``_check_routing_state``).

Once the held-share layer has its exchange over an ``expert`` axis (a
ragged all_to_all of the rows each peer's experts own, before and
after the grouped products) it replaces, of the older layer: the
capacity buckets and the drop (``moe_apply``'s ``send``/``slot``
arithmetic, ``make_moe_fn._capacity``), the one-expert-a-device squeeze,
and ``reference_moe``'s every-expert-every-token fallback (the held
share with ``experts_held == num_experts`` is that layer at the cost of
the selected experts only). ``topk_gate``, ``load_balancing_loss`` and
the softmax router stay: they are another model's routing, not a
worse form of this one.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
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


# ---------------------------------------------------------------------------
# The held-share, dropless layer
# ---------------------------------------------------------------------------

# state collection of an expert layer of this kind: ``expert_bias``
# (E,) float32 and ``assignments`` (E,) int32, the assignments each
# expert has had since the job's first step (it wraps; readers take
# differences)
MOE_STATE_COLLECTION = "moe_state"


def sigmoid_topk_route(
    router_logits, expert_bias, k, scaling=1.0, n_group=1, topk_group=1
):
    """(T, E) router logits -> (selected (T, k) int32, gates (T, k) f32).

    ``s = sigmoid(logits)`` in float32; ``selected = top_k(s + bias)``
    (the bias steers the selection and nothing else: it takes no
    gradient and is not in the gates); ``gate_e = s_e / (sum of s over
    the selected + 1e-6) * scaling``.

    With ``n_group > 1`` the selection is group-limited (DeepSeek-V3,
    arXiv:2412.19437 section 2.1.2): the experts are ``n_group`` runs
    of ``E / n_group`` consecutive ones, a group's score is the sum of
    its two largest ``s + bias``, the ``topk_group`` best groups stay
    (ties to the lower group), and the ``k`` largest ``s + bias`` among
    their experts are selected. One group of which one stays is the
    selection above, and is built as that."""
    scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    biased = scores + jax.lax.stop_gradient(expert_bias.astype(jnp.float32))
    if n_group > 1:
        by_group = biased.reshape(biased.shape[:-1] + (n_group, -1))
        group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, topk_group)
        stays = jnp.any(
            kept[..., None] == jnp.arange(n_group, dtype=kept.dtype), axis=-2
        )
        biased = jnp.where(stays[..., None], by_group, -jnp.inf).reshape(
            biased.shape
        )
    _, selected = jax.lax.top_k(biased, k)
    picked = jnp.take_along_axis(scores, selected, axis=-1)
    gates = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    return selected.astype(jnp.int32), gates * scaling


def softmax_topk_route(router_logits, k, scaling=1.0):
    """Softmax scores and no bias: ``p = softmax(logits)`` in float32;
    ``selected`` the ``k`` largest (ties to the lower index,
    ``lax.top_k``'s rule); ``gate_e = p_e / (sum of p over the
    selected) * scaling``. The largest probability is among the
    selected and is at least ``1 / num_experts``, so the sum is never
    zero and nothing stands beside it."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    picked, selected = jax.lax.top_k(probs, k)
    gates = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return selected.astype(jnp.int32), gates * scaling


def expert_assignments(selected, num_experts):
    """(E,) int32: how many of the (T, k) assignments each expert got."""
    return jnp.bincount(selected.reshape(-1), length=num_experts).astype(
        jnp.int32
    )


def expert_bias_update(expert_bias, assignments, rate):
    """The selection bias after a step that made ``assignments``
    (auxiliary-loss-free balancing, Wang et al., arXiv:2408.15664):
    ``b_e + rate * sign(mean(c) - c_e)``: up for an expert under the
    mean load, down for one over it. No gradient passes."""
    load = jax.lax.stop_gradient(assignments.astype(jnp.float32))
    return expert_bias + rate * jnp.sign(jnp.mean(load) - load)


# rows one trip of a loop over the held rows moves (and the unit the
# rows moved round up to): a few trips at the share a device of an
# 8-way expert-parallel job holds, each long enough that a trip's
# fixed cost does not show
DISPATCH_CHUNK_ROWS = 1024


def dispatch_chunk_rows(rows):
    """The chunk in which a pass over the held rows walks a buffer of
    ``rows`` rows: :data:`DISPATCH_CHUNK_ROWS` for a buffer it divides
    (a multiple of the grouped products' 512-row tile), else the
    largest divisor of ``rows`` under it (a toy buffer: often the
    whole of it). It divides the buffer so that the last chunk ends
    where the buffer does."""
    return next(
        c
        for c in range(min(DISPATCH_CHUNK_ROWS, rows), 0, -1)
        if rows % c == 0
    )


class _HeldRows(NamedTuple):
    """Where the ``n_here`` assignments to held experts are, in the two
    orders the layer walks them in. BY EXPERT (row ``r`` of the
    ``T * k``-row buffers the grouped products read and write; the
    held rows are ``r < n_here``): ``assignment_of_row``. BY TOKEN
    (position ``p < n_here``: the held assignments in token-major
    order, so that a token's are adjacent): ``row_of_position``,
    ``assignment_of_position`` (``T * k`` from ``n_here`` on), both
    padded by ``k - 1`` positions so that a chunk can look past its
    end. By token itself: ``first_position`` (T,), how many held
    assignments the tokens before ``t`` have, and ``held_count`` (T,),
    how many ``t`` has."""

    n_here: jax.Array
    assignment_of_row: jax.Array
    row_of_position: jax.Array
    assignment_of_position: jax.Array
    first_position: jax.Array
    held_count: jax.Array


def _held_rows(held_assignment, order, n_here):
    tokens, k = held_assignment.shape
    row = jnp.arange(tokens * k, dtype=jnp.int32)
    # the held rows by the assignment each holds: an assignment is
    # ``t * k + j``, so that is by token. An order is moved by a sort
    # that carries what has to move, not by a gather: 32,768 scalars
    # gathered cost the v5e 0.23 ms, a sort of them 0.03 (PERF.md
    # section 6, PR 38)
    assignment_of_position, row_of_position = jax.lax.sort(
        (jnp.where(row < n_here, order, tokens * k), row), num_keys=1
    )
    held_count = held_assignment.sum(axis=1, dtype=jnp.int32)
    return _HeldRows(
        n_here=n_here,
        assignment_of_row=order,
        row_of_position=jnp.pad(row_of_position, (0, k - 1)),
        assignment_of_position=jnp.pad(
            assignment_of_position, (0, k - 1), constant_values=tokens * k
        ),
        first_position=jnp.cumsum(held_count) - held_count,
        held_count=held_count,
    )


def _over_held_chunks(n_here, buffer_shape, dtype, body):
    """A buffer whose chunks that hold a row under ``n_here`` are
    ``body(start, chunk)`` each: as many trips as ``n_here``, read on
    the device, asks for. What the rows past them hold is UNDEFINED, as
    it is past the held rows of a grouped product's result: the buffer
    is not filled first (a fill of ``T * k`` rows for every pass costs
    the layer a fifth of its time at an eighth of the rows held)."""
    chunk = dispatch_chunk_rows(buffer_shape[0])
    return jax.lax.fori_loop(
        0,
        (n_here + chunk - 1) // chunk,
        lambda i, buffer: jax.lax.dynamic_update_slice_in_dim(
            buffer, body(i * chunk, chunk).astype(dtype), i * chunk, 0
        ),
        jax.lax.empty(buffer_shape, dtype),
    )


def _gather_held_rows(source, held_rows, scale=None):
    """(T, d) ``source`` -> (T * k, d): row ``r`` under ``n_here`` is
    the row of ``source`` of the token whose assignment was sorted to
    ``r``, times ``scale`` (T * k,) of that assignment in float32
    where one is given. Only the chunks that hold such a row are
    gathered."""
    rows = held_rows.assignment_of_row.shape[0]
    k = rows // source.shape[0]

    def body(start, chunk):
        assignment = jax.lax.dynamic_slice_in_dim(
            held_rows.assignment_of_row, start, chunk
        )
        got = source[assignment // k]
        if scale is not None:
            got = got.astype(jnp.float32) * scale[assignment][:, None]
        return got

    return _over_held_chunks(
        held_rows.n_here, (rows, source.shape[1]), source.dtype, body
    )


def _sum_held_rows_by_token(buffer, held_rows, scale=None):
    """(T * k, d) ``buffer`` by expert -> (T, d): a token's held rows
    added up in float32, each times ``scale`` (T * k,) of its
    assignment where one is given; zero for a token with none. The
    held rows are walked by token, a chunk at a time: a token's rows
    are adjacent there, at most ``k`` of them, so ``k - 1`` shifted
    adds leave every token's sum at its first position, and ONE gather
    of T rows brings the sums out. A row past ``n_here`` is never
    added to one under it (its assignment reads as no token's),
    whatever it holds."""
    rows, width = buffer.shape
    tokens = held_rows.first_position.shape[0]
    k = rows // tokens

    def body(start, chunk):
        def ahead(values):
            return jax.lax.dynamic_slice_in_dim(values, start, chunk + k - 1)

        assignment = ahead(held_rows.assignment_of_position)
        got = buffer[ahead(held_rows.row_of_position)].astype(jnp.float32)
        if scale is not None:
            got = got * scale[jnp.minimum(assignment, rows - 1)][:, None]
        token = assignment // k
        total = got[:chunk]
        for j in range(1, k):
            total = total + jnp.where(
                (token[j : j + chunk] == token[:chunk])[:, None],
                got[j : j + chunk],
                0.0,
            )
        return total

    sums = _over_held_chunks(
        held_rows.n_here, (rows, width), buffer.dtype, body
    )
    return jnp.where(
        (held_rows.held_count > 0)[:, None],
        sums[jnp.minimum(held_rows.first_position, rows - 1)],
        0,
    )


@jax.custom_vjp
def _rows_for_assignments(x, held_rows):
    """(T, d) tokens -> (T * k, d): row ``r`` under ``n_here`` is the
    token of the assignment sorted to ``r``. The transpose adds up, by
    token, the held rows of what comes back: gathers both ways, never
    a scatter; assignments of absent experts give nothing back."""
    return _gather_held_rows(x, held_rows)


_rows_for_assignments.defvjp(
    lambda x, held_rows: (_rows_for_assignments(x, held_rows), held_rows),
    lambda held_rows, g: (_sum_held_rows_by_token(g, held_rows), None),
)


@jax.custom_vjp
def _gated_sum_by_token(y, gates, held_rows):
    """(T * k, d) rows by expert, (T, k) gates -> (T, d) in ``y``'s
    dtype: ``sum over j, (t, j) held, of gates[t, j] * y[row of
    (t, j)]``, the products and the sum in float32. Only rows under
    ``n_here`` are read, here and in the transposes: ``y``'s cotangent
    is the token's times the gate, gathered for those rows alone, and
    a gate's is its row's product with the token's cotangent, zero
    for an assignment to an absent expert."""
    return _sum_held_rows_by_token(y, held_rows, gates.reshape(-1))


def _gated_sum_bwd(residuals, g):
    y, gates, held_rows = residuals
    rows = y.shape[0]
    k = gates.shape[1]
    dy = _gather_held_rows(g, held_rows, gates.reshape(-1))

    def body(start, chunk):
        assignment = jax.lax.dynamic_slice_in_dim(
            held_rows.assignment_of_row, start, chunk
        )
        mine = jax.lax.dynamic_slice_in_dim(y, start, chunk)
        return jnp.sum(
            mine.astype(jnp.float32) * g[assignment // k].astype(jnp.float32),
            axis=1,
        )

    by_row = _over_held_chunks(held_rows.n_here, (rows,), jnp.float32, body)
    # back by assignment: the rows of absent experts sorted last, and
    # what they (and the last chunk's rows past ``n_here``, where ``y``
    # is undefined) hold masked before it is anyone's gradient
    row = jnp.arange(rows, dtype=jnp.int32)
    _, dgates = jax.lax.sort(
        (
            held_rows.assignment_of_row,
            jnp.where(row < held_rows.n_here, by_row, 0.0),
        ),
        num_keys=1,
    )
    return dy, dgates.reshape(gates.shape).astype(gates.dtype), None


_gated_sum_by_token.defvjp(
    lambda y, gates, held_rows: (
        _gated_sum_by_token(y, gates, held_rows),
        (y, gates, held_rows),
    ),
    _gated_sum_bwd,
)


# what an expert's gate goes through: ``act(x W_1) * (x W_3)``, a
# SwiGLU with ``silu`` (the default) and a ReGLU with ``relu``
EXPERT_ACTS = ("silu", "relu")


def _glu(up, act):
    width = up.shape[1] // 2
    return getattr(jax.nn, act)(up[:, :width]) * up[:, width:]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _glu_held_rows(up, n_here, act):
    """``act(up[:, :f]) * up[:, f:]`` over the chunks that hold a row
    under ``n_here``, and so its cotangent."""
    rows, width = up.shape
    return _over_held_chunks(
        n_here,
        (rows, width // 2),
        up.dtype,
        lambda start, chunk: _glu(
            jax.lax.dynamic_slice_in_dim(up, start, chunk), act
        ),
    )


def _glu_bwd(act, residuals, g):
    up, n_here = residuals

    def body(start, chunk):
        _, pull = jax.vjp(
            functools.partial(_glu, act=act),
            jax.lax.dynamic_slice_in_dim(up, start, chunk),
        )
        return pull(jax.lax.dynamic_slice_in_dim(g, start, chunk))[0]

    return _over_held_chunks(n_here, up.shape, up.dtype, body), None


_glu_held_rows.defvjp(
    lambda up, n_here, act: (_glu_held_rows(up, n_here, act), (up, n_here)),
    _glu_bwd,
)


# one program where it is called outside any (a model's ``init`` is an
# eager forward pass): op by op, the loops below are traced anew at
# every call and cost establish seconds; inside a step it is inlined
# and changes nothing
@functools.partial(
    jax.jit, static_argnames=("first_expert_held", "act"), inline=True
)
def held_experts_apply(
    x, selected, gates, w_in, w_out, first_expert_held, act=EXPERT_ACTS[0]
):
    """This device's share of a gated expert layer's result (a SwiGLU's;
    with ``act="relu"`` a ReGLU's: ``relu`` where ``silu`` stands below).

    ``x`` (T, d); ``selected``, ``gates`` (T, k) over ALL the layer's
    experts (:func:`sigmoid_topk_route`); ``w_in`` (G, d, 2f): the held
    experts' ``W_1 | W_3`` side by side; ``w_out`` (G, f, d): their
    ``W_2``. The device holds experts ``first_expert_held`` ..
    ``first_expert_held + G - 1``. Returns (T, d):

        sum over e in selected(t), e held, of
            gate_e(t) * W_2e (silu(x W_1e) * (x W_3e))

    The ``T * k`` assignments are sorted (stable) so that rows of held
    experts come first, grouped by expert, and rows of absent experts
    last, covered by no group of the three grouped products
    (ops/grouped_matmul.py), whose tiles past the held rows are never
    computed. Nothing else walks the rows of absent experts either:
    how many rows are held is on the device (``group_sizes``), and
    every pass that is not a kernel (the gather in front, the
    activation, the sum by token behind, and their transposes) is a
    loop over the chunks of :func:`dispatch_chunk_rows` rows that hold
    a held row, as many trips as the routing asks for: the whole
    buffer when every expert is held, none when no row came here. No
    capacity, no dropped assignment: the result is the same for any
    routing. What a buffer holds past the held rows is UNDEFINED, on
    the way into a kernel (inside the last chunk, rows of absent
    assignments; past it, whatever the memory held) as on the way out
    of one, and nothing reads it: where a result leaves (a token's
    sum, a gate's gradient) only rows under the held count are
    picked."""
    from elasticdl_tpu.ops.grouped_matmul import grouped_matmul

    held = w_in.shape[0]
    local = selected - first_expert_held
    held_assignment = jnp.logical_and(local >= 0, local < held)
    group = jnp.where(held_assignment, local, held).reshape(-1)  # absent: last
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    group_sizes = jnp.sum(
        group[:, None] == jnp.arange(held, dtype=group.dtype),
        axis=0,
        dtype=jnp.int32,
    )
    held_rows = _held_rows(held_assignment, order, group_sizes.sum())

    rows = _rows_for_assignments(x, held_rows)
    up = grouped_matmul(rows, w_in, group_sizes)
    hidden = _glu_held_rows(up, held_rows.n_here, act)
    y = grouped_matmul(hidden, w_out, group_sizes)
    return _gated_sum_by_token(y, gates, held_rows)


def _masked_share(x, gate, w_in, w_out, act):
    """``sum_g gate[:, g] * W_2g (act(x W_1g) * (x W_3g))`` as three
    plain ``(T, d) x (d, G * f)`` products; ``gate`` (T, G) float32,
    ``act`` one of :data:`EXPERT_ACTS`."""
    held, d, _ = w_in.shape
    width = w_out.shape[1]

    def side_by_side(w):  # (G, d, f) -> (d, G * f)
        return w.transpose(1, 0, 2).reshape(d, held * width)

    # W_1 and W_3 are parted and laid out here, where that moves a
    # matrix, and not behind a product, where it would move (T, G, 2f)
    up = checkpoint_name(x @ side_by_side(w_in[..., :width]), "held_up")
    across = checkpoint_name(x @ side_by_side(w_in[..., width:]), "held_up")
    hidden = getattr(jax.nn, act)(up) * across
    # an expert's gate over its own columns, column block by block: a
    # (T, G, f) view of ``hidden`` is another tiling, a copy each way
    gated = jnp.concatenate(
        [
            hidden[:, g * width : (g + 1) * width].astype(jnp.float32)
            * gate[:, g : g + 1]
            for g in range(held)
        ],
        axis=1,
    ).astype(x.dtype)
    return (gated @ w_out.reshape(held * width, d)).astype(x.dtype)


def shared_expert_apply(x, w_in, w_out, act=EXPERT_ACTS[0]):
    """The shared expert beside the routed ones: ``W_2 (act(x W_1) * (x
    W_3))`` of EVERY token, no router and no gate. ``w_in`` (d, 2f):
    ``W_1 | W_3`` side by side; ``w_out`` (f, d). It is not a share:
    every chip of an expert-parallel deployment computes it alike for
    its own tokens, so where the shares of a layer are added up it is
    counted once. Named scope ``edl/moe/shared``."""
    with jax.named_scope("edl/moe/shared"):
        return (_glu(x @ w_in, act) @ w_out).astype(x.dtype)


def held_experts_apply_masked(
    x, selected, gates, w_in, w_out, first_expert_held, act=EXPERT_ACTS[0]
):
    """:func:`held_experts_apply`'s result by shapes alone: EVERY held
    expert over every token, its gate zero where the token did not
    select it. No sort, no gather and no grouped product: three plain
    matrix products over ``G * f`` hidden units (the gate multiplies
    the hidden units of its expert in front of ``W_2``, so the sum over
    experts is the last product's own accumulation). What it costs is
    ``G`` experts a token whatever was routed, against
    ``num_experts_per_tok * G / num_experts`` routed here in
    expectation: it is for a share whose time must not follow the
    routing (a router without balancing sends every token of a batch
    to the same few experts, and how many of those are held is luck),
    and affordable only where ``G`` is a few times
    ``num_experts_per_tok``. The backward pass keeps the first two
    products' results and recomputes what is elementwise behind them
    (the float32 hidden units are four times those two)."""
    held = w_in.shape[0]
    local = selected - first_expert_held
    gate = jnp.sum(
        jnp.where(
            local[..., None] == jnp.arange(held, dtype=local.dtype),
            gates[..., None].astype(jnp.float32),
            0.0,
        ),
        axis=1,
    )  # (T, G): an expert is selected at most once a token
    return jax.checkpoint(
        functools.partial(_masked_share, act=act),
        policy=jax.checkpoint_policies.save_only_these_names("held_up"),
    )(x, gate, w_in, w_out)


def window_routing_counters(before, after, first_expert_held, experts_held):
    """What a window's ``train_window`` event says of the routing, from
    two host copies of the model's :data:`MOE_STATE_COLLECTION` (the
    start and the end of the window; ``before`` None at the first):

    ``moe_rows_here``: assignments to held experts, summed over the
    window's steps and the expert layers; ``moe_rows_routed``: all
    assignments (steps x layers x T x k); ``moe_rows_max_expert`` and
    ``moe_rows_mean_expert``: the most and the mean over the (layer,
    held expert) pairs, each one group of a grouped product, of the
    window's totals. Of a routing that keeps a selection bias:
    ``expert_bias_abs_max`` at the window's end. Of a model whose
    attention selects its keys (``sel_pairs_kept`` in its state):
    ``sel_pairs_kept`` and ``sel_pairs_causal``, the (query, key) pairs
    the selections kept and the causal pairs they chose among, over the
    window's steps, sequences and selecting layers (not times the
    heads)."""
    import numpy as np

    def leaves(tree, name):
        return [
            np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
            if path[-1].key == name
        ]

    now = np.stack(leaves(after, "assignments"))  # (layers, E) int32
    if before is None:
        made = now.astype(np.int64)
    else:
        # the counters wrap; a window's difference does not
        made = (now - np.stack(leaves(before, "assignments"))).astype(
            np.int64
        )
    here = made[:, first_expert_held : first_expert_held + experts_held]
    counters = {
        "moe_rows_here": int(here.sum()),
        "moe_rows_routed": int(made.sum()),
        "moe_rows_max_expert": int(here.max()),
        "moe_rows_mean_expert": float(here.mean()),
    }
    biases = leaves(after, "expert_bias")
    if biases:
        counters["expert_bias_abs_max"] = float(
            max(np.abs(b).max() for b in biases)
        )
    for name in ("sel_pairs_kept", "sel_pairs_causal"):
        now = leaves(after, name)
        if now:
            pairs = np.stack(now)  # (selecting layers,) int32, wrapping
            if before is not None:
                pairs = pairs - np.stack(leaves(before, name))
            counters[name] = int(pairs.astype(np.int64).sum())
    return counters
