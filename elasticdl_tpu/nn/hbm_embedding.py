"""Vocab-sharded embedding table resident in device HBM.

This is the TPU-native replacement for the reference's sharded-PS/Redis
embedding plane (BASELINE.json north star: "row-partitioned embedding
tables live in pod HBM with ICI collectives for id lookup/update"):

- the table is a *regular trainable parameter* sharded on its vocab axis
  across a mesh axis (``P(axis, None)``); optimizer state co-shards
  automatically under jit, mirroring the PS slot-table co-location
  (reference ps/parameters.py:145-159) with zero extra machinery,
- lookup runs under shard_map: every device gathers the rows it owns for
  the (replicated) id batch and a ``psum`` over ICI assembles the full
  activation — communication is O(B x L x D), independent of vocab size,
- gradients flow through the shard_map transpose: each device receives
  exactly its shard's row gradients, so the update never materializes the
  dense (V, D) gradient anywhere.

The host-PS mode (nn/embedding.py + ps/) remains for CPU-RAM-sized tables
and async training; both share checkpoint naming via the params pytree.
Both planes implement the comm-plane interface (nn/comm_plane.py,
docs/embedding_planes.md) — this one as the ``in_graph`` plane, whose
"pull" is the a2a collective itself and whose dedup planner is the
jit-side :func:`~elasticdl_tpu.nn.sparse_comms.padded_unique` twin of
the host planner — so one model may mix planes per table
(``comm_plane.make_embedding``), e.g. a hybrid deepfm with its huge
feature table on the PS fleet and this layer's small tables living as
ordinary dense-world parameters.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.nn.sparse_comms import padded_unique


METRICS_COLLECTION = "metrics"
OVERFLOW_METRIC = "a2a_overflow"


def a2a_overflow_total(state):
    """Total overflowed-id count across every HbmEmbedding in ``state``.

    Sums the ``metrics/*/a2a_overflow`` counters the layers accumulate
    (see :class:`HbmEmbedding`); returns None when the model has no such
    counters. Accepts device or host pytrees — callers fetch per leaf,
    so the cost is a scalar transfer per embedding layer.
    """
    if not isinstance(state, dict) or METRICS_COLLECTION not in state:
        return None
    total = 0
    found = False

    def walk(node):
        nonlocal total, found
        if hasattr(node, "items"):
            for k, v in node.items():
                if k == OVERFLOW_METRIC:
                    found = True
                    # replicated counter: every shard holds the global
                    # value, so read this process's replica rather than
                    # summing copies (device_get of a non-addressable
                    # multi-host array would fail)
                    if hasattr(v, "addressable_shards"):
                        arr = np.asarray(v.addressable_shards[0].data)
                    else:
                        arr = np.asarray(jax.device_get(v))
                    total += int(arr.reshape(-1)[0])
                else:
                    walk(v)

    walk(state[METRICS_COLLECTION])
    return total if found else None


def psum_lookup_collective(table_local, ids, axis):
    """Gather+psum body for one device; ``axis`` must already be bound
    (call inside shard_map / an outer collective step).

    ``table_local``: this device's (V/n, D) table shard; ``ids``: this
    device's id slice, any shape. Returns ids.shape + (D,)."""
    me = jax.lax.axis_index(axis)
    rows_per = table_local.shape[0]
    local = ids.astype(jnp.int32) - me * rows_per
    mask = (local >= 0) & (local < rows_per)
    safe = jnp.clip(local, 0, rows_per - 1)
    rows = jnp.take(table_local, safe, axis=0)
    rows = jnp.where(mask[..., None], rows, 0)
    return jax.lax.psum(rows, axis)


def _check_divisible(table, mesh, axis):
    """Uneven vocab shards would fail deep inside shard_map tracing with
    an opaque message; fail here with an actionable one instead. On the
    elastic plane the same check runs at establish() against the NEW
    world size (parallel/elastic.py), where it matters most: a re-form
    to a non-divisor size must error clearly, not crash-loop."""
    n = mesh.shape[axis]
    if table.shape[0] % n:
        raise ValueError(
            "embedding vocab_size %d is not divisible by mesh axis "
            "%r size %d; pad the table rows to the next multiple "
            "(e.g. vocab_size=%d) so every device holds an equal shard"
            % (table.shape[0], axis, n, -(-table.shape[0] // n) * n)
        )


def sharded_lookup(table, ids, mesh, axis):
    """Gather rows of a vocab-sharded table; differentiable.

    ``table``: global (V, D) sharded P(axis, None); ``ids``: int array of
    any shape. Returns ids.shape + (D,).

    When the mesh also has a ``data`` axis distinct from the table axis,
    the id batch (and the output) shard over it, so each dp replica only
    gathers/psums its own batch slice and the psum rides the table axis
    alone. On a mesh where the table axis IS the batch axis (pure-dp), ids
    must replicate across it — the collective then carries the global
    batch, which is the unavoidable cost of vocab-sharding over the same
    axis as the batch; shard tables on ``model`` to avoid it.
    """

    _check_divisible(table, mesh, axis)

    def _lookup(table_local, ids):
        return psum_lookup_collective(table_local, ids, axis)

    axes = set(mesh.axis_names)
    batch_axis = "data" if ("data" in axes and axis != "data") else None
    ids_spec = P(*([batch_axis] + [None] * (ids.ndim - 1)))
    out_spec = P(*([batch_axis] + [None] * ids.ndim))
    return jax.shard_map(
        _lookup,
        mesh=mesh,
        in_specs=(P(axis, None), ids_spec),
        out_specs=out_spec,
        check_vma=False,
    )(table, ids)


def a2a_lookup_collective(
    table_local, ids_flat, axis, capacity=None, return_overflow=False
):
    """all_to_all routing body for one device; ``axis`` must already be
    bound (call inside shard_map / an outer collective step).

    ``table_local``: this device's (V/n, D) shard; ``ids_flat``: this
    device's flat id slice. Negative ids are SKIP slots (the
    :func:`~elasticdl_tpu.nn.sparse_comms.padded_unique` padding): they
    consume no per-peer capacity, read zero rows, and are never counted
    as overflow. Returns (ids, D) — or, with ``return_overflow=True``,
    ``(rows, n_overflowed)`` where ``n_overflowed`` is this device's
    LOCAL count of live ids that didn't fit their per-peer capacity
    bucket and therefore read zero rows. The caller owns aggregation,
    because only it knows how ids were spread: psum over ``axis`` when
    each device routed a distinct slice (the elastic plane), no-op when
    the ids were replicated (each device already counted the whole
    batch). See :func:`all_to_all_lookup` for the routing/capacity
    semantics."""
    n = jax.lax.psum(1, axis)
    me = jax.lax.axis_index(axis)
    rows_per = table_local.shape[0]
    mm = ids_flat.shape[0]  # ids local to this batch shard
    cap = mm if capacity is None else min(capacity, mm)

    live = ids_flat >= 0
    owner = jnp.clip(ids_flat // rows_per, 0, n - 1)
    # skip slots bucket past every real peer (owner n) so they sort to
    # the end and cannot displace live ids from their capacity windows
    owner = jnp.where(live, owner, n)
    order = jnp.argsort(owner, stable=True)
    sorted_owner = owner[order]
    sorted_ids = ids_flat[order]
    sorted_live = live[order]
    counts = jnp.bincount(owner, length=n + 1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(mm) - starts[sorted_owner]
    ok = (pos < cap) & sorted_live
    # overflow and skip entries write to a trash column (cap) so they
    # can't clobber a live slot; the buffer is sliced back to cap below
    pos = jnp.where(ok, pos, cap)
    write_owner = jnp.minimum(sorted_owner, n - 1)

    # (n, cap) send buffers: row p holds the ids this device asks
    # peer p for; invalid slots carry id -1
    send_ids = jnp.full((n, cap + 1), -1, jnp.int32)
    send_ids = send_ids.at[write_owner, pos].set(sorted_ids)[:, :cap]
    pos = jnp.where(ok, pos, 0)
    recv_ids = jax.lax.all_to_all(
        send_ids, axis, split_axis=0, concat_axis=0, tiled=True
    )  # row p = ids peer p asked me for

    local = recv_ids - me * rows_per
    valid = (local >= 0) & (local < rows_per)
    rows = jnp.take(
        table_local, jnp.clip(local, 0, rows_per - 1), axis=0
    )
    rows = jnp.where(valid[..., None], rows, 0)
    back = jax.lax.all_to_all(
        rows, axis, split_axis=0, concat_axis=0, tiled=True
    )  # row p = rows for the ids I sent to peer p

    out_sorted = back[write_owner, pos]
    out_sorted = jnp.where(ok[..., None], out_sorted, 0)
    inv = jnp.argsort(order, stable=True)
    out = out_sorted[inv]
    if not return_overflow:
        return out
    n_over = jnp.sum(sorted_live & ~ok).astype(jnp.int32)
    return out, n_over


def a2a_dedup_lookup_collective(
    table_local, ids_flat, axis, capacity=None, return_overflow=False
):
    """Dedup-before-comm variant of :func:`a2a_lookup_collective`.

    Batch-wide unique ids (static-shape :func:`padded_unique`) are the
    only thing routed over the ``axis`` ring; per-occurrence rows are
    restored by a LOCAL gather through the inverse map. The gather's
    transpose is a scatter-add over the inverse map, so the backward
    all_to_all also carries exactly one combined gradient row per
    unique id — with k unique ids in an m-id batch both wire directions
    shrink by m/k. ``capacity`` therefore bounds UNIQUE ids per peer
    here; a duplicate-heavy batch needs proportionally less of it.
    Overflow counts unique ids dropped (each dropped unique id zeroes
    every occurrence that maps to it)."""
    uids, inv, _ = padded_unique(ids_flat)
    out = a2a_lookup_collective(
        table_local,
        uids,
        axis,
        capacity=capacity,
        return_overflow=return_overflow,
    )
    if not return_overflow:
        return jnp.take(out, inv, axis=0)
    rows_u, n_over = out
    return jnp.take(rows_u, inv, axis=0), n_over


def all_to_all_lookup(
    table,
    ids,
    mesh,
    axis,
    capacity=None,
    return_overflow=False,
    dedup=False,
):
    """Row exchange by explicit ``all_to_all`` routing (the BASELINE.json
    north-star formulation); differentiable.

    Each device buckets its ids by owning shard (range partition:
    ``owner = id // rows_per_shard``), ships the buckets over the ``axis``
    ring with ``lax.all_to_all``, gathers locally on the owner, and ships
    the rows back. On a mesh with a ``data`` axis distinct from the table
    axis, each dp replica routes only its own id slice, so per-device
    communication is O(capacity x D) — the rows actually requested —
    versus the gather+psum form's O(ids x D) zero-padded reduction, and
    each device's take() only runs over its own requests. On a
    single-axis mesh (table axis == batch axis) the ids replicate and
    this form loses its advantage — use the psum form there
    (``HbmEmbedding(method="auto")`` picks per mesh).

    ``capacity`` bounds the per-peer bucket (static shape). None means the
    exact worst case (every id owned by one shard) — always correct, the
    right choice for tests and modest batches. Production lookups on
    hashed/unique ids set ``capacity ~= 2 x ids/n_shards``; overflowing
    ids fall back to zero rows (same contract as a dropped row in the
    reference's best-effort Redis plane) — size capacity generously. A
    mis-sized capacity is NOT silent: pass ``return_overflow=True`` to
    get ``(rows, n_overflowed)`` back (a replicated global count), which
    :class:`HbmEmbedding` accumulates into its ``metrics/a2a_overflow``
    state counter so workers can alarm on it.

    Backward: the transpose of ``all_to_all`` is ``all_to_all`` and the
    transpose of the owner-side take is a scatter-add into that shard
    alone, so the row gradients route straight back to their owners and
    the dense (V, D) gradient never exists — each device only ever holds
    its own (V/n, D) gradient shard.

    ``dedup=True`` switches to the dedup-before-comm fast path
    (:func:`a2a_dedup_lookup_collective`): each device routes only its
    batch-wide UNIQUE ids and restores per-occurrence rows by a local
    gather through the inverse map, so both wire directions carry one
    row per unique id and ``capacity`` bounds unique ids per peer —
    on duplicate-heavy batches the same correctness holds at a
    fraction of the capacity (and therefore of the ICI traffic).
    """
    _check_divisible(table, mesh, axis)
    orig_shape = ids.shape
    flat = jnp.reshape(jnp.asarray(ids).astype(jnp.int32), (-1,))

    axes = set(mesh.axis_names)
    batch_axis = "data" if ("data" in axes and axis != "data") else None
    body = a2a_dedup_lookup_collective if dedup else a2a_lookup_collective

    def _lookup(table_local, ids_flat):
        out = body(
            table_local,
            ids_flat,
            axis,
            capacity=capacity,
            return_overflow=return_overflow,
        )
        if not return_overflow:
            return out
        rows, n_over = out
        # the local count is replicated along the table axis (every
        # member of that axis routed the same id slice); total across
        # the dp replicas, whose slices are distinct
        if batch_axis is not None:
            n_over = jax.lax.psum(n_over, batch_axis)
        return rows, n_over

    out_spec = P(batch_axis, None)
    out = jax.shard_map(
        _lookup,
        mesh=mesh,
        in_specs=(P(axis, None), P(batch_axis)),
        out_specs=(out_spec, P()) if return_overflow else out_spec,
        check_vma=False,
    )(table, flat)
    if return_overflow:
        rows, n_over = out
        return jnp.reshape(rows, orig_shape + (table.shape[1],)), n_over
    return jnp.reshape(out, orig_shape + (table.shape[1],))


class HbmEmbedding(nn.Module):
    """Drop-in embedding whose table shards over ``mesh[axis]`` HBM.

    ``method``: "auto" (default) picks all_to_all row routing when the
    mesh gives the batch its own axis (where a2a's O(capacity x D) per
    device wins — the north-star formulation) and gather+psum on a
    single-axis mesh (where a2a would replicate the ids and lose);
    "a2a"/"psum" force a form. ``capacity`` tunes the a2a per-peer
    bucket (see :func:`all_to_all_lookup`).

    ``dedup`` (default True) routes only batch-wide unique ids over the
    wire and restores per-occurrence rows (and combines duplicate-row
    gradients) through a local inverse-map gather — the sparse-comms
    fast path (docs/sparse_fast_path.md). With dedup on, ``capacity``
    bounds UNIQUE ids per peer, so power-law batches need far less of
    it. Set ``dedup=False`` to meter raw per-occurrence routing (the
    pre-dedup wire behavior).

    ``collective=True``: for use INSIDE an outer shard_map (the
    multi-process elastic step, parallel/elastic.py) where nesting
    another shard_map is impossible. ``axis`` must be bound by the
    caller; the apply-time table is this device's local shard and the
    ids are the device's batch slice, so the lookup calls the raw
    collective bodies directly. a2a is the natural form here — each
    device routes exactly its local ids even when the table axis IS the
    batch axis. Init still traces densely (no axis bound at init).

    Capacity overflow is metered, not silent: every a2a lookup adds its
    global overflowed-id count to a ``metrics/a2a_overflow`` int32 state
    counter (monotone across steps; replicated, so it survives the
    elastic plane's state averaging unchanged). Read it with
    :func:`a2a_overflow_total`; a nonzero value means ids trained on
    zero rows and ``capacity`` must grow. The counter is only written
    when the ``metrics`` collection is mutable (training steps), so
    frozen-state eval forwards are unaffected.
    """

    vocab_size: int
    features: int
    mesh: object = None
    axis: str = "data"
    mask_zero: bool = False
    method: str = "auto"
    capacity: int = None
    collective: bool = False
    dedup: bool = True

    @nn.compact
    def __call__(self, ids, training=False):
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", out_axis=0
        )
        if self.collective:
            # self.variable, not self.param: flax shape-validates params
            # against their initializer at apply time, but in collective
            # mode the apply-time value is this device's (V/n, D) LOCAL
            # shard of the declared (V, D) table
            table = self.variable(
                "params",
                "table",
                lambda: init(
                    self.make_rng("params"),
                    (self.vocab_size, self.features),
                ),
            ).value
        else:
            table = self.param(
                "table", init, (self.vocab_size, self.features)
            )
        # declared whenever the caller threads state (init always; the
        # framework step builders pass every collection through), so the
        # state STRUCTURE is identical across init and apply. A bare
        # apply({"params": ...}) with no metrics collection simply goes
        # unmetered instead of erroring.
        overflow = None
        if (
            self.is_initializing()
            or self.has_variable(METRICS_COLLECTION, OVERFLOW_METRIC)
            or self.is_mutable_collection(METRICS_COLLECTION)
        ):
            overflow = self.variable(
                METRICS_COLLECTION,
                OVERFLOW_METRIC,
                lambda: jnp.zeros((), jnp.int32),
            )

        def meter(n_over):
            # init's tracing forward is not a training step: the counter
            # must start at zero
            if (
                overflow is not None
                and not self.is_initializing()
                and self.is_mutable_collection(METRICS_COLLECTION)
            ):
                overflow.value = overflow.value + n_over

        ids = jnp.asarray(ids).astype(jnp.int32)
        if self.collective and not self.is_initializing():
            if self.method == "psum":
                # each device's ids differ inside the outer shard_map, so
                # a psum of per-device lookups would sum MISALIGNED rows
                # — silently wrong activations, not a degraded mode
                raise ValueError(
                    "HbmEmbedding(collective=True) only supports a2a "
                    "routing; psum needs replicated ids, which the "
                    "elastic plane's sharded batch cannot provide"
                )
            flat = jnp.reshape(ids, (-1,))
            body = (
                a2a_dedup_lookup_collective
                if self.dedup
                else a2a_lookup_collective
            )
            out, n_over = body(
                table,
                flat,
                self.axis,
                capacity=self.capacity,
                return_overflow=True,
            )
            # each device routed a distinct batch slice here; psum makes
            # the counter the replicated global total
            meter(jax.lax.psum(n_over, self.axis))
            emb = jnp.reshape(out, ids.shape + (table.shape[1],))
        elif self.mesh is None:
            emb = jnp.take(table, ids, axis=0)
        else:
            table = jax.lax.with_sharding_constraint(
                table, NamedSharding(self.mesh, P(self.axis, None))
            )
            method = self.method
            if method == "auto":
                has_batch_axis = (
                    "data" in self.mesh.axis_names and self.axis != "data"
                )
                method = "a2a" if has_batch_axis else "psum"
            if method == "a2a":
                emb, n_over = all_to_all_lookup(
                    table,
                    ids,
                    self.mesh,
                    self.axis,
                    capacity=self.capacity,
                    return_overflow=True,
                    dedup=self.dedup,
                )
                meter(n_over)
            else:
                emb = sharded_lookup(table, ids, self.mesh, self.axis)
        if self.mask_zero:
            emb = emb * (ids != 0).astype(emb.dtype)[..., None]
        return emb


def table_sharding(mesh, axis="data"):
    """NamedSharding to place an HbmEmbedding table parameter."""
    return NamedSharding(mesh, P(axis, None))
