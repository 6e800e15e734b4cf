"""Sparse-aware optimizer wrapper for PS-mode training.

Parity: reference master/optimizer_wrapper.py — for embedding-layer
gradients it looks up the touched rows *and their optimizer-slot rows*
from the store, applies the optimizer to just those rows, and writes rows +
slots back; duplicate ids in one gradient are combined first; slot tables
are named ``"{layer}-{slot}"``.

TPU-native improvement over the reference's per-optimizer slot registry
(SGD/Adam/Adamax/Nadam/Adadelta/Adagrad/Ftrl/RMSprop hand-tables,
optimizer_wrapper.py:159-192): optax optimizer *state is introspected
structurally*. Any state leaf shaped like the parameter rows is a slot
table (keyed by its pytree path); anything else (step counters etc.) is
kept whole per layer. Fresh rows get slot values from ``opt.init`` on a
zero row, so accumulator-style initializers (adagrad/adadelta) are exact.
This works for every optax transformation, present or future, with no
registry to maintain.

Two apply planes share that introspection (docs/ps_device.md), and —
deliberately — ONE set of compiled step functions:

- **Host store** (``Parameters()``): params stay numpy dicts and the
  embedding rows stay dict-of-rows tables, but the optimizer math runs
  through the SAME jitted ``opt.update + apply_updates`` steps as the
  device plane. Every apply therefore pays the host<->device boundary:
  params and gathered rows cross H2D on the way in and D2H on the way
  back to numpy storage.
- **Device store** (``Parameters(device=True)``): the store itself is
  device-resident, so the same jitted steps run with NO boundary
  crossings — dense opt state is donated (it never escapes the apply
  lock; params are not donated, async ``pull_variable`` reads them
  lock-free), sparse rows gather/scatter straight against the arena
  tables (ps/device_store.py), and incoming gradient frames enter
  through ``device_from_host_view`` — zero-copy dlpack when the wire
  view is writable (the shm opt-in), one fused ``device_put``
  otherwise. Every device apply blocks on its outputs before
  returning, because the wire buffer may be a shm slot the reply
  overwrites the moment the handler returns.

Sharing the compiled steps is what makes the parity guarantee bitwise
rather than approximate: XLA contracts ``a*b + c`` chains into FMAs
and factors multiply-add trees inside one jit, so a jitted update is
NOT bitwise-equal to the same formula run primitive-by-primitive (~1
ulp on adam, verified on the CPU backend — and no
``xla_allow_excess_precision`` / fast-math flag disables it). With one
executable on both planes, host-vs-device divergence can only come
from storage handling, which is exactly what the parity suite
(tests/test_ps_device_parity.py) is meant to catch. What separates
the planes is then the storage boundary alone: deleted H2D/D2H
boundary crossings, zero-copy gradient ingest, donation, and no
per-row Python dict walks (what that is worth is not measured on the
chip: the PS plane has no cell).

Sparse jit shapes are padded to the next power of two (padded lanes
carry zero gradients against zero rows and are dropped at writeback),
so recompiles are bounded by ``log2`` of the batch-size range. The
duplicate-free combine branch mirrors
``common.tensor.combine_indexed_slices`` exactly — a pure reorder, no
additions — so a worker-side pre-combined push and a PS-side combine
land identical rows (the ``-0.0 + 0.0`` normalization a blanket
segment-sum would introduce is the kind of drift the parity suite
exists to catch).
"""

import threading
from functools import partial

import jax
import numpy as np
import optax

from elasticdl_tpu.common.tensor import (
    _join_path as _path_str,
    device_from_host_view,
)
from elasticdl_tpu.ps.device_store import next_pow2
from elasticdl_tpu.ps.embedding_table import get_slot_table_name


@partial(jax.jit, static_argnums=2)
def _reorder_pad(vals, order, k_pad):
    """Duplicate-free combine, device side: reorder rows into unique-id
    order and zero-fill up to ``k_pad`` — bitwise the host branch
    (``values[argsort]``, no additions)."""
    import jax.numpy as jnp

    rows = jnp.take(vals, order, axis=0)
    return (
        jnp.zeros((k_pad, vals.shape[1]), vals.dtype).at[: vals.shape[0]]
        .set(rows)
    )


@partial(jax.jit, static_argnums=2)
def _segment_pad(vals, inverse, k_pad):
    """Duplicate combine, device side: segment-sum rows of equal ids
    into ``k_pad`` lanes (lanes past the unique count stay zero)."""
    return jax.ops.segment_sum(vals, inverse, num_segments=k_pad)


def _identity(a):
    return a


def _pad_host_rows(rows, k_pad):
    """Zero-pad a host (k, dim) row block to ``k_pad`` lanes (the host
    plane's counterpart of the arena gather's padded output)."""
    rows = np.asarray(rows, dtype=np.float32)
    if rows.shape[0] == k_pad:
        return rows
    padded = np.zeros((k_pad, rows.shape[1]), dtype=np.float32)
    padded[: rows.shape[0]] = rows
    return padded


class OptimizerWrapper:
    def __init__(self, optimizer, parameters=None):
        """``optimizer``: optax GradientTransformation. ``parameters``:
        a ps.Parameters store holding the embedding tables (and the dense
        params in PS mode); its ``device`` flag selects the apply plane.
        Thread safety is uniform: every apply holds the wrapper lock
        (async mode differs only upstream, in when applies happen —
        reference uses thread-local temp vars instead,
        optimizer_wrapper.py:154-156)."""
        self._opt = optimizer
        self._params = parameters
        self._device = bool(getattr(parameters, "device", False))
        self._lock = threading.Lock()
        # every mutation of the store (dense AND sparse applies) runs
        # under this lock; the shard snapshotter captures under it too,
        # so a snapshot is always a between-applies cut (docs/
        # ps_recovery.md), never a torn mid-apply mix
        self.apply_lock = self._lock
        # per embedding layer: pytree paths of row-shaped state leaves and
        # the non-row residue of the optimizer state
        self._non_row_state = {}
        self._dense_opt_state = None
        self._template_cache = {}  # dim -> (state, treedef, row_paths)
        # params absent from a push get the SAME zero gradient every
        # time (stateful optimizers still decay their moments) — built
        # once per param, not np.zeros_like'd per apply
        self._zero_grads = {}
        if optimizer is not None:
            # BOTH planes run these (module docstring: shared
            # executables are the bitwise-parity mechanism). Dense
            # step: one fused update. Only the opt state is donated —
            # it never escapes the apply lock; params DO escape (async
            # pull_variable reads them lock-free in device mode), so
            # donating them would invalidate a reader's reference.
            def _dense_step(params, grads, state):
                updates, new_state = self._opt.update(grads, state, params)
                return optax.apply_updates(params, updates), new_state

            self._dense_step_jit = jax.jit(_dense_step, donate_argnums=2)

            # sparse step over gathered (k_pad, dim) rows; ``rows`` is
            # a fresh gather buffer (or a host-mode device_put copy)
            # referenced nowhere else, so it is donated. State leaves
            # are NOT: non-row leaves are retained across applies in
            # _non_row_state.
            def _sparse_step(grad_rows, rows, state):
                updates, new_state = self._opt.update(grad_rows, state, rows)
                return optax.apply_updates(rows, updates), new_state

            self._sparse_step_jit = jax.jit(_sparse_step, donate_argnums=1)

    # -- dense path ---------------------------------------------------------

    def _zero_grad_for(self, name, p):
        z = self._zero_grads.get(name)
        if z is None or z.shape != p.shape or z.dtype != p.dtype:
            if self._device:
                import jax.numpy as jnp

                z = jnp.zeros(p.shape, p.dtype)
            else:
                z = np.zeros_like(p)
            self._zero_grads[name] = z
        return z

    def apply_dense_gradients(self, grads):
        """Full optax update over the store's dense params — one shared
        jitted step; the planes differ only at the storage boundary."""
        store = self._params
        with self._lock:
            params = (
                dict(store.non_embedding_params)
                if self._device
                else store.non_embedding_params
            )
            full = {}
            for name, p in params.items():
                g = grads.get(name)
                if g is None:
                    full[name] = self._zero_grad_for(name, p)
                elif self._device:
                    if not isinstance(g, np.ndarray):
                        g = np.asarray(g, dtype=np.float32)
                    full[name] = device_from_host_view(g)
                else:
                    full[name] = np.asarray(g, dtype=np.float32)
            if self._dense_opt_state is None:
                self._dense_opt_state = self._opt.init(params)
            new_params, self._dense_opt_state = self._dense_step_jit(
                params, full, self._dense_opt_state
            )
            if self._device:
                store.non_embedding_params = new_params
                # fence before the wire buffer this apply may alias
                # (zero-copy dlpack import) is recycled by the reply
                jax.block_until_ready(new_params)
            else:
                # D2H back to numpy storage: np.array (not asarray)
                # because a CPU device_get may hand back a read-only
                # view of the jit output buffer, and the host store's
                # contract is plain writable ndarrays
                store.non_embedding_params = {
                    k: np.array(v, dtype=np.float32)
                    for k, v in new_params.items()
                }

    # -- sparse path --------------------------------------------------------

    @staticmethod
    def combine_duplicate_ids(indices, values):
        """Sum rows of duplicate ids (reference merges IndexedSlices).

        Delegates to the shared sparse-comms row-combine so the PS-side
        apply and the worker-side pre-push combine are the same code."""
        from elasticdl_tpu.common.tensor import combine_indexed_slices

        return combine_indexed_slices(indices, values)

    def _row_state_template(self, dim):
        """opt.init on a single zero row: slot layout + fresh-row values.

        Memoized per dim (it is structural, not data-dependent) so the
        async hot path pays no repeated opt.init/tree traversal.
        """
        cached = self._template_cache.get(dim)
        if cached is not None:
            return cached
        template_row = np.zeros((1, dim), dtype=np.float32)
        state = self._opt.init(template_row)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
        row_paths = {}
        for path, leaf in leaves:
            if hasattr(leaf, "shape") and tuple(np.shape(leaf)) == (1, dim):
                row_paths[_path_str(path)] = np.asarray(leaf)[0]
        self._template_cache[dim] = (state, treedef, row_paths)
        return self._template_cache[dim]

    def _ensure_slot_tables(self, store, layer_name, row_slot_init):
        """Slot tables for ``layer_name`` (created lazily with the
        exact fresh-row constants from the opt.init template)."""
        tables = {}
        for slot_path, fresh_row in row_slot_init.items():
            slot_table_name = get_slot_table_name(layer_name, slot_path)
            if slot_table_name not in store.embedding_params:
                store.create_slot_params(
                    [slot_path], {slot_path: float(fresh_row.flat[0])}
                )
            tables[slot_path] = store.embedding_params[slot_table_name]
        return tables

    def apply_sparse_gradients(self, layer_name, indices, values):
        """Apply one embedding layer's sparse gradient to its rows.

        One shared compiled pipeline on both planes — host-side
        unique/inverse (so unique-id ORDER matches the worker-side
        combine), jitted combine into ``k_pad`` padded lanes, jitted
        ``opt.update + apply_updates`` over the gathered rows — with
        only the row storage differing: arena gather/scatter on a
        device shard, per-row dict get/set (plus the H2D/D2H crossing
        that implies) on a host shard."""
        store = self._params
        table = store.embedding_params[layer_name]
        dim = table.dim
        ids = np.asarray(indices, dtype=np.int64).reshape(-1)
        if not isinstance(values, np.ndarray) or values.dtype != np.float32:
            values = np.asarray(values, dtype=np.float32)
        unique, inverse = np.unique(ids, return_inverse=True)
        k = int(unique.size)
        k_pad = next_pow2(k)

        with self._lock:
            # device shards import the wire view zero-copy; host shards
            # hand numpy straight to jit (its device_put IS the H2D
            # boundary the host plane pays by construction)
            ingest = device_from_host_view if self._device else _identity
            vals_dev = ingest(values)
            if k == ids.size:
                # duplicate-free: mirror the worker combine's reorder
                # branch exactly (no additions -> no -0.0 drift)
                order = np.asarray(
                    np.argsort(ids, kind="stable"), dtype=np.int32
                )
                grad_rows = _reorder_pad(vals_dev, ingest(order), k_pad)
            else:
                grad_rows = _segment_pad(
                    vals_dev,
                    ingest(np.asarray(inverse, dtype=np.int32)),
                    k_pad,
                )

            state_template, treedef, row_slot_init = (
                self._row_state_template(dim)
            )
            slot_tables = self._ensure_slot_tables(
                store, layer_name, row_slot_init
            )
            if self._device:
                slots = table.ensure_rows(unique)
                rows = table.gather_slots(slots, k_pad)
                slot_slots = {
                    key: t.ensure_rows(unique)
                    for key, t in slot_tables.items()
                }
                slot_rows = {
                    key: t.gather_slots(slot_slots[key], k_pad)
                    for key, t in slot_tables.items()
                }
            else:
                rows = _pad_host_rows(table.get(unique), k_pad)
                slot_rows = {
                    key: _pad_host_rows(t.get(unique), k_pad)
                    for key, t in slot_tables.items()
                }
            non_row = self._non_row_state.setdefault(layer_name, {})

            # rebuild the optimizer state pytree for these k_pad lanes
            leaves_with_path, _ = jax.tree_util.tree_flatten_with_path(
                state_template
            )
            rebuilt = []
            for path, leaf in leaves_with_path:
                key = _path_str(path)
                if key in slot_rows:
                    rebuilt.append(slot_rows[key])
                elif key in non_row:
                    rebuilt.append(non_row[key])
                else:
                    rebuilt.append(leaf)
            state = jax.tree_util.tree_unflatten(treedef, rebuilt)

            new_rows, new_state = self._sparse_step_jit(
                grad_rows, rows, state
            )
            new_leaves, _ = jax.tree_util.tree_flatten_with_path(new_state)

            if self._device:
                table.scatter_slots(slots, k_pad, new_rows)
                for path, leaf in new_leaves:
                    key = _path_str(path)
                    if key in slot_rows:
                        slot_tables[key].scatter_slots(
                            slot_slots[key], k_pad, leaf
                        )
                    else:
                        non_row[key] = leaf
                # fence: the wire views this apply imported zero-copy
                # must be fully consumed before the reply recycles
                # their slot
                table.sync()
                for t in slot_tables.values():
                    t.sync()
            else:
                # D2H writeback: np.array copies out of the jit output
                # buffers (device_get views may be read-only, and the
                # dict-of-rows store keeps plain writable ndarrays)
                table.set(unique, np.array(new_rows)[:k])
                for path, leaf in new_leaves:
                    key = _path_str(path)
                    if key in slot_rows:
                        slot_tables[key].set(unique, np.array(leaf)[:k])
                    else:
                        non_row[key] = leaf

        # post-apply boundary, OUTSIDE the apply lock: a tiered table
        # (docs/tiered_store.md) wakes its background demoter here —
        # an Event.set, never IO, so the apply hot path stays clean
        for t in (table, *slot_tables.values()):
            pressure = getattr(t, "signal_pressure", None)
            if pressure is not None:
                pressure()

    def apply_gradients(self, dense_grads=None, embedding_grads=None):
        """Combined apply: {name: ndarray} dense + {layer: Tensor} sparse."""
        if dense_grads:
            self.apply_dense_gradients(dense_grads)
        for layer_name, tensor in (embedding_grads or {}).items():
            self.apply_sparse_gradients(
                layer_name, tensor.indices, tensor.values
            )
