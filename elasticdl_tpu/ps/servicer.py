"""Parameter-server RPC servicer.

Parity: reference ps/servicer.py — five RPCs over the PS store:
``pull_variable`` (all dense params + init status), ``pull_embedding_vector``
(lazy-init row lookup), ``push_model`` (first-write-wins init),
``push_embedding_info``, and ``push_gradient`` (async: apply immediately,
version++; sync: reject stale versions, accumulate until ``grads_to_wait``,
average dense / concat sparse, apply, version++).

Methods take/return plain dicts (the rpc.core message model) so the same
object serves real gRPC or in-process tests unchanged.
"""

import contextlib
import threading

import numpy as np

_NULL_LOCK = contextlib.nullcontext()

from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.common.tensor import Tensor
from elasticdl_tpu.master.learning_rate_modulator import (
    add_lr_modulation_to_optimizer,
)
from elasticdl_tpu.ps.optimizer_wrapper import OptimizerWrapper
from elasticdl_tpu.ps.parameters import EmbeddingTableInfo
from elasticdl_tpu.utils import profiling


class PserverServicer:
    def __init__(
        self,
        parameters,
        grads_to_wait,
        optimizer,
        lr_staleness_modulation=False,
        use_async=False,
        wire_dtype="",
        snapshotter=None,
        shard_epoch=0,
        restored_version=None,
    ):
        self._parameters = parameters
        self._grads_to_wait = grads_to_wait
        self._wire_dtype = wire_dtype
        self._lock = threading.Lock()
        self._use_async = use_async
        self._version_lock = threading.Lock()
        self._lr_modulation = None
        if use_async and lr_staleness_modulation and optimizer is not None:
            optimizer, self._lr_modulation = add_lr_modulation_to_optimizer(
                optimizer
            )
        self._optimizer = OptimizerWrapper(optimizer, parameters)
        self._dense_sum = {}
        self._indexed_sum = {}
        self._grad_n = 0
        # durability plane (docs/ps_recovery.md): the per-shard cadence
        # snapshotter (None = durability off), this incarnation's boot
        # id, and the version the boot restored (-1 = booted fresh).
        # Every reply carries shard_epoch so a client can detect the
        # relaunch and run the reconnect protocol.
        self._snapshotter = snapshotter
        self._shard_epoch = int(shard_epoch)
        self._restored_version = (
            -1 if restored_version is None else int(restored_version)
        )
        # serving plane (docs/serving.md): record which embedding rows
        # each optimizer version touched so scorers can sync their
        # read-through caches by delta instead of re-aging every entry
        # on every version advance. base = whatever version this boot
        # serves from: rows older than that are this incarnation's
        # restored state, which the scorer's epoch-change invalidation
        # already covers (docs/ps_recovery.md).
        from elasticdl_tpu.ps.delta_log import DeltaLog

        self._delta = DeltaLog(base_version=parameters.version)

    @property
    def shard_epoch(self):
        return self._shard_epoch

    def _reply(self, resp):
        """Tag one reply dict with this incarnation's shard_epoch."""
        resp["shard_epoch"] = self._shard_epoch
        return resp

    def _maybe_snapshot(self):
        """Cadence hook, right after a version bump, OFF the apply path
        (capture is a copy under the apply lock; disk IO is the
        snapshotter's background thread)."""
        if self._snapshotter is not None:
            # the span times the capture SUBMIT (the copy under the
            # apply lock); the disk write runs on the snapshotter's
            # background thread, off every trace
            with profiling.span("ps/snapshot_submit"):
                self._snapshotter.maybe_snapshot(
                    self._parameters,
                    apply_lock=self._optimizer.apply_lock,
                )

    def drain_snapshot(self):
        """Final synchronous snapshot (the SIGTERM drain path): settle
        queued cadence writes first so the drain snapshot publishes
        newest-last, then capture+write whatever the store holds."""
        if self._snapshotter is None:
            return None
        with profiling.span("ps/snapshot_drain"):
            self._snapshotter.wait()
            return self._snapshotter.snapshot_now(
                self._parameters, apply_lock=self._optimizer.apply_lock
            )

    # -- RPC methods --------------------------------------------------------

    def pull_variable(self, req):
        """All non-embedding params + init status (reference :36-57).

        Sync mode snapshots under the gradient lock: with workers'
        overlapped data planes a pull can land mid-apply, and an
        unguarded ``to_named_arrays`` would hand back a torn mix of
        pre- and post-step values tagged with one version. Async mode
        stays lock-free — hogwild reads are its contract, and the LR
        staleness modulation already prices them in."""
        from elasticdl_tpu.rpc.wire_compression import compress_tensors

        if not self._parameters.initialized:
            return self._reply({"model_init_status": False, "version": -1})
        lock = self._lock if not self._use_async else _NULL_LOCK
        with lock:
            named = self._parameters.to_named_arrays()
            version = self._parameters.version
        params, compressed = compress_tensors(
            [Tensor(n, v) for n, v in sorted(named.items())],
            self._wire_dtype,
        )
        return self._reply({
            "model_init_status": True,
            "version": version,
            "params": params,
            "compressed_f32": compressed,
        })

    def pull_embedding_vector(self, req):
        """Rows for req['ids'] of table req['name'] (lazy init).

        The response carries this shard's model version so the worker's
        hot-row cache (worker/ps_client.py) can tag the rows and age
        them out by the same staleness counter the async LR modulation
        discounts by."""
        version = self._parameters.version
        ids = np.asarray(req["ids"], dtype=np.int64)
        if ids.size == 0:
            return self._reply({
                "rows": np.zeros((0, 0), np.float32),
                "version": version,
            })
        rows = self._parameters.get_embedding_param(req["name"], ids)
        return self._reply({"rows": rows, "version": version})

    def push_model(self, req):
        """First-write-wins model init (reference :70-79)."""
        dense = {t.name: t.values for t in req.get("params", [])}
        infos = [
            EmbeddingTableInfo(i["name"], i["dim"], i.get("initializer", "uniform"))
            for i in req.get("embedding_infos", [])
        ]
        # no servicer lock: Parameters is self-synchronized (first-
        # write-wins under ITS lock, tables built off-lock), and a
        # tiered table's constructor re-attaches spill segments from
        # disk — file IO under ``_lock`` would stall every concurrent
        # push_gradient for the whole init
        self._parameters.init_from_model(
            req.get("version", 0), dense, infos
        )
        return self._reply({})

    def push_embedding_info(self, req):
        # no servicer lock — same reasoning as push_model above
        self._parameters.init_embedding_params(
            EmbeddingTableInfo(
                i["name"], i["dim"], i.get("initializer", "uniform")
            )
            for i in req.get("embedding_infos", [])
        )
        return self._reply({})

    def push_gradient(self, req):
        """Sync/async gradient apply (reference :88-150)."""
        from elasticdl_tpu.rpc.wire_compression import decompress_tensors

        version = int(req.get("model_version", -1))
        gradients = decompress_tensors(
            req.get("gradients", []), req.get("compressed_f32")
        )
        if self._use_async:
            self._apply(gradients, version)
            return self._reply(
                {"accepted": True, "version": self._parameters.version}
            )

        with self._lock:
            if version < self._parameters.version:
                logger.warning(
                    "Dropping stale gradient for version %d (current %d)",
                    version,
                    self._parameters.version,
                )
                return self._reply({
                    "accepted": False,
                    "version": self._parameters.version,
                })
            # AUDITED retention sites (docs/wire.md): sync accumulation
            # outlives this request, and the request's tensors are
            # zero-copy views into a wire buffer that may be a shm slot
            # the client recycles right after the reply — so the first
            # round MUST materialize. ``combined()`` always returns
            # fresh arrays (sparse), ``.copy()`` covers dense; later
            # rounds allocate through ``+`` anyway.
            for t in gradients:
                self._parameters.check_grad(t)
                if t.is_indexed_slices():
                    if t.name in self._indexed_sum:
                        # row-combine as we accumulate: Tensor.__add__
                        # concatenates, so grads_to_wait stale-free
                        # rounds would otherwise buffer one copy of
                        # every duplicate row until apply time
                        self._indexed_sum[t.name] = (
                            self._indexed_sum[t.name] + t
                        ).combined()
                    else:
                        self._indexed_sum[t.name] = t.combined()
                else:
                    if t.name in self._dense_sum:
                        self._dense_sum[t.name] = (
                            self._dense_sum[t.name] + t.values
                        )
                    else:
                        self._dense_sum[t.name] = t.values.copy()
            self._grad_n += 1
            if self._grad_n >= self._grads_to_wait:
                dense = {
                    k: v / self._grads_to_wait
                    for k, v in self._dense_sum.items()
                }
                with profiling.span("ps/apply", sync=True):
                    self._optimizer.apply_gradients(
                        dense_grads=dense,
                        embedding_grads=self._indexed_sum,
                    )
                    # note BEFORE the version bump becomes visible:
                    # serving_status reads version + delta unlocked,
                    # and advertising a version whose update is not in
                    # the log yet would let a scorer re-tag rows that
                    # version rewrote as provably-unchanged. The safe
                    # direction is the reverse (tables may run AHEAD of
                    # version — an early delta only re-pulls sooner).
                    # The accumulated tensors are .combined(): indices
                    # are already one-per-unique-row.
                    new_version = self._parameters.version + 1
                    for name, t in self._indexed_sum.items():
                        self._delta.note(name, t.indices, new_version)
                        self._note_applied(name, t.indices, new_version)
                    self._parameters.version = new_version
                self._dense_sum.clear()
                self._indexed_sum.clear()
                self._grad_n = 0
                applied = True
            else:
                applied = False
            reply = self._reply(
                {"accepted": True, "version": self._parameters.version}
            )
        if applied:
            # off the accumulation lock: the cadence hook captures under
            # the optimizer's apply lock and submits to the snapshotter
            # queue (a blocking put when full) — neither should stall
            # concurrent push_gradient accumulation
            self._maybe_snapshot()
        return reply

    def _apply(self, gradients, request_version):
        # async applies consume the request's zero-copy views entirely
        # WITHIN this handler call (the optimizer reads them and writes
        # back fresh arrays), so nothing here needs materializing —
        # the wire buffer is guaranteed alive until the reply is packed
        if self._lr_modulation:
            staleness = max(1, self._parameters.version - request_version)
            self._lr_modulation.set_multiplier(1.0 / staleness)
        dense, sparse = {}, {}
        for t in gradients:
            self._parameters.check_grad(t)
            if t.is_indexed_slices():
                sparse[t.name] = t
            else:
                dense[t.name] = t.values
        # nests under the rpc/push_gradient server span when the caller
        # shipped its span context, so a trace shows wire vs apply time
        with profiling.span("ps/apply"):
            self._optimizer.apply_gradients(
                dense_grads=dense, embedding_grads=sparse
            )
            with self._version_lock:
                # rows are written (apply above) and the delta is noted
                # BEFORE the new version becomes visible: serving_status
                # must never advertise a version whose update the log
                # does not carry yet, or a scorer re-tags rows that
                # version rewrote as provably-unchanged. Over-advertising
                # the table (note lands, bump not yet visible) is safe —
                # the scorer just pulls the delta one poll early. The
                # optimizer combines duplicate ids at apply; the log
                # dedups at read time either way.
                new_version = self._parameters.version + 1
                for name, t in sparse.items():
                    self._delta.note(name, t.indices, new_version)
                    self._note_applied(name, t.indices, new_version)
                self._parameters.version = new_version
        self._maybe_snapshot()

    def _note_applied(self, name, ids, version):
        """Forward the delta note to a tiered table (docs/
        tiered_store.md): rows a recent version applied to are the
        demoter's do-not-evict set and the promotion signal. The same
        update feeds the row table and its slot tables, so the note
        fans out to the layer's whole table family (slot naming is
        ``"{layer}-{slot}"``, embedding_table.get_slot_table_name)."""
        tables = self._parameters.embedding_params
        family = [tables.get(name)]
        for key, t in tables.items():
            if t.is_slot and key.startswith(name + "-"):
                family.append(t)
        for t in family:
            note = getattr(t, "note_applied", None)
            if note is not None:
                note(ids, version)

    def ps_status(self, req):
        """Shard liveness/identity probe (docs/ps_recovery.md).

        Read-only and idempotent (edlint R9): clients probe it after a
        data-plane failure to learn whether the shard came back as a
        NEW incarnation (shard_epoch changed), how far its restored
        state rolled back (version), and whether it needs the model
        re-pushed (initialized False — relaunch with no snapshot).
        A tiered shard (docs/tiered_store.md) additionally reports its
        aggregated tier counters under ``tiered`` — how a driver
        sees that the disk tier was exercised
        (tests/test_tiered_store.py reads them here)."""
        resp = {
            "version": self._parameters.version,
            "initialized": bool(self._parameters.initialized),
            "restored_version": self._restored_version,
            "snapshot_every": (
                self._snapshotter.every_versions
                if self._snapshotter is not None
                else 0
            ),
        }
        tiered = None
        for table in list(self._parameters.embedding_params.values()):
            stats = getattr(table, "stats", None)
            if stats is None:
                continue
            s = stats()
            if tiered is None:
                tiered = dict.fromkeys(s, 0)
            for key, value in s.items():
                tiered[key] = tiered.get(key, 0) + int(value)
        if tiered is not None:
            resp["tiered"] = tiered
        return self._reply(resp)

    # -- serving-plane RPCs (docs/serving.md) -------------------------------

    def serving_status(self, req):
        """Per-table freshness advertisement for the scorer fleet.

        Read-only and idempotent (edlint R9): scorers poll it to learn
        (a) this incarnation's identity (``shard_epoch`` rides every
        reply — a change triggers the PR-10 shard-selective cache
        invalidation), (b) the shard's current optimizer version, and
        (c) per NON-SLOT embedding table, the newest version that
        touched it (``tables``) plus the oldest since-version the delta
        log can still answer completely (``floors``). A table with no
        recorded update since boot reports the boot/base version —
        sound, because a materialized row only ever changes through a
        noted apply (lazy init happens at first pull, before any cache
        copy exists)."""
        # version FIRST, delta state after: with the apply paths noting
        # updates before their version bump becomes visible, this read
        # order guarantees tables[] covers every update the advertised
        # version includes (tables may run ahead — harmlessly early)
        version = self._parameters.version
        last = self._delta.table_versions()
        floors = self._delta.floors()
        base = self._restored_version if self._restored_version >= 0 else 0
        tables = {}
        table_floors = {}
        for name, table in list(self._parameters.embedding_params.items()):
            if table.is_slot:
                continue  # optimizer state, never served
            tables[name] = int(last.get(name, base))
            table_floors[name] = int(floors.get(name, base))
        return self._reply({
            "version": version,
            "initialized": bool(self._parameters.initialized),
            "tables": tables,
            "floors": table_floors,
        })

    def pull_embedding_delta(self, req):
        """Row ids of ``req['name']`` updated after
        ``req['since_version']`` (docs/serving.md).

        Read-only and idempotent (edlint R9) — the reply is computed
        fresh from the delta log, so replaying it is harmless and the
        scorer's capped-backoff retry policy may resend it freely.
        ``complete=False`` means ``since_version`` predates the
        retained window; the scorer must fall back to
        ``HotRowCache.invalidate_table`` instead of trusting a partial
        id list. ``version`` is the newest update version the answer
        covers — the scorer's next ``since_version``."""
        name = req["name"]
        since = int(req.get("since_version", -1))
        ids, covered, complete = self._delta.since(name, since)
        return self._reply({
            "ids": ids,
            "version": int(covered),
            "complete": bool(complete),
        })

    # -- rpc.core wiring ----------------------------------------------------

    def rpc_methods(self):
        """{method_name: fn} map for rpc.core.serve, instrumented with
        per-method service-time histograms
        (edl_rpc_server_latency_seconds{role="ps"}) — push-window reaps
        and fan-out tails become visible without touching callers."""
        from elasticdl_tpu.utils.profiling import (
            instrument_service_methods,
        )

        return instrument_service_methods(
            {
                "pull_variable": self.pull_variable,
                "pull_embedding_vector": self.pull_embedding_vector,
                "push_model": self.push_model,
                "push_embedding_info": self.push_embedding_info,
                "push_gradient": self.push_gradient,
                "ps_status": self.ps_status,
                "serving_status": self.serving_status,
                "pull_embedding_delta": self.pull_embedding_delta,
            },
            role="ps",
        )
