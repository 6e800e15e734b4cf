"""Worker-side sharded-PS client.

Parity: the multi-PS paths inside reference worker/worker.py — variables
partitioned to PS shards by name hash (:279-291), embedding rows by
``id % N`` (:229-252), per-shard gradient pushes (:383-450), and the
pull-merge of dense params. Partition placement uses common/hash_utils so
row/variable placement is stable across restarts and matches the
checkpoint layout.

Overlap (docs/dense_overlap.md): every logical data-plane call fans its
per-shard RPCs out concurrently over a small thread pool, so an N-shard
fleet costs one round trip instead of N; ``push_inflight > 0`` makes
``push_gradient`` non-blocking behind a bounded in-flight window that
drains at every ``pull_dense`` and at worker task boundaries. The caller
contract is single-threaded: one worker thread drives the client; the
internal pools only ever run the per-shard legs and the queued pushes.
"""

import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from elasticdl_tpu.common.hash_utils import (
    scatter_embedding_vector,
    string_to_id,
)
from elasticdl_tpu.common.tensor import Tensor, release_message


# HotRowCache moved behind the comm-plane interface (nn/comm_plane.py)
# so one version-tagged cache instance can serve every plane a table
# rides; imported here for the historical call sites.
from elasticdl_tpu.nn.comm_plane import HotRowCache  # noqa: E402,F401


class PSClient:
    def __init__(
        self,
        ps_stubs,
        wire_dtype="",
        combine_push=True,
        hot_row_cache_rows=0,
        staleness_window=1,
        fanout=True,
        push_inflight=0,
        cache=None,
        on_shard_reset=None,
    ):
        """``ps_stubs``: list of objects exposing the Pserver dict-RPC
        methods — rpc.core Clients bound with ``BoundPS`` below, or
        in-process PserverServicer instances (the reference test rung 2
        uses both). ``wire_dtype="bfloat16"`` compresses pushed
        gradients (see rpc/wire_compression.py); pulled params
        decompress by the response's own field.

        Sparse fast path knobs (docs/sparse_fast_path.md):
        ``combine_push`` (default on) segment-sums duplicate sparse rows
        before the wire so each push carries one row per unique id;
        ``hot_row_cache_rows`` > 0 enables a :class:`HotRowCache` of
        that many rows whose entries stay valid for
        ``staleness_window`` PS versions (wire it to the worker's SSP
        window, ``get_model_steps``).

        Overlap knobs (docs/dense_overlap.md): ``fanout`` (default on)
        issues the per-shard RPCs of one logical call concurrently;
        ``push_inflight`` > 0 makes ``push_gradient`` non-blocking with
        at most that many logical pushes on the wire (1 = classic
        double buffering: compute batch k+1 while batch k's gradients
        travel). The window drains at every ``pull_dense`` and via
        :meth:`drain`."""
        self._ps = ps_stubs
        self._wire_dtype = wire_dtype
        self._combine_push = combine_push
        # ``cache``: an externally-owned (plane-shared) HotRowCache —
        # the comm-plane refactor lets one version-tagged cache back
        # every PS-resident table, whichever client pulls them
        # (docs/embedding_planes.md); hot_row_cache_rows > 0 keeps the
        # historical per-client construction.
        self._cache = cache if cache is not None else (
            HotRowCache(hot_row_cache_rows, staleness_window)
            if hot_row_cache_rows > 0
            else None
        )
        self._fanout_enabled = bool(fanout)
        self._fanout_pool = None
        self._push_inflight = max(0, int(push_inflight))
        self._push_pool = None
        # lazy pool creation happens on whichever thread first needs a
        # pool (the minibatch path or a push-window driver) and close()
        # tears them down from the worker's finally — pool handles ride
        # one lock so a racing pair can't double-create and leak the
        # loser's threads, and _closed keeps a late caller (a prefetch
        # warm pull racing teardown) from resurrecting a pool nothing
        # would ever shut down (edlint R8)
        self._pool_lock = threading.Lock()
        self._closed = False
        self._pending_pushes = deque()
        # combined outcome of async pushes reaped since the last drain
        self._reaped_accepted = True
        self._last_push_version = -1
        # -- reconnect protocol state (docs/ps_recovery.md) --
        # Every PS reply carries the serving incarnation's shard_epoch
        # (a boot id). A changed epoch means the shard died and came
        # back — possibly restored to an OLDER version — and this
        # client runs the reconnect protocol: invalidate that shard's
        # cache entries, abandon the in-flight push window (the
        # non-idempotent pushes raced the dead incarnation; they are
        # dropped, never resent), and re-push model/embedding infos if
        # the shard reports uninitialized. Detection can happen on
        # fan-out/push threads, so the state rides its own lock.
        self._epoch_mu = threading.Lock()
        self._shard_epochs = {}  # shard -> last seen shard_epoch
        self._seen_versions = {}  # shard -> newest version seen
        self._reset_gen = 0  # bumps at every detected epoch change
        self._shard_fail_t = {}  # shard -> first-failure monotonic time
        self._last_probe_t = {}  # shard -> last ps_status probe time
        self._needs_reinit = set()  # shards reporting uninitialized
        self._on_shard_reset = on_shard_reset

    @property
    def hot_row_cache(self):
        """The HotRowCache (None when disabled) — stats live on it."""
        return self._cache

    # -- the reconnect protocol (docs/ps_recovery.md) -----------------------

    def set_on_shard_reset(self, callback):
        """``callback(shards)`` runs on the next data-plane call after a
        relaunched shard reported UNINITIALIZED state (relaunch with no
        snapshot to restore): the worker re-pushes its model + embedding
        infos (first-write-wins, so live shards ignore the re-push)."""
        self._on_shard_reset = callback

    @property
    def shard_epochs(self):
        """{shard: last seen shard_epoch} (diagnostics/tests)."""
        with self._epoch_mu:
            return dict(self._shard_epochs)

    def _note_shard_reply(self, shard, resp):
        """Track the replying incarnation; run the reset protocol on an
        epoch change. Called from whichever thread processed the reply
        (worker, fan-out, or push driver) — state rides _epoch_mu, and
        the cache invalidation happens outside it (the cache has its
        own lock; nesting would add a lock-order edge for nothing)."""
        if not isinstance(resp, dict):
            return
        epoch = resp.get("shard_epoch")
        if epoch is None:
            return
        version = resp.get("version")
        with self._epoch_mu:
            prev = self._shard_epochs.get(shard)
            if prev is not None and epoch < prev:
                # a DELAYED reply from the dead incarnation (its fan-out
                # leg resolved after the relaunch was already detected):
                # epochs are monotonic per shard, so this is stale —
                # recording it would regress the epoch and spuriously
                # re-run the reset against the live incarnation
                return
            self._shard_epochs[shard] = epoch
            changed = prev is not None and epoch > prev
            seen = self._seen_versions.get(shard, -1)
            if changed:
                self._reset_gen += 1
                # re-anchor the version clock at the restored value:
                # the dead incarnation's high-water mark is void
                self._seen_versions[shard] = (
                    int(version) if version is not None else -1
                )
                if (
                    resp.get("initialized") is False
                    or resp.get("model_init_status") is False
                ):
                    self._needs_reinit.add(shard)
                fail_t = self._shard_fail_t.pop(shard, None)
            else:
                if version is not None and int(version) > seen:
                    self._seen_versions[shard] = int(version)
                # a healthy reply clears any stale failure stamp
                self._shard_fail_t.pop(shard, None)
        if not changed:
            return
        rollback = max(
            0, seen - (int(version) if version is not None else seen)
        )
        dropped = 0
        if self._cache is not None:
            dropped = self._cache.invalidate_shard(shard, version=version)
        from elasticdl_tpu.utils import profiling

        profiling.events.emit(
            "ps_shard_restore",
            shard=shard,
            old_epoch=prev,
            new_epoch=epoch,
            version=version,
            rollback_depth=rollback,
            cache_rows_invalidated=dropped,
            restore_latency_s=(
                round(time.monotonic() - fail_t, 3)
                if fail_t is not None
                else None
            ),
        )
        from elasticdl_tpu.common.log_utils import default_logger

        default_logger.warning(
            "PS shard %s relaunched (epoch %s -> %s): version rolled "
            "back %d to %s; %d cached rows invalidated, in-flight push "
            "window abandoned",
            shard,
            prev,
            epoch,
            rollback,
            version,
            dropped,
        )

    def _note_shard_failures(self, shard_keys):
        """Stamp first-failure times and probe the failing shards'
        status (idempotent ``ps_status``): a shard that already came
        back as a new incarnation is detected HERE — before the retry
        machinery re-runs the batch — so the cache/window reset happens
        ahead of the next pull, and an uninitialized relaunch gets
        flagged for the model re-push instead of erroring forever on
        its empty store."""
        shards = set()
        for key in shard_keys:
            shard = key[1] if isinstance(key, tuple) else key
            if isinstance(shard, (int, np.integer)):
                shards.add(int(shard))
        now = time.monotonic()
        with self._epoch_mu:
            for shard in shards:
                self._shard_fail_t.setdefault(shard, now)
            # throttle: the probe pays the data-plane deadline/retry
            # budget against a possibly-dead endpoint, and failures can
            # arrive once per minibatch — probing each shard at most
            # once per second bounds the added failure-path latency
            # without delaying relaunch detection meaningfully
            shards = {
                s
                for s in shards
                if now - self._last_probe_t.get(s, -10.0) >= 1.0
            }
            for shard in shards:
                self._last_probe_t[shard] = now
        for shard in shards:
            try:
                status = self._ps[shard].ps_status({})
            except Exception:  # noqa: BLE001 — still down
                from elasticdl_tpu.common.log_utils import default_logger

                default_logger.debug(
                    "ps_status probe of shard %s failed (still down); "
                    "the next data-plane failure re-probes",
                    shard,
                    exc_info=True,
                )
                continue
            self._note_shard_reply(shard, status)
            if isinstance(status, dict):
                release_message(status)

    def _gen_snapshot(self):
        with self._epoch_mu:
            return self._reset_gen

    def _service_reinit(self):
        """Run the worker's re-push callback for shards that came back
        empty. Runs on the thread entering a data-plane call (the
        worker thread, or the prefetch pipeline's pull thread — both
        only READ the model pytree, and push_model is first-write-wins
        on every shard, so a racing re-push is harmless)."""
        with self._epoch_mu:
            if not self._needs_reinit:
                return
            shards = sorted(self._needs_reinit)
            self._needs_reinit.clear()
        cb = self._on_shard_reset
        if cb is None:
            return
        try:
            cb(shards)
        except Exception:
            # a transient re-push failure (the shard still flapping)
            # must not LOSE the flag — nothing re-adds it until another
            # epoch change, and the empty store would wedge every later
            # pull. Re-arm and let the failure surface normally (the
            # task retry re-enters here).
            with self._epoch_mu:
                self._needs_reinit.update(shards)
            raise

    # -- serving-plane reads (docs/serving.md) ------------------------------

    def serving_status(self, shard):
        """One shard's per-table freshness advertisement
        (ps/servicer.serving_status): {version, shard_epoch, tables,
        floors, initialized}. Rides the reconnect protocol — a changed
        ``shard_epoch`` in the reply triggers the shard-selective cache
        invalidation right here, so a scorer's poll loop detects a PS
        relaunch without waiting for a data-plane pull to fail
        (docs/ps_recovery.md)."""
        resp = self._ps[shard].serving_status({})
        self._note_shard_reply(shard, resp)
        try:
            return {
                "version": int(resp.get("version", -1)),
                "shard_epoch": resp.get("shard_epoch"),
                "initialized": bool(resp.get("initialized", False)),
                "tables": dict(resp.get("tables") or {}),
                "floors": dict(resp.get("floors") or {}),
            }
        finally:
            release_message(resp)

    def pull_embedding_delta(self, shard, name, since_version):
        """Ids of ``name``'s rows shard ``shard`` updated after
        ``since_version`` -> (ids int64, covered_version, complete).
        Idempotent read (edlint R9) — safe under the retriable
        data-plane channel."""
        resp = self._ps[shard].pull_embedding_delta(
            {"name": name, "since_version": int(since_version)}
        )
        self._note_shard_reply(shard, resp)
        try:
            # materialize: the decoded ids are a zero-copy view into
            # the reply buffer (possibly a recycling shm slot)
            ids = np.array(resp["ids"], dtype=np.int64, copy=True)
            return (
                ids,
                int(resp.get("version", since_version)),
                bool(resp.get("complete", False)),
            )
        finally:
            release_message(resp)

    @property
    def num_ps(self):
        return len(self._ps)

    @property
    def push_inflight_window(self):
        return self._push_inflight

    def _ps_of_var(self, name):
        return self._ps[string_to_id(name, self.num_ps)]

    # -- concurrent shard fan-out -------------------------------------------

    def _get_fanout_pool(self):
        with self._pool_lock:
            if self._closed:
                raise RuntimeError("PSClient is closed")
            if self._fanout_pool is None:
                # wider than num_ps: one multi-table pull produces
                # (tables x shards) legs that should all fly in one
                # round
                self._fanout_pool = ThreadPoolExecutor(
                    max_workers=min(16, max(self.num_ps, 8)),
                    thread_name_prefix="edl-ps-fanout",
                )
            return self._fanout_pool

    def _run_sharded(self, calls):
        """Run ``[(shard, thunk), ...]`` and return ``{shard: result}``.

        With fan-out on, every thunk is submitted to the pool at once
        and the per-shard round trips overlap, so one logical call costs
        the slowest shard, not the sum of shards. Completion handling is
        deterministic either way: results are consumed in ascending
        shard order, and on failure the lowest-numbered failing shard's
        exception is raised only after EVERY call finished — no RPC is
        left in flight mutating caller-visible state after the raise.
        """
        if not calls:
            return {}
        if not self._fanout_enabled or len(calls) == 1:
            try:
                return {shard: thunk() for shard, thunk in calls}
            except Exception:  # noqa: BLE001 — probe, then re-raise
                # serial legs run in-line, so the failing shard is not
                # attributable here — probe every shard of the call
                # (ps_status is an idempotent read; a healthy shard's
                # probe just refreshes its epoch record)
                self._note_shard_failures([shard for shard, _ in calls])
                raise
        pool = self._get_fanout_pool()
        futs = [(shard, pool.submit(thunk)) for shard, thunk in calls]
        results, errors = {}, []
        for shard, fut in futs:
            try:
                results[shard] = fut.result()
            except Exception as err:  # noqa: BLE001 — re-raised below
                errors.append((shard, err))
        if errors:
            errors.sort(key=lambda pair: pair[0])
            # reconnect protocol: stamp + probe the failing shards so a
            # relaunched incarnation is detected before the retry runs
            self._note_shard_failures([shard for shard, _ in errors])
            raise errors[0][1]
        return results

    def close(self):
        """Drain pending pushes and release the fan-out/push threads.

        Best-effort on the drain: close() runs from teardown paths
        (worker main's finally), where a dead-shard error has already
        surfaced through drain()/pull_dense and must not mask the
        original failure — it is logged, not re-raised."""
        try:
            self.drain()
        except Exception as err:  # noqa: BLE001 — teardown best-effort
            from elasticdl_tpu.common.log_utils import default_logger

            default_logger.warning(
                "async push window failed to drain at close: %s", err
            )
        finally:
            # detach under the lock, shut down outside it (shutdown
            # waits on worker threads; holding the lock across that
            # would stall a concurrent _get_fanout_pool for the
            # duration)
            with self._pool_lock:
                self._closed = True
                pools = (self._push_pool, self._fanout_pool)
                self._push_pool = None
                self._fanout_pool = None
            for pool in pools:
                if pool is not None:
                    pool.shutdown(wait=True)

    # -- model lifecycle ----------------------------------------------------

    def push_model(self, named_params, embedding_infos=None, version=0):
        """Partition dense vars by name hash; infos go to every shard.

        All shard pushes go out concurrently; the call returns only
        once every shard has acked its partition."""
        partitions = [{} for _ in range(self.num_ps)]
        for name, arr in named_params.items():
            partitions[string_to_id(name, self.num_ps)][name] = arr
        infos = [
            {"name": i.name, "dim": i.dim, "initializer": i.initializer}
            for i in embedding_infos or ()
        ]
        calls = []
        for shard, (ps, part) in enumerate(zip(self._ps, partitions)):
            req = {
                "version": version,
                "params": [Tensor(n, v) for n, v in part.items()],
                "embedding_infos": infos,
            }
            calls.append(
                (shard, lambda ps=ps, req=req: ps.push_model(req))
            )
        for shard, resp in self._run_sharded(calls).items():
            # the earliest epoch baseline: a later reply with a
            # DIFFERENT epoch is then a detectable relaunch
            self._note_shard_reply(shard, resp)
            release_message(resp)

    def push_embedding_info(self, embedding_infos):
        infos = [
            {"name": i.name, "dim": i.dim, "initializer": i.initializer}
            for i in embedding_infos
        ]
        resps = self._run_sharded(
            [
                (
                    shard,
                    lambda ps=ps: ps.push_embedding_info(
                        {"embedding_infos": infos}
                    ),
                )
                for shard, ps in enumerate(self._ps)
            ]
        )
        for shard, resp in resps.items():
            self._note_shard_reply(shard, resp)
            release_message(resp)

    def pull_dense(self):
        """Merge every shard's params; returns (all_initialized, version,
        {name: ndarray}).

        Drains the async-push window first, so the pulled model always
        reflects this worker's own completed pushes (the in-flight
        window never widens the SSP staleness bound). All shard pulls
        are issued concurrently; responses merge in ascending shard
        order (names are hash-partitioned, so order cannot change the
        result — the fixed order keeps failure handling deterministic).
        """
        from elasticdl_tpu.rpc.wire_compression import decompress_tensors

        self._service_reinit()
        self.drain()
        resps = self._run_sharded(
            [
                (shard, lambda ps=ps: ps.pull_variable({}))
                for shard, ps in enumerate(self._ps)
            ]
        )
        named = {}
        versions = []
        try:
            for shard in range(self.num_ps):
                resp = resps[shard]
                self._note_shard_reply(shard, resp)
                if not resp.get("model_init_status"):
                    return False, -1, {}
                versions.append(resp["version"])
                if self._cache is not None:
                    self._cache.note_version(shard, resp["version"])
                for t in decompress_tensors(
                    resp.get("params", []), resp.get("compressed_f32")
                ):
                    # AUDITED retention site (docs/wire.md): the worker
                    # keeps these params across steps, so zero-copy
                    # decoded views must materialize here — the single
                    # decode copy of the dense pull. Owned arrays
                    # (in-process stubs, already-upcast bf16) pass
                    # through untouched.
                    named[t.name] = t.materialize().values
        finally:
            for resp in resps.values():
                release_message(resp)
        return True, min(versions), named

    # -- gradients ----------------------------------------------------------

    def push_gradient(self, dense_named, sparse_tensors, version):
        """Per-shard push: dense by var hash, sparse rows by id shard.

        Returns the COMBINED result across shards: ``accepted`` only
        when EVERY shard accepted, ``version`` the minimum shard
        version. This deliberately departs from the reference's
        TODO-choose-last tail (worker.py:444-450), which reported only
        the final shard's response and silently masked an earlier
        shard's stale-gradient rejection.

        With ``push_inflight`` > 0 the call is non-blocking: the whole
        fan-out (compression included) runs on a push thread while the
        worker computes the next batch, bounded to ``push_inflight``
        logical pushes in flight (submitting past the window first
        reaps the oldest). The immediate return is optimistic —
        ``(True, last reconciled version)`` — and the true combined
        outcome is reconciled at the next ``pull_dense``/:meth:`drain`,
        where a shard failure also re-raises. The default window of 1
        keeps pushes strictly ordered per shard.
        """
        reqs = [[] for _ in range(self.num_ps)]
        for name, arr in (dense_named or {}).items():
            reqs[string_to_id(name, self.num_ps)].append(Tensor(name, arr))
        for t in sparse_tensors or ():
            if self._combine_push:
                # one row per unique id on the wire; the PS applies the
                # sum either way (optimizer_wrapper combines at apply),
                # so this only shrinks the payload
                t = t.combined()
            for shard, (values, ids) in scatter_embedding_vector(
                t.values, t.indices, self.num_ps
            ).items():
                reqs[shard].append(Tensor(t.name, values, indices=ids))
        self._service_reinit()
        if self._push_inflight <= 0:
            return self._push_shards(reqs, version)
        while len(self._pending_pushes) >= self._push_inflight:
            self._reap_push(self._pending_pushes.popleft())
        with self._pool_lock:
            if self._closed:
                raise RuntimeError("PSClient is closed")
            if self._push_pool is None:
                # one driver thread per window slot, separate from the
                # fan-out pool (a driver waits on fan-out futures;
                # sharing the pool could starve its own legs)
                self._push_pool = ThreadPoolExecutor(
                    max_workers=self._push_inflight,
                    thread_name_prefix="edl-ps-push",
                )
            push_pool = self._push_pool
        # each queued push remembers the reset generation it was
        # submitted under: an epoch change detected before the reap
        # ABANDONS it (outcome dropped, failure swallowed, never
        # resent) — the window raced a dead incarnation and resolving
        # it against the restored one would double-count or wedge
        self._pending_pushes.append(
            (push_pool.submit(self._push_shards, reqs, version),
             self._gen_snapshot())
        )
        return True, self._last_push_version

    def _push_shards(self, reqs, version):
        """One logical push: compress + send every shard leg, combine."""

        def push_one(shard):
            from elasticdl_tpu.rpc.wire_compression import compress_tensors

            tensors, compressed = compress_tensors(
                reqs[shard], self._wire_dtype
            )
            return self._ps[shard].push_gradient(
                {
                    "model_version": version,
                    "gradients": tensors,
                    "compressed_f32": compressed,
                }
            )

        resps = self._run_sharded(
            [
                (shard, lambda shard=shard: push_one(shard))
                for shard in range(self.num_ps)
            ]
        )
        accepted, out_version = True, None
        for shard in range(self.num_ps):
            resp = resps[shard]
            self._note_shard_reply(shard, resp)
            accepted = accepted and bool(resp["accepted"])
            out_version = (
                resp["version"]
                if out_version is None
                else min(out_version, resp["version"])
            )
            if self._cache is not None:
                # the apply this push triggered advanced the shard's
                # version: noting it here ages our cached copies of the
                # rows it just rewrote
                self._cache.note_version(shard, resp["version"])
            release_message(resp)  # scalar reply: its shm slot recycles
        return accepted, (-1 if out_version is None else out_version)

    def _reap_push(self, entry):
        fut, gen = entry
        try:
            accepted, version = fut.result()
        except Exception as err:  # noqa: BLE001 — re-raise unless abandoned
            if self._gen_snapshot() != gen:
                # epoch-abandonment: this push was in flight across a
                # shard relaunch. Its gradient is part of the bounded
                # rollback the restore already priced in; resending a
                # non-idempotent push could double-apply on shards
                # whose leg DID land, so the whole push is dropped.
                from elasticdl_tpu.common.log_utils import default_logger
                from elasticdl_tpu.utils import profiling

                profiling.events.emit(
                    "ps_push_window_dropped", reason=str(err)[:200]
                )
                default_logger.warning(
                    "in-flight gradient push abandoned across a PS "
                    "shard relaunch (dropped, not resent): %s",
                    err,
                )
                return True, -1
            raise
        if self._gen_snapshot() != gen:
            # the push resolved, but against a mix of incarnations: its
            # combined accepted/version verdict is void — ignore it
            return True, -1
        self._reaped_accepted = self._reaped_accepted and accepted
        if version >= 0:
            self._last_push_version = max(
                self._last_push_version, version
            )
        return accepted, version

    def drain(self):
        """Complete every in-flight async push synchronously.

        Returns ``(accepted, version)`` combined over all pushes reaped
        since the previous drain — ``accepted`` is False if ANY shard
        of any push rejected, ``version`` is the newest version any
        push response reported (-1 when nothing completed). A shard
        failure (e.g. deadline expiry on a dead pod) re-raises here —
        UNLESS the failing push was abandoned by the reconnect protocol
        (submitted before a detected shard relaunch): abandoned pushes
        are dropped silently, never resent, and never wedge the drain
        (docs/ps_recovery.md). Called automatically by ``pull_dense``;
        the worker also calls it at task boundaries, before eval, and
        before checkpoints.
        """
        while self._pending_pushes:
            self._reap_push(self._pending_pushes.popleft())
        accepted = self._reaped_accepted
        self._reaped_accepted = True
        return accepted, self._last_push_version

    @property
    def pending_push_count(self):
        return len(self._pending_pushes)

    # -- embeddings ---------------------------------------------------------

    def pull_embedding_vectors(self, name, ids):
        """Scatter ids to shards by id%N, gather, restore original order.

        With the hot-row cache enabled, cached fresh rows are served
        locally and only the misses cross the wire (a shard whose ids
        all hit is skipped entirely); pulled rows enter the cache tagged
        with the response's model version. The cache is probed once per
        DISTINCT id (duplicates fan out from that single probe via
        numpy mask ops — hit/miss stats count probes), and per-shard
        miss filtering is a mask select, not a per-id Python loop.
        Shard pulls fan out concurrently; responses land in disjoint
        row ranges and merge in ascending shard order."""
        return self.pull_embedding_vectors_multi({name: ids})[name]

    def pull_embedding_vectors_multi(self, ids_by_name):
        """Pull several tables' rows in ONE fan-out round.

        ``{table_name: ids} -> {table_name: rows}``: every
        (table, shard) leg flies concurrently, so a model with T
        embedding layers pays one round trip per batch instead of T
        (the worker's batch prepare pulls all layers through here).
        Semantics per table are exactly :meth:`pull_embedding_vectors`;
        responses merge in sorted (table, shard) order."""
        self._service_reinit()
        state = {}
        calls = []
        for name in ids_by_name:
            ids = np.asarray(ids_by_name[name], dtype=np.int64)
            st = {"ids": ids, "out": None, "positions": {}}
            state[name] = st
            if ids.size == 0:
                st["out"] = np.zeros((0, 0), np.float32)
                continue
            shard_ids = ids % self.num_ps
            hit_mask = np.zeros(ids.shape, dtype=bool)
            if self._cache is not None:
                uniq, inverse = np.unique(ids, return_inverse=True)
                uniq_rows = self._cache.get_rows(name, uniq)
                uniq_hit = np.fromiter(
                    (r is not None for r in uniq_rows),
                    dtype=bool,
                    count=len(uniq_rows),
                )
                hit_mask = uniq_hit[inverse]
                if uniq_hit.any():
                    hit_rows = np.stack(
                        [r for r in uniq_rows if r is not None]
                    ).astype(np.float32, copy=False)
                    out = np.empty(
                        (len(ids), hit_rows.shape[1]), np.float32
                    )
                    # row index into hit_rows for every hitting unique
                    uniq_to_hit = np.cumsum(uniq_hit) - 1
                    out[hit_mask] = hit_rows[
                        uniq_to_hit[inverse[hit_mask]]
                    ]
                    st["out"] = out
            for shard in np.unique(shard_ids[~hit_mask]):
                shard = int(shard)
                positions = np.nonzero(
                    (shard_ids == shard) & ~hit_mask
                )[0]
                st["positions"][shard] = positions
                req = {"name": name, "ids": ids[positions]}
                calls.append(
                    (
                        (name, shard),
                        lambda shard=shard, req=req: self._ps[
                            shard
                        ].pull_embedding_vector(req),
                    )
                )
        resps = self._run_sharded(calls)
        for name, shard in sorted(resps):
            resp = resps[(name, shard)]
            self._note_shard_reply(shard, resp)
            st = state[name]
            positions = st["positions"][shard]
            got = np.asarray(resp["rows"], dtype=np.float32)
            if got.shape[0] != len(positions):
                raise ValueError(
                    "PS shard %d returned %d rows for %d ids of %r"
                    % (shard, got.shape[0], len(positions), name)
                )
            if st["out"] is None:
                st["out"] = np.empty(
                    (len(st["ids"]), got.shape[1]), np.float32
                )
            # the scatter into the caller-owned output (and the cache's
            # own row copies below) IS this path's one decode copy, so
            # the zero-copy view ``got`` never outlives its message
            st["out"][positions] = got
            if self._cache is not None:
                version = resp.get("version")
                self._cache.note_version(shard, version)
                self._cache.put_rows(
                    name, st["ids"][positions], shard, version, got
                )
            release_message(resp)
        return {name: st["out"] for name, st in state.items()}


class PSRpcError(RuntimeError):
    """A PS data-plane RPC failed terminally (deadline expiry, dead
    pod past retries). RuntimeError on purpose: the worker's minibatch
    machinery converts RuntimeError into a failed-task report (the
    task requeues and the worker lives), whereas a raw grpc.RpcError
    would propagate out of the task loop and kill the worker process.
    ``code`` carries the gRPC status for callers that branch on it."""

    def __init__(self, addr, method, cause):
        super().__init__(
            "PS %s %s failed: %s" % (addr, method, cause)
        )
        self.addr = addr
        self.method = method
        self.cause = cause
        code = getattr(cause, "code", None)
        self.code = code() if callable(code) else None


class BoundPS:
    """Adapts an rpc.core Client to the dict-method PS interface.

    ``deadline_s`` bounds every data-plane RPC (rpc/core.Client), so a
    dead PS pod fails the call in ~``deadline_s`` seconds instead of
    hanging a fan-out forever; ``retries``/``backoff_s`` retry
    UNAVAILABLE transients (a restarting pod) — except on
    ``push_gradient``, which is NOT idempotent (an async PS applies on
    receipt; resending after a post-apply connection drop would apply
    the gradient twice). ``None`` keeps the historical blocking
    channel. Terminal transport failures surface as :class:`PSRpcError`
    (a RuntimeError), feeding the worker's minibatch retry loop.

    ``shm`` (docs/wire.md): ``"auto"`` negotiates the co-located
    shared-memory payload path at first call (``transport_hello``) and
    silently keeps the bytes path cross-host or on any attach/setup
    failure; ``"off"`` (default — the conservative choice for direct
    constructions in tests) never negotiates. Slot geometry
    rides ``shm_slots`` x ``shm_slot_mb``.
    """

    def __init__(
        self,
        addr,
        deadline_s=None,
        retries=0,
        backoff_s=0.2,
        shm="off",
        shm_slots=4,
        shm_slot_mb=8,
    ):
        from elasticdl_tpu.rpc.core import Client

        self._addr = addr
        self._client = Client(
            addr,
            deadline_s=deadline_s,
            retries=retries,
            backoff_s=backoff_s,
        )
        self._shm = None
        if shm in ("auto", "on"):
            from elasticdl_tpu.rpc.shm_transport import ShmChannel

            self._shm = ShmChannel(
                self._client, n_slots=shm_slots, slot_mb=shm_slot_mb
            )
        elif shm not in ("off", "", None, False):
            raise ValueError("shm must be 'auto', 'on' or 'off'")

    @property
    def shm_channel(self):
        """The ShmChannel (None when disabled) — state/stats live on it."""
        return self._shm

    def close(self):
        """Release the channel: unlink the shm ring (if negotiated) and
        close the gRPC channel. Safe to call repeatedly."""
        if self._shm is not None:
            self._shm.close()
        self._client.close()

    def __getattr__(self, method):
        def call(req):
            import grpc

            from elasticdl_tpu.utils import profiling

            try:
                if self._shm is not None:
                    # ShmChannel applies the same retry guard
                    # internally (push_gradient never resends)
                    return self._shm.call(method, **req)
                return self._client.call(
                    method,
                    _retriable=(method != "push_gradient"),
                    **req
                )
            except grpc.RpcError as err:
                wrapped = PSRpcError(self._addr, method, err)
                # fleet-visible event: rides the worker's next telemetry
                # snapshot into the master's job log
                profiling.events.emit(
                    "ps_shard_failure",
                    addr=self._addr,
                    method=method,
                    code=getattr(wrapped.code, "name", None),
                )
                raise wrapped from err

        return call
