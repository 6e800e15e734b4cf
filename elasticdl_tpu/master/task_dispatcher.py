"""Dynamic task dispatch — the elasticity core.

Parity: reference master/task_dispatcher.py:33-262. Data is partitioned
into tasks of ``records_per_task`` records over named shards; any worker can
process any task, so workers joining/leaving mid-epoch never block the job.
Failed / orphaned tasks are re-queued (report(success=False), recover_tasks).
Training epochs are created lazily when the todo queue drains; a deferred
SAVE_MODEL task is appended after all training tasks finish.

This component is framework-agnostic by design (it moved from the reference
unchanged in *semantics*); on TPU it additionally drives membership epochs:
a mesh resize looks to the dispatcher exactly like "some workers died and
their tasks were recovered".
"""

import os
import random
import threading
import time

from elasticdl_tpu.common.constants import (
    SaveModelConfig,
    TaskExecCounterKey,
    TaskType,
)
from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.utils import profiling


class Task:
    """One unit of dispatchable work: records [start, end) of a shard."""

    __slots__ = (
        "shard_name",
        "start",
        "end",
        "type",
        "model_version",
        "extended_config",
    )

    def __init__(self, shard_name, start, end, type, model_version=-1, **kw):
        self.shard_name = shard_name
        self.start = start
        self.end = end
        self.type = type
        self.model_version = model_version
        self.extended_config = kw

    def _info(self):
        return (
            self.shard_name,
            self.start,
            self.end,
            self.type,
            self.model_version,
        )

    def __repr__(self):
        return "Task%s" % (self._info(),)


class TaskDispatcher:
    """Creates and dispatches Tasks; tracks each task's lifecycle.

    shards dicts map shard_name -> (start_index, num_records), matching the
    reference's ``{file: (start, count)}`` contract (task_dispatcher.py:44-54).
    """

    def __init__(
        self,
        training_shards,
        evaluation_shards,
        prediction_shards,
        records_per_task,
        num_epochs,
        journal=None,
        streaming=False,
    ):
        self._lock = threading.Lock()
        self._num_epochs = num_epochs
        self._epoch = 0
        # unbounded streaming source (docs/serving.md): while active,
        # the lazy epoch rollover below fires EVERY time todo drains —
        # the dispatcher is an infinite task stream over the shards
        # (train on today's clicks, serve tomorrow's) until
        # set_streaming(False) lets the stream drain and the job finish
        # through the ordinary end-of-epoch path. Everything downstream
        # (requeue, journal, recovery, SSP) is epoch-shaped already, so
        # the stream is just "epochs forever".
        self._streaming = bool(streaming)
        self._training_shards = training_shards
        self._evaluation_shards = evaluation_shards
        self._prediction_shards = prediction_shards
        self._records_per_task = records_per_task
        # durable dispatch journal (docs/master_recovery.md): every
        # lifecycle transition below appends a record — an ENQUEUE
        # only, the journal's writer thread owns all IO, so holding
        # the ledger lock across an append never blocks (edlint R5)
        self._journal = journal
        # deterministic task order for chaos replays: the
        # dispatcher's shuffle is the one entropy source a multi-run
        # divergence gate cannot pin from outside the process
        seed = os.environ.get("EDL_TASK_SHUFFLE_SEED")
        self._shuffle = (
            random.Random(int(seed)).shuffle if seed else random.shuffle
        )

        self._todo = []
        self._doing = {}  # task_id -> (worker_id, Task)
        self._task_id = 0
        self._eval_todo = []
        self._evaluation_service = None
        self._tasks_done_deferred_callbacks = []
        # task-lifecycle tracing (docs/observability.md): every Task is
        # stamped with a trace id at FIRST dispatch (stable across
        # requeues — the same Task object returns to todo), and each
        # dispatch records (trace, attempt, t0) so report() can emit a
        # per-task timeline event with the dispatch->report latency
        self._trace_seq = 0
        self._dispatch_meta = {}  # task_id -> (trace_id, attempt, t0)
        # master recovery tables (apply_recovery): traces completed in
        # a PREVIOUS incarnation (the dedup table for replayed acks —
        # trace -> (type, epoch), GC'd at epoch rollover like the
        # journal's fold) and the still-pending recovered tasks
        # addressable by their pre-crash trace ids
        self._done_traces = {}
        self._trace_lookup = {}  # trace -> Task (recovered, not done)

        if self._training_shards:
            logger.info("Epoch %d begins", self._epoch)
            self.create_tasks(TaskType.TRAINING)
        elif self._evaluation_shards:
            self.create_tasks(TaskType.EVALUATION)
        elif self._prediction_shards:
            self.create_tasks(TaskType.PREDICTION)

    def create_tasks(self, task_type, model_version=-1):
        """Generate and queue one task set. Takes the dispatcher lock:
        the evaluation service calls this from its own round machinery
        (under ITS lock, never ours — complete_task runs off the
        dispatcher lock, so the eval->dispatcher order is acyclic)."""
        with self._lock:
            self._create_tasks_locked(task_type, model_version)

    def _create_tasks_locked(self, task_type, model_version=-1):
        logger.info(
            "Generating %s task set (model version %d)",
            TaskType(task_type).name.lower(),
            model_version,
        )
        if task_type == TaskType.TRAINING:
            shards = self._training_shards
        elif task_type == TaskType.EVALUATION:
            shards = self._evaluation_shards
        else:
            shards = self._prediction_shards
        tasks = []
        for shard_name, (shard_start, shard_count) in shards.items():
            shard_max = shard_start + shard_count
            for start in range(shard_start, shard_max, self._records_per_task):
                tasks.append(
                    Task(
                        shard_name=shard_name,
                        start=start,
                        end=min(start + self._records_per_task, shard_max),
                        type=task_type,
                        model_version=model_version,
                        # creation epoch rides the task: the journal
                        # key must name the epoch the task BELONGS to,
                        # not whatever epoch is current when its ack
                        # lands (an epoch-0 straggler acked after the
                        # epoch-1 rollover must not retire an epoch-1
                        # task at recovery)
                        _epoch=self._epoch,
                    )
                )
        if task_type == TaskType.TRAINING:
            self._shuffle(tasks)
            self._todo.extend(tasks)
            self._j("epoch", epoch=self._epoch)
        elif task_type == TaskType.EVALUATION:
            self._eval_todo.extend(tasks)
        else:
            self._todo.extend(tasks)

    def count_tasks(self, task_type):
        """Number of tasks one create_tasks(task_type) call would create."""
        if task_type == TaskType.TRAINING:
            shards = self._training_shards
        elif task_type == TaskType.EVALUATION:
            shards = self._evaluation_shards
        else:
            shards = self._prediction_shards
        n = 0
        for _, (shard_start, shard_count) in shards.items():
            n += len(
                range(
                    shard_start,
                    shard_start + shard_count,
                    self._records_per_task,
                )
            )
        return n

    def _j(self, kind, **fields):
        if self._journal is not None:
            self._journal.append(kind, **fields)

    def _task_key(self, task):
        """Boot-stable task identity for the journal (journal.task_key:
        WHAT the task covers — including the epoch it was CREATED in —
        not the per-boot task_id)."""
        from elasticdl_tpu.master.journal import task_key

        return task_key(
            task.type,
            task.extended_config.get("_epoch", self._epoch),
            task.shard_name,
            task.start,
            task.end,
        )

    def _task_xc(self, task):
        """Journaled extended config: only what a relaunched master
        cannot regenerate from its own args (the SAVE_MODEL path)."""
        if task.type != TaskType.SAVE_MODEL:
            return None
        path = task.extended_config.get(SaveModelConfig.SAVED_MODEL_PATH)
        return {SaveModelConfig.SAVED_MODEL_PATH: path} if path else None

    def _stamp_dispatch(self, task_id, task):
        """Assign/refresh the trace id + dispatch record (lock held)."""
        trace = task.extended_config.get("trace_id")
        attempt = 0
        if trace is None:
            self._trace_seq += 1
            trace = "t%06d" % self._trace_seq
            task.extended_config["trace_id"] = trace
        else:
            attempt = task.extended_config.get("_attempt", 0)
        task.extended_config["_attempt"] = attempt
        self._dispatch_meta[task_id] = (trace, attempt, time.monotonic())
        self._j(
            "dispatch",
            task=task_id,
            trace=trace,
            attempt=attempt,
            key=list(self._task_key(task)),
            xc=self._task_xc(task),
        )

    def get_eval_task(self, worker_id):
        """Return the next evaluation (task_id, Task), or (-1, None)."""
        with self._lock:
            if not self._eval_todo:
                return -1, None
            self._task_id += 1
            task = self._eval_todo.pop()
            self._doing[self._task_id] = (worker_id, task)
            self._stamp_dispatch(self._task_id, task)
            return self._task_id, task

    def _create_save_model_task(self, saved_model_path):
        """Append one SAVE_MODEL task carrying a small data shard.

        The task includes a slice of training data because model export needs
        a sample batch to trace input signatures
        (reference task_dispatcher.py:142-169).
        """
        shards = self._training_shards
        assert shards
        shard_name, (shard_start, shard_count) = next(iter(shards.items()))
        self._todo.append(
            Task(
                shard_name=shard_name,
                start=shard_start,
                end=shard_start + min(self._records_per_task, shard_count),
                type=TaskType.SAVE_MODEL,
                _epoch=self._epoch,
                **{SaveModelConfig.SAVED_MODEL_PATH: saved_model_path},
            )
        )

    def add_deferred_callback_create_save_model_task(self, saved_model_path):
        self._tasks_done_deferred_callbacks.append(
            lambda: self._create_save_model_task(saved_model_path)
        )

    def invoke_deferred_callback(self):
        """Pop and invoke one deferred callback; False if none remain."""
        if not self._tasks_done_deferred_callbacks:
            return False
        with self._lock:
            if not self._tasks_done_deferred_callbacks:
                return False
            self._tasks_done_deferred_callbacks.pop()()
            return True

    def get(self, worker_id):
        """Return the next (task_id, Task), or (-1, None) when drained.

        Lazily rolls over to the next training epoch when todo empties
        (reference task_dispatcher.py:198-201). The dispatch is a
        master-plane span: it binds the dispatched task's trace after
        the stamp, so a worker's ``_sctx``-carrying ``get_task`` shows
        the ledger time inside the caller's trace
        (docs/observability.md)."""
        sp = profiling.span("master/dispatch", worker=worker_id)
        with sp:
            task_id, task = self._get_next(worker_id)
            if task is not None:
                sp.set_trace(task.extended_config.get("trace_id"))
            return task_id, task

    def set_streaming(self, active):
        """Flip the unbounded-stream mode. Turning it off does NOT
        abort anything: already-queued tasks drain, in-flight tasks
        report, and the job finishes through the normal path."""
        with self._lock:
            self._streaming = bool(active)

    @property
    def streaming(self):
        with self._lock:
            return self._streaming

    def _get_next(self, worker_id):
        with self._lock:
            if not self._todo and self._training_shards and (
                self._streaming or self._epoch < self._num_epochs - 1
            ):
                self._epoch += 1
                self._create_tasks_locked(TaskType.TRAINING)
                # a rolled-over epoch's completed traces can no longer
                # receive replayed acks (the replay window is seconds;
                # the rollover is minutes) — GC them so the dedup table
                # and every journal compaction stay bounded by ONE
                # epoch's task count
                train = int(TaskType.TRAINING)
                self._done_traces = {
                    t: te
                    for t, te in self._done_traces.items()
                    if te[0] != train or te[1] >= self._epoch
                }
                logger.info("Epoch %d begins", self._epoch)
            if not self._todo:
                return -1, None
            self._task_id += 1
            task = self._todo.pop()
            self._doing[self._task_id] = (worker_id, task)
            self._stamp_dispatch(self._task_id, task)
            return self._task_id, task

    def report(self, task_id, success, exec_counters=None):
        """Report task completion; failures re-queue the task.

        ``exec_counters`` (optional, from the worker's ack) rides into
        the per-task timeline event — e.g. ``consume_s``, the worker's
        own first-record-to-ack wall time. It also carries the worker's
        view of the task's ``trace_id``/``attempt``: across a master
        relaunch the worker's held acks name task ids of the DEAD
        incarnation, and the trace is what lets this incarnation
        resolve them — marking the recovered task done exactly once and
        deduping any replay of an ack the old master already counted
        (docs/master_recovery.md)."""
        sp = profiling.span(
            "master/report", task=task_id, success=bool(success)
        )
        with sp:
            self._report(task_id, success, exec_counters, sp)

    def _report(self, task_id, success, exec_counters, sp):
        evaluation_task_completed = False
        counters = exec_counters or {}
        ack_trace = counters.get(TaskExecCounterKey.TRACE_ID)
        if ack_trace is not None:
            sp.set_trace(str(ack_trace))
        with self._lock:
            worker_id, task = self._doing.pop(task_id, (-1, None))
            meta = self._dispatch_meta.pop(task_id, None)
            if (
                task is not None
                and ack_trace is not None
                and meta is not None
                and str(ack_trace) != meta[0]
            ):
                # the ack names a task id from ANOTHER incarnation
                # that happens to collide with a live dispatch: hand
                # the live task back untouched and resolve the ack by
                # its trace (task_seq seeding makes this unreachable
                # unless the journal chain was lost — belt and braces)
                self._doing[task_id] = (worker_id, task)
                self._dispatch_meta[task_id] = meta
                logger.warning(
                    "ack for task id %d names trace %s but the live "
                    "dispatch is %s; resolving by trace",
                    task_id,
                    ack_trace,
                    meta[0],
                )
                task, meta = None, None
            if not task:
                if ack_trace is not None:
                    self._report_by_trace_locked(
                        str(ack_trace),
                        counters.get(TaskExecCounterKey.ATTEMPT, -1),
                        success,
                    )
                else:
                    logger.warning(
                        "Report for untracked task id %d; ignoring",
                        task_id,
                    )
            elif not success:
                task.extended_config["_attempt"] = (
                    task.extended_config.get("_attempt", 0) + 1
                )
                if meta is not None:
                    self._j(
                        "requeue",
                        trace=meta[0],
                        attempt=task.extended_config["_attempt"],
                        key=list(self._task_key(task)),
                    )
                if task.type == TaskType.TRAINING:
                    self._todo.append(task)
                elif task.type == TaskType.EVALUATION:
                    self._eval_todo.append(task)
                else:
                    self._todo.append(task)
            elif (
                task.type == TaskType.EVALUATION
                and self._evaluation_service is not None
            ):
                self._mark_done_locked(task, meta)
                evaluation_task_completed = True
            else:
                self._mark_done_locked(task, meta)
                logger.info(
                    "Task %d done; %d still outstanding",
                    task_id,
                    len(self._todo) + len(self._doing),
                )
        if task and meta:
            trace, attempt, t0 = meta
            sp.set_trace(trace)
            timeline = {
                "trace_id": trace,
                "task_id": task_id,
                "worker_id": worker_id,
                "attempt": attempt,
                "shard": task.shard_name,
                "dispatch_to_report_s": round(
                    time.monotonic() - t0, 6
                ),
            }
            if exec_counters and "consume_s" in exec_counters:
                timeline["consume_s"] = exec_counters["consume_s"]
            # _ship=False: master-side events are already home — only
            # worker-process events ride telemetry snapshots upstream
            profiling.events.emit(
                "task_done" if success else "task_requeued",
                _ship=False,
                **timeline,
            )
        if evaluation_task_completed:
            self._evaluation_service.complete_task()

    def _mark_done_locked(self, task, meta):
        """Journal a successful completion + retire its trace (lock
        held). The trace joins the dedup set so a replay of this ack —
        a worker resending through a master outage — is a no-op."""
        trace = meta[0] if meta else task.extended_config.get("trace_id")
        attempt = (
            meta[1] if meta else task.extended_config.get("_attempt", 0)
        )
        if trace is None:
            return
        key = self._task_key(task)
        self._done_traces[trace] = (key[0], key[1])
        self._trace_lookup.pop(trace, None)
        self._j("done", trace=trace, attempt=attempt, key=list(key))

    def _report_by_trace_locked(self, trace, attempt, success):
        """Resolve an ack whose task_id this incarnation never minted
        (it names a task dispatched by the PREVIOUS master): dedup
        against the journal's done set, or mark the recovered task done
        exactly once wherever it currently sits (lock held)."""
        if trace in self._done_traces:
            self._j("dup", trace=trace, attempt=attempt)
            logger.info(
                "replayed ack for already-done trace %s deduped", trace
            )
            return
        task = self._trace_lookup.get(trace)
        if task is None:
            logger.warning(
                "ack names unknown trace %s (job args changed across "
                "the relaunch?); ignoring",
                trace,
            )
            return
        if not success:
            # the recovered task is already queued for re-dispatch; a
            # stale failure ack adds nothing (and must not double-queue)
            logger.info(
                "stale failure ack for recovered trace %s ignored", trace
            )
            return
        # retire the task from wherever it lives now: still in todo
        # (not yet re-dispatched), re-dispatched (doing — the second
        # worker's eventual ack will dedup), or an eval queue
        removed = False
        try:
            self._todo.remove(task)
            removed = True
        except ValueError:
            pass
        if not removed:
            for tid, (_, t) in list(self._doing.items()):
                if t is task:
                    del self._doing[tid]
                    self._dispatch_meta.pop(tid, None)
                    removed = True
                    break
        if not removed:
            try:
                self._eval_todo.remove(task)
                removed = True
            except ValueError:
                pass
        if not removed:
            logger.warning(
                "recovered trace %s resolved but its task was not "
                "queued; marking done anyway",
                trace,
            )
        key = self._task_key(task)
        self._done_traces[trace] = (key[0], key[1])
        self._trace_lookup.pop(trace, None)
        self._j("done", trace=trace, attempt=attempt, key=list(key))
        logger.info(
            "recovered task (trace %s) marked done by a replayed ack",
            trace,
        )

    def apply_recovery(self, state):
        """Fast-forward this freshly constructed dispatcher to a
        journal's :class:`~elasticdl_tpu.master.journal.RecoveryState`.

        Called once at boot, BEFORE the RPC server serves: done tasks
        stay done (their keys are filtered out of the regenerated todo),
        tasks in flight at the crash requeue EXACTLY ONCE (they are in
        the regenerated set exactly once, re-stamped with their
        pre-crash trace ids so the PR-6 trace survives the master
        restart and late acks resolve), and the trace dedup set is
        installed so an ack the dead master already counted is a no-op.
        """
        with self._lock:
            self._trace_seq = max(self._trace_seq, state.trace_seq)
            # mint task ids PAST every id a previous incarnation ever
            # handed out: a worker's late ack names an OLD id, and an
            # id collision with a freshly-dispatched task would retire
            # the wrong one (the trace guard in report() is the second
            # line of defense)
            self._task_id = max(self._task_id, state.task_seq)
            self._done_traces = dict(state.done_traces)
            if state.epoch > self._epoch and self._training_shards:
                # the crash happened mid-epoch E: regenerate exactly
                # epoch E's task set (earlier epochs completed
                # wholesale, later ones are still future)
                self._todo = [
                    t for t in self._todo if t.type != TaskType.TRAINING
                ]
                self._epoch = state.epoch
                self._create_tasks_locked(TaskType.TRAINING)
                logger.info(
                    "recovery: resuming training epoch %d", self._epoch
                )
            dropped = 0
            kept = []
            for t in self._todo:
                if self._task_key(t) in state.done_keys:
                    dropped += 1
                else:
                    kept.append(t)
            self._todo = kept
            # re-stamp in-flight-at-crash tasks with their old traces
            by_key = {
                p["key"]: (trace, p["attempt"], p.get("xc"))
                for trace, p in state.pending.items()
            }
            requeued = []
            for t in self._todo:
                k = self._task_key(t)
                if k in by_key:
                    trace, attempt, _ = by_key.pop(k)
                    t.extended_config["trace_id"] = trace
                    t.extended_config["_attempt"] = attempt + 1
                    self._trace_lookup[trace] = t
                    requeued.append((trace, attempt + 1, k))
            # leftover pending tasks match nothing regenerated: an
            # EARLIER epoch's straggler (epoch E regenerates only its
            # own keys) or a SAVE_MODEL task minted by a deferred
            # callback this boot has not run — reconstruct them from
            # their journaled keys so they requeue exactly once too.
            # EVALUATION pendings are dropped: eval rounds pin model
            # versions the relaunch cannot honor, and the evaluation
            # service re-creates its rounds from its own triggers.
            dropped_eval = set()
            for k, (trace, attempt, xc) in list(by_key.items()):
                if k[0] == int(TaskType.EVALUATION):
                    logger.info(
                        "recovery: dropping in-flight evaluation task "
                        "(trace %s); the eval service re-triggers",
                        trace,
                    )
                    dropped_eval.add(trace)
                    del by_key[k]
                    continue
                task = Task(
                    shard_name=k[2],
                    start=k[3],
                    end=k[4],
                    type=TaskType(k[0]),
                    _epoch=k[1],
                    **(xc or {}),
                )
                task.extended_config["trace_id"] = trace
                task.extended_config["_attempt"] = attempt + 1
                self._todo.append(task)
                self._trace_lookup[trace] = task
                requeued.append((trace, attempt + 1, k))
                del by_key[k]
            # deferred callbacks the dead master already consumed (a
            # SAVE_MODEL task exists — done or requeued) must not fire
            # again and queue a second export
            save = int(TaskType.SAVE_MODEL)
            saves_minted = sum(
                1 for k in state.done_keys if k[0] == save
            ) + sum(1 for t in self._todo if t.type == TaskType.SAVE_MODEL)
            for _ in range(
                min(saves_minted, len(self._tasks_done_deferred_callbacks))
            ):
                self._tasks_done_deferred_callbacks.pop()
            for trace, attempt, k in requeued:
                self._j(
                    "requeue",
                    trace=trace,
                    attempt=attempt,
                    key=list(k),
                    recovery=True,
                )
            # deliberately-dropped eval traces are not "unresolved" —
            # warning about them would send operators hunting a config
            # mismatch that does not exist
            unresolved = sorted(
                set(state.pending)
                - set(self._trace_lookup)
                - dropped_eval
            )
        if unresolved:
            logger.warning(
                "recovery: %d pending trace(s) matched no regenerated "
                "task (did records_per_task or the data args change "
                "across the relaunch?): %s",
                len(unresolved),
                unresolved[:8],
            )
        profiling.events.emit(
            "master_recovery",
            _ship=False,
            epoch=state.epoch,
            done_tasks=len(state.done_keys),
            requeued=len(requeued),
            deduped_counter=state.counters.get("deduped", 0),
        )
        logger.info(
            "recovery: epoch %d, %d done task(s) retired, %d in-flight "
            "task(s) requeued with preserved traces",
            state.epoch,
            dropped,
            len(requeued),
        )

    def queue_depths(self):
        """Live queue sizes for the telemetry plane's depth gauge."""
        with self._lock:
            return {
                "todo": len(self._todo),
                "doing": len(self._doing),
                "eval_todo": len(self._eval_todo),
            }

    def finished(self):
        """True when no todo/eval/doing tasks remain.

        Under the lock: a lock-free read could interleave between
        get()'s pop from ``_todo`` and its insert into ``_doing`` and
        spuriously observe ALL queues empty while a task is in flight —
        master.py's completion poll would end the job early."""
        with self._lock:
            return (
                not self._todo and not self._eval_todo and not self._doing
            )

    def recover_tasks(self, worker_id):
        """Re-queue all in-flight tasks of a dead worker.

        Called by the instance manager on pod deletion / membership change
        (reference k8s_instance_manager.py:207, task_dispatcher.py:247-255).
        """
        with self._lock:
            ids = [
                tid
                for tid, (wid, _) in self._doing.items()
                if wid == worker_id
            ]
        for tid in ids:
            self.report(tid, False)

    def set_evaluation_service(self, evaluation_service):
        with self._lock:
            self._evaluation_service = evaluation_service
            if self._evaluation_shards and not self._training_shards:
                evaluation_service.init_eval_only_job(len(self._eval_todo))
