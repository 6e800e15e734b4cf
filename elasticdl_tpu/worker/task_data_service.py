"""Turns the master's task queue into one continuous record stream.

Role parity with the reference's worker-side task data service
(reference worker/task_data_service.py): the worker sees a single
iterable of records, while underneath this service pulls shard tasks
from the master on demand, remembers which tasks the consumed records
belong to, and acknowledges each task back to the master once the
worker has burned through its record range.  Control tasks are handled
inline: a WAIT ends the current stream so the worker re-polls later,
and a SAVE_MODEL is parked for the export path instead of being fed to
training.

The implementation is this repo's own: completion accounting lives in a
small in-flight ledger (`_drain_acknowledged`) keyed by a running
record cursor, rather than the reference's inline while-loop, and the
stream itself is a plain generator handed to the repo's tf-free
`Dataset` shim (data/dataset.py).

Pipelined input plane (docs/input_pipeline.md):

- ``task_prefetch=N`` runs a background fetcher thread that keeps up to
  N shard tasks fetched ahead of the one being consumed — the master
  RPC round trip and the cold first-record read of task N+1 overlap the
  consumption of task N. The fetcher is a full participant in the
  ``_round_id`` abandonment protocol: a spare park
  (``requeue_inflight``) hands every prefetched-but-unconsumed task
  back to the master exactly once.
- ``ack_queue_size=M`` moves task acknowledgment RPCs off the hot loop:
  completed tasks queue on a bounded ack queue drained at task/eval/
  checkpoint boundaries (``drain_acks``; same boundary discipline as
  the worker's ``_drain_ps_pushes``). Failure acks bypass the queue —
  the master must requeue a failed task promptly.
"""

import concurrent.futures
import itertools
import queue
import threading
import time
from collections import deque

from elasticdl_tpu.common.constants import TaskExecCounterKey, TaskType
from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.data.data_reader import create_data_reader
from elasticdl_tpu.data.dataset import Dataset, create_dataset_from_tasks
from elasticdl_tpu.data.input_stats import InputPlaneStats
from elasticdl_tpu.utils import profiling

_ABANDON_MSG = "round abandoned (spare park)"
_SENTINEL = object()


def _task_span(task):
    """Number of records a shard task covers."""
    return task.end - task.start


class _TaskFetcher:
    """Background task prefetcher for ONE stream round.

    Keeps up to ``depth`` tasks fetched ahead of the consumer: a single
    fetch thread pulls tasks from the master in order and parks them on
    an in-order queue, while a small warm pool reads each fetched
    task's first ``prefetch_warm_records`` records CONCURRENTLY — the
    cold reads of tasks N+1..N+depth overlap the consumption of task N
    (and each other) instead of riding the consumer's critical path.
    The queue itself is unbounded — depth is enforced by a semaphore
    the consumer releases as it pops — so fetcher puts never block (no
    abandoned-consumer put leak by construction;
    scripts/greps_guard.py).

    Abandonment: ``shutdown`` (idempotent, called by both the consumer
    generator's close and ``requeue_inflight``) cancels the fetch loop
    and hands every queued-but-unconsumed shard task back to the master
    exactly once. A fetch mid-``get_task`` when the round is abandoned
    notices the ``_round_id`` bump on return and hands its task back
    itself — the same step-aside protocol the serial producer pins in
    tests.
    """

    def __init__(self, service, gen_id, depth):
        self._service = service
        self._gen_id = gen_id
        self._q = queue.Queue()
        self._slots = threading.Semaphore(max(1, depth))
        self._cancel = threading.Event()
        # serializes puts against shutdown's cancel+drain so no item can
        # land in the queue after the final drain (exactly-once hand-back)
        self._offer_lock = threading.Lock()
        # one warm per in-flight task plus the one being consumed
        self._warm_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, depth) + 1,
            thread_name_prefix="edl-task-warm",
        )
        self._thread = threading.Thread(
            target=self._fetch_loop,
            daemon=True,
            name="edl-task-fetcher",
        )

    def start(self):
        self._thread.start()

    def _offer(self, item):
        """Enqueue ``item`` unless the round was already shut down."""
        with self._offer_lock:
            if self._cancel.is_set():
                return False
            self._q.put(item)
            return True

    def _fetch_loop(self):
        service = self._service
        try:
            while not self._cancel.is_set():
                if not self._slots.acquire(timeout=0.2):
                    continue  # consumer still working the window; re-check cancel
                with service._ledger_lock:
                    task = service._primed_task
                    service._primed_task = None
                if task is None:
                    task = service._worker.get_task()
                with service._ledger_lock:
                    stale = service._round_id != self._gen_id
                if stale or self._cancel.is_set():
                    # round abandoned while this fetch was in flight:
                    # hand the task straight back (appending it would
                    # leak it in the master's doing-set)
                    self._hand_back(task)
                    return
                records = None
                if task.shard_name and task.type != TaskType.SAVE_MODEL:
                    # warm asynchronously: the fetch loop moves straight
                    # on to the NEXT get_task while this task's head
                    # records are read in the pool
                    try:
                        records = self._warm_pool.submit(
                            service._warm_records, task
                        )
                    except RuntimeError:
                        # shutdown closed the pool between our stale
                        # check and here: the round is being abandoned —
                        # this task must go back like any other
                        self._hand_back(task)
                        return
                if not self._offer((task, records)):
                    self._hand_back(task)
                    return
                if not task.shard_name:
                    return  # WAIT/exhausted ends the round's fetching
        except BaseException as e:  # propagate into the consumer
            self._offer(e)

    def _hand_back(self, task):
        if task is not None and task.shard_name:
            self._service._worker.report_task_result(
                task.task_id, _ABANDON_MSG
            )

    def next_item(self):
        """The next fetched (task, records) in fetch order; None once the
        round is shut down. Re-raises a fetcher-side exception (a failed
        ``get_task`` or a failed warm read, in order)."""
        while True:
            try:
                item = self._q.get(timeout=0.2)
            except queue.Empty:
                if self._cancel.is_set():
                    return None
                continue
            if isinstance(item, BaseException):
                raise item
            self._slots.release()
            task, warm = item
            if warm is None:
                return task, None
            # resolve the warm future: usually already done (the pool
            # read it while earlier tasks were consumed); .result()
            # re-raises a reader error at the right task position
            try:
                records = warm.result()
            except concurrent.futures.CancelledError:
                # shutdown's cancel_futures beat this pop's resolution:
                # the round is being abandoned — hand the task back and
                # end the stream quietly (not a reader error)
                self._hand_back(task)
                return None
            except BaseException:
                # the task was popped but never reached the ledger, so
                # neither shutdown's drain nor requeue_inflight can see
                # it: hand it back HERE or it leaks in the master's
                # doing-set (another worker retries the read)
                self._service._worker.report_task_result(
                    task.task_id, "prefetch read failed"
                )
                raise
            return task, records

    def shutdown(self):
        """Cancel the fetch loop and hand back every queued task.

        Idempotent and shared by the consumer generator's close and
        ``requeue_inflight``: queue pops are atomic, so however many
        callers race here each task is reported back exactly once.
        """
        with self._offer_lock:
            self._cancel.set()
        # no new puts can land past this point; drain what's queued
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if isinstance(item, BaseException):
                continue
            task, _ = item
            self._hand_back(task)
        # in-flight warm reads finish and are dropped; nothing new starts
        self._warm_pool.shutdown(wait=False, cancel_futures=True)


class TaskDataService:
    """One worker's bridge between master tasks and its input stream.

    The worker object passed in must expose ``get_task()`` and
    ``report_task_result(task_id, err_msg, exec_counters=)`` — the same
    two calls every worker runtime in this repo already makes over the
    master channel.
    """

    def __init__(
        self,
        worker,
        training_with_evaluation,
        data_reader_params=None,
        task_prefetch=0,
        ack_queue_size=0,
        prefetch_warm_records=32,
        data_reader=None,
        stats=None,
    ):
        self._worker = worker
        self._training_with_evaluation = training_with_evaluation
        self._ledger_lock = threading.Lock()
        self._stream_open = True  # may get_dataset() hand out a new stream?
        self._parked_export_task = None
        self._clear_ledger()
        if data_reader is not None:
            # injected reader (the tests' fault injection)
            self.data_reader = data_reader
        else:
            reader_kwargs = dict(data_reader_params or {})
            self.data_reader = create_data_reader(
                data_origin=reader_kwargs.pop("data_origin", None),
                **reader_kwargs,
            )
        # First task is peeked once to prime reader metadata, then replayed
        # into the stream so no records are lost.
        self._primed_task = None
        self._metadata_primed = False
        # bumped (under the ledger lock) whenever an open round is
        # abandoned wholesale; stale producers notice and step aside
        self._round_id = 0
        # pipelined input plane (docs/input_pipeline.md)
        self._task_prefetch = max(0, int(task_prefetch))
        # how many of a prefetched task's records the fetcher reads
        # ahead (bounds prefetch memory at task_prefetch * this many
        # records); the rest stream on the consumer as before
        self._prefetch_warm_records = max(0, int(prefetch_warm_records))
        self._fetcher = None  # the open round's _TaskFetcher, if any
        self._ack_queue_size = max(0, int(ack_queue_size))
        self._ack_queue = deque()
        self._ack_lock = threading.Lock()
        # set (under the ledger lock) when a failure ack was queued;
        # report_record_done flushes right after releasing that lock
        self._ack_flush_needed = False
        self.stats = stats if stats is not None else InputPlaneStats()

    # ------------------------------------------------------------------
    # in-flight ledger
    # ------------------------------------------------------------------

    def _clear_ledger(self):
        self._inflight = deque()  # tasks whose records are being consumed
        self._record_cursor = 0  # records consumed against head of ledger
        self._bad_records = 0  # failed records charged to the head task

    def get_current_task(self):
        return self._inflight[0] if self._inflight else None

    def remaining_records_in_head_task(self):
        """Unconsumed record count of the ledger's head task (0 if empty).

        A failed train step calls report_record_done with exactly this
        amount to finish + fail-report the task it was on, without
        spilling the charge into tasks queued behind it.
        """
        with self._ledger_lock:
            if not self._inflight:
                return 0
            return max(0, _task_span(self._inflight[0]) - self._record_cursor)

    def _acknowledge(self, task, err_msg, outbox):
        """Queue one finished task's acknowledgment (ledger lock held).

        Never sends from here: the caller holds the ledger lock, and a
        master RPC under it would stall the fetcher's round checks and
        any concurrent spare-park requeue for a full round trip (edlint
        R5 pinned exactly this chain). With ``ack_queue_size`` > 0 a
        SUCCESS ack joins the bounded queue drained at boundaries (or
        on overflow); otherwise it lands in the caller's ``outbox`` and
        is sent right after the lock is released — the same
        snapshot-then-release pattern ps/servicer.pull_variable uses.
        Failure acks still flush promptly: the master must requeue a
        failed task, and the flush preserves ack order.
        """
        counters = (
            {TaskExecCounterKey.FAIL_COUNT: self._bad_records}
            if self._bad_records
            else None
        )
        t0 = getattr(task, "_edl_consume_t0", None)
        if t0 is not None:
            # worker-side half of the task timeline: first-ledger-append
            # to ack wall time rides the exec counters so the master's
            # task_done event carries both clocks
            counters = dict(counters or {})
            counters["consume_s"] = round(time.perf_counter() - t0, 6)
        trace = (getattr(task, "extended_config", None) or {}).get(
            "trace_id"
        )
        if trace is not None:
            # master recovery plane (docs/master_recovery.md): the ack
            # names the dispatcher's trace so a RELAUNCHED master (task
            # ids re-minted, this ack replayed through the failover
            # channel) can resolve it to the journaled task and dedup a
            # completion the dead incarnation already counted
            counters = dict(counters or {})
            counters[TaskExecCounterKey.TRACE_ID] = trace
            counters[TaskExecCounterKey.ATTEMPT] = task.extended_config.get(
                "_attempt", 0
            )
        if err_msg:
            logger.warning(
                "task %d finished with %d/%d bad records; last error: %s",
                task.task_id,
                self._bad_records,
                _task_span(task),
                err_msg,
            )
        self._bad_records = 0
        if self._ack_queue_size:
            # append only — _acknowledge runs under the ledger lock, and
            # an inline drain here would hold that lock across up to
            # queue-size master RPCs, stalling the fetcher's round
            # checks and a concurrent spare-park requeue. The caller
            # (report_record_done) drains AFTER releasing the ledger
            # lock, on overflow or (immediately) behind a failure; FIFO
            # order keeps earlier successes landing before the failure.
            with self._ack_lock:
                self._ack_queue.append((task.task_id, err_msg, counters))
            if err_msg:
                self._ack_flush_needed = True
            return
        outbox.append((task.task_id, err_msg, counters))

    def drain_acks(self):
        """Send every queued task acknowledgment to the master.

        Called at task/eval/checkpoint boundaries (the worker's
        ``_drain_ps_pushes`` discipline), on ack-queue overflow, before
        a failure ack, and by ``requeue_inflight`` before it
        fail-reports the in-flight set. Pops are atomic, so concurrent
        drains send disjoint acks — each exactly once, in order.
        """
        while True:
            with self._ack_lock:
                if not self._ack_queue:
                    return
                task_id, err_msg, counters = self._ack_queue.popleft()
            with profiling.span(
                "task/ack",
                trace_id=(counters or {}).get(TaskExecCounterKey.TRACE_ID),
            ), self.stats.timed("ack_s"):
                self._worker.report_task_result(
                    task_id, err_msg, exec_counters=counters
                )

    def _drain_acknowledged(self, err_msg, outbox):
        """Pop every ledger task the cursor has moved past, queueing its
        ack (bounded ack queue or the caller's ``outbox``).

        One batch can straddle several small tasks, so a single cursor
        advance may complete more than one; any failure tally rides out
        with the first task drained.
        """
        while self._inflight and self._record_cursor >= _task_span(
            self._inflight[0]
        ):
            done = self._inflight.popleft()
            self._record_cursor -= _task_span(done)
            self._acknowledge(done, err_msg, outbox)

    def report_record_done(self, count, err_msg=""):
        """Advance the cursor by ``count`` consumed records."""
        outbox = []
        with self._ledger_lock:
            self._record_cursor += count
            if err_msg:
                self._bad_records += count
            self._drain_acknowledged(err_msg, outbox)
        # inline acks go out AFTER the ledger lock is released: the
        # tasks are already popped, so a racing requeue_inflight cannot
        # double-report them, and the RPC no longer serializes the
        # fetcher/requeue paths behind a master round trip
        for task_id, msg, counters in outbox:
            with profiling.span(
                "task/ack",
                trace_id=(counters or {}).get(TaskExecCounterKey.TRACE_ID),
            ), self.stats.timed("ack_s"):
                self._worker.report_task_result(
                    task_id, msg, exec_counters=counters
                )
        if self._ack_queue_size:
            # backpressure OUTSIDE the ledger lock: completed-but-unacked
            # tasks must not pile up in the master's doing-set past the
            # bound — and a failure ack flushes the queue right here,
            # still within the caller's report_record_done — but the
            # drain RPCs must not serialize the ledger
            flush = self._ack_flush_needed
            self._ack_flush_needed = False
            with self._ack_lock:
                overflow = len(self._ack_queue) > self._ack_queue_size
            if overflow or flush:
                self.drain_acks()

    def requeue_inflight(self, err_msg):
        """Fail-report every in-flight (and primed) task — the master
        requeues them for other workers — and abandon the open record
        stream so the next ``get_dataset`` starts a clean round.

        A worker parked as an elastic SPARE cannot rewind its stream:
        the round's generator is mid-``read_records`` and ``prefetch``
        still buffers records of the tasks being handed back, so
        advancing the old stream after a requeue would charge leftover
        records against the NEXT ledger task (acknowledging work that
        never trained, double-training the requeued task elsewhere).
        Dropping the whole round is the only consistent cut. Bumping
        ``_round_id`` under the lock tells a producer thread mid-
        ``get_task`` to hand its fresh task straight back instead of
        appending to the cleared ledger (see ``_record_stream``); the
        abandoned producer itself is cancelled by prefetch when the
        consumer generator is dropped. With task prefetch the round's
        fetcher is shut down here too: every prefetched-but-unconsumed
        task is handed back exactly once (fetcher ``shutdown``), and a
        fetch mid-``get_task`` steps aside via the round bump."""
        with self._ledger_lock:
            self._round_id += 1
            inflight = list(self._inflight)
            self._clear_ledger()
            if self._primed_task is not None:
                # pulled for metadata priming, never consumed: it is in
                # the master's "doing" set and must go back too
                inflight.append(self._primed_task)
                self._primed_task = None
            fetcher, self._fetcher = self._fetcher, None
        # queued success acks first: they are for OTHER (completed)
        # tasks and must not be lost behind the fail-reports
        self.drain_acks()
        if fetcher is not None:
            fetcher.shutdown()
        for task in inflight:
            self._worker.report_task_result(task.task_id, err_msg)
        self._stream_open = True

    # ------------------------------------------------------------------
    # dataset construction
    # ------------------------------------------------------------------

    def get_validation_dataset(self, eval_task):
        """(dataset, model_version, task_id) for one eval task, or None."""
        if not eval_task:
            return None
        return (
            create_dataset_from_tasks([eval_task], self.data_reader),
            eval_task.model_version,
            eval_task.task_id,
        )

    def get_save_model_task_and_dataset(self):
        task, self._parked_export_task = self._parked_export_task, None
        if task is None:
            return None, None
        return task, create_dataset_from_tasks([task], self.data_reader)

    def _prime_reader_metadata(self):
        """Peek the first task so the reader can expose its metadata.

        Only a single record is pulled (enough for the reader to learn
        schema/shape info); the task itself is replayed by the stream so
        its records still reach training.
        """
        if self._metadata_primed:
            return
        task = self._worker.get_task()
        if task.shard_name:
            with self._ledger_lock:
                self._primed_task = task
            for _ in self.data_reader.read_records(task):
                break
        self._metadata_primed = True

    def get_dataset(self):
        """A Dataset spanning every task the master will hand out, or None."""
        if not self._stream_open:
            return None
        # a new round starts with an empty ack queue: the master must
        # see the previous round's completions before new work is pulled
        self.drain_acks()
        with self._ledger_lock:
            if self._inflight:
                logger.error(
                    "refusing a new dataset: %d in-flight tasks are still "
                    "unacknowledged",
                    len(self._inflight),
                )
                return None
            self._clear_ledger()
        self._prime_reader_metadata()
        self._stream_open = False
        return Dataset.from_generator(self._record_stream, stats=self.stats)

    def _warm_records(self, task, warm=None):
        """A record iterator for ``task`` with the first ``warm`` records
        already read — the cold read (file open / first page) happens on
        the caller's (fetcher) thread, off the consumer's critical path.

        Readers in this repo are stateless per read (mmap-backed
        recordio; ODPS opens a slice per call), so warming task N+1
        while task N's records are being consumed is safe: each task
        owns its own iterator and only one thread at a time advances it.
        """
        if warm is None:
            warm = self._prefetch_warm_records
        it = iter(self.data_reader.read_records(task))
        head = []
        # the dispatcher's trace id labels the prefetch-warm span, so a
        # profiler timeline joins this read to the same task's train
        # span on the consumer thread (docs/observability.md)
        trace_id = (getattr(task, "extended_config", None) or {}).get(
            "trace_id", "untraced"
        )
        with profiling.span(
            "task/warm", trace_id=trace_id, records=warm
        ), self.stats.timed("read_s"):
            for _ in range(max(0, warm)):
                rec = next(it, _SENTINEL)
                if rec is _SENTINEL:
                    return iter(head)
                head.append(rec)
        return itertools.chain(head, it)

    def _append_to_ledger(self, task, gen_id):
        """Append ``task`` to the in-flight ledger; False if the round
        went stale under our feet (the task is handed back instead).

        The round re-check happens under the SAME hold as the append:
        requeue_inflight can bump ``_round_id`` and clear the ledger at
        any point, and an append after that would charge the next
        round's records against a task the master already requeued
        (double-train + wrong accounting).
        """
        with self._ledger_lock:
            stale = self._round_id != gen_id
            if not stale:
                task._edl_consume_t0 = time.perf_counter()
                self._inflight.append(task)
        if stale:
            self._worker.report_task_result(task.task_id, _ABANDON_MSG)
        return not stale

    def _yield_records(self, records):
        """Yield a task's records, charging reader time (not the
        downstream consumer's time) to the read_s counter.

        The per-record timings accumulate in locals and hit the (locked)
        stats object ONCE per task — per-record lock traffic would tax
        exactly the hot loop this plane exists to shrink."""
        stats = self.stats
        it = iter(records)
        read_s = 0.0
        n = 0
        perf = time.perf_counter
        try:
            while True:
                t0 = perf()
                record = next(it, _SENTINEL)
                read_s += perf() - t0
                if record is _SENTINEL:
                    return
                if record is not None:
                    n += 1
                    yield record
        finally:
            stats.add("read_s", read_s)
            stats.count("records", n)

    def _handle_control_task(self, task):
        """WAIT pauses the stream, exhaustion ends it (True = stream
        over); SAVE_MODEL parks for the export path (False = continue)."""
        if not task.shard_name:
            if task.type == TaskType.WAIT:
                # More data may show up (e.g. a lazy next epoch); let
                # the worker loop ask again.
                self._stream_open = True
                logger.info("record stream paused (WAIT); will re-poll")
            else:
                logger.info("task queue exhausted; record stream ends")
            return True
        return False

    def _record_stream(self):
        """Generator: pull tasks until the master says stop, yield records."""
        gen_id = self._round_id
        if self._task_prefetch > 0:
            yield from self._record_stream_prefetched(gen_id)
            return
        while True:
            with self._ledger_lock:
                task, self._primed_task = self._primed_task, None
            if task is None:
                with profiling.span("task/wait"), self.stats.timed(
                    "task_starved_s"
                ):
                    task = self._worker.get_task()
            if self._round_id != gen_id:
                # the round was abandoned (spare park) while this
                # producer was fetching: hand the task straight back —
                # appending it to the cleared ledger would leak it in
                # the master's doing-set forever
                if task.shard_name:
                    self._worker.report_task_result(
                        task.task_id, _ABANDON_MSG
                    )
                return
            if not task.shard_name:
                self._handle_control_task(task)
                return
            if task.type == TaskType.SAVE_MODEL:
                self._parked_export_task = task
                continue
            if not self._append_to_ledger(task, gen_id):
                return
            self.stats.count("tasks")
            yield from self._yield_records(
                self.data_reader.read_records(task)
            )

    def _record_stream_prefetched(self, gen_id):
        """The ``task_prefetch`` consumer: tasks (and their warm first
        records) arrive from the background fetcher in fetch order; this
        generator owns the ledger appends and the control-task handling,
        so the consuming semantics are identical to the serial path."""
        fetcher = _TaskFetcher(self, gen_id, self._task_prefetch)
        with self._ledger_lock:
            if self._round_id != gen_id:
                return  # abandoned before the first record
            self._fetcher = fetcher
        fetcher.start()
        try:
            while True:
                with profiling.span("task/wait"), self.stats.timed(
                    "task_starved_s"
                ):
                    item = fetcher.next_item()
                if item is None:
                    return  # round shut down under us
                task, records = item
                if not task.shard_name:
                    self._handle_control_task(task)
                    return
                if task.type == TaskType.SAVE_MODEL:
                    self._parked_export_task = task
                    continue
                if not self._append_to_ledger(task, gen_id):
                    return
                self.stats.count("tasks")
                yield from self._yield_records(records)
        finally:
            # normal exhaustion, an error, and GC/close of an abandoned
            # consumer all land here; requeue_inflight may already have
            # detached and shut the fetcher down (shutdown is idempotent
            # and hands queued tasks back exactly once either way)
            with self._ledger_lock:
                if self._fetcher is fetcher:
                    self._fetcher = None
            fetcher.shutdown()
