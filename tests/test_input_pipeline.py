"""Pipelined input plane tests (docs/input_pipeline.md).

Pins the tentpole invariants of the pipelined worker input plane:

- ordered parallel decode (`Dataset.map(fn, num_parallel_calls=N)`) is
  element-for-element equivalent to the serial map, including where an
  exception surfaces and what happens when the consumer is abandoned;
- vectorized batch assembly is array-for-array equivalent to the legacy
  `_tree_stack` on nested dict/tuple pytrees and the partial final batch;
- task prefetch yields the identical record stream and ack sequence as
  the serial fetch loop;
- a spare-park `requeue_inflight` under active task prefetch returns
  every unconsumed task to the master EXACTLY once — no doing-set leak,
  no double report — whether the race lands mid-`get_task` or
  mid-consumption;
- queued task acks defer to the boundary drain (overflow drains inline,
  failure acks flush immediately, requeue drains before fail-reports).
"""

import threading
import time

import numpy as np
import pytest

from elasticdl_tpu.common.constants import TaskType
from elasticdl_tpu.data.data_reader import AbstractDataReader, Metadata
from elasticdl_tpu.data.dataset import Dataset, _tree_stack
from elasticdl_tpu.data.input_stats import InputPlaneStats
from elasticdl_tpu.master.servicer import TaskResponse
from elasticdl_tpu.worker.task_data_service import TaskDataService


# ---------------------------------------------------------------------------
# fixtures: a ledgered fake master + a deterministic reader
# ---------------------------------------------------------------------------


class StubMaster:
    """Duck-types the worker surface TaskDataService uses, with the
    master-side doing-set ledger the leak assertions check."""

    def __init__(self, n_tasks, records_per_task, get_task_hook=None):
        self._lock = threading.Lock()
        self._todo = [
            TaskResponse(
                shard_name="shard_%d" % i,
                start=0,
                end=records_per_task,
                type=TaskType.TRAINING,
                model_version=0,
            )
            for i in range(n_tasks)
        ]
        self._next_id = 0
        self.doing = {}
        self.reports = []  # (task_id, err_msg) in arrival order
        self.dispensed = []  # task_ids in dispatch order
        self._get_task_hook = get_task_hook

    def get_task(self, task_type=None):
        if self._get_task_hook:
            self._get_task_hook(self)
        with self._lock:
            if not self._todo:
                return TaskResponse()  # empty shard: stream ends
            task = self._todo.pop(0)
            self._next_id += 1
            task.task_id = self._next_id
            self.doing[self._next_id] = task
            self.dispensed.append(self._next_id)
            return task

    def report_task_result(self, task_id, err_msg="", exec_counters=None):
        with self._lock:
            self.doing.pop(task_id, None)
            self.reports.append((task_id, err_msg))


class ListReader(AbstractDataReader):
    """shard_i record j -> b"shard_i:j"; optional per-record latency."""

    def __init__(self, latency_s=0.0):
        self._latency_s = latency_s

    def read_records(self, task):
        for i in range(task.start, task.end):
            if self._latency_s:
                time.sleep(self._latency_s)
            yield ("%s:%d" % (task.shard_name, i)).encode()

    def create_shards(self):
        return {}

    @property
    def metadata(self):
        return Metadata()


def make_service(stub, reader=None, **kwargs):
    return TaskDataService(
        stub, False, data_reader=reader or ListReader(), **kwargs
    )


def settle(predicate, timeout=5.0):
    """Wait for a cross-thread condition with a hard deadline."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return predicate()


# ---------------------------------------------------------------------------
# ordered parallel decode
# ---------------------------------------------------------------------------


def test_parallel_map_matches_serial_in_order():
    src = list(range(200))

    def fn(x):
        # jitter so pool completion order differs from submission order
        time.sleep((x % 5) * 1e-4)
        return x * 3

    serial = list(Dataset.from_tensors(src).map(fn))
    for n in (2, 4, 7):
        parallel = list(
            Dataset.from_tensors(src).map(fn, num_parallel_calls=n)
        )
        assert parallel == serial


def test_parallel_map_exception_surfaces_at_its_ordinal():
    def fn(x):
        if x == 7:
            raise RuntimeError("boom@7")
        time.sleep((x % 3) * 1e-4)
        return x * 2

    got = []
    with pytest.raises(RuntimeError, match="boom@7"):
        for v in Dataset.from_tensors(range(30)).map(
            fn, num_parallel_calls=4
        ):
            got.append(v)
    # elements before the failing one all arrived, in order, and
    # nothing past it leaked out
    assert got == [x * 2 for x in range(7)]


def test_parallel_map_cooperative_cancel_on_abandoned_consumer():
    pulled = []
    lock = threading.Lock()

    def src():
        i = 0
        while True:  # infinite source: only cancel can stop the pulls
            with lock:
                pulled.append(i)
            yield i
            i += 1

    it = iter(
        Dataset.from_generator(src).map(
            lambda x: x, num_parallel_calls=4
        )
    )
    assert [next(it) for _ in range(5)] == list(range(5))
    it.close()  # abandon the consumer (the spare-park shape)
    with lock:
        n_after_close = len(pulled)
    # the submission window bounds how far the source ran ahead
    assert n_after_close <= 5 + 2 * 4 + 1
    time.sleep(0.25)
    with lock:
        assert len(pulled) == n_after_close  # no pulls after the close


# ---------------------------------------------------------------------------
# vectorized batch assembly
# ---------------------------------------------------------------------------


def _assert_tree_equal(a, b):
    assert type(a) is type(b)
    if isinstance(a, dict):
        assert a.keys() == b.keys()
        for k in a:
            _assert_tree_equal(a[k], b[k])
    elif isinstance(a, (tuple, list)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _assert_tree_equal(x, y)
    else:
        assert a.dtype == b.dtype
        assert a.shape == b.shape
        np.testing.assert_array_equal(a, b)


def test_vectorized_batch_matches_tree_stack_on_nested_pytrees():
    elems = [
        (
            {
                "a": np.full((2, 3), i, np.float32),
                "b": (np.arange(4, dtype=np.int32) + i, np.int64(i)),
            },
            np.float64(i) / 7.0,
        )
        for i in range(10)
    ]
    # batch 4 over 10 elements: two full batches + a partial final batch
    fast = list(Dataset.from_tensors(elems).batch(4))
    ref = list(Dataset.from_tensors(elems).batch(4, vectorized=False))
    assert len(fast) == len(ref) == 3
    for f, r in zip(fast, ref):
        _assert_tree_equal(f, r)
    assert fast[-1][1].shape == (2,)  # the partial batch kept its size


def test_vectorized_batch_drop_remainder_and_scalars():
    elems = [{"x": i, "y": float(i)} for i in range(7)]
    fast = list(Dataset.from_tensors(elems).batch(3, drop_remainder=True))
    ref = list(
        Dataset.from_tensors(elems).batch(
            3, drop_remainder=True, vectorized=False
        )
    )
    assert len(fast) == len(ref) == 2
    for f, r in zip(fast, ref):
        _assert_tree_equal(f, r)


def test_vectorized_batch_falls_back_on_mixed_leaf_dtypes():
    # legacy np.stack PROMOTES int+float to float; raw buffer assignment
    # would silently truncate — the fast path must detect and fall back
    elems = [{"y": np.int64(3)}, {"y": np.float64(2.7)}]
    (fast,) = list(Dataset.from_tensors(elems).batch(2))
    (ref,) = list(
        Dataset.from_tensors(elems).batch(2, vectorized=False)
    )
    _assert_tree_equal(fast, ref)
    assert fast["y"].dtype == np.float64
    np.testing.assert_allclose(fast["y"], [3.0, 2.7])

    # first element narrower than a later one (shape mismatch): both
    # paths must agree (np.stack raises; the fast path defers to it)
    bad = [{"y": np.zeros(2)}, {"y": np.zeros(3)}]
    with pytest.raises(ValueError):
        list(Dataset.from_tensors(bad).batch(2))


def test_vectorized_batch_falls_back_for_bytes_leaves():
    elems = [b"a" * (i + 1) for i in range(5)]  # varying lengths
    fast = list(Dataset.from_tensors(elems).batch(2))
    ref = [_tree_stack(elems[0:2]), _tree_stack(elems[2:4]), _tree_stack(elems[4:5])]
    for f, r in zip(fast, ref):
        np.testing.assert_array_equal(f, r)


# ---------------------------------------------------------------------------
# shuffle satellite: reshuffle each iteration
# ---------------------------------------------------------------------------


def test_shuffle_reshuffles_each_iteration_deterministically():
    ds = Dataset.from_tensors(range(64)).shuffle(16, seed=11)
    first, second = list(ds), list(ds)
    assert sorted(first) == sorted(second) == list(range(64))
    assert first != second  # epoch 2 must not replay epoch 1's order

    replay = Dataset.from_tensors(range(64)).shuffle(
        16, seed=11, reshuffle_each_iteration=False
    )
    assert list(replay) == list(replay)

    # seeded determinism within one iteration: same seed, same epoch
    # index -> same order across dataset instances
    again = Dataset.from_tensors(range(64)).shuffle(16, seed=11)
    assert list(again) == first


# ---------------------------------------------------------------------------
# task prefetch
# ---------------------------------------------------------------------------


def _drain_stream(service):
    records = []
    ds = service.get_dataset()
    assert ds is not None
    for rec in ds:
        records.append(rec)
        service.report_record_done(1)
    service.drain_acks()
    return records


def test_task_prefetch_stream_equivalent_to_serial():
    serial_stub = StubMaster(5, 8)
    serial = _drain_stream(make_service(serial_stub, task_prefetch=0))

    for depth in (1, 3):
        stub = StubMaster(5, 8)
        pre = _drain_stream(
            make_service(stub, task_prefetch=depth)
        )
        assert pre == serial
        assert settle(lambda: not stub.doing)
        # identical ack sequence: every task acked once, in task order
        assert stub.reports == serial_stub.reports


def test_task_prefetch_with_queued_acks_equivalent():
    stub = StubMaster(4, 6)
    service = make_service(stub, task_prefetch=2, ack_queue_size=8)
    records = _drain_stream(service)
    assert len(records) == 4 * 6
    assert not stub.doing
    assert sorted(t for t, _ in stub.reports) == [1, 2, 3, 4]
    assert all(msg == "" for _, msg in stub.reports)


def test_whole_pipelined_plane_yields_the_serial_batches():
    """Every stage switched at once, as the worker runs them: task
    prefetch with whole-task warm read-ahead, ordered parallel decode,
    vectorized batch assembly and the boundary-drained ack queue yield
    the IDENTICAL batches in the IDENTICAL order as the serial plane
    (no prefetch, serial map, ``_tree_stack`` batches, synchronous
    acks), every record once, and leave nothing in the master's
    doing-set."""
    n_tasks, per_task, batch_size = 8, 48, 16

    def parse(record):
        shard, i = record.decode().split(":")
        seed = int(shard.split("_")[1]) * per_task + int(i)
        x = np.random.default_rng(seed).standard_normal(32)
        x = np.tanh(x.astype(np.float32)) * np.float32(seed % 7 + 1)
        return {"x": x, "y": np.int64(seed)}

    def run(pipelined):
        stub = StubMaster(n_tasks, per_task)
        service = make_service(
            stub,
            task_prefetch=2 if pipelined else 0,
            ack_queue_size=8 if pipelined else 0,
            prefetch_warm_records=per_task,
        )
        batches = []
        while True:
            ds = service.get_dataset()
            if ds is None:
                break
            ds = (
                ds.map(parse, num_parallel_calls=4 if pipelined else None)
                .batch(batch_size, vectorized=pipelined)
                .prefetch(2)
            )
            for b in ds:
                batches.append(b)
                service.report_record_done(int(b["y"].shape[0]))
            service.drain_acks()
        assert not stub.doing
        assert sorted(t for t, _ in stub.reports) == list(
            range(1, n_tasks + 1)
        )
        return batches

    serial, pipelined = run(False), run(True)
    assert len(serial) == len(pipelined) == n_tasks * per_task // batch_size
    for sb, pb in zip(serial, pipelined):
        _assert_tree_equal(sb, pb)
    seen = np.concatenate([b["y"] for b in pipelined])
    np.testing.assert_array_equal(seen, np.arange(n_tasks * per_task))


def test_task_prefetch_propagates_reader_errors_and_hands_task_back():
    class BoomReader(ListReader):
        def read_records(self, task):
            if task.shard_name == "shard_2":
                raise IOError("bad shard")
            yield from ListReader.read_records(self, task)

    stub = StubMaster(4, 4)
    service = make_service(
        stub, reader=BoomReader(), task_prefetch=2
    )
    with pytest.raises(IOError, match="bad shard"):
        _drain_stream(service)
    # the failed-read task was popped from the fetch queue but never
    # reached the ledger: it must still go back to the master (no
    # doing-set leak), along with everything the fetcher held
    assert settle(lambda: not stub.doing, timeout=5.0)
    reported = [t for t, _ in stub.reports]
    assert len(reported) == len(set(reported))
    assert set(stub.dispensed) == set(reported)


def test_requeue_under_active_prefetch_returns_every_task_once():
    """The tentpole race: a spare park while the fetcher holds prefetched
    tasks and the consumer is mid-task. Every dispensed task must end up
    acked or requeued EXACTLY once, with the master's doing-set empty."""
    stub = StubMaster(8, 10)
    service = make_service(
        stub, reader=ListReader(latency_s=0.002), task_prefetch=3
    )
    ds = service.get_dataset()
    it = iter(ds)
    consumed = 0
    for _ in range(15):  # 1.5 tasks in: ledger has in-flight work
        next(it)
        consumed += 1
        service.report_record_done(1)
    # give the fetcher time to stack prefetched-but-unconsumed tasks
    assert settle(lambda: len(stub.dispensed) >= 4)

    service.requeue_inflight("spare park")
    it.close()  # the park drops the round's stream

    # the fetcher hands back everything it held (its own thread may be
    # mid-get_task; that task comes back too)
    assert settle(lambda: not stub.doing, timeout=5.0)
    reported = [t for t, _ in stub.reports]
    assert len(reported) == len(set(reported)), (
        "task reported twice: %r" % stub.reports
    )
    # task 1 completed (10 records consumed): acked clean. Every other
    # dispensed task went back with the requeue/abandon message.
    acked = {t for t, msg in stub.reports if msg == ""}
    failed = {t for t, msg in stub.reports if msg != ""}
    assert acked == {1}
    assert failed == set(stub.dispensed) - {1}

    # the next round opens cleanly after the park
    assert service.get_dataset() is not None


def test_requeue_landing_mid_get_task_with_prefetch():
    """requeue_inflight racing the fetcher's in-flight get_task: the
    fetcher must hand its fresh task straight back, not append it."""
    service_box = {}
    fired = threading.Event()

    def hook(stub):
        # fire exactly once, from the FETCHER thread, after tasks began
        if len(stub.dispensed) == 2 and not fired.is_set():
            fired.set()
            service_box["svc"].requeue_inflight("spare park")

    stub = StubMaster(6, 4, get_task_hook=hook)
    service = make_service(stub, task_prefetch=1)
    service_box["svc"] = service
    ds = service.get_dataset()
    it = iter(ds)
    got = []
    try:
        for rec in it:
            got.append(rec)
            service.report_record_done(1)
    finally:
        it.close()
    assert settle(lambda: not stub.doing, timeout=5.0)
    reported = [t for t, _ in stub.reports]
    assert len(reported) == len(set(reported))
    assert set(stub.dispensed) == set(reported)


# ---------------------------------------------------------------------------
# async task acknowledgment
# ---------------------------------------------------------------------------


def test_queued_acks_defer_to_boundary_drain():
    stub = StubMaster(3, 4)
    service = make_service(stub, ack_queue_size=8)
    ds = service.get_dataset()
    records = list(ds)
    assert len(records) == 12
    service.report_record_done(8)  # completes tasks 1 and 2
    assert stub.reports == []  # queued, not sent: off the hot loop
    assert len(stub.doing) == 3
    service.drain_acks()
    assert stub.reports == [(1, ""), (2, "")]
    service.report_record_done(4)
    service.drain_acks()
    assert settle(lambda: not stub.doing)


def test_ack_queue_overflow_drains_inline():
    stub = StubMaster(5, 2)
    service = make_service(stub, ack_queue_size=2)
    ds = service.get_dataset()
    list(ds)
    service.report_record_done(6)  # 3 completed tasks > queue bound 2
    assert len(stub.reports) >= 3  # backpressure drained inline
    service.report_record_done(4)
    service.drain_acks()
    assert [t for t, _ in stub.reports] == [1, 2, 3, 4, 5]


def test_failure_ack_flushes_queue_and_reports_immediately():
    stub = StubMaster(3, 4)
    service = make_service(stub, ack_queue_size=8)
    ds = service.get_dataset()
    list(ds)
    service.report_record_done(4)  # task 1 clean -> queued
    assert stub.reports == []
    service.report_record_done(4, err_msg="step diverged")
    # ordered flush: task 1's clean ack lands BEFORE task 2's failure
    assert stub.reports[0] == (1, "")
    assert stub.reports[1][0] == 2 and stub.reports[1][1]
    service.report_record_done(4)
    service.drain_acks()
    assert settle(lambda: not stub.doing)


def test_requeue_drains_queued_acks_before_fail_reports():
    stub = StubMaster(3, 4)
    service = make_service(stub, ack_queue_size=8)
    ds = service.get_dataset()
    it = iter(ds)
    for _ in range(6):
        next(it)
    service.report_record_done(4)  # task 1 completed -> queued ack
    service.requeue_inflight("spare park")
    it.close()
    assert settle(lambda: not stub.doing)
    assert stub.reports[0] == (1, "")  # the queued clean ack went first
    failed = {t for t, msg in stub.reports if msg}
    assert 2 in failed and 1 not in failed


# ---------------------------------------------------------------------------
# input-plane observability
# ---------------------------------------------------------------------------


def test_input_stats_populate_across_stages():
    stub = StubMaster(3, 8)
    stats = InputPlaneStats()
    service = make_service(
        stub,
        reader=ListReader(latency_s=0.001),
        task_prefetch=1,
        stats=stats,
    )
    ds = service.get_dataset()
    ds = ds.map(
        lambda r: {"x": np.float32(len(r))}, num_parallel_calls=2
    ).batch(4).prefetch(1)
    batches = list(ds)
    service.drain_acks()
    snap = stats.snapshot()
    assert snap["tasks"] == 3
    assert snap["records"] == 24
    assert snap["batches"] == len(batches) == 6
    assert snap["read_s"] > 0
    assert snap["parse_s"] > 0
    assert snap["batch_s"] >= 0
    line = stats.format_line()
    assert "tasks=3" in line and "records=24" in line
    stats.reset()
    assert stats.snapshot()["records"] == 0


def test_stats_charge_ack_time():
    stub = StubMaster(2, 2)
    stats = InputPlaneStats()
    service = make_service(stub, ack_queue_size=4, stats=stats)
    ds = service.get_dataset()
    list(ds)
    service.report_record_done(4)
    service.drain_acks()
    assert stats.snapshot()["ack_s"] >= 0
    assert settle(lambda: not stub.doing)


# ---------------------------------------------------------------------------
# ODPS reader cache satellite
# ---------------------------------------------------------------------------


def test_odps_reader_cached_per_table_and_closed(monkeypatch):
    import elasticdl_tpu.data.odps_io as odps_io
    from elasticdl_tpu.data.data_reader import ODPSDataReader

    made = []

    class FakeODPSReader:
        def __init__(self, **kwargs):
            self.table = kwargs["table"]
            self.closed = False
            made.append(self)

        def table_schema_names(self):
            return ["c0"]

        def read_batch(self, start, end, columns=None):
            for i in range(start, end):
                yield (i,)

        def close(self):
            self.closed = True

    monkeypatch.setattr(odps_io, "ODPSReader", FakeODPSReader)
    reader = ODPSDataReader(
        project="p", access_id="i", access_key="k", table="t"
    )
    t1 = TaskResponse(
        shard_name="t:shard_0", start=0, end=3, type=TaskType.TRAINING
    )
    t2 = TaskResponse(
        shard_name="t:shard_1", start=3, end=6, type=TaskType.TRAINING
    )
    assert len(list(reader.read_records(t1))) == 3
    assert len(list(reader.read_records(t2))) == 3
    assert len(made) == 1  # one reader per table, not per task
    other = TaskResponse(
        shard_name="u:shard_0", start=0, end=2, type=TaskType.TRAINING
    )
    list(reader.read_records(other))
    assert len(made) == 2
    reader.close()
    assert all(r.closed for r in made)
    assert reader._readers == {}
