"""Telemetry-plane tests (docs/observability.md).

Covers the metrics registry (label cardinality bound, histogram bucket
edges, concurrent increments — this suite runs under EDL_LOCKTRACE=1 in
scripts/check.sh), the Prometheus text exposition (golden parse), the
JSONL event log (monotonic ids across a simulated resize + task
requeue), the dispatcher's task-lifecycle tracing, the worker
snapshot -> master aggregation path, the /metrics HTTP endpoint, the
RPC-layer instrumentation, and the TensorBoard export.
"""

import json
import threading
import time
import urllib.request

import pytest

from elasticdl_tpu.common.constants import TaskType
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.master.telemetry import (
    JobTelemetry,
    TelemetryHTTPServer,
    TelemetryTBExporter,
)
from elasticdl_tpu.utils import profiling
from elasticdl_tpu.utils.profiling import EventLog, MetricsRegistry
from elasticdl_tpu.worker.telemetry import WorkerTelemetry


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_basicss():
    r = MetricsRegistry()
    c = r.counter("edl_t_total", "help", labels=("method",))
    c.inc(method="a")
    c.inc(2, method="a")
    c.inc(method="b")
    assert c.value(method="a") == 3
    assert c.value(method="b") == 1
    g = r.gauge("edl_t_depth")
    g.set(5)
    g.inc(2)
    assert g.value() == 7
    # re-registration returns the same family; mismatched shape refuses
    assert r.counter("edl_t_total", labels=("method",)) is c
    with pytest.raises(ValueError):
        r.counter("edl_t_total", labels=("other",))
    with pytest.raises(ValueError):
        r.gauge("edl_t_total")


def test_histogram_bucket_edges_are_le_inclusive():
    r = MetricsRegistry()
    h = r.histogram("edl_t_lat", buckets=(0.01, 0.1, 1.0))
    # exactly-on-edge observations land IN that bucket (prometheus le)
    for v in (0.01, 0.005, 0.1, 0.5, 1.0, 3.0):
        h.observe(v)
    buckets, total, count = h.data()
    assert buckets == [2, 1, 2, 1]  # <=0.01, <=0.1, <=1.0, +Inf
    assert count == 6
    assert total == pytest.approx(sum((0.01, 0.005, 0.1, 0.5, 1.0, 3.0)))
    # exposition buckets are CUMULATIVE
    text = r.prometheus_text()
    assert 'edl_t_lat_bucket{le="0.01"} 2' in text
    assert 'edl_t_lat_bucket{le="0.1"} 3' in text
    assert 'edl_t_lat_bucket{le="1"} 5' in text
    assert 'edl_t_lat_bucket{le="+Inf"} 6' in text
    assert "edl_t_lat_count 6" in text


def test_label_cardinality_is_bounded():
    r = MetricsRegistry()
    c = r.counter("edl_t_total", labels=("id",))
    for i in range(MetricsRegistry.MAX_SERIES + 50):
        c.inc(id="row-%d" % i)
    # the runaway label collapsed into the overflow series
    assert c.series_count() <= MetricsRegistry.MAX_SERIES + 1
    from elasticdl_tpu.utils.profiling import _Metric

    assert c.value(id=_Metric.OVERFLOW) == 50
    # existing series keep incrementing normally after the overflow
    c.inc(5, id="row-0")
    assert c.value(id="row-0") == 6


def test_concurrent_increments_are_exact():
    r = MetricsRegistry()
    c = r.counter("edl_t_total", labels=("who",))
    h = r.histogram("edl_t_lat", buckets=(0.5,))
    n_threads, per_thread = 8, 500

    def work(i):
        for _ in range(per_thread):
            c.inc(who="w%d" % (i % 2))
            h.observe(0.1)

    threads = [
        threading.Thread(target=work, args=(i,)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(who="w0") + c.value(who="w1") == n_threads * per_thread
    _, _, count = h.data()
    assert count == n_threads * per_thread


def test_metrics_disabled_is_a_noop():
    r = MetricsRegistry()
    c = r.counter("edl_t_total")
    profiling.set_metrics_enabled(False)
    try:
        c.inc(5)
        assert c.value() == 0
    finally:
        profiling.set_metrics_enabled(True)
    c.inc(1)
    assert c.value() == 1


# ---------------------------------------------------------------------------
# prometheus exposition: golden parse
# ---------------------------------------------------------------------------


def _parse_prometheus(text):
    """Minimal 0.0.4 parser: {name: {frozenset(label items): value}},
    plus the TYPE map. Raises on malformed sample lines, so the test
    doubles as a format check."""
    import re

    types, samples = {}, {}
    sample_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? ([^ ]+)$"
    )
    label_re = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        m = sample_re.match(line)
        assert m, "malformed sample line: %r" % line
        name, labels, value = m.groups()
        parsed = frozenset(label_re.findall(labels or ""))
        samples.setdefault(name, {})[parsed] = float(value)
    return types, samples


def test_prometheus_exposition_golden_parse():
    r = MetricsRegistry()
    c = r.counter("edl_rpc_errors_total", "errors", labels=("method", "code"))
    c.inc(3, method="get_task", code="UNAVAILABLE")
    g = r.gauge("edl_queue_depth", labels=("queue",))
    g.set(7, queue="todo")
    h = r.histogram("edl_lat_seconds", labels=("m",), buckets=(0.1,))
    h.observe(0.05, m='we"ird\nname')  # exercises label escaping
    r.register_collector(lambda: [("edl_live", {"k": "v"}, 1.5)])
    types, samples = _parse_prometheus(r.prometheus_text())
    assert types["edl_rpc_errors_total"] == "counter"
    assert types["edl_queue_depth"] == "gauge"
    assert types["edl_lat_seconds"] == "histogram"
    assert (
        samples["edl_rpc_errors_total"][
            frozenset(
                {("method", "get_task"), ("code", "UNAVAILABLE")}
            )
        ]
        == 3
    )
    assert samples["edl_queue_depth"][frozenset({("queue", "todo")})] == 7
    assert samples["edl_live"][frozenset({("k", "v")})] == 1.5
    # the escaped label round-trips through the parser
    (key,) = samples["edl_lat_seconds_count"].keys()
    assert ("m", 'we\\"ird\\nname') in key


def test_counters_shim_bridges_into_the_default_registry():
    profiling.counters.inc("telemetry_test/bridge", 4)
    try:
        text = profiling.metrics.prometheus_text()
        assert 'edl_counter{name="telemetry_test/bridge"} 4' in text
    finally:
        profiling.counters.reset("telemetry_test/")


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------


def test_event_log_monotonic_ids_and_jsonl_sink(tmp_path):
    log = EventLog()
    path = str(tmp_path / "events.jsonl")
    log.attach_file(path)
    log.emit("resize_begin", epoch=1, world_size=4)
    log.emit("task_requeued", task_id=7, trace_id="t000007")
    log.emit("resize_end", epoch=1, compile_phase="cache_miss")
    lines = [
        json.loads(l)
        for l in open(path, encoding="utf-8").read().splitlines()
    ]
    assert [e["kind"] for e in lines] == [
        "resize_begin",
        "task_requeued",
        "resize_end",
    ]
    ids = [e["id"] for e in lines]
    assert ids == sorted(ids) and len(set(ids)) == 3
    assert lines[1]["trace_id"] == "t000007"
    log.close_file()


def test_event_log_pending_drain_and_ingest_do_not_loop():
    log = EventLog()
    log.emit("ps_shard_failure", addr="x:1")
    shipped = log.drain_pending()
    assert [e["kind"] for e in shipped] == ["ps_shard_failure"]
    assert log.drain_pending() == []  # drained exactly once
    # master-side re-log: new ids, provenance kept, and NOT re-shipped
    log.ingest(shipped, worker="3")
    assert log.drain_pending() == []
    tail = log.tail(10)
    assert tail[-1]["kind"] == "ps_shard_failure"
    assert tail[-1]["worker"] == "3"
    assert tail[-1]["src_id"] == shipped[0]["id"]
    assert tail[-1]["id"] > shipped[0]["id"]


# ---------------------------------------------------------------------------
# dispatcher: trace ids, timeline events, queue depth
# ---------------------------------------------------------------------------


def _dispatcher(records=8, per_task=2):
    return TaskDispatcher({"f": (0, records)}, {}, {}, per_task, 1)


def test_dispatcher_stamps_stable_trace_ids_across_requeue():
    profiling.events.reset()
    d = _dispatcher()
    task_id, task = d.get(worker_id=0)
    trace = task.extended_config["trace_id"]
    assert trace.startswith("t")
    d.report(task_id, False)  # requeue
    # the SAME logical task redispatches under the same trace id with a
    # bumped attempt
    seen = {}
    for _ in range(d.queue_depths()["todo"]):
        tid, t = d.get(worker_id=1)
        seen[t.extended_config["trace_id"]] = (
            tid,
            t.extended_config["_attempt"],
        )
    assert trace in seen
    assert seen[trace][1] == 1  # second attempt
    events = profiling.events.tail(10)
    requeues = [e for e in events if e["kind"] == "task_requeued"]
    assert len(requeues) == 1
    assert requeues[0]["trace_id"] == trace
    assert requeues[0]["attempt"] == 0
    assert requeues[0]["dispatch_to_report_s"] >= 0


def test_event_ordering_across_simulated_resize_plus_requeue(tmp_path):
    """The JSONL log interleaves a resize with a task requeue in emit
    order, ids strictly increasing (the satellite's ordering pin)."""
    profiling.events.reset()
    path = str(tmp_path / "events.jsonl")
    profiling.events.attach_file(path)
    try:
        d = _dispatcher()
        t1, _ = d.get(worker_id=0)
        profiling.events.emit(
            "resize_begin", epoch=2, world_size=3, _ship=False
        )
        d.report(t1, False)  # requeue lands INSIDE the resize window
        profiling.events.emit(
            "resize_end",
            epoch=2,
            compile_phase="cache_hit",
            _ship=False,
        )
        t2, _ = d.get(worker_id=1)
        d.report(t2, True)
        lines = [
            json.loads(l)
            for l in open(path, encoding="utf-8").read().splitlines()
        ]
        kinds = [e["kind"] for e in lines]
        assert kinds == [
            "resize_begin",
            "task_requeued",
            "resize_end",
            "task_done",
        ]
        ids = [e["id"] for e in lines]
        assert all(b > a for a, b in zip(ids, ids[1:]))
    finally:
        profiling.events.reset()


def test_queue_depths_track_dispatch_lifecycle():
    d = _dispatcher(records=8, per_task=2)
    assert d.queue_depths() == {"todo": 4, "doing": 0, "eval_todo": 0}
    tid, _ = d.get(worker_id=0)
    assert d.queue_depths()["doing"] == 1
    assert d.queue_depths()["todo"] == 3
    d.report(tid, True)
    assert d.queue_depths()["doing"] == 0


def test_timeline_event_carries_worker_consume_time():
    profiling.events.reset()
    d = _dispatcher()
    tid, _ = d.get(worker_id=5)
    d.report(tid, True, exec_counters={"consume_s": 0.25})
    done = [
        e for e in profiling.events.tail(5) if e["kind"] == "task_done"
    ]
    assert done and done[0]["consume_s"] == 0.25
    assert done[0]["worker_id"] == 5


# ---------------------------------------------------------------------------
# worker snapshot -> master aggregation -> endpoint
# ---------------------------------------------------------------------------


def test_worker_telemetry_snapshot_rates_and_interval_gate():
    from elasticdl_tpu.data.input_stats import InputPlaneStats

    stats = InputPlaneStats()
    wt = WorkerTelemetry(3, stats=stats, interval_s=3600.0)
    wt.on_batch(16)
    wt.on_batch(16)
    assert wt.maybe_snapshot() is None  # interval not elapsed
    stats.add("consumer_starved_s", 0.5)
    snap = wt.maybe_snapshot(force=True)
    assert snap["worker_id"] == 3
    assert snap["examples_total"] == 32
    assert snap["steps_total"] == 2
    assert snap["examples_per_sec"] > 0
    assert snap["input"]["consumer_starved_s"] == pytest.approx(0.5)
    assert 0.0 <= snap["consumer_starved_ratio"] <= 1.0


def test_job_telemetry_aggregates_and_serves_metrics_endpoint():
    profiling.events.reset()
    d = _dispatcher()
    registry = MetricsRegistry()
    jt = JobTelemetry(task_dispatcher=d, registry=registry)
    jt.ingest(
        {
            "worker_id": 0,
            "examples_per_sec": 100.0,
            "steps_per_sec": 5.0,
            "input": {"consumer_starved_s": 0.1, "read_s": 0.2},
            "consumer_starved_ratio": 0.05,
            "hot_row_hit_rate": 0.9,
            "events": [
                {"kind": "ps_shard_failure", "id": 9, "addr": "x:1"}
            ],
        }
    )
    jt.ingest({"worker_id": 1, "examples_per_sec": 50.0})
    server = TelemetryHTTPServer(jt, port=0)
    try:
        body = urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % server.port, timeout=10
        ).read().decode("utf-8")
        _, samples = _parse_prometheus(body)
        per_worker = samples["edl_worker_examples_per_sec"]
        assert per_worker[frozenset({("worker", "0")})] == 100.0
        assert per_worker[frozenset({("worker", "1")})] == 50.0
        assert (
            samples["edl_job_examples_per_sec"][frozenset()] == 150.0
        )
        # live queue depth from the dispatcher collector
        assert (
            samples["edl_task_queue_depth"][
                frozenset({("queue", "todo")})
            ]
            == 4
        )
        assert (
            samples["edl_worker_hot_row_hit_rate"][
                frozenset({("worker", "0")})
            ]
            == 0.9
        )
        # shipped worker event was re-logged with the worker label
        ev_body = urllib.request.urlopen(
            "http://127.0.0.1:%d/events" % server.port, timeout=10
        ).read().decode("utf-8")
        events = [
            json.loads(l) for l in ev_body.splitlines() if l.strip()
        ]
        failures = [
            e for e in events if e["kind"] == "ps_shard_failure"
        ]
        assert failures and failures[0]["worker"] == "0"
        assert failures[0]["src_id"] == 9
    finally:
        server.close()
        profiling.events.reset()


def test_servicer_report_telemetry_path():
    import optax

    from elasticdl_tpu.master.servicer import MasterServicer

    d = _dispatcher()
    registry = MetricsRegistry()
    jt = JobTelemetry(task_dispatcher=d, registry=registry)
    servicer = MasterServicer(
        1, 16, optax.sgd(0.1), d, telemetry=jt
    )
    servicer.report_telemetry(
        {"worker_id": 7, "examples_per_sec": 42.0}
    )
    assert jt.worker_snapshots()["7"]["examples_per_sec"] == 42.0
    text = jt.prometheus_text()
    assert 'edl_worker_examples_per_sec{worker="7"} 42' in text


# ---------------------------------------------------------------------------
# RPC-layer instrumentation (client + servicer side)
# ---------------------------------------------------------------------------


def test_rpc_layer_records_client_and_server_histograms():
    from elasticdl_tpu.rpc.core import Client, serve
    from elasticdl_tpu.utils.profiling import (
        instrument_service_methods,
    )

    methods = instrument_service_methods(
        {"echo": lambda req: {"x": req.get("x", 0) + 1}},
        role="testsrv",
    )
    server = serve(methods, 0)
    client = Client("localhost:%d" % server._edl_port)
    try:
        before = profiling.metrics.histogram(
            "edl_rpc_client_latency_seconds", labels=("method",)
        ).data(method="echo")
        n_before = before[2] if before else 0
        assert client.call("echo", x=41)["x"] == 42
        after = profiling.metrics.histogram(
            "edl_rpc_client_latency_seconds", labels=("method",)
        ).data(method="echo")
        assert after[2] == n_before + 1
        srv = profiling.metrics.histogram(
            "edl_rpc_server_latency_seconds", labels=("role", "method")
        ).data(role="testsrv", method="echo")
        assert srv is not None and srv[2] >= 1
    finally:
        client.close()
        server.stop(grace=None)


def test_rpc_client_error_counter_on_dead_endpoint():
    import grpc

    from elasticdl_tpu.rpc.core import Client

    errors = profiling.metrics.counter(
        "edl_rpc_client_errors_total", labels=("method", "code")
    )
    before = errors.value(method="nope", code="UNAVAILABLE")
    client = Client("localhost:1", deadline_s=2.0)  # nothing listens
    try:
        with pytest.raises(grpc.RpcError):
            client.call("nope")
    finally:
        client.close()
    assert errors.value(method="nope", code="UNAVAILABLE") == before + 1


# ---------------------------------------------------------------------------
# TensorBoard export
# ---------------------------------------------------------------------------


def test_telemetry_tb_exporter_round_trip(tmp_path):
    import glob

    from elasticdl_tpu.common.tb_events import read_events

    registry = MetricsRegistry()
    registry.counter("edl_t_total").inc(3)
    h = registry.histogram("edl_t_lat", buckets=(0.1,))
    h.observe(0.05)
    h.observe(0.15)
    exporter = TelemetryTBExporter(
        str(tmp_path), registry=registry, interval_s=3600.0, step_fn=lambda: 7
    )
    try:
        exporter.flush()
    finally:
        exporter.close()
    (path,) = glob.glob(str(tmp_path / "*.telemetry"))
    scalars = {}
    for _, step, pairs in read_events(path):
        for tag, value in pairs:
            scalars[tag] = (step, value)
    assert scalars["telemetry/edl_t_total"] == (7, 3.0)
    assert scalars["telemetry/edl_t_lat/count"][1] == 2.0
    assert scalars["telemetry/edl_t_lat/mean"][1] == pytest.approx(
        0.1, rel=1e-5
    )


def test_telemetry_tb_exporter_concurrent_flush_exactness(tmp_path):
    """edlint R8 regression (static lockset finding): the exporter
    thread and close()'s final flush both run flush(); the _flushes
    bump must not lose updates and two flushes must not interleave
    add_scalars. Serialized flushes make this exact."""
    import threading

    registry = MetricsRegistry()
    registry.counter("edl_t_total").inc(1)
    exporter = TelemetryTBExporter(
        str(tmp_path), registry=registry, interval_s=3600.0
    )
    n, per = 8, 5
    try:
        def pound():
            for _ in range(per):
                exporter.flush()

        threads = [threading.Thread(target=pound) for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert exporter._flushes == n * per
    finally:
        exporter.close()
    # close() ran one final flush after the join
    assert exporter._flushes == n * per + 1


def test_worker_ships_snapshot_through_stub():
    class _Stub:
        def __init__(self):
            self.snaps = []

        def report_telemetry(self, snap):
            self.snaps.append(snap)

    wt = WorkerTelemetry(2, interval_s=0.001)
    wt.on_batch(8)
    time.sleep(0.005)
    stub = _Stub()
    assert wt.ship(stub)
    assert stub.snaps and stub.snaps[0]["worker_id"] == 2
    # a stub without the method is silently skipped (bare test fixtures)
    assert not wt.ship(object(), force=True)


def test_failed_ship_requeues_drained_events():
    class _DownStub:
        def report_telemetry(self, snap):
            raise RuntimeError("master unreachable")

    profiling.events.reset()
    profiling.events.emit("ps_shard_failure", addr="x:1")
    wt = WorkerTelemetry(4, interval_s=0.001)
    time.sleep(0.005)
    assert not wt.ship(_DownStub())
    # the drained event went back on the pending buffer and rides the
    # next successful snapshot
    class _UpStub:
        def __init__(self):
            self.snaps = []

        def report_telemetry(self, snap):
            self.snaps.append(snap)

    up = _UpStub()
    assert wt.ship(up, force=True)
    kinds = [e["kind"] for e in up.snaps[0].get("events", [])]
    assert "ps_shard_failure" in kinds
