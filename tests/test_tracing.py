"""Distributed-tracing plane tests (docs/observability.md).

Covers the Span API (nesting, thread-local context, the EDL_METRICS
kill switch, ring/pending bounds), cross-process span-context
propagation over real gRPC (the ``_sctx`` wire field + server-side
``rpc/*`` spans), trace-id survival across a task requeue AND a master
crash/relaunch (journal replay — pre- and post-failover spans link
into one trace), the worker-snapshot shipping path into the master's
``/trace`` endpoint, the ``/events?since=`` cursor, the Chrome
trace-event export, the tracetool critical-path breakdown, and the
crash flight recorder (trigger kinds, rate limit, prune, chaos-kill
wiring). Runs under EDL_LOCKTRACE=1 in scripts/check.sh.
"""

import json
import os
import threading
import time
import urllib.request

import pytest

from elasticdl_tpu.common.constants import TaskExecCounterKey, TaskType
from elasticdl_tpu.master.journal import MasterJournal
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.master.telemetry import (
    JobTelemetry,
    ProcessTelemetry,
    TelemetryHTTPServer,
)
from elasticdl_tpu.tools.tracetool import critical_path
from elasticdl_tpu.utils import profiling
from elasticdl_tpu.utils.profiling import (
    NULL_SPAN,
    SpanLog,
    chrome_trace,
)
from elasticdl_tpu.worker.telemetry import WorkerTelemetry


@pytest.fixture(autouse=True)
def _clean_tracing_state():
    profiling.spans.reset()
    profiling.events.reset()
    profiling.flight_recorder.disarm()
    yield
    profiling.spans.reset()
    profiling.events.reset()
    profiling.flight_recorder.disarm()


# ---------------------------------------------------------------------------
# the Span API
# ---------------------------------------------------------------------------


def test_span_nesting_inherits_trace_and_parent():
    with profiling.span("step", trace_id="t1", examples=16) as outer:
        with profiling.span("step/compute") as inner:
            assert inner.trace_id == "t1"
            assert inner.parent_id == outer.span_id
            assert profiling.current_span() is inner
        assert profiling.current_span() is outer
    assert profiling.current_span() is None
    recs = {r["name"]: r for r in profiling.spans.tail()}
    assert recs["step"]["trace"] == "t1"
    assert recs["step"]["examples"] == 16
    assert recs["step/compute"]["parent"] == recs["step"]["span"]
    assert recs["step/compute"]["dur"] >= 0
    # span ids are process-scoped unique and carry the proc tag
    assert recs["step"]["span"].startswith(recs["step"]["proc"] + "/")


def test_span_records_error_kind_on_exception():
    with pytest.raises(ValueError):
        with profiling.span("step", trace_id="t1"):
            raise ValueError("boom")
    (rec,) = profiling.spans.tail()
    assert rec["error"] == "ValueError"


def test_kill_switch_returns_null_span_and_records_nothing():
    profiling.set_metrics_enabled(False)
    try:
        sp = profiling.span("step", trace_id="t1")
        assert sp is NULL_SPAN
        with sp:
            assert profiling.wire_span_context() is None
        assert profiling.spans.tail() == []
        # flight recorder honors the switch too
        assert profiling.flight_recorder.trigger("chaos_kill") is None
    finally:
        profiling.set_metrics_enabled(True)


def test_span_ring_and_pending_are_bounded_and_requeue_preserves_order():
    log = SpanLog(capacity=4, pending_capacity=3)
    for i in range(6):
        with log.begin("s%d" % i, trace_id="t"):
            pass
    assert [r["name"] for r in log.tail()] == ["s2", "s3", "s4", "s5"]
    drained = log.drain_pending()
    assert [r["name"] for r in drained] == ["s3", "s4", "s5"]
    log.requeue(drained[:2])
    assert [r["name"] for r in log.drain_pending()] == ["s3", "s4"]


def test_untraced_context_is_not_propagated():
    with profiling.span("host/maintenance"):
        # no trace id -> nothing rides the wire (servers would record
        # orphan spans for every untraced RPC otherwise)
        assert profiling.wire_span_context() is None
    assert profiling.span_from_wire({}, "rpc/x") is NULL_SPAN
    assert (
        profiling.span_from_wire({"_sctx": "bogus"}, "rpc/x")
        is NULL_SPAN
    )
    assert profiling.span_from_wire(None, "rpc/x") is NULL_SPAN


# ---------------------------------------------------------------------------
# cross-process propagation
# ---------------------------------------------------------------------------


def test_span_context_propagates_over_real_grpc():
    from elasticdl_tpu.rpc.core import Client, serve

    def handler(req):
        # a nested server-side span (the ps/apply shape) must parent on
        # the rpc span the instrumentation wrapper entered
        with profiling.span("ps/apply"):
            return {"ok": True}

    methods = profiling.instrument_service_methods(
        {"push_gradient": handler}, role="ps"
    )
    server = serve(methods, 0)
    client = Client("localhost:%d" % server._edl_port)
    try:
        with profiling.span("step", trace_id="t42") as caller:
            client.call(
                "push_gradient", _retriable=False, model_version=1
            )
    finally:
        client.close()
        server.stop(grace=None)
    recs = {r["name"]: r for r in profiling.spans.tail()}
    rpc = recs["rpc/push_gradient"]
    assert rpc["trace"] == "t42"
    assert rpc["parent"] == caller.span_id
    assert rpc["role"] == "ps"
    apply_rec = recs["ps/apply"]
    assert apply_rec["trace"] == "t42"
    assert apply_rec["parent"] == rpc["span"]


def test_untraced_rpc_records_no_server_span():
    from elasticdl_tpu.rpc.core import Client, serve

    methods = profiling.instrument_service_methods(
        {"ps_status": lambda req: {"ok": True}}, role="ps"
    )
    server = serve(methods, 0)
    client = Client("localhost:%d" % server._edl_port)
    try:
        client.call("ps_status")  # no open span -> no _sctx
    finally:
        client.close()
        server.stop(grace=None)
    assert [
        r for r in profiling.spans.tail() if r["name"].startswith("rpc/")
    ] == []


def test_pipelined_embedding_pull_span_carries_trace():
    from elasticdl_tpu.nn.comm_plane import EmbeddingPullPipeline

    pipe = EmbeddingPullPipeline()
    try:
        key = object()
        pipe.submit(key, {"plan": 1}, lambda: {"rows": 7}, trace_id="t9")
        plan, pulled = pipe.consume(key)
        assert pulled == {"rows": 7} and plan == {"plan": 1}
    finally:
        pipe.close()
    (rec,) = [
        r
        for r in profiling.spans.tail()
        if r["name"] == "step/embedding_pull_bg"
    ]
    assert rec["trace"] == "t9" and rec["pipelined"] is True


# ---------------------------------------------------------------------------
# trace ids survive requeue and master relaunch
# ---------------------------------------------------------------------------

SHARDS = {"data.edlr": (0, 24)}


def _dispatcher(journal=None):
    return TaskDispatcher(dict(SHARDS), {}, {}, 12, 1, journal=journal)


def _worker_step_span(task):
    trace = task.extended_config["trace_id"]
    with profiling.span("step", trace_id=trace):
        with profiling.span("step/compute"):
            pass
    return trace


def test_spans_link_across_a_task_requeue():
    d = _dispatcher()
    tid, task = d.get(worker_id=0)
    trace = _worker_step_span(task)  # worker A trains, then fails
    d.report(tid, False)
    tid2, task2 = d.get(worker_id=1)  # worker B picks the requeue up
    assert task2.extended_config["trace_id"] == trace
    assert task2.extended_config["_attempt"] == 1
    _worker_step_span(task2)
    linked = [
        r for r in profiling.spans.tail() if r.get("trace") == trace
    ]
    # both attempts' step+compute spans, plus the master's dispatch and
    # report spans, all join the one trace
    names = [r["name"] for r in linked]
    assert names.count("step") == 2 and names.count("step/compute") == 2
    assert "master/dispatch" in names and "master/report" in names


def test_spans_link_across_a_master_crash_and_relaunch(tmp_path):
    # one task total, so the relaunch's first dispatch IS the recovered
    # task (two tasks would leave the pick to the epoch shuffle)
    def _dispatcher(journal):
        return TaskDispatcher(
            {"data.edlr": (0, 12)}, {}, {}, 12, 1, journal=journal
        )

    journal = MasterJournal(str(tmp_path))
    state = journal.replay()
    d = _dispatcher(journal=journal)
    d.apply_recovery(state)
    journal.start()
    tid, task = d.get(worker_id=0)
    trace = _worker_step_span(task)
    journal.close()  # the crash: one task in flight

    journal2 = MasterJournal(str(tmp_path))
    state2 = journal2.replay()
    # snapshot NOW: the journal keeps folding post-boot records into
    # this same state object, so the done ack below will clear it
    pending_at_boot = set(state2.pending)
    d2 = _dispatcher(journal=journal2)
    d2.apply_recovery(state2)
    journal2.start()
    tid2, task2 = d2.get(worker_id=1)
    # the relaunched master re-dispatches the in-flight task with its
    # PRE-CRASH trace (attempt bumped), so post-failover spans join the
    # pre-failover ones
    assert task2.extended_config["trace_id"] == trace
    assert task2.extended_config["_attempt"] == 1
    _worker_step_span(task2)
    d2.report(
        tid2,
        True,
        exec_counters={
            TaskExecCounterKey.TRACE_ID: trace,
            TaskExecCounterKey.ATTEMPT: 1,
        },
    )
    journal2.close()
    linked = [
        r for r in profiling.spans.tail() if r.get("trace") == trace
    ]
    assert [r["name"] for r in linked].count("step") == 2
    # the master-plane report span resolved the same trace
    assert any(r["name"] == "master/report" for r in linked)
    # the crash left exactly this trace in flight at boot
    assert pending_at_boot == {trace}


# ---------------------------------------------------------------------------
# shipping: worker snapshot -> master /trace
# ---------------------------------------------------------------------------


class _Stub:
    def __init__(self, fail=False):
        self.fail = fail
        self.snaps = []

    def report_telemetry(self, snap):
        if self.fail:
            raise RuntimeError("master unreachable")
        self.snaps.append(snap)


def test_worker_snapshot_ships_spans_and_failed_ship_requeues():
    wt = WorkerTelemetry(3, interval_s=60.0)  # force=True below
    with profiling.span("step", trace_id="t7"):
        pass
    stub = _Stub()
    assert wt.ship(stub, force=True)
    (snap,) = stub.snaps
    assert [s["name"] for s in snap["spans"]] == ["step"]
    assert profiling.spans.drain_pending() == []

    with profiling.span("step", trace_id="t8"):
        pass
    assert not wt.ship(_Stub(fail=True), force=True)
    # the drained spans went back on the pending buffer
    requeued = profiling.spans.drain_pending()
    assert [s["trace"] for s in requeued] == ["t8"]


def test_job_telemetry_serves_worker_spans_on_trace_endpoint():
    jt = JobTelemetry()
    # spans "shipped from" a worker process (foreign proc tag — the
    # in-process dedup keeps same-proc spans out by design)
    jt.ingest(
        {
            "worker_id": 5,
            "spans": [
                {
                    "name": "step",
                    "trace": "t1",
                    "span": "worker-5/1",
                    "parent": None,
                    "proc": "worker-5",
                    "thread": "MainThread",
                    "ts": 1000.0,
                    "dur": 0.25,
                },
                {
                    "name": "step/compute",
                    "trace": "t1",
                    "span": "worker-5/2",
                    "parent": "worker-5/1",
                    "proc": "worker-5",
                    "thread": "MainThread",
                    "ts": 1000.1,
                    "dur": 0.2,
                },
            ],
        }
    )
    server = TelemetryHTTPServer(jt, port=0)
    try:
        doc = json.loads(
            urllib.request.urlopen(
                "http://127.0.0.1:%d/trace" % server.port, timeout=10
            ).read()
        )
        events = doc["traceEvents"]
        steps = [e for e in events if e.get("name") == "step"]
        assert steps and steps[0]["ph"] == "X"
        assert steps[0]["dur"] == 0.25e6  # microseconds
        procs = [
            e["args"]["name"]
            for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"
        ]
        assert "worker-5" in procs
        # ?trace_id= filters
        doc2 = json.loads(
            urllib.request.urlopen(
                "http://127.0.0.1:%d/trace?trace_id=absent" % server.port,
                timeout=10,
            ).read()
        )
        assert [
            e for e in doc2["traceEvents"] if e.get("ph") == "X"
        ] == []
    finally:
        server.close()


def test_resent_snapshot_spans_ingest_exactly_once():
    # report_telemetry is retriable: a snapshot resent through a
    # connection-reset window carries the SAME spans — ingest must be
    # idempotent by span id or /trace doubles every step
    shipped = [
        {
            "name": "step",
            "trace": "t1",
            "span": "worker-9/1",
            "parent": None,
            "proc": "worker-9",
            "thread": "MainThread",
            "ts": 1.0,
            "dur": 0.1,
        }
    ]
    profiling.spans.ingest(shipped)
    profiling.spans.ingest(shipped)  # the retry
    assert (
        len([r for r in profiling.spans.tail() if r["name"] == "step"])
        == 1
    )


def test_same_process_spans_are_not_duplicated_by_ingest():
    # the in-process local mode: worker and master share one SpanLog
    with profiling.span("step", trace_id="t1"):
        pass
    drained = profiling.spans.drain_pending()
    profiling.spans.ingest(drained)  # JobTelemetry would do this
    assert len(
        [r for r in profiling.spans.tail() if r["name"] == "step"]
    ) == 1


def test_events_endpoint_since_cursor():
    jt = JobTelemetry()
    first = profiling.events.emit("resize_begin", epoch=1)
    second = profiling.events.emit("resize_end", epoch=1)
    server = TelemetryHTTPServer(jt, port=0)
    try:
        url = "http://127.0.0.1:%d/events" % server.port
        all_events = [
            json.loads(l)
            for l in urllib.request.urlopen(url, timeout=10)
            .read()
            .decode()
            .splitlines()
            if l.strip()
        ]
        assert {e["id"] for e in all_events} >= {
            first["id"],
            second["id"],
        }
        newer = [
            json.loads(l)
            for l in urllib.request.urlopen(
                url + "?since=%d" % first["id"], timeout=10
            )
            .read()
            .decode()
            .splitlines()
            if l.strip()
        ]
        assert [e["id"] for e in newer] == [second["id"]]
        assert profiling.events.last_id() == second["id"]
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(url + "?since=banana", timeout=10)
        assert err.value.code == 400
    finally:
        server.close()


def test_process_telemetry_serves_ps_shard_surface():
    # the --ps_telemetry_port adapter: /metrics + /healthz + /trace
    # parity with the master endpoint (docs/ps_recovery.md)
    health = {"state": "restoring"}
    pt = ProcessTelemetry()
    profiling.metrics.counter(
        "edl_tracing_test_total", "t"
    ).inc()
    with profiling.span("ps/apply", trace_id="t1"):
        pass
    server = TelemetryHTTPServer(
        pt, port=0, health_fn=lambda: health["state"]
    )
    try:
        base = "http://127.0.0.1:%d" % server.port
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(base + "/healthz", timeout=10)
        assert err.value.code == 503  # restoring -> not ready
        health["state"] = "serving"
        assert (
            urllib.request.urlopen(base + "/healthz", timeout=10).status
            == 200
        )
        body = (
            urllib.request.urlopen(base + "/metrics", timeout=10)
            .read()
            .decode()
        )
        assert "edl_tracing_test_total" in body
        doc = json.loads(
            urllib.request.urlopen(base + "/trace", timeout=10).read()
        )
        assert any(
            e.get("name") == "ps/apply" for e in doc["traceEvents"]
        )
    finally:
        server.close()


# ---------------------------------------------------------------------------
# chrome trace + tracetool
# ---------------------------------------------------------------------------


def _synthetic_steps(n=8, slow_at=6):
    out = []
    t = 0.0
    for i in range(n):
        pull, compute, push = 0.01, 0.03, (0.08 if i == slow_at else 0.01)
        dur = pull + compute + push + 0.002  # 2ms unattributed glue
        sid = "w/%d" % (10 * i)
        out.append(
            {
                "name": "step",
                "trace": "t%03d" % i,
                "span": sid,
                "parent": None,
                "proc": "worker-0",
                "thread": "MainThread",
                "ts": t,
                "dur": dur,
            }
        )
        for j, (nm, d) in enumerate(
            (
                ("step/pull_model", pull),
                ("step/compute", compute),
                ("step/grad_push", push),
            )
        ):
            out.append(
                {
                    "name": nm,
                    "trace": "t%03d" % i,
                    "span": "w/%d" % (10 * i + j + 1),
                    "parent": sid,
                    "proc": "worker-0",
                    "thread": "MainThread",
                    "ts": t,
                    "dur": d,
                }
            )
        t += dur
    return out


def test_tracetool_breakdown_attribution_and_dominant_phase():
    doc = chrome_trace(_synthetic_steps())
    report = critical_path(doc)
    assert report["steps"] == 8
    assert report["attribution"] >= 0.9
    shares = report["phases"]
    assert set(shares) == {
        "step/pull_model",
        "step/compute",
        "step/grad_push",
    }
    assert abs(sum(p["share"] for p in shares.values())
               - report["attribution"]) < 0.01
    # the p99 slow step is the grad_push outlier, flagged as dominant
    slow = report["slowest"][0]
    assert slow["trace"] == "t006"
    assert slow["dominant"] == "step/grad_push"
    # raw SpanLog records work too (the tests' convenience path)
    assert critical_path(_synthetic_steps())["steps"] == 8


def test_tracetool_cli_round_trip(tmp_path):
    from elasticdl_tpu.tools import tracetool

    path = tmp_path / "trace.json"
    path.write_text(json.dumps(chrome_trace(_synthetic_steps())))
    assert tracetool.main([str(path)]) == 0
    assert tracetool.main([str(path), "--json"]) == 0
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"traceEvents": []}))
    assert tracetool.main([str(empty)]) == 1
    assert tracetool.main([str(tmp_path / "missing.json")]) == 2


def test_live_job_serves_metrics_and_a_trace_that_explains_its_steps(
    tmp_path,
):
    """A real local job: the master's own gRPC server and telemetry
    endpoint, a Worker on a MasterClient. Scraped from the job's start
    on, ``/metrics`` comes to carry the per-worker rate, the RPC latency
    histograms of both sides and the live queue depth; after the job,
    ``/trace`` round-trips through tracetool and the worker's named
    child spans explain at least 90% of its steps' wall time (less
    means a phase of the step carries no span)."""
    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.master.master import Master
    from elasticdl_tpu.master.rpc_service import MasterClient
    from elasticdl_tpu.worker.worker import Worker
    from tests.test_utils import (
        MODEL_ZOO_PATH,
        DatasetName,
        create_recordio_file,
    )

    create_recordio_file(
        96, DatasetName.IMAGE_DEFAULT, (28, 28), temp_dir=str(tmp_path)
    )
    model_def = "mnist_subclass.mnist_subclass.CustomModel"
    args = parse_master_args(
        [
            "--job_name", "trace-live",
            "--model_zoo", MODEL_ZOO_PATH,
            "--model_def", model_def,
            "--minibatch_size", "16",
            "--training_data", str(tmp_path),
            "--num_workers", "0",
            "--num_ps_pods", "0",
            "--use_async", "true",
            "--port", "0",
            "--telemetry_port", "0",
            "--telemetry_report_secs", "0.2",
        ]
    )
    master = Master(args)
    master.prepare()
    stub = MasterClient("localhost:%d" % master.port)
    worker = Worker(
        0,
        master.job_type,
        16,
        MODEL_ZOO_PATH,
        model_def,
        stub=stub,
        telemetry_report_secs=0.2,
    )
    worker_err = []

    def drive():
        try:
            worker.run()
        except Exception as e:  # asserted on below
            worker_err.append(e)

    base = "http://127.0.0.1:%d" % master.telemetry_port
    required = [
        'edl_worker_examples_per_sec{worker="0"}',
        "edl_rpc_client_latency_seconds_bucket",
        'edl_rpc_server_latency_seconds_bucket{role="master"',
        "edl_task_queue_depth",
    ]
    missing = list(required)
    t = threading.Thread(target=drive, name="edl-test-live-worker")
    t.start()
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and missing and not worker_err:
            text = urllib.request.urlopen(
                base + "/metrics", timeout=10
            ).read().decode("utf-8")
            missing = [m for m in required if m not in text]
            time.sleep(0.1)
        t.join(timeout=120)
        assert not t.is_alive()
        trace_doc = json.loads(
            urllib.request.urlopen(base + "/trace", timeout=10).read()
        )
    finally:
        master.request_stop()
        master.run(poll_secs=0.1)
        stub.close()
    assert not worker_err, worker_err
    assert not missing, "families never served: %s" % missing
    report = critical_path(trace_doc)
    assert report["steps"] >= 96 // 16
    assert report["attribution"] >= 0.90, report["phases"]


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def _read_postmortem(path):
    lines = [
        json.loads(l)
        for l in open(path, encoding="utf-8")
        if l.strip()
    ]
    return lines[0], lines[1:]


def test_flight_recorder_dumps_on_trigger_event(tmp_path):
    profiling.flight_recorder.arm(str(tmp_path), min_interval_s=0.0)
    with profiling.span("step", trace_id="t1"):
        pass
    profiling.events.emit("worker_join", worker=0)  # not a trigger
    assert os.listdir(str(tmp_path)) == []
    profiling.events.emit("ps_shard_failure", addr="x:1", method="pull")
    (dump,) = os.listdir(str(tmp_path))
    assert dump.startswith("postmortem-") and dump.endswith(
        "ps_shard_failure.jsonl"
    )
    header, body = _read_postmortem(os.path.join(str(tmp_path), dump))
    assert header["postmortem"] == "ps_shard_failure"
    assert header["trigger"]["addr"] == "x:1"
    kinds = {e["kind"] for e in body if e["type"] == "event"}
    assert {"worker_join", "ps_shard_failure"} <= kinds
    span_names = [s["name"] for s in body if s["type"] == "span"]
    assert "step" in span_names


def test_dead_shard_rpc_failure_leaves_a_postmortem(tmp_path):
    """The whole chain, from the wire: a PS shard dies under a bound
    client, the client's next call fails terminally and emits
    ``ps_shard_failure`` itself, and the armed recorder writes a
    postmortem whose every line parses and which holds the trigger
    event and the spans recorded before it (the last step that still
    completed)."""
    from elasticdl_tpu.worker.ps_client import BoundPS, PSRpcError
    from tests.fake_ps import serve_slow_ps

    profiling.flight_recorder.arm(str(tmp_path), min_interval_s=0.0)
    server, addr = serve_slow_ps(delay_s=0.0)
    bound = BoundPS(addr, deadline_s=2.0, retries=0)
    try:
        with profiling.span("step", trace_id="drill-1"):
            assert "model_init_status" in bound.pull_variable({})
        assert os.listdir(str(tmp_path)) == []
        server.stop(None)  # the shard is gone: no drain, no goodbye
        with pytest.raises(PSRpcError):
            with profiling.span("step", trace_id="drill-2"):
                bound.pull_variable({})
    finally:
        bound.close()
        server.stop(None)
    (dump,) = os.listdir(str(tmp_path))
    header, body = _read_postmortem(os.path.join(str(tmp_path), dump))
    assert header["postmortem"] == "ps_shard_failure"
    assert header["trigger"]["addr"] == addr
    assert header["trigger"]["method"] == "pull_variable"
    kinds = {e["kind"] for e in body if e["type"] == "event"}
    assert "ps_shard_failure" in kinds
    assert "drill-1" in {s["trace"] for s in body if s["type"] == "span"}


def test_flight_recorder_rate_limit_and_prune(tmp_path):
    profiling.flight_recorder.arm(
        str(tmp_path), keep=2, min_interval_s=3600.0
    )
    assert profiling.flight_recorder.trigger("chaos_kill") is not None
    # inside the interval: suppressed (a requeue storm must not spam)
    assert profiling.flight_recorder.trigger("chaos_kill") is None
    profiling.flight_recorder.arm(
        str(tmp_path), keep=2, min_interval_s=0.0
    )
    for _ in range(4):
        assert profiling.flight_recorder.trigger("task_requeued")
    dumps = sorted(os.listdir(str(tmp_path)))
    assert len(dumps) == 2  # pruned to keep=2, newest kept
    assert dumps[-1].endswith("task_requeued.jsonl")


def test_disarmed_recorder_ignores_triggers(tmp_path):
    assert not profiling.flight_recorder.armed
    assert profiling.flight_recorder.trigger("chaos_kill") is None
    profiling.events.emit("ps_shard_failure", addr="x")  # no crash
    assert os.listdir(str(tmp_path)) == []


def test_chaos_kill_emits_event_and_triggers_recorder(tmp_path):
    from elasticdl_tpu.tools.chaos import ChaosOp, FleetChaos

    profiling.flight_recorder.arm(str(tmp_path), min_interval_s=0.0)

    class _Manager:
        killed = []

        def kill_ps(self, shard):
            self.killed.append(shard)

    chaos = FleetChaos(
        _Manager(),
        status_fn=lambda shard: {"version": 99},
        schedule=[ChaosOp("kill", 0, at_version=5)],
        poll_s=0.01,
    )
    chaos.start()
    try:
        deadline = 5.0
        import time as _time

        t0 = _time.monotonic()
        while not chaos.done() and _time.monotonic() - t0 < deadline:
            _time.sleep(0.02)
        assert chaos.done()
    finally:
        chaos.stop()
    assert _Manager.killed == [0]
    kinds = [e["kind"] for e in profiling.events.tail()]
    assert "chaos_kill" in kinds
    dumps = [
        f for f in os.listdir(str(tmp_path)) if "chaos_kill" in f
    ]
    assert dumps, "the chaos kill must leave a postmortem"
    header, body = _read_postmortem(
        os.path.join(str(tmp_path), dumps[0])
    )
    assert header["postmortem"] == "chaos_kill"
    assert all(
        isinstance(line, dict) for line in body
    )  # every line parses
