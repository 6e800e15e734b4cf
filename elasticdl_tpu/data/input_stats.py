"""Per-stage counters for the worker input plane.

One ``InputPlaneStats`` object rides a whole dataset round: the task
data service charges task-starvation/read time, ``Dataset.map`` charges
parse time, ``Dataset.batch`` charges batch-assembly time, and
``Dataset.prefetch`` charges the time its consumer spent waiting on an
empty buffer. The worker logs a snapshot at every task-stream boundary
(docs/input_pipeline.md has the counter glossary);
tests/test_input_pipeline.py reads the same counters.

Time counters are wall seconds as seen by the charging stage; with
parallel decode the parse counter aggregates across pool threads, so it
can legitimately exceed the round's wall time (it is CPU-seconds of
decode, not a latency).
"""

import threading
import time


class InputPlaneStats:
    """Thread-safe additive counters for the input pipeline stages."""

    TIME_FIELDS = (
        # consumer waited for the master to hand over a task
        "task_starved_s",
        # pulling records out of the data reader
        "read_s",
        # user parse fn (Dataset.map); CPU-seconds across decode threads
        "parse_s",
        # batch assembly (Dataset.batch)
        "batch_s",
        # downstream consumer waited on an empty prefetch buffer
        "consumer_starved_s",
        # task acknowledgment RPCs (sync acks charge the hot loop,
        # queued acks charge their boundary drain)
        "ack_s",
    )
    COUNT_FIELDS = ("tasks", "records", "batches")

    def __init__(self):
        self._lock = threading.Lock()
        self._values = {}
        self.reset()

    def reset(self):
        with self._lock:
            for f in self.TIME_FIELDS + self.COUNT_FIELDS:
                self._values[f] = 0.0 if f in self.TIME_FIELDS else 0

    def add(self, field, seconds):
        with self._lock:
            self._values[field] += seconds

    def count(self, field, n=1):
        with self._lock:
            self._values[field] += n

    def timed(self, field):
        """Context manager charging its body's wall time to ``field``."""
        return _Timed(self, field)

    def snapshot(self):
        with self._lock:
            return dict(self._values)

    def publish_to(self, registry, worker=""):
        """Mirror the current counters into ``registry`` gauges
        (``edl_input_stage_seconds{stage=...}`` / ``edl_input_count``)
        so a stalled stream is visible mid-epoch — the worker's own
        boundary log only fires at stream ends. Called at the telemetry
        snapshot cadence, never per record."""
        snap = self.snapshot()
        # gauges, not counters (the stats reset at stream boundaries),
        # so no Prometheus-reserved _total suffix
        seconds = registry.gauge(
            "edl_input_stage_seconds",
            "Input-plane stage seconds since the last stream boundary",
            labels=("worker", "stage"),
        )
        counts = registry.gauge(
            "edl_input_count",
            "Input-plane item counts since the last stream boundary",
            labels=("worker", "kind"),
        )
        worker = str(worker)
        for f in self.TIME_FIELDS:
            seconds.set(snap[f], worker=worker, stage=f[: -len("_s")])
        for f in self.COUNT_FIELDS:
            counts.set(snap[f], worker=worker, kind=f)
        return snap

    def format_line(self):
        """One log line: counts plus per-stage times in ms."""
        s = self.snapshot()
        times = " ".join(
            "%s=%.0fms" % (f[: -len("_s")], s[f] * 1e3)
            for f in self.TIME_FIELDS
        )
        return "input-plane: tasks=%d records=%d batches=%d %s" % (
            s["tasks"],
            s["records"],
            s["batches"],
            times,
        )


class _Timed:
    def __init__(self, stats, field):
        self._stats = stats
        self._field = field

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._stats.add(self._field, time.perf_counter() - self._t0)
        return False
