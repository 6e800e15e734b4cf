"""The allreduce worker's account of its own step loop
(docs/observability.md "The allreduce worker's phases").

Three layers: ``profiling.span`` on the profiler's clock (a
``TraceAnnotation`` exactly while a trace is open), ``PhaseClock``'s
arithmetic on a fake clock, and one real CPU elastic allreduce job
(``edl train --num_workers 1``, traced, with one ``_next_batch`` made
to sleep) whose ``train_window`` events and profiler trace are read
back.
"""

import glob
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from elasticdl_tpu.utils import profiling
from elasticdl_tpu.utils.profiling import STEP_PHASES, PhaseClock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# spans and phases on the profiler's clock
# ---------------------------------------------------------------------------


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records its life."""

    log = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name, self.kwargs))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name))
        return False


@pytest.fixture
def fake_annotation(monkeypatch):
    import jax

    _FakeAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(profiling, "_trace_dir", None)
    return _FakeAnnotation.log


def test_span_is_an_annotation_exactly_while_a_trace_is_open(
    fake_annotation, monkeypatch
):
    with profiling.span("quiet", records=3):
        pass
    assert fake_annotation == []  # no trace open: no annotation at all

    monkeypatch.setattr(profiling, "_trace_dir", "/somewhere")
    with profiling.span("task/warm", trace_id="t-7", records=3):
        assert fake_annotation == [
            ("enter", "task/warm", {"records": 3, "trace": "t-7"})
        ]
    assert fake_annotation[-1] == ("exit", "task/warm")
    with profiling.span("untraced"):
        pass
    assert fake_annotation[-2] == ("enter", "untraced", {})

    monkeypatch.setattr(profiling, "_trace_dir", None)
    with profiling.span("quiet-again"):
        pass
    assert len(fake_annotation) == 4
    # the span plane saw all four, on its own clock
    names = [s["name"] for s in profiling.spans.tail(8)]
    assert names[-4:] == ["quiet", "task/warm", "untraced", "quiet-again"]


def test_span_leaves_its_annotation_when_the_trace_closed_under_it(
    fake_annotation, monkeypatch
):
    """The worker stops its trace inside open spans (a world is left at
    a pause): what was entered is still left."""
    monkeypatch.setattr(profiling, "_trace_dir", "/somewhere")
    with profiling.span("outlives"):
        monkeypatch.setattr(profiling, "_trace_dir", None)
    assert [e[0] for e in fake_annotation] == ["enter", "exit"]


def test_span_exception_still_leaves_annotation(fake_annotation, monkeypatch):
    monkeypatch.setattr(profiling, "_trace_dir", "/somewhere")
    with pytest.raises(KeyError):
        with profiling.span("fails"):
            raise KeyError("x")
    assert fake_annotation[-1] == ("exit", "fails")
    assert profiling.spans.tail(1)[0]["error"] == "KeyError"


@pytest.mark.parametrize("phase", STEP_PHASES)
def test_phase_is_an_annotation_only_while_a_trace_is_open(
    phase, fake_annotation, monkeypatch
):
    clock = PhaseClock()
    with clock.measure(phase):
        pass
    assert fake_annotation == []
    monkeypatch.setattr(profiling, "_trace_dir", "/somewhere")
    with clock.measure(phase):
        pass
    assert fake_annotation == [
        ("enter", "edl/step/" + phase, {}),
        ("exit", "edl/step/" + phase),
    ]


# ---------------------------------------------------------------------------
# phase-clock arithmetic
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("phase", STEP_PHASES)
def test_phase_total_lands_in_its_own_field(phase):
    now = _FakeClock()
    clock = PhaseClock(clock=now)
    for dur in (0.25, 0.5):
        with clock.measure(phase):
            now.t += dur
    now.t += 9.0  # between phases: nobody's
    account = clock.close_window()
    assert account.pop(phase + "_s") == 0.75
    assert account.pop("slowest_call_s") == 0.5
    assert account.pop("slowest_call_phase") == phase
    assert account.pop("slowest_call_step") == 0
    assert account == {p + "_s": 0.0 for p in STEP_PHASES if p != phase}


def test_phase_clock_keeps_the_longest_call_and_its_step():
    now = _FakeClock()
    clock = PhaseClock(clock=now)
    for step, (phase, dur) in enumerate(
        [("dispatch", 0.002), ("input_wait", 0.3), ("fetch", 0.1)], 1
    ):
        clock.step = step
        with clock.measure(phase):
            now.t += dur
    account = clock.close_window()
    assert account["slowest_call_s"] == 0.3
    assert account["slowest_call_phase"] == "input_wait"
    assert account["slowest_call_step"] == 2
    assert account["dispatch_s"] == 0.002 and account["fetch_s"] == 0.1


def test_phase_clock_resets_at_the_window():
    now = _FakeClock()
    clock = PhaseClock(clock=now)
    with clock.measure("report"):
        now.t += 1.0
    assert clock.close_window()["report_s"] == 1.0
    fresh = clock.close_window()
    assert all(fresh[p + "_s"] == 0.0 for p in STEP_PHASES)
    assert fresh["slowest_call_s"] == 0.0
    assert fresh["slowest_call_phase"] == ""


def test_nested_phase_raises():
    clock = PhaseClock()
    with clock.measure("dispatch"):
        with pytest.raises(RuntimeError, match="disjoint"):
            with clock.measure("fetch"):
                pass
    # and the outer phase closed normally: a new one opens
    with clock.measure("fetch"):
        pass


def test_unknown_phase_is_refused():
    with pytest.raises(ValueError):
        PhaseClock().measure("lunch")


def test_exception_inside_a_phase_still_closes_it():
    now = _FakeClock()
    clock = PhaseClock(clock=now)
    with pytest.raises(OSError):
        with clock.measure("cadence"):
            now.t += 2.0
            raise OSError("disk")
    with clock.measure("report"):  # not "nested": cadence was closed
        now.t += 0.5
    account = clock.close_window()
    assert account["cadence_s"] == 2.0 and account["report_s"] == 0.5


def test_phases_of_two_threads_do_not_collide():
    """The step's dispatch runs on a thread of its own (escapable_call):
    a phase left open by a thread that never came back must not stop
    the loop thread from measuring."""
    import threading

    clock = PhaseClock()
    entered, release = threading.Event(), threading.Event()

    def wedged():
        with clock.measure("dispatch"):
            entered.set()
            release.wait(5.0)

    t = threading.Thread(target=wedged, daemon=True)
    t.start()
    assert entered.wait(5.0)
    with clock.measure("world_poll"):
        pass
    release.set()
    t.join(5.0)
    assert not t.is_alive()
    assert clock.close_window()["dispatch_s"] > 0.0


def test_phases_honor_the_kill_switch(monkeypatch):
    monkeypatch.setattr(profiling, "_metrics_on", False)
    clock = PhaseClock()
    with clock.measure("dispatch"):
        pass
    assert clock.close_window()["dispatch_s"] == 0.0


def test_span_log_record_keeps_the_given_clock():
    log = profiling.SpanLog()
    log.record("train/window", 1234.5, 1.25, steps=8, dispatch_s=0.5)
    (rec,) = log.tail()
    assert (rec["name"], rec["ts"], rec["dur"]) == ("train/window", 1234.5, 1.25)
    assert rec["steps"] == 8 and rec["dispatch_s"] == 0.5
    assert log.drain_pending() == [rec]  # it ships like any span


# ---------------------------------------------------------------------------
# peak device memory
# ---------------------------------------------------------------------------


def _trainer_on(devices):
    from elasticdl_tpu.parallel.elastic import ElasticDPTrainer

    trainer = ElasticDPTrainer.__new__(ElasticDPTrainer)
    trainer._mesh = devices and SimpleNamespace(local_devices=devices)
    return trainer


def _device(stats):
    return SimpleNamespace(memory_stats=lambda: stats)


@pytest.mark.parametrize(
    "devices, expected",
    [
        (None, None),  # between worlds
        ([_device(None)], None),  # the CPU backend reports nothing
        ([_device({"bytes_in_use": 5})], None),
        ([_device({"peak_bytes_in_use": 7}), _device(None)], 7),
        (
            [
                _device({"peak_bytes_in_use": 7}),
                _device({"peak_bytes_in_use": 9}),
            ],
            9,
        ),
    ],
)
def test_peak_hbm_bytes_is_none_where_the_backend_gives_none(
    devices, expected
):
    assert _trainer_on(devices).peak_hbm_bytes() == expected


# ---------------------------------------------------------------------------
# a CPU elastic allreduce job, traced
# ---------------------------------------------------------------------------

STEPS, MINIBATCH, SYNC_EVERY = 40, 8, 8
SLOW_CALL = 12  # the _next_batch call that sleeps (the first is _prime's)
# longer than a whole window of device work on a loaded CPU: since the
# step donates its state the host runs a window ahead, and the sync
# step's one `fetch` waits for all of it (0.5 s here when the box is
# quiet, 1.6 s seen under six-way load), which would otherwise be the
# window's slowest call
SLOW_SECONDS = 4.0

# test-only steering of the worker process: sitecustomize on its path
HOOK = """
import os, time
n = int(os.environ.get("EDL_TEST_SLOW_BATCH_CALL", "0"))
if n:
    from elasticdl_tpu.worker import elastic_allreduce_worker as w
    _orig, _calls = w.ElasticAllReduceWorker._next_batch, [0]

    def _next_batch(self):
        _calls[0] += 1
        if _calls[0] == n:
            time.sleep(%r)
        return _orig(self)

    w.ElasticAllReduceWorker._next_batch = _next_batch
""" % SLOW_SECONDS


@pytest.fixture(scope="module")
def job_dir(tmp_path_factory):
    """One traced CPU job; the directory with its ``events.jsonl`` and
    its ``trace``."""
    from elasticdl_tpu.data.example import encode_example
    from elasticdl_tpu.data.recordio import create_recordio

    out = tmp_path_factory.mktemp("phases_job")
    data, hook = out / "data", out / "hook"
    data.mkdir()
    hook.mkdir()
    (hook / "sitecustomize.py").write_text(HOOK)
    rng = np.random.default_rng(0)
    with create_recordio(str(data / "tokens.edlr")) as w:
        for _ in range(STEPS * MINIBATCH):
            tokens = rng.integers(0, 64, size=256).astype(np.int64)
            w.write(encode_example({"tokens": tokens}))
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        EDL_DIST_PLATFORM="cpu",
        EDL_LOCAL_DEVICES="1",
        XLA_FLAGS="",
        EDL_PROFILE_DIR=str(out / "trace"),
        EDL_TEST_SLOW_BATCH_CALL=str(SLOW_CALL),
        PYTHONPATH=os.pathsep.join([str(hook), REPO]),
    )
    events_path = out / "events.jsonl"
    got = subprocess.run(
        [
            sys.executable, "-m", "elasticdl_tpu.cli", "train",
            "--job_name", "phases",
            "--distribution_strategy", "AllreduceStrategy",
            "--num_workers", "1",
            "--model_zoo", os.path.join(REPO, "model_zoo"),
            "--model_def", "transformer_lm.transformer_lm.custom_model",
            "--model_params",
            # wide enough that a step is ~70 ms of device work: with
            # the host running ahead of the device the loop's glue
            # between phases competes with the step for the CPU, and at
            # 15 ms a step under load it reached a fifth of a window
            "vocab_size=64,num_layers=2,num_heads=4,head_dim=32,"
            "embed_dim=128,mlp_dim=512,use_flash=False",
            "--training_data", str(data),
            "--minibatch_size", str(MINIBATCH),
            "--num_minibatches_per_task", str(SYNC_EVERY),
            "--num_epochs", "1",
            "--telemetry_events_path", str(events_path),
        ],  # fmt: skip
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600,
    )  # fmt: skip
    assert got.returncode == 0, got.stderr[-3000:]
    return out


def _events(job_dir, kind):
    with open(job_dir / "events.jsonl") as f:
        events = [json.loads(line) for line in f if line.strip()]
    return [e for e in events if e["kind"] == kind]


@pytest.fixture(scope="module")
def job(job_dir):
    """(train_window events, ProfileData) of the traced job."""
    windows = _events(job_dir, "train_window")
    assert sum(w["steps"] for w in windows) == STEPS
    (xplane,) = glob.glob(
        str(job_dir / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    from jax.profiler import ProfileData

    return windows, ProfileData.from_file(xplane)


NUMBER_FIELDS = tuple(p + "_s" for p in STEP_PHASES) + (
    "slowest_call_s",
    "slowest_call_step",
    "loop_cpu_s",
)


@pytest.mark.parametrize("field", NUMBER_FIELDS)
def test_every_train_window_carries_the_field(job, field):
    windows, _ = job
    assert len(windows) == STEPS // SYNC_EVERY + 1
    for w in windows:
        assert w[field] >= 0, (field, w)


def test_a_window_ends_one_step_behind_its_aligned_sync(job):
    """The worker validates step ``i - 1`` with step ``i`` dispatched:
    a world's first window holds ``sync_every - 1`` steps, every later
    one ``sync_every``, and the job's end validates the one step left
    in flight; no step is counted twice or lost."""
    windows, _ = job
    assert [w["steps"] for w in windows] == (
        [SYNC_EVERY - 1]
        + [SYNC_EVERY] * (STEPS // SYNC_EVERY - 1)
        + [1]
    )


def test_in_flight_at_fetch_is_one_until_the_device_is_drained(job):
    """Every closing fetch but the last left one dispatched step for
    the device to run while the host reported; the last one is the
    weight-0 step's, which waits for its own result."""
    windows, _ = job
    assert [w["in_flight_at_fetch"] for w in windows] == (
        [1] * (len(windows) - 1) + [0]
    )


def test_every_train_window_names_its_slowest_phase(job):
    windows, _ = job
    assert all(w["slowest_call_phase"] in STEP_PHASES for w in windows)


def test_phases_account_for_the_window(job):
    """The phases are disjoint pieces of the window: never more than
    its seconds, and, once the step is built, nearly all of them. The
    first window also holds the step's trace and lowering
    (describe_step), which is no phase of the loop."""
    windows, _ = job
    for i, w in enumerate(windows):
        accounted = sum(w[p + "_s"] for p in STEP_PHASES)
        assert accounted <= w["seconds"] + 1e-3, w
        if i > 0:
            assert accounted >= 0.9 * w["seconds"], w
        assert w["slowest_call_s"] <= accounted + 1e-4
        assert w["loop_cpu_s"] <= w["seconds"] + 0.05


def test_a_slow_next_batch_is_named_by_its_window(job):
    windows, _ = job
    # call 12 of _next_batch is the loop's 11th step: the second window
    slow = windows[1]
    assert slow["slowest_call_phase"] == "input_wait"
    assert slow["slowest_call_step"] == SLOW_CALL - 1
    assert SLOW_SECONDS <= slow["slowest_call_s"] <= slow["input_wait_s"]
    # blocked, not busy: the loop thread's CPU did not see the sleep
    assert slow["loop_cpu_s"] < slow["seconds"] - 0.8 * SLOW_SECONDS


def test_peak_hbm_bytes_is_absent_on_the_cpu(job):
    windows, _ = job
    assert all("peak_hbm_bytes" not in w for w in windows)


def _host_event_names(data):
    return [
        e.name
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    ]


def test_trace_holds_one_dispatch_annotation_per_step(job):
    windows, data = job
    names = _host_event_names(data)
    dispatched = names.count("edl/step/dispatch")
    # every step call, the weight-0 ones that drain the job's end too
    assert STEPS <= dispatched <= STEPS + 4
    for phase in ("world_poll", "input_wait", "batch_place"):
        assert names.count("edl/step/" + phase) == dispatched
    assert names.count("edl/step/report") >= len(windows)
    assert {n for n in names if n.startswith("edl/step/")} <= {
        "edl/step/" + p for p in STEP_PHASES
    }


def test_trace_holds_no_python_tracer_event(job):
    _, data = job
    names = _host_event_names(data)
    assert names and not [n for n in names if n.startswith("$")]


def test_a_traced_job_says_what_its_compiled_step_holds(job_dir):
    """Under ``EDL_PROFILE_DIR`` the worker writes the compiled step's
    ops by class beside the trace and ``step_built`` says how many it
    classed, with the compiler's account of the step's memory
    (docs/observability.md "The device step's classes")."""
    from elasticdl_tpu.utils import step_ops

    (built,) = _events(job_dir, "step_built")
    with open(job_dir / "trace" / step_ops.FILE_NAME) as f:
        ops_map = json.load(f)
    assert ops_map["module"] == "jit_per_device"
    assert 0 < built["step_ops_named"] == len(ops_map["ops"])
    assert built["step_ops_named"] <= built["step_ops_total"]
    held = set("+".join(ops_map["ops"].values()).split("+"))
    assert {"fwd", "bwd", "optimizer"} <= held <= set(step_ops.CLASSES)
    assert "remat" not in held  # this job recomputes nothing
    for field in ("step_argument_bytes", "step_temp_bytes", "step_alias_bytes"):
        assert isinstance(built[field], int) and built[field] > 0, field
    # the state is the step's largest argument, donated and so aliased
    assert built["step_alias_bytes"] <= built["step_argument_bytes"]
