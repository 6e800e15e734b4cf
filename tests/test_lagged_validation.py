"""The allreduce worker validates one step behind what it has
dispatched (docs/distributed.md "The sync cadence").

Two layers. The worker's loop (``_train_epoch``) over a recording stub
of the trainer, whose "device" finishes a step only when somebody waits
for it: the ORDER of dispatches, waits and task reports is the thing
under test, and a stub shows it where a CPU step that is done before
the host looks would not. And the real ``ElasticDPTrainer`` on the CPU
mesh: ``settle(lag=1)`` hands out every loss once, reads the receipt
and never the train state.
"""

from types import SimpleNamespace

import jax
import numpy as np
import pytest

from elasticdl_tpu.common.constants import JobType
from elasticdl_tpu.utils import profiling
from elasticdl_tpu.worker import elastic_allreduce_worker as worker_mod
from elasticdl_tpu.worker.elastic_allreduce_worker import (
    ElasticAllReduceWorker,
)

SYNC_EVERY, ROWS = 8, 4


class _Stub:
    """The master as the loop sees it: an epoch that bumps at a chosen
    poll, and the drain announcement."""

    def __init__(self, log, bump_at_poll=None):
        self.log, self.bump_at_poll, self.polls = log, bump_at_poll, 0
        self.bumped = False

    def get_comm_world(self, worker_id, host, awaiting=False):
        self.polls += 1
        if self.bump_at_poll is not None and self.polls >= self.bump_at_poll:
            self.bumped = True
        return {"epoch": int(self.bumped), "live": 0}

    def leave_comm_world(self, worker_id):
        self.log.append(("leave_announced",))
        self.bumped = True


class _Trainer:
    """What ``_train_epoch`` asks of the trainer, recorded. A step's
    loss is its number, so a list of losses says which steps it holds
    and in what order; its epoch consensus is the hint it was given."""

    is_sharded = False
    abort_check = None

    def __init__(self, log, fail_wait_for=None, fail_dispatch_of=None):
        self.log = log
        self.fail_wait_for, self.fail_dispatch_of = fail_wait_for, fail_dispatch_of
        self.mesh = SimpleNamespace(devices=SimpleNamespace(size=1))
        self.dispatched = 0
        self.in_flight = []  # (step, has_data, epoch_hint)
        self.epoch_consensus = self.n_active = None
        self.on_dispatch = lambda step: None

    # the live state: nothing at a sync point may read it
    @property
    def _ts(self):
        self.log.append(("read_ts",))
        raise AssertionError("the sync point read trainer._ts")

    version = _ts

    @property
    def steps_in_flight(self):
        return len(self.in_flight)

    @property
    def validated_version(self):
        return self.dispatched - len(self.in_flight)

    def train_step(self, features, labels, minibatch, sync=True, epoch_hint=0):
        assert not sync, "the loop dispatches; it never waits in train_step"
        if self.dispatched + 1 == self.fail_dispatch_of:
            raise RuntimeError("collective failed at dispatch")
        self.dispatched += 1
        self.log.append(("dispatch", self.dispatched))
        self.in_flight.append((self.dispatched, features is not None, epoch_hint))
        self.on_dispatch(self.dispatched)
        return None, None, 0 if features is None else len(labels)

    def settle(self, lag=0):
        taken = self.in_flight[: len(self.in_flight) - lag]
        if not taken:
            return []
        step, has_data, hint = taken[-1]
        self.log.append(("wait", step))
        if self.fail_wait_for is not None and step >= self.fail_wait_for:
            raise RuntimeError("collective failed")
        del self.in_flight[: len(taken)]
        self.epoch_consensus, self.n_active = hint, int(has_data)
        return [float(s) for s, data, _ in taken if data]

    def validate(self):
        if self.in_flight:
            self.log.append(("wait", self.in_flight[-1][0]))
        return self.fail_wait_for is None

    drain_metrics = settle

    def leave(self):
        self.log.append(("leave",))
        self.in_flight = []

    def stage_next(self, features, labels, minibatch):
        self.log.append(("stage", int(features[0])))

    def routing_state(self):
        return None

    def aux_losses(self):
        return {}

    def embedding_overflow_total(self):
        return None

    def peak_hbm_bytes(self):
        return None

    def state_device_coverage(self):
        return 1

    def mirror_enabled(self):
        return False

    def hint_world_sizes(self, sizes):
        pass


def _worker(monkeypatch, n_batches, sync_every=SYNC_EVERY, **trainer_kwargs):
    """A worker whose collaborators all write to one log, with
    ``n_batches`` batches of ROWS records to train on; returns it with
    the log and the ``train_window`` events it emits."""
    log, windows = [], []
    w = ElasticAllReduceWorker.__new__(ElasticAllReduceWorker)
    w._worker_id, w._host = 0, "h"
    w._job_type = JobType.TRAINING_ONLY
    w._minibatch_size, w._sync_every = ROWS, sync_every
    w._epoch_poll_secs = 1000.0
    w._stub = _Stub(log)
    w.trainer = _Trainer(log, **trainer_kwargs)
    w._task_data_service = SimpleNamespace(
        report_record_done=lambda count, err="": log.append(
            ("report", count, bool(err))
        )
    )
    w._telemetry = SimpleNamespace(on_batch=lambda count: None)
    w._ckpt = None
    w._last_size_hint = 0
    w._losses_reported, w._model_facts, w._routing_seen = 0, {}, None
    w._window_t0 = w._window_cpu0 = None
    w._batch_gen = iter(
        (np.full((ROWS,), i), np.zeros((ROWS,))) for i in range(1, n_batches + 1)
    )
    w._retry_batch, w._staged_peek = None, worker_mod._NO_PEEK
    w._unreported, w._drained, w._overflow_alarmed = [], False, 0
    w._preempted = w._drain_announced = False
    w._drain_deadline = 0.0
    w._await_epoch_bump = lambda epoch: True
    monkeypatch.setattr(
        profiling.events,
        "emit",
        lambda kind, **fields: kind == "train_window" and windows.append(fields),
    )
    monkeypatch.setattr(worker_mod.time, "sleep", lambda s: None)
    return w, log, windows


WORLD = SimpleNamespace(epoch=0, num_processes=1, process_id=0)


# ---------------------------------------------------------------------------
# the order of events at an aligned sync
# ---------------------------------------------------------------------------


def test_the_aligned_step_is_dispatched_before_the_one_before_is_waited_for(
    monkeypatch,
):
    w, log, _ = _worker(monkeypatch, n_batches=20)
    losses = []
    assert w._train_epoch(WORLD, losses) == "done"
    for i in (SYNC_EVERY, 2 * SYNC_EVERY):
        assert log.index(("dispatch", i)) < log.index(("wait", i - 1))
        # and nobody waits for the aligned step itself at its own sync
        assert log.index(("wait", i - 1)) < log.index(("dispatch", i + 1))
        between = log[
            log.index(("dispatch", i)) : log.index(("dispatch", i + 1))
        ]
        assert ("wait", i) not in between


def test_the_sync_point_reads_nothing_of_the_live_state(monkeypatch):
    w, log, _ = _worker(monkeypatch, n_batches=20)
    assert w._train_epoch(WORLD, []) == "done"
    assert ("read_ts",) not in log


def test_the_reports_and_the_next_dispatch_follow_the_wait_at_once(monkeypatch):
    """Everything between the wait for step ``i - 1`` and the dispatch
    of step ``i + 1`` (the reports, the staging of the next batch) runs
    with step ``i`` on the device: no second wait sits in between."""
    w, log, _ = _worker(monkeypatch, n_batches=20)
    w._train_epoch(WORLD, [])
    i = SYNC_EVERY
    between = log[log.index(("wait", i - 1)) + 1 : log.index(("dispatch", i + 1))]
    assert [e[0] for e in between] == ["report"] * (SYNC_EVERY - 1) + ["stage"]


def test_a_task_report_carries_the_validated_version(monkeypatch):
    """``report_task_result`` runs inside the sync point's flush, with
    the aligned step on the device: the version it piggybacks is the
    validated step's, from its receipt, never a read of the live
    state's (which would wait a whole step and drain the device)."""
    from elasticdl_tpu.common.constants import TaskExecCounterKey

    w, log, _ = _worker(monkeypatch, n_batches=20)
    sent = []
    w._stub.report_task_result = lambda task_id, err, counters: sent.append(counters)
    w._telemetry.ship = lambda stub: None
    # a task completes inside the flush at index 16
    w._task_data_service.report_record_done = lambda count, err="": (
        w.trainer.dispatched == 2 * SYNC_EVERY and w.report_task_result(7)
    )
    w._train_epoch(WORLD, [])
    assert ("read_ts",) not in log
    assert {c[TaskExecCounterKey.MODEL_VERSION] for c in sent} == {
        2 * SYNC_EVERY - 1
    }


# ---------------------------------------------------------------------------
# records are reported for validated steps only
# ---------------------------------------------------------------------------


def test_unreported_is_flushed_for_validated_steps_only(monkeypatch):
    w, log, _ = _worker(monkeypatch, n_batches=20)
    held = []
    # at the dispatch of the step after an aligned one: what is held
    w.trainer.on_dispatch = lambda step: held.append(
        (step, list(w._unreported), sum(1 for e in log if e[0] == "report"))
    )
    w._train_epoch(WORLD, [])
    by_step = {step: (unreported, reported) for step, unreported, reported in held}
    # step 9 is dispatched with step 8's records held (not validated)
    # and steps 1..7 reported
    assert by_step[SYNC_EVERY + 1] == ([ROWS], SYNC_EVERY - 1)
    assert by_step[2 * SYNC_EVERY + 1] == ([ROWS], 2 * SYNC_EVERY - 1)
    # a step's report never precedes the wait that validated it
    waits = [e[1] for e in log if e[0] == "wait"]
    assert waits == sorted(waits)
    reported = 0
    for event in log:
        if event[0] == "wait":
            validated = event[1]
        elif event[0] == "report":
            reported += 1
            assert reported <= validated


# ---------------------------------------------------------------------------
# no loss and no record is lost where a world is left
# ---------------------------------------------------------------------------


def _pause(w):
    w._stub.bump_at_poll = 5  # steps 5.. carry the bumped epoch
    return "reform", SYNC_EVERY


def _drain(w):
    # SIGTERM after step 3: announced, then paused with the world
    w.trainer.on_dispatch = lambda step: step == 3 and setattr(w, "_preempted", True)
    return "reform", SYNC_EVERY


def _preemption(w):
    # the announcement never lands: the deadline hard-leaves mid-window
    w._epoch_poll_secs = -1.0
    w._stub.leave_comm_world = lambda worker_id: None
    w.trainer.on_dispatch = lambda step: step == 11 and setattr(w, "_preempted", True)
    return "preempted", 11


def _job_end(w):
    return "done", 20


@pytest.mark.parametrize("leave", [_pause, _drain, _preemption, _job_end])
def test_every_way_out_keeps_every_loss_and_reports_every_record_once(
    monkeypatch, leave
):
    w, log, windows = _worker(monkeypatch, n_batches=20)
    verdict, steps = leave(w)
    losses = []
    assert w._train_epoch(WORLD, losses) == verdict
    assert w.trainer.dispatched - (verdict == "done") == steps  # one weight-0 step
    assert losses == [float(i) for i in range(1, steps + 1)]
    assert [e for e in log if e[0] == "report"] == [("report", ROWS, False)] * steps
    assert w._unreported == [] and w.trainer.steps_in_flight == 0
    assert sum(win["steps"] for win in windows) == steps
    assert windows[-1]["in_flight_at_fetch"] == 0


def test_the_pause_lands_at_the_aligned_index_with_its_step_dispatched(
    monkeypatch,
):
    """The consensus read at index ``i`` is step ``i - 1``'s on every
    rank: a bump first carried by the aligned step itself pauses a
    whole window later, and one carried by the step before pauses
    here, with step ``i`` dispatched on every rank."""
    for bump_at_poll, paused_after in ((SYNC_EVERY - 1, SYNC_EVERY),
                                       (SYNC_EVERY, 2 * SYNC_EVERY)):  # fmt: skip
        w, log, _ = _worker(monkeypatch, n_batches=40)
        w._stub.bump_at_poll = bump_at_poll
        assert w._train_epoch(WORLD, []) == "reform"
        assert w.trainer.dispatched == paused_after
        assert log[-1] == ("leave",)


# ---------------------------------------------------------------------------
# a failed collective
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "failure, failed_steps",
    [
        # the wait at index 16 (for step 15) fails: steps 8..16 were
        # dispatched and not validated
        (dict(fail_wait_for=2 * SYNC_EVERY - 1), SYNC_EVERY + 1),
        # the dispatch of step 13 fails: steps 8..12 and its own batch
        (dict(fail_dispatch_of=13), 6),
    ],
)
def test_a_failed_collective_fail_reports_at_most_a_window_and_a_step(
    monkeypatch, failure, failed_steps
):
    w, log, windows = _worker(monkeypatch, n_batches=40, **failure)
    losses = []
    assert w._train_epoch(WORLD, losses) == "reform"
    reports = [e for e in log if e[0] == "report"]
    assert reports == (
        [("report", ROWS, False)] * (SYNC_EVERY - 1)
        + [("report", ROWS, True)] * failed_steps
    )
    assert failed_steps <= SYNC_EVERY + 1
    # the failed window's losses are not recorded, its steps in no event
    assert losses == [float(i) for i in range(1, SYNC_EVERY)]
    assert [win["steps"] for win in windows] == [SYNC_EVERY - 1]
    assert w._unreported == []


# ---------------------------------------------------------------------------
# the windows' account
# ---------------------------------------------------------------------------


def test_the_windows_hold_what_each_validation_covered(monkeypatch):
    w, _, windows = _worker(monkeypatch, n_batches=20)
    w._train_epoch(WORLD, [])
    assert [win["steps"] for win in windows] == [7, 8, 5]
    assert [win["in_flight_at_fetch"] for win in windows] == [1, 1, 0]
    assert [win["first_loss"] for win in windows] == [1.0, 8.0, 16.0]
    assert [win["last_loss"] for win in windows] == [7.0, 15.0, 20.0]


def test_sync_every_one_validates_every_step_one_step_late(monkeypatch):
    w, log, windows = _worker(monkeypatch, n_batches=5, sync_every=1)
    losses = []
    assert w._train_epoch(WORLD, losses) == "done"
    assert losses == [1.0, 2.0, 3.0, 4.0, 5.0]
    # no window at the first sync (nothing behind it), one a step after
    assert [win["steps"] for win in windows] == [1] * 5
    assert [win["in_flight_at_fetch"] for win in windows] == [1, 1, 1, 1, 0]
    for i in range(2, 6):
        assert log.index(("dispatch", i)) < log.index(("wait", i - 1))


# ---------------------------------------------------------------------------
# the real trainer on the CPU mesh
# ---------------------------------------------------------------------------


@pytest.fixture
def make_trainer(monkeypatch):
    """Established trainers of one toy model on a mesh of one CPU
    device, every one from the same seed."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    import elasticdl_tpu.parallel.distributed as dist_mod
    from elasticdl_tpu.nn.hbm_embedding import METRICS_COLLECTION
    from elasticdl_tpu.parallel import elastic
    from elasticdl_tpu.training.step import AUX_LOSS_COLLECTION

    class Model(nn.Module):
        """A dense layer that writes an ``aux_loss`` leaf and counts in
        a ``metrics`` leaf, as the zoo's models do."""

        @nn.compact
        def __call__(self, x, training=False):
            y = nn.Dense(1)(x)[:, 0]
            aux = self.variable(AUX_LOSS_COLLECTION, "tiny", lambda: jnp.float32(0))
            aux.value = 1e-3 * jnp.mean(y**2)
            seen = self.variable(
                METRICS_COLLECTION, "a2a_overflow", lambda: jnp.int32(0)
            )
            if training:
                seen.value = seen.value + 1
            return y

    monkeypatch.setattr(dist_mod, "ensure_world", lambda s, **k: None)
    monkeypatch.setattr(
        elastic,
        "build_world_mesh",
        lambda axes_fn=None: Mesh(np.asarray(jax.devices()[:1]), ("data",)),
    )
    made = []

    def make():
        t = elastic.ElasticDPTrainer(
            Model(), lambda out, y: jnp.mean((out - y) ** 2), optax.sgd(0.05)
        )
        made.append(t)
        t.establish(
            dist_mod.WorldSpec(
                coordinator="", num_processes=1, process_id=0, epoch=0
            ),
            example_batch=_batch(0),
        )
        return t

    yield make
    for t in made:
        t.close()


@pytest.fixture
def trainer(make_trainer):
    return make_trainer()


def _batch(seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(ROWS, 3)).astype(np.float32)
    return x, x.sum(axis=1)


def test_settle_one_behind_hands_out_every_loss_once_in_order(make_trainer):
    trainer = make_trainer()
    lagged = []
    for i in range(1, 11):
        trainer.train_step(*_batch(i), ROWS, sync=False)
        if i % 4 == 0:
            lagged.extend(trainer.settle(lag=1))
            assert trainer.steps_in_flight == 1
            assert len(lagged) == i - 1
            assert trainer._checked_ts is None  # a process-local mesh keeps none
    assert trainer.validate()
    lagged.extend(trainer.drain_metrics())
    assert trainer.steps_in_flight == 0

    every_step = make_trainer()
    synced = [
        every_step.train_step(*_batch(i), ROWS, sync=True)[0] for i in range(1, 11)
    ]
    assert lagged == synced


def test_the_receipt_is_read_without_the_train_state(trainer):
    """With step 2 dispatched, step 1's receipt says what step 1 left
    (the counter reads 1, not 2) and nothing touches the live state,
    which step 2 has donated."""

    class Poison:
        def __getattr__(self, name):
            raise AssertionError("the sync point read the train state")

    trainer.train_step(*_batch(1), ROWS, sync=False)
    first = trainer._ts
    trainer.train_step(*_batch(2), ROWS, sync=False)
    assert all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(first))
    live, trainer._ts = trainer._ts, Poison()
    try:
        (loss,) = trainer.settle(lag=1)
        assert np.isfinite(loss) and trainer.steps_in_flight == 1
        assert trainer.embedding_overflow_total() == 1
        (tiny,) = trainer.aux_losses().values()
        assert 0 < tiny < loss
        assert trainer.routing_state() is None  # this model keeps none
        assert trainer.n_active == 1 and trainer.epoch_consensus == 0
        assert trainer.validated_version == 1
    finally:
        trainer._ts = live
    trainer.settle()
    assert trainer.embedding_overflow_total() == 2
    assert trainer.validated_version == trainer.version == 2


def test_a_synced_step_returns_its_own_loss_and_keeps_the_earlier_ones(trainer):
    trainer.train_step(*_batch(1), ROWS, sync=False)
    trainer.train_step(*_batch(2), ROWS, sync=False)
    loss, n_active, count = trainer.train_step(*_batch(3), ROWS, sync=True)
    assert (n_active, count, trainer.steps_in_flight) == (1, ROWS, 0)
    earlier = trainer.settle()
    assert len(earlier) == 2 and loss not in earlier
    assert trainer.settle() == []
