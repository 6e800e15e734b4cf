"""Elastic multi-process allreduce: the north-star behavior for the
collective plane.

Rungs here mirror the reference test ladder (SURVEY.md §4.3): unit tests
for the membership epochs and the weighted lockstep step in one process,
then real OS-process jobs over gloo CPU collectives — including killing a
worker mid-job and asserting the job completes with all records
processed, i.e. ``test_elastic_job.py`` but for ALLREDUCE.
"""

import os
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from elasticdl_tpu.common.args import parse_master_args
from elasticdl_tpu.master.local_instance_manager import LocalInstanceManager
from elasticdl_tpu.master.master import Master
from elasticdl_tpu.master.membership_service import MembershipService
from tests.test_utils import MODEL_ZOO_PATH, DatasetName, create_recordio_file

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- rung 1: units -----------------------------------------------------------


def _poll_ready(m, worker_id):
    """Poll until the two-phase formation reports ready (bounded)."""
    for _ in range(10):
        w = m.get_world(worker_id)
        if w["ready"]:
            return w
    raise AssertionError("world never became ready for %d" % worker_id)


def test_membership_epochs():
    m = MembershipService(expected_workers=2, form_grace_secs=60)
    assert m.get_world(0)["ready"] is False  # quorum not met
    # two-phase: after the quorum registers, ready only once both
    # members have polled (confirmed) the new epoch
    m.get_world(1)
    m.get_world(0)  # both members confirm the freshly-bumped epoch
    w = _poll_ready(m, 1)
    assert w["num_processes"] == 2
    assert _poll_ready(m, 0)["process_id"] == 0
    assert _poll_ready(m, 1)["process_id"] == 1
    epoch = w["epoch"]

    # death shrinks the world and bumps the epoch
    m.remove(0)
    w1 = _poll_ready(m, 1)
    assert w1["epoch"] > epoch
    assert w1["num_processes"] == 1 and w1["process_id"] == 0

    # a relaunch (higher id) parks in the lobby while the survivor's
    # world is still forming — growth must not strand members in a stale
    # initialize barrier
    m.get_world(2)
    assert m.get_world(2)["ready"] is False
    w_mid = m.get_world(1, awaiting=False)  # survivor trains: formed
    # formation complete -> the parked joiner triggers the growth bump
    assert w_mid["epoch"] > w1["epoch"] or not w_mid.get("ready", True)
    m.get_world(1)  # survivor confirms the grown world
    m.get_world(2)
    w2 = _poll_ready(m, 2)
    assert w2["epoch"] > w1["epoch"]
    assert w2["num_processes"] == 2 and w2["process_id"] == 1
    assert _poll_ready(m, 1)["process_id"] == 0

    # coordinator address rotates with the epoch
    assert _poll_ready(m, 1)["coordinator"] != w["coordinator"]

    # once the grown world is training, a further joiner bumps immediately
    m.get_world(1, awaiting=False)
    m.get_world(2, awaiting=False)
    e2 = m.epoch
    m.get_world(3)
    assert m.epoch > e2


def test_membership_dead_list_only_real_crashes():
    """The ``dead`` list drives the survivors' wedge-escape abort
    probe: ANNOUNCED protocol-clean exits (rc 0 completion after the
    worker's own leave_comm_world, rc 75 after the drain announcement)
    must stay off it, every unannounced exit — whatever the code —
    must land on it, and entries are pruned once no lagging member's
    world can reference them."""
    m = MembershipService(expected_workers=3, form_grace_secs=60)
    for w in (0, 1, 2):
        m.get_world(w)
    assert m.get_world(0)["dead"] == []

    # announced clean completion: worker.main announces after global
    # quiescence, then the watch sees rc 0 — not listed dead
    m.remove(0, departing=True)
    m.remove(0, exit_code=0)
    assert 0 not in m.get_world(1)["dead"]

    # graceful drain announces departing first; the instance manager's
    # later rc-75 watch event must not retroactively mark it dead
    m.remove(1, departing=True)
    m.remove(1, exit_code=75)  # watch sees rc 75
    assert 1 not in m.get_world(2)["dead"]

    # a real crash IS listed (the abort probe keys on exactly this)
    m.remove(2)
    assert 2 in m.get_world(3)["dead"]

    # an UNANNOUNCED rc 0 — user code calling sys.exit(0) mid-step —
    # leaves peers' collectives hanging exactly like a kill: listed
    # (the probe is the ONLY escape; the fencer can't cull pollers)
    m.get_world(19)
    m.remove(19, exit_code=0)
    assert 19 in m.get_world(3)["dead"]

    # an UNANNOUNCED rc-75 hard-leave (the leave RPC never landed)
    # wedges survivors like any crash: listed
    m.get_world(20)
    m.remove(20, exit_code=75)
    assert 20 in m.get_world(3)["dead"]

    # a drained member that segfaults before the consensus pause broke
    # the collective: the earlier announcement does not exempt a
    # non-clean code
    m.get_world(21)
    m.remove(21, departing=True)
    m.remove(21, exit_code=139)  # watch sees a segfault
    assert 21 in m.get_world(3)["dead"]

    # pruning: once epochs advance past the retention window, the stale
    # death drops out of the payload
    for joiner in range(4, 11):
        m.get_world(joiner)
        # drive the two-phase formation to completion so the next
        # registration bumps instead of parking in the lobby
        for _ in range(5):
            members = [w for w, _ in m._world]
            for wid in members:
                m.get_world(wid)
            for wid in members:
                m.get_world(wid, awaiting=False)
    assert 2 not in m.get_world(3)["dead"]


def test_membership_unconfirmed_member_dropped_after_timeout():
    """A member that stops polling (wedged in a stale initialize) must
    not block formation forever: after the confirm timeout the world
    re-forms from the responsive members."""
    m = MembershipService(
        expected_workers=2, form_grace_secs=60, confirm_timeout_secs=0.3
    )
    m.get_world(0)
    m.get_world(1)  # forms epoch 1, world [0, 1], awaiting confirmation
    # only worker 1 keeps polling; 0 goes quiet for > 2 s
    m._last_poll[0] = time.time() - 3.0
    deadline = time.time() + 5
    w = m.get_world(1)
    while not w["ready"]:
        assert time.time() < deadline
        time.sleep(0.05)
        w = m.get_world(1)
    assert w["num_processes"] == 1 and w["process_id"] == 0


def test_membership_grace_forms_partial_world():
    m = MembershipService(expected_workers=3, form_grace_secs=0.2)
    assert m.get_world(0)["ready"] is False
    time.sleep(0.3)
    w = m.get_world(0)
    assert w["ready"] and w["num_processes"] == 1


def test_weighted_step_matches_plain_and_drain_is_noop():
    """Single process, 8 virtual devices: all-weights-1 must equal the
    plain trainer's math (deterministic model — per-shard dropout draws
    can't be expected to reproduce the global-batch draw), and a weight-0
    (drain) step must change nothing."""
    import flax.linen as nn
    import jax
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from elasticdl_tpu.parallel.elastic import (
        broadcast_from_device0,
        host_copy,
        make_elastic_train_step,
    )
    from elasticdl_tpu.nn.model_api import init_variables, split_variables
    from elasticdl_tpu.training.step import TrainState

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, inputs, training=False):
            x = inputs["image"].reshape((inputs["image"].shape[0], -1))
            x = nn.relu(nn.Dense(32)(x))
            return nn.Dense(10)(x)

    def loss_fn(output, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            output, labels.reshape(-1)
        ).mean()

    model = MLP()
    rng = np.random.default_rng(0)
    features = {
        "image": rng.random((16, 28, 28), dtype=np.float32),
    }
    labels = rng.integers(0, 10, size=(16, 1)).astype(np.int64)

    variables = init_variables(
        model, jax.random.PRNGKey(0), {"image": features["image"][:1]}
    )
    params, state = split_variables(variables)

    opt = optax.sgd(0.1)
    ts0 = TrainState.create(params, state, opt)

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    ts = broadcast_from_device0(mesh, host_copy(ts0))
    step = make_elastic_train_step(model, loss_fn, opt, mesh)

    def put(tree, spec):
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, spec)), tree
        )

    g_feat = put(features, P("data"))
    g_lab = put(labels, P("data"))
    ones = put(np.ones(8, np.float32), P("data"))
    zeros = put(np.zeros(8, np.float32), P("data"))
    ep = put(np.zeros(8, np.int32), P("data"))
    key = jax.random.PRNGKey(7)

    with mesh:
        ts1, loss, n, _, _ = step(ts, g_feat, g_lab, ones, ep, key)
    assert int(n) == 8 and np.isfinite(float(loss))
    assert int(host_copy(ts1.version)) == 1

    # plain reference step on the same host state
    from elasticdl_tpu.training.step import make_train_step

    plain = make_train_step(model, loss_fn, opt)
    ts_plain, loss_plain = plain(ts0, features, labels, key)
    np.testing.assert_allclose(float(loss), float(loss_plain), rtol=1e-5)
    h1, hp = host_copy(ts1.params), host_copy(ts_plain.params)
    for a, b in zip(
        jax.tree_util.tree_leaves(h1), jax.tree_util.tree_leaves(hp)
    ):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    # drain step: weight 0 everywhere is an exact no-op
    with mesh:
        ts2, _, n0, _, _ = step(ts1, g_feat, g_lab, zeros, ep, key)
    assert int(n0) == 0
    assert int(host_copy(ts2.version)) == 1
    for a, b in zip(
        jax.tree_util.tree_leaves(host_copy(ts2.params)),
        jax.tree_util.tree_leaves(host_copy(ts1.params)),
    ):
        np.testing.assert_array_equal(a, b)


def test_weighted_step_with_accumulation_matches_plain():
    """accum_steps=2 on the weighted plane must equal the plain
    full-batch step (16 rows -> 8 devices x 2 microbatches of 1)."""
    import flax.linen as nn
    import jax
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from elasticdl_tpu.nn.model_api import init_variables, split_variables
    from elasticdl_tpu.parallel.elastic import (
        broadcast_from_device0,
        host_copy,
        make_elastic_train_step,
    )
    from elasticdl_tpu.training.step import TrainState, make_train_step

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, inputs, training=False):
            x = inputs["image"].reshape((inputs["image"].shape[0], -1))
            x = nn.relu(nn.Dense(32)(x))
            return nn.Dense(10)(x)

    def loss_fn(output, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            output, labels.reshape(-1)
        ).mean()

    model = MLP()
    rng = np.random.default_rng(3)
    features = {"image": rng.random((16, 28, 28), dtype=np.float32)}
    labels = rng.integers(0, 10, size=(16, 1)).astype(np.int64)
    variables = init_variables(
        model, jax.random.PRNGKey(0), {"image": features["image"][:1]}
    )
    params, state = split_variables(variables)
    opt = optax.sgd(0.1)
    ts0 = TrainState.create(params, state, opt)

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    ts = broadcast_from_device0(mesh, host_copy(ts0))
    step = make_elastic_train_step(model, loss_fn, opt, mesh, accum_steps=2)

    def put(tree, spec):
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, spec)), tree
        )

    key = jax.random.PRNGKey(7)
    with mesh:
        ts1, loss, n, _, _ = step(
            ts,
            put(features, P("data")),
            put(labels, P("data")),
            put(np.ones(8, np.float32), P("data")),
            put(np.zeros(8, np.int32), P("data")),
            key,
        )
    assert int(n) == 8

    plain = make_train_step(model, loss_fn, opt)
    ts_plain, loss_plain = plain(ts0, features, labels, key)
    np.testing.assert_allclose(float(loss), float(loss_plain), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(host_copy(ts1.params)),
        jax.tree_util.tree_leaves(host_copy(ts_plain.params)),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_replicated_eval_pins_version_snapshot():
    """Eval rounds pin a version; the replicated plane must score every
    task of round V with version-V params even after training moves on
    (reference pinned-checkpoint semantics), and report the version it
    actually scored when it cannot pin exactly."""
    from elasticdl_tpu.common.constants import JobType
    from elasticdl_tpu.worker.elastic_allreduce_worker import (
        ElasticAllReduceWorker,
    )
    from tests.test_utils import MODEL_ZOO_PATH

    worker = ElasticAllReduceWorker(
        worker_id=0,
        job_type=JobType.TRAINING_ONLY,
        minibatch_size=4,
        model_zoo=MODEL_ZOO_PATH,
        model_def="mnist_subclass.mnist_subclass.CustomModel",
        stub=None,
    )

    class FakeTS:
        def __init__(self, tag):
            self.params = {"w": tag}
            self.state = {}

    class FakeTrainer:
        is_sharded = False
        version = 5

        def snapshot(self):
            return FakeTS(self.version)

    worker.trainer = FakeTrainer()
    worker._forward_fn = lambda params, state, x: params["w"]

    # round pinned at the current version: exact
    assert worker._local_forward("x", pinned_version=5) == 5
    assert worker._eval_scored_version == 5

    # training advances; round-5 tasks KEEP scoring the v5 snapshot
    worker.trainer.version = 7
    assert worker._local_forward("x", pinned_version=5) == 5
    assert worker._eval_scored_version == 5

    # a new round at v7 refreshes
    assert worker._local_forward("x", pinned_version=7) == 7
    assert worker._eval_scored_version == 7

    # late grab (round pinned v6 never snapshotted): scores current and
    # reports the true version
    worker.trainer.version = 9
    assert worker._local_forward("x", pinned_version=6) == 9
    assert worker._eval_scored_version == 9


def test_elastic_worker_routes_transformer_configs():
    """The multi-process elastic worker trains transformer_lm REPLICATED
    when no pipeline is requested, and routes pipelined configs to the
    collective (in-step ring) form — the r4 NotImplementedError boundary
    is gone."""
    from elasticdl_tpu.common.constants import JobType
    from elasticdl_tpu.worker.elastic_allreduce_worker import (
        ElasticAllReduceWorker,
    )
    from tests.test_utils import MODEL_ZOO_PATH

    kwargs = dict(
        worker_id=0,
        job_type=JobType.TRAINING_ONLY,
        minibatch_size=4,
        model_zoo=MODEL_ZOO_PATH,
        model_def="transformer_lm.transformer_lm.custom_model",
        stub=None,
    )
    # replicated training: fine
    worker = ElasticAllReduceWorker(
        model_params="vocab_size=64,num_layers=2", **kwargs
    )
    assert not worker.trainer.is_sharded

    # pipelined config: stage params shard over "pipe"; the trainer gets
    # the collective builder + the zoo's mesh-axes layout
    worker = ElasticAllReduceWorker(
        model_params="vocab_size=64,num_layers=2,pipeline_stages=2",
        **kwargs,
    )
    assert worker.trainer.is_sharded
    assert worker.trainer._mesh_axes_fn is not None
    assert worker.trainer._mesh_axes_fn(8) == {"data": 4, "pipe": 2}


def test_evaluation_round_records_scored_versions():
    from elasticdl_tpu.master.evaluation_service import _EvaluationJob

    job = _EvaluationJob(
        {"acc": lambda labels, predictions: np.equal(labels, predictions)},
        model_version=10,
        total_tasks=2,
    )
    assert job.report_evaluation_metrics(
        10, {"output": np.ones(2)}, np.ones(2), scored_version=8
    )
    assert job.scored_versions == {8}
    # wrong pinned version still dropped
    assert not job.report_evaluation_metrics(
        9, {"output": np.ones(2)}, np.ones(2), scored_version=9
    )


# -- rung 2: real OS processes over gloo ------------------------------------


def _count_successes(task_d):
    """Patch task_d.report to collect successful task ids (shared by the
    kill and scale-up rungs)."""
    completed = []
    orig_report = task_d.report

    def counting_report(task_id, success, **kwargs):
        if success:
            completed.append(task_id)
        return orig_report(task_id, success, **kwargs)

    task_d.report = counting_report
    return completed


def _master_for(data_dir, num_workers, num_epochs=2, extra=()):
    args = parse_master_args(
        [
            "--job_name",
            "elastic-ar-test",
            "--model_zoo",
            MODEL_ZOO_PATH,
            "--model_def",
            "mnist_subclass.mnist_subclass.CustomModel",
            "--minibatch_size",
            "16",
            "--num_minibatches_per_task",
            "4",
            "--num_epochs",
            str(num_epochs),
            "--training_data",
            data_dir,
            "--num_workers",
            str(num_workers),
            "--num_ps_pods",
            "0",
            "--port",
            "0",
            "--distribution_strategy",
            "AllreduceStrategy",
        ]
        + list(extra)
    )
    master = Master(args)
    master.prepare()
    return master


def _worker_command_for(master, extra=()):
    def worker_command(worker_id):
        return [
            sys.executable,
            "-m",
            "elasticdl_tpu.worker.main",
            "--worker_id",
            str(worker_id),
            "--job_type",
            "training_only",
            "--master_addr",
            "localhost:%d" % master.port,
            "--model_zoo",
            MODEL_ZOO_PATH,
            "--model_def",
            "mnist_subclass.mnist_subclass.CustomModel",
            "--minibatch_size",
            "16",
            "--distribution_strategy",
            "AllreduceStrategy",
            "--comm_host",
            "localhost",
        ] + list(extra)

    return worker_command


def _worker_env():
    env = dict(os.environ)
    env.update(
        {
            "PYTHONPATH": REPO,
            "EDL_DIST_PLATFORM": "cpu",
            "EDL_LOCAL_DEVICES": "1",
            "EDL_COMM_HOST": "localhost",
            # init timeout deliberately < the master's 15 s confirm
            # window: a member stuck in a stale formation barrier raises
            # WorldBroken and re-polls before the fencer kills it
            "EDL_WORLD_INIT_TIMEOUT": "10",
            "EDL_HEARTBEAT_TIMEOUT": "10",
            "EDL_SHUTDOWN_TIMEOUT": "5",
            # fenced/wedged workers dump all-thread stacks on SIGABRT
            "PYTHONFAULTHANDLER": "1",
        }
    )
    # the parent test process pins these for its own virtual mesh; they
    # must not leak a conflicting device count into the workers
    env.pop("XLA_FLAGS", None)
    return env


@pytest.mark.slow
def test_elastic_allreduce_two_process_job(tmp_path):
    create_recordio_file(
        256, DatasetName.IMAGE_DEFAULT, (28, 28), temp_dir=str(tmp_path)
    )
    master = _master_for(str(tmp_path), num_workers=2, num_epochs=1)
    manager = LocalInstanceManager(
        master.task_d,
        2,
        _worker_command_for(master),
        env=_worker_env(),
        membership=master.membership,
    )
    master.instance_manager = manager
    manager.start_workers()
    runner = threading.Thread(
        target=master.run, kwargs={"poll_secs": 0.5}, daemon=True
    )
    runner.start()
    runner.join(timeout=300)
    assert not runner.is_alive(), "master did not finish"
    assert master.task_d.finished()
    manager.stop_relaunch_and_remove_all_pods()


def run_three_worker_job(tmp_path):
    """The 3-worker/2-epoch elastic job with a mid-job SIGKILL of one
    worker: the kill rung's harness."""
    create_recordio_file(
        384, DatasetName.IMAGE_DEFAULT, (28, 28), temp_dir=str(tmp_path)
    )
    master = _master_for(str(tmp_path), num_workers=3, num_epochs=2)

    completed = _count_successes(master.task_d)

    manager = LocalInstanceManager(
        master.task_d,
        3,
        _worker_command_for(master),
        env=_worker_env(),
        membership=master.membership,
        max_relaunches=10,
        # a pre-warmed spare: the kill's relaunch cost becomes
        # membership-only (the standby already paid its jax import)
        num_standby=1,
    )
    master.instance_manager = manager
    manager.start_workers()
    runner = threading.Thread(
        target=master.run, kwargs={"poll_secs": 0.5}, daemon=True
    )
    runner.start()

    # wait for real collective progress, then kill a worker mid-job
    deadline = time.time() + 240
    while len(completed) < 2:
        assert time.time() < deadline, "job made no progress"
        assert runner.is_alive(), "master exited early"
        time.sleep(0.5)
    victims = manager.live_workers()
    assert victims, "no live workers to kill"
    manager.kill_worker(victims[-1])

    runner.join(timeout=420)
    assert not runner.is_alive(), "master did not finish"
    assert master.task_d.finished()
    # every task completed (3 workers, 384*2 records / 64 per task)
    assert len(set(completed)) == 12
    manager.stop_relaunch_and_remove_all_pods()


@pytest.mark.slow
def test_elastic_allreduce_survives_worker_kill(tmp_path):
    run_three_worker_job(tmp_path)


@pytest.mark.slow
def test_elastic_allreduce_graceful_preemption_drain(tmp_path):
    """SIGTERM (a cloud preemption notice) must drain gracefully: the
    worker flushes its window and LEAVES the world cleanly (exit 75,
    EX_TEMPFAIL), survivors re-form without a broken collective, a
    replacement launches, and every task completes."""
    data_dir = tmp_path / "data"
    data_dir.mkdir()
    create_recordio_file(
        384, DatasetName.IMAGE_DEFAULT, (28, 28), temp_dir=str(data_dir)
    )
    log_dir = str(tmp_path / "logs")
    master = _master_for(str(data_dir), num_workers=3, num_epochs=6)
    completed = _count_successes(master.task_d)

    manager = LocalInstanceManager(
        master.task_d,
        3,
        _worker_command_for(master),
        env=_worker_env(),
        membership=master.membership,
        max_relaunches=10,
        log_dir=log_dir,
    )
    master.instance_manager = manager
    manager.start_workers()
    runner = threading.Thread(
        target=master.run, kwargs={"poll_secs": 0.5}, daemon=True
    )
    runner.start()

    deadline = time.time() + 240
    while len(completed) < 1:
        assert time.time() < deadline, "job made no progress"
        assert runner.is_alive(), "master exited early"
        time.sleep(0.5)
    victims = manager.live_workers()
    assert victims, "no live workers to terminate"
    victim = victims[-1]
    manager.terminate_worker(victim)

    runner.join(timeout=420)
    assert not runner.is_alive(), "master did not finish after the drain"
    assert master.task_d.finished()
    assert len(set(completed)) == 36
    # the terminated worker exited through the graceful-drain path
    assert manager.exit_codes.get(("worker", victim)) == 75, (
        manager.exit_codes
    )
    manager.stop_relaunch_and_remove_all_pods()
    # the drain's whole point: the victim announced and the world paused
    # at a batch boundary — NO worker ever hit a broken collective (the
    # SIGKILL rung, by contrast, exercises the failed-step path)
    import glob as _glob

    logs = {
        path: open(path, "rb").read().decode("utf-8", "replace")
        for path in _glob.glob(os.path.join(log_dir, "worker-*.log"))
    }
    victim_log = logs.get(os.path.join(log_dir, "worker-%d.log" % victim))
    assert victim_log and "drain announced" in victim_log, (
        "victim never announced its drain"
    )
    offenders = [
        path
        for path, text in logs.items()
        if "collective step failed" in text
    ]
    assert not offenders, (
        "graceful drain still broke a collective: %s" % offenders
    )


@pytest.mark.slow
def test_elastic_allreduce_scales_up_mid_job(tmp_path):
    """Pure growth (no kill): a worker added mid-job parks in the
    joiner lobby until the 2-worker formation is seen training, then a
    growth bump folds it in — the job finishes with all tasks done and
    the world actually reached size 3."""
    create_recordio_file(
        768, DatasetName.IMAGE_DEFAULT, (28, 28), temp_dir=str(tmp_path)
    )
    # 8 lazy epochs x 12 tasks: the job must outlive the joiner's cold
    # start (jax import + reader prime) by a wide margin — each worker's
    # shuffle buffer alone swallows 16 tasks (1024 records) at priming,
    # so small jobs drain before a late joiner can ever grab a task
    master = _master_for(str(tmp_path), num_workers=2, num_epochs=8)

    completed = _count_successes(master.task_d)

    # every get_world registers; record the live-set size at each one so
    # a short-lived 3-member world cannot be missed by polling
    live_sizes = []
    orig_register = master.membership.register

    def spy_register(worker_id, host="localhost"):
        result = orig_register(worker_id, host)
        live_sizes.append(len(master.membership._live))
        return result

    master.membership.register = spy_register

    manager = LocalInstanceManager(
        master.task_d,
        2,
        _worker_command_for(master),
        env=_worker_env(),
        membership=master.membership,
    )
    master.instance_manager = manager
    manager.start_workers()
    runner = threading.Thread(
        target=master.run, kwargs={"poll_secs": 0.5}, daemon=True
    )
    runner.start()

    # add the third worker the moment the 2-worker world forms (the
    # first completion REPORT lands much later: record counts are held
    # back through the deferred-sync window)
    deadline = time.time() + 240
    while master.membership.epoch < 1:
        assert time.time() < deadline, "initial world never formed"
        assert runner.is_alive(), "master exited early"
        time.sleep(0.2)
    manager._start_worker()

    runner.join(timeout=420)
    assert not runner.is_alive(), "master did not finish"
    assert master.task_d.finished()
    # >= not ==: a fence-and-relaunch race on a loaded host can push
    # the live set past 3 transiently; growth is what matters
    assert max(live_sizes) >= 3, (
        "third worker never joined the live set (max=%d)"
        % max(live_sizes)
    )
    # 768*8 records / 64 per task = 96 tasks, all completed exactly once
    assert len(set(completed)) == 96
    manager.stop_relaunch_and_remove_all_pods()


@pytest.mark.slow
def test_elastic_allreduce_resumes_from_sharded_checkpoint(tmp_path):
    """Job 1 writes sharded checkpoints; job 2 (fresh master + fresh
    workers, same checkpoint dir) must resume from them — its exported
    model version continues past job 1's steps instead of restarting."""
    from elasticdl_tpu.common.model_utils import load_from_checkpoint_file
    from elasticdl_tpu.common.sharded_checkpoint import (
        ShardedCheckpointManager,
    )

    data_dir = tmp_path / "data"
    data_dir.mkdir()
    create_recordio_file(
        256, DatasetName.IMAGE_DEFAULT, (28, 28), temp_dir=str(data_dir)
    )
    ckpt_dir = str(tmp_path / "ckpt")
    out_dir = str(tmp_path / "export")

    def run_job():
        master = _master_for(
            str(data_dir),
            num_workers=2,
            num_epochs=1,
            extra=[
                "--checkpoint_dir",
                ckpt_dir,
                "--checkpoint_steps",
                "4",
                "--output",
                out_dir,
            ],
        )
        manager = LocalInstanceManager(
            master.task_d,
            2,
            _worker_command_for(
                master,
                extra=[
                    "--checkpoint_dir",
                    ckpt_dir,
                    "--checkpoint_steps",
                    "4",
                ],
            ),
            env=_worker_env(),
            membership=master.membership,
        )
        master.instance_manager = manager
        manager.start_workers()
        runner = threading.Thread(
            target=master.run, kwargs={"poll_secs": 0.5}, daemon=True
        )
        runner.start()
        runner.join(timeout=300)
        assert not runner.is_alive(), "master did not finish"
        assert master.task_d.finished()
        manager.stop_relaunch_and_remove_all_pods()

    run_job()
    mgr = ShardedCheckpointManager(ckpt_dir)
    v1 = mgr.versions()
    assert v1, "job 1 wrote no sharded checkpoints"

    run_job()
    v2 = mgr.versions()
    # job 2 resumed: its checkpoints continue past job 1's last version
    assert max(v2) > max(v1), (v1, v2)
    # and the exported model's version reflects the resumed counter
    exports = []
    for root, _, files in os.walk(out_dir):
        for f in files:
            if f.endswith(".chkpt"):
                exports.append(os.path.join(root, f))
    assert exports
    versions = [load_from_checkpoint_file(p)[0] for p in exports]
    assert max(versions) > max(v1), (versions, v1)


@pytest.mark.slow
def test_elastic_allreduce_evaluation_interleave(tmp_path, monkeypatch):
    """TRAINING_WITH_EVALUATION on the elastic plane: the coordinating
    master learns versions from worker task reports (it applies no
    gradients), triggers gap-based eval rounds pinning version NUMBERS,
    and workers score them with their own device state."""
    monkeypatch.setenv("EDL_FORM_GRACE_SECS", "120")
    train_dir = tmp_path / "train"
    val_dir = tmp_path / "val"
    train_dir.mkdir()
    val_dir.mkdir()
    create_recordio_file(
        192, DatasetName.IMAGE_DEFAULT, (28, 28), temp_dir=str(train_dir)
    )
    create_recordio_file(
        32, DatasetName.IMAGE_DEFAULT, (28, 28), temp_dir=str(val_dir)
    )
    master = _master_for(
        str(train_dir),
        num_workers=2,
        num_epochs=2,
        extra=(
            "--validation_data",
            str(val_dir),
            "--evaluation_steps",
            "4",
            "--evaluation_start_delay_secs",
            "0",
        ),
    )
    assert master.evaluation_service is not None

    published = []
    orig_publish = master.evaluation_service._publish_summary

    def capture_publish(round_):
        published.append(
            (round_.model_version, round_.get_evaluation_summary())
        )
        return orig_publish(round_)

    master.evaluation_service._publish_summary = capture_publish

    manager = LocalInstanceManager(
        master.task_d,
        2,
        _worker_command_for(
            master, extra=("--job_type", "training_with_evaluation")
        ),
        env=_worker_env(),
        membership=master.membership,
    )
    master.instance_manager = manager
    manager.start_workers()
    runner = threading.Thread(
        target=master.run, kwargs={"poll_secs": 0.5}, daemon=True
    )
    runner.start()
    runner.join(timeout=300)
    assert not runner.is_alive(), "master did not finish"
    assert master.task_d.finished()
    manager.stop_relaunch_and_remove_all_pods()

    assert published, "no evaluation round ever completed"
    for version, metrics in published:
        assert version > 0
        assert metrics, "empty evaluation summary"


def test_membership_world_size_multiple_rounds_down():
    """Pipelined jobs need worlds whose size divides the stage count:
    formation rounds DOWN to the multiple, overflow members poll as
    spares ({"spare": True}), and reaching the multiple folds them in."""
    m = MembershipService(
        expected_workers=4, form_grace_secs=0.01, world_size_multiple=2
    )

    def drive_formation(members):
        # confirm (awaiting=True) then mark trained (awaiting=False) so
        # the two-phase formation completes and lobby joiners fold in
        for _ in range(6):
            for wid in members:
                m.get_world(wid)
            for wid in members:
                m.get_world(wid, awaiting=False)

    m.get_world(0)
    time.sleep(0.05)
    for w in (0, 1, 2):
        m.get_world(w)
    drive_formation([0, 1])
    # 3 live -> world of 2, lowest ids win; 2 polls as a spare
    w2 = m.get_world(2)
    assert not w2["ready"] and w2.get("spare")
    world = _poll_ready(m, 0)
    assert world["num_processes"] == 2
    assert world["members"] == [0, 1]
    # the 4th member arrives -> the next bump forms a full world of 4
    m.get_world(3)
    drive_formation([0, 1, 2, 3])
    world = _poll_ready(m, 2)
    assert world["num_processes"] == 4
    # a death drops 4 -> world of 2 again (3 survivors round down)
    m.remove(1)
    drive_formation([0, 2])
    world = _poll_ready(m, 0)
    assert world["num_processes"] == 2
    assert world["members"] == [0, 2]
    spare = m.get_world(3)
    assert not spare["ready"] and spare.get("spare")


def test_spare_worker_requeues_inflight_tasks():
    """A worker parked as a spare must hand its pulled tasks back (the
    members finish them; a spare holding tasks stalls the job)."""
    from elasticdl_tpu.common.constants import JobType
    from elasticdl_tpu.worker.elastic_allreduce_worker import (
        ElasticAllReduceWorker,
    )
    from tests.test_utils import MODEL_ZOO_PATH

    class SpareStub:
        """Master stub: always answers 'you are a spare'."""

        def __init__(self):
            self.reported = []

        def get_comm_world(self, worker_id, host=None, awaiting=True):
            return {"epoch": 3, "ready": False, "spare": True, "dead": []}

        def report_task_result(self, task_id, err_msg, exec_counters=None):
            self.reported.append((task_id, err_msg))
            return {}

    stub = SpareStub()
    worker = ElasticAllReduceWorker(
        worker_id=5,
        job_type=JobType.TRAINING_ONLY,
        minibatch_size=4,
        model_zoo=MODEL_ZOO_PATH,
        model_def="transformer_lm.transformer_lm.custom_model",
        model_params="vocab_size=64,num_layers=2,pipeline_stages=2",
        stub=stub,
    )
    # simulate a primed worker holding one in-flight task
    tds = worker._task_data_service
    task = SimpleNamespace(task_id=9, start=0, end=8, type=None)
    tds._inflight.append(task)
    tds._record_cursor = 4  # half consumed (the primed batch)
    worker._retry_batch = ({"tokens": np.zeros((4, 8), np.int32)},
                           np.zeros((4, 8), np.int32))

    worker._requeue_as_spare()
    assert worker._retry_batch is None
    assert tds.get_current_task() is None
    assert stub.reported and stub.reported[0][0] == 9
    assert "spare" in stub.reported[0][1]
