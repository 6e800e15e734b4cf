"""Elastic multi-process job test — the north-star behavior.

A real master RPC server + two worker OS processes; one worker is killed
mid-job. Its in-flight tasks must be recovered and the job must complete
("survives killing 50% of worker processes"). Mirrors the
reference's k8s pod-deletion recovery (k8s_instance_manager_test.py) at
the process level.
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

from elasticdl_tpu.common.args import parse_master_args
from elasticdl_tpu.master.local_instance_manager import LocalInstanceManager
from elasticdl_tpu.master.master import Master
from tests.test_utils import MODEL_ZOO_PATH, DatasetName, create_recordio_file

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_elastic_job_survives_worker_kill(tmp_path):
    data_file = create_recordio_file(
        512, DatasetName.IMAGE_DEFAULT, (28, 28), temp_dir=str(tmp_path)
    )
    data_dir = str(tmp_path)

    args = parse_master_args(
        [
            "--job_name",
            "elastic-test",
            "--model_zoo",
            MODEL_ZOO_PATH,
            "--model_def",
            "mnist_subclass.mnist_subclass.CustomModel",
            "--minibatch_size",
            "16",
            "--num_epochs",
            "2",
            "--training_data",
            data_dir,
            "--num_ps_pods",
            "0",
            "--port",
            "0",
            "--use_async",
            "true",
        ]
    )
    master = Master(args)
    master.prepare()

    env = dict(os.environ)
    env.update(
        {
            "PYTHONPATH": REPO,
            "JAX_PLATFORMS": "cpu",
        }
    )

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
        ]

    manager = LocalInstanceManager(
        master.task_d, 2, worker_command, env=env
    )
    master.instance_manager = manager
    manager.start_workers()

    runner = threading.Thread(
        target=master.run, kwargs={"poll_secs": 0.5}, daemon=True
    )
    runner.start()

    # wait until real progress, then kill 50% of the workers
    deadline = time.time() + 180
    while master.master_servicer.get_model_version() < 3:
        assert time.time() < deadline, "job made no progress"
        time.sleep(0.5)
    victims = manager.live_workers()
    assert victims, "no live workers to kill"
    manager.kill_worker(victims[0])

    runner.join(timeout=240)
    assert not runner.is_alive(), "master did not finish"
    assert master.task_d.finished()
    # all 512*2 records were processed despite the kill
    assert master.master_servicer.get_model_version() >= 512 * 2 // 16 - 8
    manager.stop_relaunch_and_remove_all_pods()


def _tiny_master(tmp_path):
    create_recordio_file(
        64, DatasetName.IMAGE_DEFAULT, (28, 28), temp_dir=str(tmp_path)
    )
    args = parse_master_args(
        [
            "--job_name", "exhausted-test",
            "--model_zoo", MODEL_ZOO_PATH,
            "--model_def", "mnist_subclass.mnist_subclass.CustomModel",
            "--minibatch_size", "16",
            "--training_data", str(tmp_path),
            "--num_ps_pods", "0",
            "--port", "0",
        ]
    )  # fmt: skip
    master = Master(args)
    master.prepare()
    return master


def test_job_ends_nonzero_when_every_worker_is_gone_for_good(tmp_path):
    """Workers that die every time, a relaunch budget of one: once the
    budget is spent nobody will ever take the outstanding tasks, and the
    master must say so with its exit code instead of polling forever."""
    import sys
    import threading

    master = _tiny_master(tmp_path)
    spawned = []

    def worker_command(worker_id):
        spawned.append(worker_id)
        return [sys.executable, "-c", "import sys; sys.exit(3)"]

    manager = LocalInstanceManager(
        master.task_d,
        1,
        worker_command,
        restart_policy="Always",
        max_relaunches=1,
    )
    master.instance_manager = manager
    assert not manager.workers_exhausted()  # nothing started yet
    manager.start_workers()
    rc = []
    runner = threading.Thread(
        target=lambda: rc.append(master.run(poll_secs=0.1)), daemon=True
    )
    runner.start()
    runner.join(timeout=60)
    assert not runner.is_alive(), "master kept polling with no workers left"
    assert rc == [1]
    assert spawned == [0, 1]  # the original and its one relaunch
    assert not master.task_d.finished()


def test_wait_stopped_reaps_a_worker_that_ignores_sigterm(tmp_path):
    """`edl train` must not return while a process it started still
    holds the accelerator: wait_stopped waits out the grace, then
    kills."""
    import sys
    import time

    ready = tmp_path / "ready"
    code = (
        "import signal, sys, time\n"
        "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
        "open(sys.argv[1], 'w').close()\n"
        "time.sleep(120)\n"
    )

    class _NoTasks:
        def recover_tasks(self, worker_id):
            pass

    manager = LocalInstanceManager(
        _NoTasks(),
        1,
        lambda worker_id: [sys.executable, "-c", code, str(ready)],
    )
    manager.start_workers()
    deadline = time.monotonic() + 30
    while not ready.exists():
        assert time.monotonic() < deadline, "worker never started"
        time.sleep(0.05)
    (proc,) = manager._procs.values()
    manager.stop_relaunch_and_remove_all_pods()
    manager.wait_stopped(grace_secs=0.5)
    assert proc.poll() == -9
