"""ALLREDUCE worker E2E: task dispatch + on-device DP + elastic resize.

The BASELINE 'cifar10_subclass allreduce / elastic allreduce' configs:
training driven by master tasks while parameters stay on the mesh; a
mid-job mesh shrink (half the devices "lost") must not lose progress.
"""

import jax
import numpy as np

from elasticdl_tpu.common.constants import JobType
from elasticdl_tpu.master.checkpoint_service import CheckpointService
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.worker.allreduce_worker import AllReduceWorker
from tests.in_process_master import InProcessMaster
from tests.test_utils import MODEL_ZOO_PATH, DatasetName, create_recordio_file


def _job(num_epochs=2):
    f = create_recordio_file(128, DatasetName.IMAGE_DEFAULT, (28, 28))
    shards = {f: (0, 128)}
    task_d = TaskDispatcher(shards, {}, {}, 64, num_epochs)
    master = MasterServicer(
        1,
        16,
        None,  # pure control plane: no parameters on the master
        task_d,
        checkpoint_service=CheckpointService("", 0, 0, False),
        use_async=True,
    )
    worker = AllReduceWorker(
        worker_id=0,
        job_type=JobType.TRAINING_ONLY,
        minibatch_size=16,
        model_zoo=MODEL_ZOO_PATH,
        model_def="mnist_subclass.mnist_subclass.CustomModel",
        stub=InProcessMaster(master),
    )
    return task_d, master, worker


def test_allreduce_worker_completes_job():
    task_d, master, worker = _job()
    losses = worker.run()
    assert task_d.finished()
    # 128 records x 2 epochs / batch 16 = 16 on-device steps
    assert worker.trainer.version == 16
    assert len(losses) == 16
    assert all(np.isfinite(losses))


def test_allreduce_worker_accum_survives_tail_batches():
    """Tail batches must pad to devices x accum_steps, not just devices
    — otherwise the microbatch split rejects every task's last batch and
    the job wedges in a fail-report/requeue loop."""
    f = create_recordio_file(120, DatasetName.IMAGE_DEFAULT, (28, 28))
    task_d = TaskDispatcher({f: (0, 120)}, {}, {}, 64, 1)
    master = MasterServicer(
        1,
        16,
        None,
        task_d,
        checkpoint_service=CheckpointService("", 0, 0, False),
        use_async=True,
    )
    worker = AllReduceWorker(
        worker_id=0,
        job_type=JobType.TRAINING_ONLY,
        minibatch_size=16,
        model_zoo=MODEL_ZOO_PATH,
        model_def="mnist_subclass.mnist_subclass.CustomModel",
        stub=InProcessMaster(master),
        accum_steps=4,
    )
    losses = worker.run()
    assert task_d.finished()
    # 120 records / batch 16 = 8 batches (incl. one 8-row tail)
    assert worker.trainer.version == 8
    assert all(np.isfinite(losses))


def test_allreduce_worker_elastic_resize_mid_job():
    task_d, master, worker = _job(num_epochs=1)
    # consume the first dataset round manually: train a few batches then
    # shrink the mesh, as a membership epoch would
    first = [False]

    original = worker._train_batch

    def train_and_shrink(batch):
        result = original(batch)
        if not first[0]:
            first[0] = True
            worker.trainer.resize(jax.devices()[:4])
        return result

    worker._train_batch = train_and_shrink
    worker.run()
    assert task_d.finished()
    assert worker.trainer.num_devices == 4
    assert worker.trainer.version == 8


def test_allreduce_rejects_eval_and_predict_only_jobs():
    import pytest

    for job_type in (JobType.EVALUATION_ONLY, JobType.PREDICTION_ONLY):
        with pytest.raises(NotImplementedError, match="ParameterServer"):
            AllReduceWorker(
                worker_id=0,
                job_type=job_type,
                minibatch_size=16,
                model_zoo=MODEL_ZOO_PATH,
                model_def="mnist_subclass.mnist_subclass.CustomModel",
                stub=None,
            )


def test_allreduce_worker_resumes_from_sharded_checkpoint(tmp_path):
    """Job 2 on the same checkpoint dir must CONTINUE job 1's version
    counter (restore at first batch), not silently re-initialize and
    overwrite job 1's checkpoint directories."""
    from elasticdl_tpu.common.sharded_checkpoint import (
        ShardedCheckpointManager,
    )

    ckpt_dir = str(tmp_path / "ckpt")

    def run_job():
        f = create_recordio_file(128, DatasetName.IMAGE_DEFAULT, (28, 28))
        task_d = TaskDispatcher({f: (0, 128)}, {}, {}, 64, 1)
        master = MasterServicer(
            1,
            16,
            None,
            task_d,
            checkpoint_service=CheckpointService("", 0, 0, False),
            use_async=True,
        )
        worker = AllReduceWorker(
            worker_id=0,
            job_type=JobType.TRAINING_ONLY,
            minibatch_size=16,
            model_zoo=MODEL_ZOO_PATH,
            model_def="mnist_subclass.mnist_subclass.CustomModel",
            stub=InProcessMaster(master),
            checkpoint_dir=ckpt_dir,
            checkpoint_steps=4,
        )
        worker.run()
        assert task_d.finished()
        return worker.trainer.version

    v1 = run_job()
    assert v1 == 8  # 128 records / batch 16
    versions_after_1 = ShardedCheckpointManager(ckpt_dir).versions()
    assert versions_after_1, "job 1 wrote no checkpoints"

    v2 = run_job()
    # job 2 restored job 1's final state: its counter continued
    assert v2 == v1 + 8, (v1, v2)
    versions_after_2 = ShardedCheckpointManager(ckpt_dir).versions()
    assert max(versions_after_2) > max(versions_after_1)


def test_allreduce_worker_gives_up_on_a_step_that_fails_every_time():
    """A step failing for a reason no requeue can cure (a kernel the
    compiler refuses, say) must end the job loudly, not spin in a
    fail-report/requeue loop forever."""
    import pytest

    task_d, master, worker = _job(num_epochs=1)
    calls = []

    def refuse(batch):
        calls.append(1)
        raise RuntimeError("Mosaic refuses this kernel")

    worker._train_batch = refuse
    with pytest.raises(RuntimeError, match="3 times in a row"):
        worker.run()
    assert len(calls) == AllReduceWorker.MAX_CONSECUTIVE_STEP_FAILURES
    assert not task_d.finished()
