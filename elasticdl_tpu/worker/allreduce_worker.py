"""ALLREDUCE-strategy worker: task-driven on-device data parallelism.

The reference never implemented its allreduce design (docs/designs/
allreduce.md is a survey; SURVEY.md §2.2) — this is the TPU-native
realization. The worker pulls tasks from the master exactly like the PS
worker (same dispatcher, same elasticity: a resize looks like recovered
tasks), but parameters never leave device HBM: every minibatch is one
fused jitted step over the device mesh, and the gradient exchange is the
in-step XLA collective (parallel/trainer.py).

The master runs in pure control-plane mode (optimizer=None): tasks, eval
bookkeeping, SAVE_MODEL. Checkpoints are written by this worker from the
device state since the master holds no parameters.

Elasticity inside one host: ``resize(devices)`` re-forms the mesh
mid-job. Across hosts the same loop runs per-process over a
``jax.distributed`` mesh; membership changes pause at a task boundary and
re-enter through ``resize``.
"""

import os
import time

import numpy as np

from elasticdl_tpu.common.constants import (
    GetModelMethod,
    JobType,
    MetricsDictKey,
    Mode,
    SaveModelConfig,
    TaskType,
)
from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.common.model_utils import get_model_spec
from elasticdl_tpu.parallel.trainer import AllReduceTrainer
from elasticdl_tpu.worker.task_data_service import TaskDataService


class AllReduceWorker:
    def __init__(
        self,
        worker_id,
        job_type,
        minibatch_size,
        model_zoo,
        model_def,
        model_params=None,
        dataset_fn="dataset_fn",
        loss="loss",
        optimizer="optimizer",
        eval_metrics_fn="eval_metrics_fn",
        stub=None,
        devices=None,
        data_reader_params=None,
        seed=0,
        accum_steps=1,
        precision=None,
        checkpoint_dir="",
        checkpoint_steps=0,
        keep_checkpoint_max=0,
        remat="",
    ):
        if job_type in (
            JobType.EVALUATION_ONLY,
            JobType.PREDICTION_ONLY,
        ):
            # this single-process run loop only trains (with optional
            # eval interleave); pure eval jobs are served by the elastic
            # worker's checkpoint-scored eval-only drain (api.py routes
            # them there), and predict by ParameterServerStrategy
            raise NotImplementedError(
                "%s is not served by the single-process ALLREDUCE loop; "
                "evaluation_only runs via the elastic worker "
                "(checkpoint-scored), prediction under "
                "ParameterServerStrategy" % job_type
            )
        self._worker_id = worker_id
        self._job_type = job_type
        self._minibatch_size = minibatch_size
        self._accum_steps = max(1, accum_steps)
        self._stub = stub
        spec = get_model_spec(
            model_zoo=model_zoo,
            model_def=model_def,
            model_params=model_params,
            dataset_fn=dataset_fn,
            loss=loss,
            optimizer=optimizer,
            eval_metrics_fn=eval_metrics_fn,
        )
        self._dataset_fn = spec.dataset_fn
        # strategy-aware model rewriting (the ModelHandler concept,
        # reference model_handler.py:94-106): a zoo module that defines
        # ``build_distributed_model(mesh)`` gets its HBM-sharded variant
        # here — embedding tables row-shard over device memory and update
        # inside the jitted step instead of living in a host PS store
        from elasticdl_tpu.common.model_utils import (
            get_dict_from_params_str,
            get_module_file_path,
            load_module,
        )
        from elasticdl_tpu.parallel.mesh import create_mesh

        module = load_module(
            get_module_file_path(model_zoo, model_def)
        ).__dict__
        params_dict = get_dict_from_params_str(model_params) or {}
        mesh_shape = None
        if "mesh_axes" in module:
            # the model declares its parallelism layout (e.g. a
            # transformer with pipeline_stages wants {"data": n/S,
            # "pipe": S}); None keeps the default all-data mesh
            import jax as _jax

            n_dev = len(devices) if devices else len(_jax.devices())
            mesh_shape = module["mesh_axes"](n_dev, **params_dict)
        mesh = create_mesh(
            mesh_shape,
            axis_names=tuple(mesh_shape) if mesh_shape else None,
            devices=devices,
        )
        model = spec.model
        param_specs = None
        if "build_distributed_model" in module:
            model = module["build_distributed_model"](
                mesh=mesh, **params_dict
            )
            if "param_shardings" in module:
                # full model params, uniformly with the other hooks —
                # zoo param_shardings declare **_params catch-alls
                param_specs = module["param_shardings"](
                    mesh, **params_dict
                )
        from elasticdl_tpu.training.step import parse_remat

        self.trainer = AllReduceTrainer(
            model, spec.loss, spec.optimizer(), mesh=mesh,
            param_specs=param_specs, seed=seed,
            accum_steps=accum_steps, precision=precision,
            remat=parse_remat(remat),
        )
        self._forward_fn = None
        self._model = model
        from elasticdl_tpu.common.export import export_provenance

        self._export_meta = export_provenance(
            model_zoo, model_def, model_params
        )
        self._evaluation_result = {}
        self._task_data_service = TaskDataService(
            self,
            self._job_type == JobType.TRAINING_WITH_EVALUATION,
            data_reader_params=data_reader_params,
        )
        # worker-side sharded checkpoints: in ALLREDUCE mode parameters
        # live on this worker's mesh, so the worker (not the master)
        # writes them — same cadence/format as the multi-process elastic
        # plane, so eval-only jobs and resumes read either
        self._ckpt = None
        self._last_ckpt_version = 0
        self._restore_attempted = False
        if checkpoint_dir and checkpoint_steps:
            from elasticdl_tpu.common.sharded_checkpoint import (
                ShardedCheckpointManager,
            )

            self._ckpt = ShardedCheckpointManager(
                checkpoint_dir,
                checkpoint_steps,
                keep_checkpoint_max,
            )
            self._ckpt.set_expected_writers(1)

    # master surface used by TaskDataService
    def get_task(self, task_type=None):
        return self._stub.get_task(self._worker_id, task_type)

    def report_task_result(self, task_id, err_msg="", exec_counters=None):
        from elasticdl_tpu.worker.reporting import with_model_version

        return self._stub.report_task_result(
            task_id,
            err_msg,
            with_model_version(lambda: self.trainer.version, exec_counters),
        )

    # -- steps --------------------------------------------------------------

    def _pad_to_devices(self, features, labels):
        """Pad a partial batch up to a multiple of mesh size x
        accum_steps (each device must hold whole microbatches).

        Padding repeats the final example; the padded rows slightly
        re-weight the last partial batch of a task (bounded by
        n_devices*accum/batch) — the price of static shapes on the mesh.
        """
        import jax

        n = self.trainer.num_devices * self._accum_steps
        leaf = jax.tree_util.tree_leaves(features)[0]
        b = np.asarray(leaf).shape[0]
        pad = (-b) % n
        if pad == 0:
            return features, labels, b

        def _pad(x):
            x = np.asarray(x)
            return np.concatenate([x, np.repeat(x[-1:], pad, axis=0)])

        return (
            jax.tree_util.tree_map(_pad, features),
            jax.tree_util.tree_map(_pad, labels),
            b,
        )

    def _maybe_restore(self):
        """Resume from the newest restorable checkpoint once state
        exists (first batch). Same fall-through-older semantics as the
        elastic plane: a torn newest directory must not wedge resume —
        and without this, a restarted local job would silently
        re-initialize and overwrite the previous run's versions."""
        if self._ckpt is None or self._restore_attempted:
            return
        self._restore_attempted = True
        for directory in self._ckpt.dirs_newest_first():
            try:
                restored = self.trainer.restore_sharded(directory)
                self._last_ckpt_version = restored
                logger.info(
                    "resumed from checkpoint v%d (%s)", restored, directory
                )
                return
            except Exception:
                logger.warning(
                    "checkpoint %s unrestorable; trying older",
                    directory,
                    exc_info=True,
                )

    def _train_batch(self, dataset_batch):
        features, labels = dataset_batch
        features, labels, count = self._pad_to_devices(features, labels)
        if self.trainer.train_state is None:
            self.trainer.init_from_batch((features, labels))
            self._maybe_restore()
        # the per-step fetch keeps failure accounting exact (a failed
        # step surfaces on the batch that failed, before its records are
        # reported done); the multi-process elastic worker is the plane
        # where deferred sync pays — it validates in windows instead
        loss = self.trainer.train_step(features, labels)
        return float(loss), count

    def _forward(self, features):
        import jax

        if self._forward_fn is None:
            from elasticdl_tpu.training.step import make_forward_fn

            self._forward_fn = make_forward_fn(self._model)
        ts = self.trainer.train_state
        return self._forward_fn(ts.params, ts.state, features)

    # -- evaluation ---------------------------------------------------------

    def _process_eval_task(self, task):
        eval_info = self._task_data_service.get_validation_dataset(task)
        if not eval_info:
            return
        eval_dataset, model_version, task_id = eval_info
        eval_dataset = self._dataset_fn(
            eval_dataset,
            Mode.EVALUATION,
            self._task_data_service.data_reader.metadata,
        )
        eval_dataset = eval_dataset.batch(self._minibatch_size).prefetch(1)
        err_msg = ""
        outputs_key = MetricsDictKey.MODEL_OUTPUT
        for features, labels in eval_dataset:
            outputs = self._forward(features)
            if not isinstance(outputs, dict):
                outputs = {outputs_key: outputs}
            for k, v in outputs.items():
                self._evaluation_result.setdefault(
                    outputs_key, {}
                ).setdefault(k, []).append(np.asarray(v))
            self._evaluation_result.setdefault(
                MetricsDictKey.LABEL, []
            ).append(np.asarray(labels))
        if outputs_key in self._evaluation_result:
            outputs = {
                name: np.concatenate(chunks)
                for name, chunks in self._evaluation_result[
                    outputs_key
                ].items()
            }
            labels = np.concatenate(
                self._evaluation_result[MetricsDictKey.LABEL]
            )
            self._stub.report_evaluation_metrics(
                model_version, outputs, labels
            )
        self.report_task_result(task_id, err_msg)
        self._evaluation_result = {}

    def _evaluate_only(self):
        executed = False
        while True:
            task = self.get_task(TaskType.EVALUATION)
            if not task.shard_name:
                break
            self._process_eval_task(task)
            executed = True
        return executed

    def _process_save_model_task_if_needed(self):
        task, dataset = (
            self._task_data_service.get_save_model_task_and_dataset()
        )
        if task is None or dataset is None:
            return
        saved_model_path = task.extended_config.get(
            SaveModelConfig.SAVED_MODEL_PATH
        )
        saved_model_path = os.path.join(
            saved_model_path, str(int(time.time()))
        )
        ts = self.trainer.get_host_state()
        from elasticdl_tpu.common.export import (
            example_batch_for_export,
            export_model,
            make_serving_fn,
        )

        example = example_batch_for_export(
            dataset,
            self._dataset_fn,
            self._task_data_service.data_reader.metadata,
            self._minibatch_size,
            Mode.PREDICTION,
        )
        export_model(
            saved_model_path,
            ts.params,
            self.trainer.version,
            metadata=self._export_meta,
            serving_fn=(
                make_serving_fn(self._model, ts.state)
                if example is not None
                else None
            ),
            example_features=example,
        )
        logger.info("Exported model to %s", saved_model_path)
        self.report_task_result(task_id=task.task_id, err_msg="")

    # -- main loop ----------------------------------------------------------

    # a step that fails this many times in a row, with no step
    # succeeding in between, is failing for a reason a requeue cannot
    # cure (a kernel the compiler refuses, a shape the model rejects)
    MAX_CONSECUTIVE_STEP_FAILURES = 3

    def run(self):
        losses = []
        failures_in_a_row = 0
        while True:
            dataset = self._task_data_service.get_dataset()
            if not dataset:
                break
            dataset = self._dataset_fn(
                dataset,
                Mode.TRAINING,
                self._task_data_service.data_reader.metadata,
            )
            dataset = dataset.batch(self._minibatch_size).prefetch(1)
            batches = 0
            for dataset_batch in dataset:
                batches += 1
                if self._job_type == JobType.TRAINING_WITH_EVALUATION:
                    self._evaluate_only()
                err_msg = ""
                try:
                    loss, count = self._train_batch(dataset_batch)
                    losses.append(loss)
                    failures_in_a_row = 0
                except Exception as e:  # report, don't die: task requeues
                    err_msg = str(e)
                    logger.exception("train step failed")
                    failures_in_a_row += 1
                    # drain exactly the head task so it fail-reports and
                    # requeues now; when no task is pending (failure after
                    # the task drained) charge the batch size instead of
                    # masking the real error with an AttributeError
                    count = (
                        self._task_data_service.remaining_records_in_head_task()
                        or len(dataset_batch[1])
                    )
                self._task_data_service.report_record_done(count, err_msg)
                if failures_in_a_row >= self.MAX_CONSECUTIVE_STEP_FAILURES:
                    # requeueing forever would hide a deterministic
                    # failure behind a job that never ends
                    raise RuntimeError(
                        "train step failed %d times in a row; last "
                        "error: %s" % (failures_in_a_row, err_msg)
                    )
                self._save_ckpt_if_due()
            if self._job_type == JobType.TRAINING_WITH_EVALUATION:
                self._evaluate_only()
            self._process_save_model_task_if_needed()
            if batches == 0:
                time.sleep(0.2)
        self._save_ckpt_if_due(final=True)
        return losses

    def _save_ckpt_if_due(self, final=False):
        """Write a sharded checkpoint at the version cadence (and once at
        job end, so eval-only jobs always find the finished state)."""
        if self._ckpt is None or not self._ckpt.is_enabled():
            return
        version = self.trainer.version
        if version <= self._last_ckpt_version:
            return
        if final or version - self._last_ckpt_version >= self._ckpt.steps:
            self._ckpt.save(self.trainer.train_state, version)
            self._last_ckpt_version = version
