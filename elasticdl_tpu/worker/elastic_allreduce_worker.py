"""Elastic multi-process ALLREDUCE worker: one process per TPU host.

The reference's north-star behavior — a job that survives killing half its
workers — exists there only for the PS plane, where
workers never talk to each other. This worker realizes it for the
collective plane: each process pulls tasks from the master exactly like a
PS worker (same dispatcher, same recover_tasks elasticity), but trains via
the global-mesh weighted lockstep step (parallel/elastic.py), and on any
membership change re-forms the ``jax.distributed`` world under the
master's MembershipService epochs.

Run loop shape:

    prime (first local batch in hand)           # join only once shapes known
    loop:
        await world (master membership RPC)
        establish (join + broadcast state from rank 0)
        step until: out-of-data-globally | epoch bump | collective failure
    final SAVE_MODEL if assigned

Epoch bumps are observed at batch boundaries (a cheap get_comm_world call
per step — the PS worker pays a get_model RPC per step for the same
cadence, reference worker.py:630-637). A peer death mid-collective instead
surfaces as a step error; the pre-step state is still addressable (on a
mesh that spans processes the elastic step does not donate: double
buffering is the price of kill-anywhere recovery, paid where a peer
exists), so the worker snapshots, waits for the master to notice the
death and bump the epoch, and re-forms. A world of this process alone has
no peer to lose: its step donates the train state, and a step that fails
there leaves the latest host snapshot as the only source. Evaluation
tasks run between steps on host-fetched params over local devices only —
never on the global mesh — so slow eval can't wedge the collective plane.

Serving-only jobs (JobType.EVALUATION_ONLY / PREDICTION_ONLY) skip the
whole collective machinery: no membership, no world, no trainer state —
tasks drain against host-twin forwards over checkpoint-loaded params
(_run_eval_only / _run_predict_only), matching the reference's
one-loop-serves-all-modes worker (reference worker/worker.py:866-876).
"""

import os
import socket
import time

import numpy as np

from elasticdl_tpu.common.constants import (
    JobType,
    MetricsDictKey,
    Mode,
    SaveModelConfig,
    TaskExecCounterKey,
)
from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.common.model_utils import get_model_spec
from elasticdl_tpu.parallel.distributed import WorldSpec, WorldBroken
from elasticdl_tpu.parallel.elastic import ElasticDPTrainer
from elasticdl_tpu.worker.task_data_service import TaskDataService

# distinguishes "no batch peeked ahead" from "peeked the stream's None
# WAIT signal" in the H2D-overlap lookahead
_NO_PEEK = object()


class ElasticAllReduceWorker:
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
        data_reader_params=None,
        seed=0,
        comm_host=None,
        epoch_poll_secs=10.0,
        sync_every=8,
        checkpoint_dir="",
        checkpoint_steps=0,
        keep_checkpoint_max=0,
        precision=None,
        accum_steps=1,
        checkpoint_filename_for_init="",
        prediction_outputs_processor="PredictionOutputsProcessor",
        remat="",
        replica_refresh_steps=8,
        task_prefetch=0,
        speculative_compile=False,
        telemetry_report_secs=5.0,
    ):
        self._worker_id = worker_id
        self._job_type = job_type
        self._minibatch_size = minibatch_size
        self._stub = stub
        self._sync_every = max(1, sync_every)
        self._host = comm_host or os.environ.get("EDL_COMM_HOST", "")
        if not self._host:
            # advertise an address peers can dial: on k8s the bare pod
            # hostname is not resolvable from sibling pods, but the pod IP
            # (what the hostname resolves to locally) is routable
            hostname = socket.gethostname()
            try:
                self._host = socket.gethostbyname(hostname)
            except OSError:
                self._host = hostname
        self._epoch_poll_secs = epoch_poll_secs
        spec = get_model_spec(
            model_zoo=model_zoo,
            model_def=model_def,
            model_params=model_params,
            dataset_fn=dataset_fn,
            loss=loss,
            optimizer=optimizer,
            eval_metrics_fn=eval_metrics_fn,
            prediction_outputs_processor=prediction_outputs_processor,
        )
        self._dataset_fn = spec.dataset_fn
        self._model = spec.model
        self._eval_metrics_fn = spec.eval_metrics_fn
        from elasticdl_tpu.common.export import export_provenance

        self._export_meta = export_provenance(
            model_zoo, model_def, model_params
        )
        from elasticdl_tpu.common.model_utils import (
            get_module_file_path,
            load_module,
        )

        zoo_module = load_module(
            get_module_file_path(model_zoo, model_def)
        ).__dict__
        self._init_ckpt_file = checkpoint_filename_for_init
        self._prediction_outputs_processor = (
            spec.prediction_outputs_processor
        )
        # serving jobs (pure eval / pure predict) need no collective at
        # all: tasks drain against a host-twin forward over local
        # devices with params loaded from a sharded checkpoint dir (the
        # elastic plane's own format) or an exported model file — the
        # reference serves all three modes from one worker loop
        # (reference worker/worker.py:866-876)
        self._serving_only = self._job_type in (
            JobType.EVALUATION_ONLY,
            JobType.PREDICTION_ONLY,
        )
        from elasticdl_tpu.common.model_utils import (
            get_dict_from_params_str,
        )

        extra = get_dict_from_params_str(model_params) or {}
        # per-table plane guard (docs/embedding_planes.md): a PS-plane
        # table has no parameter — its rows live on the PS fleet and
        # are pulled per batch, which the collective lockstep step
        # cannot do. Fail HERE with the pointer, not deep inside
        # establish after the world already formed (where it would
        # crash-loop under relaunch). Resolved through the same
        # selector the zoo uses (an explicit per-table spec defaults
        # UNLISTED tables to ps, so string-sniffing the spec would
        # miss e.g. "embedding:hbm"); zoos that don't declare TABLES
        # get the conservative reading: only an all-tables "hbm" spec
        # is provably collective-servable.
        plane_spec = str(extra.get("embedding_plane", "") or "")
        if plane_spec:
            from elasticdl_tpu.nn.comm_plane import resolve_table_planes

            tables = zoo_module.get("TABLES")
            if tables:
                planes = resolve_table_planes(
                    plane_spec,
                    tables,
                    hybrid_default=zoo_module.get("HYBRID_SPLIT"),
                )
                has_ps_tables = "ps" in planes.values()
            else:
                has_ps_tables = plane_spec != "hbm"
            if has_ps_tables:
                raise NotImplementedError(
                    "model config embedding_plane=%r places tables on "
                    "the PS plane, which the elastic allreduce worker "
                    "cannot serve; run PS-resident tables on the "
                    "parameter-server worker (--embedding_plane=hybrid "
                    "keeps dense local while the PS fleet serves the "
                    "sparse tables)" % plane_spec
                )
        wants_sharded = self._zoo_wants_sharded_params(
            zoo_module, model_params
        )
        # host-twin zoos (build_host_model) serve sharded tables by
        # scoring a dense same-structure twin against checkpoint
        # shards; zoos without the twin serve with the degenerate
        # (mesh=None) distributed form, which consumes checkpoints AND
        # exported model files
        host_twin_serving = (
            self._serving_only and "build_host_model" in zoo_module
        )
        if self._serving_only:
            if not (checkpoint_dir or checkpoint_filename_for_init):
                raise ValueError(
                    "%s on the allreduce plane scores a saved model: "
                    "pass --checkpoint_dir (sharded checkpoints from a "
                    "previous elastic job) or "
                    "--checkpoint_filename_for_init (an exported model "
                    "file)" % self._job_type
                )
            if host_twin_serving and not checkpoint_dir:
                # the sharded host-twin path only reads checkpoint dirs
                raise ValueError(
                    "%s for sharded-parameter model %s needs "
                    "--checkpoint_dir (sharded checkpoints); an "
                    "exported model file cannot feed the host-twin "
                    "forward" % (self._job_type, model_def)
                )
        builder = None
        mesh_axes_fn = None
        layout_planner = None
        self._host_model_factory = None
        if (
            self._serving_only
            and not host_twin_serving
            and "build_distributed_model" in zoo_module
        ):
            # score with the degenerate (mesh=None) distributed form: it
            # has the same parameter STRUCTURE the distributed training
            # job checkpointed (e.g. the pipelined transformer's stacked
            # stage subtree) and runs sequentially on local devices —
            # pass the same --model_params the training job used
            self._model = zoo_module["build_distributed_model"](
                mesh=None, **extra
            )
        pjit_dense = wants_sharded and self._zoo_wants_pjit_dense(
            zoo_module, model_params
        )
        if pjit_dense and not self._serving_only:
            # pjit dense plane (docs/distributed.md): the specs shard
            # the PLAIN module over the 2D data x model mesh — no
            # collective zoo form exists or is needed, XLA partitions
            # the global-semantics model from the NamedShardings. The
            # trainer detects the model-axis specs and routes the step
            # through make_pjit_train_step. Serving-only jobs need
            # none of this: they fall through to the degenerate
            # (mesh=None) plain-module path below, whose scoring
            # assembles FULL host arrays from the training job's
            # sharded checkpoints via load_sharded_to_host — the TP
            # shard files carry their slice metadata.
            def builder(
                mesh, _module=self._model, _zoo=zoo_module, _extra=extra
            ):
                return (
                    _module,
                    _zoo["param_shardings"](mesh, **_extra),
                )

            if "mesh_axes" in zoo_module:
                mesh_axes_fn = (
                    lambda n, _zoo=zoo_module, _extra=extra: _zoo[
                        "mesh_axes"
                    ](n, **_extra)
                )
            # elastic layout re-solve (docs/distributed.md "Layout
            # re-solve"): resizes on the pjit dense plane re-plan
            # dp x tp x micro-batch per world size instead of
            # replaying the launch layout. The zoo's static mesh_axes
            # stays as the fallback until the first establish derives
            # the model profile; the per-device budget comes from
            # EDL_LAYOUT_MEM_BUDGET_MB (unset: every layout fits).
            from elasticdl_tpu.parallel.layout_solver import (
                LayoutPlanner,
            )

            layout_planner = LayoutPlanner(
                fallback_axes_fn=mesh_axes_fn
            )
        elif (
            "build_distributed_model" in zoo_module
            and "build_collective_model" not in zoo_module
            and not self._serving_only
            and wants_sharded
        ):
            # training the plain replicated model instead would either
            # OOM (the table was sharded because it doesn't fit) or
            # silently change the declared strategy
            raise NotImplementedError(
                "model %s declares sharded parameters for this config "
                "(param_shardings is non-empty) but no "
                "build_collective_model hook; the multi-process elastic "
                "plane needs the collective-lookup form — add "
                "build_collective_model (see "
                "model_zoo/deepfm_edl_embedding or "
                "model_zoo/transformer_lm) or run the "
                "single-process ALLREDUCE strategy" % model_def
            )
        if (
            "build_collective_model" in zoo_module
            and not pjit_dense
            and (
                host_twin_serving
                or (not self._serving_only and wants_sharded)
            )
        ):
            # sharded parameters on the elastic plane (HBM vocab tables,
            # stacked pipeline stages): the model uses raw collectives
            # inside the weighted step's shard_map, parameters shard per
            # param_shardings, and re-forms restore from the replica
            # plane / sharded checkpoints. The module is built EAGERLY
            # (a flax dataclass — no device work) so unsupported
            # configs fail here, at worker construction, not after
            # world formation
            collective_module = zoo_module["build_collective_model"](
                **extra
            )

            def builder(
                mesh, _module=collective_module, _zoo=zoo_module, _extra=extra
            ):
                return (
                    _module,
                    _zoo["param_shardings"](mesh, **_extra),
                )

            if "mesh_axes" in zoo_module:
                # the elastic world's mesh layout (e.g. data x pipe for
                # pipelined models); evaluated per world size at each
                # establish
                mesh_axes_fn = (
                    lambda n, _zoo=zoo_module, _extra=extra: _zoo[
                        "mesh_axes"
                    ](n, **_extra)
                )

            if "build_host_model" in zoo_module:
                # optional since r5: TRAINING_WITH_EVALUATION scores
                # IN-PLANE (collective lockstep eval at aligned sync
                # points — no checkpoint, no host twin, tables never
                # materialize in one host's RAM); the twin remains the
                # serving-only scoring path and the export trace
                self._host_model_factory = (
                    lambda _zoo=zoo_module, _extra=extra: _zoo[
                        "build_host_model"
                    ](**_extra)
                )
        from elasticdl_tpu.training.step import parse_remat

        self.trainer = ElasticDPTrainer(
            spec.model,
            spec.loss,
            spec.optimizer(),
            seed=seed,
            precision=precision,
            accum_steps=accum_steps,
            distributed_builder=builder,
            remat=parse_remat(remat),
            mesh_axes_fn=mesh_axes_fn,
            layout_planner=layout_planner,
        )
        # in-memory replica plane: bounded-staleness no-disk recovery
        # for the sharded leaves (parallel/elastic.py ShardMirror);
        # 0 disables. The flag reaches every rank identically via the
        # arg relay, which the collective refresh relies on.
        self.trainer.mirror_steps = max(0, int(replica_refresh_steps))
        # compile-plane fast path (docs/compile_plane.md): the fixed
        # minibatch lets speculative AOT compiles derive the exact batch
        # shapes a future establish will step with; the persistent
        # compile cache makes relaunched processes and re-formed worlds
        # skip XLA compiles they have paid before
        self.trainer.default_minibatch_size = minibatch_size
        self.trainer.speculative_compile = bool(speculative_compile)
        from elasticdl_tpu.parallel.compile_plane import (
            enable_persistent_cache,
        )

        enable_persistent_cache()
        self._last_size_hint = 0
        # escapable sync waits: a peer death can wedge this rank's fetch
        # forever (gloo listener-side hang); the trainer polls this hook
        # while waiting so a wedged rank notices the master has moved
        # the world on and takes the failed-step recovery path instead
        # of getting fenced (state intact for the replica plane)
        self.trainer.abort_check = self._world_moved_on
        # task prefetch composes with the spare-park protocol: the
        # fetcher participates in requeue_inflight's round abandonment,
        # so every prefetched-but-unconsumed task goes back to the
        # master (docs/input_pipeline.md). Acks stay synchronous on this
        # plane — the validate/flush window already defers them.
        self._task_data_service = TaskDataService(
            self,
            self._job_type == JobType.TRAINING_WITH_EVALUATION,
            data_reader_params=data_reader_params,
            task_prefetch=task_prefetch,
        )
        # job telemetry: step/examples rates + resize / compile-plane
        # events ride the task-report channel (docs/observability.md)
        from elasticdl_tpu.worker.telemetry import WorkerTelemetry

        self._telemetry = WorkerTelemetry(
            worker_id,
            stats=self._task_data_service.stats,
            interval_s=telemetry_report_secs,
        )
        self._ckpt = None
        if checkpoint_dir and checkpoint_steps:
            from elasticdl_tpu.common.sharded_checkpoint import (
                ShardedCheckpointManager,
            )

            # async_io: saves block only for the HBM->host snapshot;
            # file writes overlap the next training window
            self._ckpt = ShardedCheckpointManager(
                checkpoint_dir,
                checkpoint_steps,
                keep_checkpoint_max,
                async_io=True,
            )
            self.trainer.restore_provider = self._ckpt_dirs_newest_first
        elif checkpoint_dir and self._serving_only:
            from elasticdl_tpu.common.sharded_checkpoint import (
                ShardedCheckpointManager,
            )

            # read-only: serving jobs load checkpoints, never write
            self._ckpt = ShardedCheckpointManager(checkpoint_dir)
        elif builder is not None:
            logger.warning(
                "sharded-parameter elastic job without --checkpoint_steps:"
                " any membership change RE-INITIALIZES the model"
            )
        self._restore_attempted = False
        self._last_ckpt_version = 0
        self._step_reported = False  # the once-per-process step report
        self._losses_reported = 0  # losses already in a train_window event
        self._model_facts = {}  # the model's own step_built facts
        self._routing_seen = None  # routing state at the last window's end
        self._window_t0 = None
        self._window_cpu0 = None  # the loop thread's CPU at _window_t0
        self._batch_gen = None
        self._retry_batch = None
        # one-batch lookahead for the H2D overlap: _NO_PEEK means
        # nothing peeked (a peeked None is the stream's WAIT signal and
        # must be delivered, not re-pulled)
        self._staged_peek = _NO_PEEK
        self._unreported = []  # counts of consumed-but-unvalidated steps
        self._drained = False
        self._forward_fn = None
        self._eval_params_version = None
        self._eval_params = None
        self._eval_scored_version = None  # version params actually carry
        self._overflow_alarmed = 0
        self._preempted = False
        self._drain_announced = False
        self._drain_deadline = 0.0

    # -- graceful preemption ------------------------------------------------

    # distinct from 0 ("done, don't replace me") and from crash codes:
    # the instance manager relaunches a replacement for this exit
    PREEMPTED_EXIT_CODE = 75  # EX_TEMPFAIL

    def request_drain(self, *_signal_args):
        """SIGTERM handler hook: drain gracefully at the next batch
        boundary instead of dying mid-collective.

        Cloud preemptions deliver SIGTERM with notice (k8s
        terminationGracePeriod, TPU-VM maintenance events). A drained
        worker flushes its sync window, checkpoints (sharded plane),
        reports its records, and LEAVES the world cleanly — so the
        survivors observe an ordinary membership epoch at a batch
        boundary rather than a broken collective + failed-step recovery,
        and no work is lost at all."""
        self._preempted = True
        logger.info(
            "preemption notice received; draining at the next batch "
            "boundary"
        )

    def enable_drain_on_sigterm(self):
        """Install the SIGTERM -> request_drain handler, and keep it
        installed: ``jax.distributed.initialize`` registers XLA's own
        C++ preemption notifier for SIGTERM (preemption_notifier.cc),
        silently REPLACING any Python handler registered before it — so
        the worker re-installs after every establish (see _run)."""
        self._drain_signal_enabled = True
        self._install_drain_handler()

    def _install_drain_handler(self):
        if not getattr(self, "_drain_signal_enabled", False):
            return
        import signal
        import threading

        if threading.current_thread() is not threading.main_thread():
            return  # in-process test workers: signals stay with the host
        signal.signal(signal.SIGTERM, self.request_drain)

    @staticmethod
    def _zoo_wants_pjit_dense(zoo_module, model_params):
        """Does this config shard the DENSE model over the ``model``
        axis (the pjit/GSPMD path, plain module), rather than declaring
        collective-form sharded parameters? Probed with mesh=None like
        :meth:`_zoo_wants_sharded_params`."""
        ps = zoo_module.get("param_shardings")
        if ps is None:
            return False
        from elasticdl_tpu.common.model_utils import (
            get_dict_from_params_str,
        )
        from elasticdl_tpu.parallel.elastic import (
            collect_sharded_paths,
            specs_use_axis,
        )

        try:
            specs = ps(
                None, **(get_dict_from_params_str(model_params) or {})
            )
            return specs_use_axis(collect_sharded_paths(specs), "model")
        except Exception:
            logger.debug(
                "model ps() pjit probe failed; assuming collective "
                "form",
                exc_info=True,
            )
            return False

    @staticmethod
    def _zoo_wants_sharded_params(zoo_module, model_params):
        """Does this zoo + model_params combination actually shard
        parameters? Keying the collective-hook requirement on
        build_distributed_model's mere PRESENCE would wrongly reject
        configs whose distributed form is optional (e.g. transformer_lm
        without pipeline_stages trains replicated). param_shardings is
        probed with mesh=None — zoo hooks accept that and answer from
        the params alone; no mesh (= no JAX backend init) may happen
        before the world forms."""
        ps = zoo_module.get("param_shardings")
        if ps is None:
            return True  # conservative: hook declared, intent unknown
        from elasticdl_tpu.common.model_utils import (
            get_dict_from_params_str,
        )

        try:
            return bool(
                ps(None, **(get_dict_from_params_str(model_params) or {}))
            )
        except Exception:
            logger.debug(
                "model ps() probe failed; assuming PS mode",
                exc_info=True,
            )
            return True

    def _ckpt_dirs_newest_first(self):
        """Candidate checkpoint dirs, newest first; drains in-flight
        async saves so an establish/restore never reads a half-written
        one. More than one candidate matters: a killed rank can leave
        the newest version torn (its manifest missing) while an older
        complete one sits behind it."""
        if self._ckpt is None:
            return []
        try:
            self._ckpt.wait()
        except Exception:
            logger.warning(
                "async checkpoint write failed; restoring from the "
                "previous complete checkpoint",
                exc_info=True,
            )
        return self._ckpt.dirs_newest_first()

    def _latest_ckpt_dir(self):
        dirs = self._ckpt_dirs_newest_first()
        return dirs[0] if dirs else None

    # master surface used by TaskDataService
    def get_task(self, task_type=None):
        return self._stub.get_task(self._worker_id, task_type)

    def report_task_result(self, task_id, err_msg="", exec_counters=None):
        from elasticdl_tpu.worker.reporting import with_model_version

        # the VALIDATED version, which came with a receipt: a sync
        # point reports with the newest step still on the device, and
        # reading that step's version would wait for it
        result = self._stub.report_task_result(
            task_id,
            err_msg,
            with_model_version(
                lambda: self.trainer.validated_version, exec_counters
            ),
        )
        # piggyback the (rate-limited) telemetry snapshot — resize and
        # speculative-compile events reach the master's event log here
        self._telemetry.ship(self._stub)
        return result

    # -- data ---------------------------------------------------------------

    def _batches(self):
        """Continuous (features, labels) stream over all task rounds.

        Yields None on a WAIT round (no data *now*, job not finished) so
        the caller can keep the collective plane ticking; StopIteration
        means the master has no more training work for this process.
        """
        while True:
            if self._unreported:
                # settle the sync window before the round rolls over:
                # held-back reports keep the finished round's tasks
                # "pending", which would wedge the next get_dataset
                ok = self.trainer.validate()
                self._flush_unreported(
                    "" if ok else "collective failed before validation"
                )
            dataset = self._task_data_service.get_dataset()
            if not dataset:
                return
            dataset = self._dataset_fn(
                dataset,
                Mode.TRAINING,
                self._task_data_service.data_reader.metadata,
            )
            dataset = dataset.batch(self._minibatch_size).prefetch(1)
            got = False
            for batch in dataset:
                got = True
                yield batch
            self._process_save_model_task_if_needed()
            if not got:
                yield None

    def _next_batch(self):
        if self._retry_batch is not None:
            batch, self._retry_batch = self._retry_batch, None
            return batch
        if self._staged_peek is not _NO_PEEK:
            # the H2D-overlap lookahead already pulled this item (and
            # its placement may be staging on the feeder thread)
            batch, self._staged_peek = self._staged_peek, _NO_PEEK
            return batch
        if self._drained:
            return None
        try:
            batch = next(self._batch_gen)
        except StopIteration:
            self._drained = True
            return None
        return batch

    def _peek_and_stage_next(self):
        """Pull batch N+1 and hand it to the trainer's feeder thread so
        its H2D placement overlaps the sync-point cadence work
        (checkpoint save, eval rounds, mirror refresh) and the next
        step's dispatch. Called ONLY after _flush_unreported has settled
        the ledger: a round boundary crossed here then sees every
        consumed record reported — the same state the unpeeked loop's
        next _next_batch call would cross it with. The peeked item (a
        None WAIT signal included) is delivered by the next _next_batch
        call, so the stream's semantics are byte-identical."""
        if (
            self._staged_peek is not _NO_PEEK
            or self._retry_batch is not None
            or self._drained
        ):
            return
        try:
            peek = next(self._batch_gen)
        except StopIteration:
            self._drained = True
            return
        self._staged_peek = peek
        if peek is not None:
            self.trainer.stage_next(
                peek[0], peek[1], self._minibatch_size
            )

    # -- membership ---------------------------------------------------------

    def _await_world(self):
        """Poll the master until a world including us is ready.

        Returns a WorldSpec, or None if the job finished while waiting
        (every process drained and the master stopped handing out work).

        A ``spare`` reply means a ``world_size_multiple`` round-down
        left this live worker out of the current world (e.g. 3
        survivors of a 2-stage pipelined job form a world of 2): it
        idles here WITHOUT a mesh slot, so any pulled-but-untrained
        work goes back to the master immediately — a spare holding
        tasks would stall job completion for everyone.
        """
        spare_flushed = False
        while True:
            if self._preempted:
                return None  # drain notice while between worlds
            w = self._stub.get_comm_world(
                self._worker_id, self._host, awaiting=True
            )
            if w.get("ready"):
                # member ids of this world: the wedge-escape probe needs
                # them to tell "one of MY peers died" from growth/drain
                self._world_members = list(w.get("members", ()))
                return WorldSpec(
                    coordinator=w["coordinator"],
                    num_processes=w["num_processes"],
                    process_id=w["process_id"],
                    epoch=w["epoch"],
                )
            if w.get("spare") and not spare_flushed:
                spare_flushed = True
                self._requeue_as_spare()
            if self._drained and self._retry_batch is None:
                return None
            time.sleep(0.2)

    def _requeue_as_spare(self):
        """Hand every in-flight task back to the master (fail-report +
        requeue), drop the primed batch, and abandon the current data
        round: a spare trains nothing, world members can finish the
        work it was holding, and the round's buffered stream cannot be
        rewound past the requeued tasks (TaskDataService
        ``requeue_inflight``). On rejoin the run loop re-primes from a
        fresh round."""
        tds = self._task_data_service
        # no early-out on an "empty" ledger: the round may still be OPEN
        # with a producer thread about to pull a fresh task — the round
        # bump below is what tells it to step aside
        logger.info(
            "parked as spare (world-size rounding); requeueing "
            "in-flight work and abandoning the open round"
        )
        msg = "parked as spare (world size rounding)"
        self._retry_batch = None
        # a peeked batch belongs to a task being requeued wholesale
        self._staged_peek = _NO_PEEK
        # settle any stepped-but-unreported window first (normally empty
        # — the reform pause flushed it); its cursor advance must land
        # before the ledger is requeued wholesale
        self._flush_unreported(msg)
        tds.requeue_inflight(msg)
        # restart the batch stream: the abandoned round's generator and
        # its prefetch buffer die with the old handle
        self._batch_gen = self._batches()

    def _await_epoch_bump(self, stale_epoch):
        """After a collective failure: wait for the master to re-form.

        Returns True once the epoch bumps; False if it never does within
        the poll window (the failure wasn't a membership event and should
        propagate as a real bug, not be retried forever).
        """
        deadline = time.time() + self._epoch_poll_secs
        while time.time() < deadline:
            w = self._stub.get_comm_world(
                self._worker_id, self._host, awaiting=False
            )
            if w["epoch"] != stale_epoch:
                return True
            time.sleep(0.3)
        return False

    # -- the run loop --------------------------------------------------------

    def run(self):
        from elasticdl_tpu.utils.profiling import maybe_stop_trace

        try:
            return self._run()
        finally:
            # final telemetry flush (PS-mode Worker.run does the same):
            # a job shorter than the report interval, and any events
            # emitted after the last interval-gated ack, still land one
            # snapshot. Best-effort — the master may already be gone.
            self._telemetry.ship(self._stub, force=True)
            # flush any open trace even on the exception path — the run
            # that crashed is the one whose profile matters most
            maybe_stop_trace()
            # a crash path skips _finalize; queued async checkpoint
            # writes must still land (save() already returned and
            # advanced the cadence — dropping them here would lose up
            # to checkpoint_steps of durable progress)
            self._drain_ckpt()
            # compile-plane helper threads (speculative compiler, H2D
            # feeder) must not outlive the worker
            self.trainer.close()

    def _run(self):
        if self._job_type == JobType.EVALUATION_ONLY:
            return self._run_eval_only()
        if self._job_type == JobType.PREDICTION_ONLY:
            return self._run_predict_only()
        losses = []
        self._batch_gen = self._batches()
        # register with the membership BEFORE priming: a promoted
        # standby's death-bump is DEFERRED waiting for exactly this
        # registration, so announcing first lets the survivors pause
        # and settle in parallel with our dataset/reader priming
        # (measured ~5.7 s serial before this). The
        # awaiting=False poll registers without confirming a formation
        # we are not yet ready to join.
        try:
            self._stub.get_comm_world(
                self._worker_id, self._host, awaiting=False
            )
        except Exception:
            # registration happens via the await loop anyway
            logger.debug("pre-registration poll failed", exc_info=True)
        first = self._prime()
        if first is None:
            # no training data ever assigned; still serve eval/save
            # tasks. We pre-registered above, so announce the leave —
            # an unconfirmed member would hold every peer's formation
            # for the confirm window and then get fenced mid-eval
            try:
                self._stub.leave_comm_world(self._worker_id)
            except Exception:
                logger.debug(
                    "leave announcement missed; the confirm-timeout "
                    "fencer clears this member",
                    exc_info=True,
                )
            self._finalize()
            return losses
        self._retry_batch = first

        while True:
            world = self._await_world()
            if world is None:
                break
            try:
                example = self._retry_batch or self.trainer._last_local
                if example is None:
                    # rejoining after a spare park requeued everything:
                    # prime a fresh batch (shapes gate the mesh slot)
                    first = self._prime()
                    if first is None:
                        # drained/preempted while parked: leave so the
                        # members' formation doesn't wait out the
                        # confirm window on us
                        try:
                            self._stub.leave_comm_world(self._worker_id)
                        except Exception:
                            logger.debug(
                                "leave announcement missed; the "
                                "confirm-timeout fencer clears this "
                                "member",
                                exc_info=True,
                            )
                        break
                    self._retry_batch = example = first
                self.trainer.establish(world, example_batch=example)
                if self._ckpt is not None:
                    # ring eviction must know what "complete" means in
                    # this world: every rank writes sharded versions,
                    # rank 0 alone writes replicated ones
                    self._ckpt.set_expected_writers(
                        world.num_processes
                        if self.trainer.is_sharded
                        else 1
                    )
                    # PadDim0 leaves the new world padded: manifests
                    # record the logical rows so host-side restores
                    # (export, twin scoring) clip the padding off
                    self._ckpt.set_logical_dim0(
                        self.trainer.logical_dim0_by_path()
                    )
                if (
                    self._ckpt is not None
                    and not self._restore_attempted
                    and not self.trainer.is_sharded
                ):
                    self._restore_attempted = True
                    # resume only when the WHOLE world is virgin (the
                    # broadcast state carries version 0). A fresh process
                    # joining a live job receives the survivors' state in
                    # the broadcast; restoring a stale checkpoint over
                    # just this replica would silently de-synchronize the
                    # replicated parameters. (Sharded-parameter jobs
                    # restore inside establish() instead, every epoch.)
                    if self.trainer.version == 0:
                        self._restore_latest_checkpoint()
                if self.trainer.is_sharded:
                    self._last_ckpt_version = max(0, self.trainer.version)
            except WorldBroken:
                logger.warning(
                    "world %d broke during formation; re-polling", world.epoch
                )
                # the failed initialize may still have displaced the
                # drain handler — take it back before re-polling
                self._install_drain_handler()
                continue
            # jax.distributed.initialize (inside establish) installs
            # XLA's own SIGTERM notifier, displacing the drain handler —
            # take it back so preemption notices reach request_drain
            self._install_drain_handler()
            from elasticdl_tpu.utils.profiling import maybe_start_trace

            maybe_start_trace()  # safe only now: the backend is world-aware
            self._report_step_built()
            outcome = self._train_epoch(world, losses)
            if outcome in ("done", "preempted"):
                break
            if self._preempted:
                # the announced drain exits through the ordinary reform
                # pause ("reform"); a drained worker must not re-join
                break
        self._report_losses(losses)
        # ship now, while the master still serves: landing the last
        # checkpoint in _finalize can outlast its exit grace, and run()'s
        # closing ship then finds nobody listening
        self._telemetry.ship(self._stub, force=True)
        self._finalize()
        return losses

    def _report_step_built(self):
        """Say once, after the first establish, what this process
        trains on and with: the mesh's devices, the attention the built
        step holds and how many of its inputs it donates (both read off
        the step, see describe_step), the record reader, and where
        compiled programs are kept. One log line and
        one ``step_built`` event (scalar fields: it ships to the master's
        event log with the next task report)."""
        from elasticdl_tpu.utils import profiling, step_ops

        if self._step_reported:
            if profiling.profile_dir():
                # a re-formed world compiled its step anew: the map of
                # its ops by class replaces the last world's
                try:
                    self.trainer.describe_step()
                except Exception:
                    logger.warning(
                        "could not describe the re-built step", exc_info=True
                    )
            return
        self._step_reported = True
        import jax

        from elasticdl_tpu.data.recordio import reader_kind
        from elasticdl_tpu.ops.flash_attention import (
            STEP_BUILT_FIELDS as flash_fields,
            attention_in_step,
        )

        # the first train_window's clocks start here: describe_step
        # does the step's trace and lowering, which the first step call
        # then reuses, so they belong to that window's cost (to its
        # seconds, and to no phase of the loop)
        self._window_t0 = time.time()
        self._window_cpu0 = time.thread_time()
        profiling.phases.close_window()
        try:
            facts = self.trainer.describe_step()
        except Exception:
            # a report must not pre-empt the step-failure path: whatever
            # stops the step from tracing here stops the step itself a
            # moment later, where the failure is accounted for
            logger.warning(
                "could not describe the built step", exc_info=True
            )
            return
        devices = self.trainer.mesh.devices
        report = {
            "platform": devices.flat[0].platform,
            "device_kind": devices.flat[0].device_kind,
            "device_count": int(devices.size),
            "mesh": ",".join(
                "%s=%d" % kv for kv in self.trainer.mesh.shape.items()
            ),
            "attention": attention_in_step(facts),
            "pallas_calls": facts["pallas_calls"],
            "pallas_interpreted": facts["pallas_interpreted"],
            "tpu_custom_calls": facts["tpu_custom_calls"],
            "triangular_solves": facts["triangular_solves"],
            "mosaic_kernels": ",".join(facts["mosaic_kernels"]),
            "donated_inputs": facts["donated_inputs"],
            "record_reader": reader_kind(),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir
            or "",
        }
        # a traced run's own: the compiled step's ops by class and the
        # compiler's account of its memory (describe_step); a step with
        # the flash kernels: the steps of their grids, those of them
        # with no tile to compute, and the forwards whose row sums are
        # kept by lanes (grid_steps_in)
        report.update(
            (k, facts[k])
            for k in (*step_ops.STEP_BUILT_FIELDS, *flash_fields)
            if k in facts
        )
        # what the model says of its own layout (a zoo module's
        # ``step_facts``: layers by kind, experts held and routed over,
        # and what follows from the length of the batch the step was
        # built for); absent on a model that has nothing to say
        step_facts = getattr(self._model, "step_facts", None)
        self._model_facts = (
            step_facts(self.trainer.example_features()) if step_facts else {}
        )
        report.update(self._model_facts)
        logger.info(
            "step built: %s",
            " ".join("%s=%s" % kv for kv in report.items()),
        )
        profiling.events.emit(
            "step_built", worker=self._worker_id, **report
        )

    def _report_losses(self, losses):
        """One ``train_window`` event for the losses recorded since the
        last one: how many steps, the first and last loss, how many were
        not finite, the wall seconds the window took (the first
        window's includes the step's trace, lowering and compile), on
        how many devices the train state sits, and the loop's own
        account of those seconds (docs/observability.md "The allreduce
        worker's phases"): ``<phase>_s`` for each of
        ``profiling.STEP_PHASES``, the single slowest call, the loop
        thread's CPU seconds, the devices' peak memory where the
        backend reports it, of a model with held-share expert
        layers the window's routing counters (:meth:`_window_routing`),
        and, of a model that adds to its loss through the ``aux_loss``
        collection, the window's last loss apart: ``lm_loss`` and each
        part the model wrote (``mtp_loss``), which add up to
        ``last_loss``.
        The same fields go to the span plane as one
        ``train/window`` span, so ``/trace`` and the flight recorder
        hold the last windows of a worker that died. Called at sync
        points, where the losses of the steps just validated have been
        taken (``trainer.settle``), BEFORE their task reports go out,
        so the event rides them to the master. A window is what one
        validation covered: at an aligned sync the worker validates one
        step behind what it has dispatched, so the window ends with the
        step BEFORE the aligned one (a world's first window holds
        ``sync_every - 1`` steps, and its last, closed where the world
        is left, the one or more that were still in flight), and
        ``in_flight_at_fetch`` says how many dispatched steps the
        closing fetch did not wait for: 1 where the device had the
        next step to run while the host reported, 0 where it was
        drained (a weight-0 step, a world's last window). The clocks
        restart here: what this method does after closing the window is
        the next window's ``report``."""
        window = losses[self._losses_reported :]
        if not window:
            return
        from elasticdl_tpu.utils import profiling

        self._losses_reported = len(losses)
        # what the window's last step left in its receipt, which came
        # to the host with its loss: nothing here asks the device
        routing = self._window_routing()
        # the last step's loss apart, of a model that adds to it
        # through ``aux_loss``: each part under the name the model
        # wrote it by (``mtp_loss``), and ``lm_loss``, what is left
        # (every zoo model that writes one is a language model)
        parts = self.trainer.aux_losses()
        if parts:
            parts["lm_loss"] = float(window[-1]) - sum(parts.values())
        now, cpu = time.time(), time.thread_time()
        if self._window_t0 is None:  # no step was ever built
            self._window_t0, self._window_cpu0 = now, cpu
        t0, cpu0 = self._window_t0, self._window_cpu0
        self._window_t0, self._window_cpu0 = now, cpu
        account = profiling.phases.close_window()
        with profiling.phases.measure("report"):
            account["loop_cpu_s"] = round(cpu - cpu0, 5)
            peak = self.trainer.peak_hbm_bytes()
            if peak is not None:
                account["peak_hbm_bytes"] = peak
            fields = dict(
                worker=self._worker_id,
                steps=len(window),
                in_flight_at_fetch=self.trainer.steps_in_flight,
                first_loss=float(window[0]),
                last_loss=float(window[-1]),
                nonfinite=int(np.sum(~np.isfinite(window))),
                seconds=round(now - t0, 3),
                state_on_devices=self.trainer.state_device_coverage(),
                **account,
                **routing,
                **parts,
            )
            profiling.events.emit("train_window", **fields)
            if profiling.metrics_enabled():
                profiling.spans.record(
                    "train/window", t0, now - t0, **fields
                )

    def _window_routing(self):
        """The window's routing counters of a model with held-share
        expert layers (``moe_rows_here``, ``moe_rows_routed``,
        ``moe_rows_max_expert``, ``moe_rows_mean_expert``,
        ``expert_bias_abs_max``: parallel/expert.py
        ``window_routing_counters``), from the routing state in the
        receipt of this window's last step and in that of the window
        before's; no fields for a model without."""
        routing = self.trainer.routing_state()
        if routing is None or "experts_held" not in self._model_facts:
            return {}
        from elasticdl_tpu.parallel.expert import window_routing_counters

        before, self._routing_seen = self._routing_seen, routing
        return window_routing_counters(
            before,
            routing,
            self._model_facts["first_expert_held"],
            self._model_facts["experts_held"],
        )

    def _restore_latest_checkpoint(self):
        """Resume from the newest restorable checkpoint; a partial or
        corrupt directory falls back to the next-older one instead of
        crash-looping the worker."""
        self._ckpt.wait()  # an in-flight async save must land first
        for directory in self._ckpt.dirs_newest_first():
            try:
                self.trainer.restore_sharded(directory)
                self._last_ckpt_version = self.trainer.version
                return True
            except Exception:
                logger.warning(
                    "checkpoint %s unrestorable; trying older",
                    directory,
                    exc_info=True,
                )
        return False

    def _prime(self):
        """Block until the first local batch is in hand (its shapes gate
        world membership — a shapeless process can't hold a mesh slot).

        Heartbeats the membership (from a side thread — the slow part
        is INSIDE the batch generator: reader setup, shuffle-buffer
        fill) while blocked: this worker may already be REGISTERED
        (register-before-prime), and a registered member whose last
        poll goes stale looks dead to the confirm-timeout fencer — a
        cold reader that primes slowly would get the fresh process
        killed mid-prime. The awaiting=False poll refreshes liveness
        without confirming a formation we can't join yet (the master
        waits on a responsive-but-slow member instead of fencing it)."""
        import threading

        done = threading.Event()
        # bounded: a beat that never stops would keep a truly WEDGED
        # primer (reader stuck on a dead filesystem) looking alive
        # forever, holding every peer's formation — past the deadline
        # the beats stop and the confirm-timeout fencer regains
        # authority over this process
        deadline = time.time() + 120.0

        def beat():
            while time.time() < deadline and not done.wait(1.0):
                try:
                    self._stub.get_comm_world(
                        self._worker_id, self._host, awaiting=False
                    )
                except Exception:
                    logger.debug(
                        "liveness beat missed (master busy/unreachable)",
                        exc_info=True,
                    )

        beater = None
        if self._stub is not None:
            beater = threading.Thread(target=beat, daemon=True)
            beater.start()
        try:
            while True:
                if self._preempted:
                    return None
                batch = self._next_batch()
                if batch is not None:
                    return batch
                if self._drained:
                    return None
                time.sleep(0.2)
        finally:
            done.set()
            if beater is not None:
                # a beat mid-RPC must land before the caller announces a
                # leave (register-after-leave is additionally blocked by
                # the membership's departing blacklist; joining removes
                # the race entirely)
                beater.join(timeout=5.0)

    def _world_moved_on(self):
        """The trainer's escapable-wait abort probe: True when one of
        this world's members actually DIED (watch/fence removal) — its
        collectives are unrecoverable and a bump is coming (possibly
        deferred for a standby promotion, so the epoch alone is NOT the
        gate: waiting for it would hold a wedged survivor through the
        whole deferral). A growth bump or a graceful drain advances the
        epoch while every member is still stepping — those must never
        abort a healthy (merely slow, e.g. compiling) dispatch, which
        is why the probe keys on deaths, not epochs."""
        from elasticdl_tpu.parallel import distributed

        spec = distributed.current_spec()
        if spec is None:
            return False
        try:
            w = self._stub.get_comm_world(
                self._worker_id, self._host, awaiting=False
            )
        except Exception:
            logger.debug(
                "world probe failed; not treating as moved-on",
                exc_info=True,
            )
            return False
        dead = set(w.get("dead", ()))
        members = getattr(self, "_world_members", None) or ()
        return any(
            m in dead for m in members if m != self._worker_id
        )

    def _flush_unreported(self, err_msg="", keep=0):
        """Report record counts held back while their steps were
        unvalidated; the newest ``keep`` stay held (the steps still in
        flight: the one an aligned sync has dispatched and not
        validated). With an err_msg the
        consumed-but-unapplied records count as failures (per-task
        failure counters), and a task that drains on the failing flush
        fail-reports + requeues — the reference's failed-minibatch
        accounting semantics."""
        settled = len(self._unreported) - keep
        pending = self._unreported[:settled]
        del self._unreported[:settled]
        for count in pending:
            self._task_data_service.report_record_done(count, err_msg)

    def _settle_and_leave(self, verdict, validate=True, losses=None):
        """The leave epilogue every pause path shares: settle every
        step in flight, the one an aligned sync dispatched and did not
        wait for included (validated steps report done, a failed window
        fail-reports + requeues), checkpoint the sharded plane, close
        any open trace, and leave the world. The validated steps'
        losses drain into ``losses`` and close the world's last
        ``train_window`` — leave() drops the receipts in flight, so
        without this the pause paths would silently lose up to
        sync_every recorded steps."""
        ok = self.trainer.validate() if validate else False
        if ok and losses is not None:
            losses.extend(self.trainer.drain_metrics())
            self._report_losses(losses)
        self._flush_unreported(
            "" if ok else "collective failed before validation"
        )
        if ok and self.trainer.is_sharded:
            # a checkpoint written at the pause point makes the
            # re-form's restore lossless (all members pause at the same
            # version, so no rank's manifest is torn)
            self._save_ckpt_if_newer()
        from elasticdl_tpu.utils.profiling import maybe_stop_trace

        maybe_stop_trace()  # the trace must not outlive its world
        self.trainer.leave()
        return verdict

    def _train_epoch(self, world, losses):
        from elasticdl_tpu.utils.profiling import phases

        step_i = 0
        while True:
            phases.step = step_i + 1
            if self._preempted and not self._drain_announced:
                # graceful drain rides the ORDINARY reform protocol:
                # announce the departure so the master bumps the epoch
                # now, then KEEP STEPPING — every member (this one
                # included) observes the bump at the same lockstep
                # iteration and pauses at the batch boundary, so no
                # collective is ever left hanging on a vanished rank.
                # Leaving immediately instead would strand survivors'
                # in-flight steps and send them down the failed-step
                # recovery path this drain exists to avoid.
                self._drain_announced = True
                self._drain_deadline = (
                    time.time() + self._epoch_poll_secs
                )
                try:
                    self._stub.leave_comm_world(self._worker_id)
                    logger.info(
                        "drain announced; stepping until the world "
                        "pauses"
                    )
                except Exception:
                    logger.warning(
                        "drain announcement failed; will hard-leave",
                        exc_info=True,
                    )
            if (
                self._drain_announced
                and self._drain_deadline
                and time.time() > self._drain_deadline
            ):
                # the announcement never landed (master unreachable?):
                # settle what we can and leave anyway — survivors take
                # the failure-recovery path, same as a hard kill
                return self._settle_and_leave("preempted", losses=losses)
            if (
                self._job_type == JobType.TRAINING_WITH_EVALUATION
                and not self.trainer.is_sharded
            ):
                # sharded jobs evaluate IN-PLANE at aligned sync points
                # below (the collective rounds must line up across
                # ranks); the replicated plane scores a local snapshot
                # and can drain whenever
                with phases.measure("cadence"):
                    self._evaluate_only()
            with phases.measure("world_poll"):
                w = self._stub.get_comm_world(
                    self._worker_id, self._host, awaiting=False
                )
            # membership-service size hint: the live+lobby head count is
            # the world the next growth bump would form — feed it to the
            # speculative compiler so that establish finds its
            # executable already built (docs/compile_plane.md)
            hint = int(w.get("live", 0) or 0)
            if hint and hint != self._last_size_hint:
                self._last_size_hint = hint
                per_proc = self.trainer.mesh.devices.size // max(
                    1, world.num_processes
                )
                self.trainer.hint_world_sizes([hint * per_proc])
            if self._drain_announced and w["epoch"] != world.epoch:
                # the drain bump IS visible: the consensus pause will
                # land within one sync window — disarm the hard-leave
                # fallback so a slow eval round or a long first-step
                # compile cannot turn a clean drain into a broken
                # collective
                self._drain_deadline = 0.0
            # NOTE: a polled epoch bump does NOT pause here. With
            # deferred sync the hosts run ahead of the device unevenly,
            # so members OBSERVE a bump at different host iterations; a
            # member pausing at its observation point strands peers'
            # already-dispatched steps on a vanished rank. Instead the
            # polled epoch rides INTO the step (epoch_hint) and the
            # in-step pmax consensus — read back at aligned sync indices,
            # which are the same step for every member — triggers the
            # pause below.
            with phases.measure("input_wait"):
                batch = self._next_batch()
            step_i += 1
            # waiting for a step's result leaves the device with nothing
            # to run until the host has dispatched again. So the loop
            # only dispatches, and every sync_every steps it validates
            # ONE STEP BEHIND: with step i dispatched it waits for step
            # i - 1's receipt (trainer.settle), which arrives as the
            # device starts step i, and everything the sync point does
            # (the window's event, the task reports, staging, cadence,
            # the next poll, placement and dispatch) runs under step
            # i's device time. Records consumed by a step are reported
            # only once it validated: step i's at the next sync point.
            # A weight-0 step waits for its own result as well (its
            # n_active drives the exit).
            # aligned_sync points land at the same step INDEX on every
            # rank (loop iterations are lockstep — one collective per
            # iteration) and read the same step i - 1 there, so the
            # epoch consensus and version reads agree globally; a
            # drain-forced sync is local to the draining rank
            aligned_sync = step_i % self._sync_every == 0
            sync = batch is None or aligned_sync
            count = n_active = None
            try:
                features, labels = (None, None) if batch is None else batch
                _, _, count = self.trainer.train_step(
                    features,
                    labels,
                    self._minibatch_size,
                    sync=False,
                    epoch_hint=w["epoch"],
                )
                if batch is not None:
                    self._unreported.append(count)
                if aligned_sync:
                    # the validated steps' losses, chronological
                    losses.extend(self.trainer.settle(lag=1))
                # what every rank read of step i - 1, whatever this
                # rank goes on to read of its own weight-0 step
                consensus = self.trainer.epoch_consensus
                if batch is None:
                    losses.extend(self.trainer.settle())
                    n_active = self.trainer.n_active
            except Exception:
                logger.exception("collective step failed")
                # the whole unvalidated window (including this batch)
                # fail-reports: its task drains + requeues, and the
                # records are re-read by whichever worker picks it up —
                # retrying the batch here would double-charge the
                # requeued task's accounting
                if batch is not None and count is None:
                    # the dispatch itself failed: its batch is not
                    # among the held counts yet
                    leaf = batch[1]
                    self._unreported.append(int(np.asarray(leaf).shape[0]))
                self._settle_and_leave("reform", validate=False)
                if not self._await_epoch_bump(world.epoch):
                    raise
                return "reform"
            if batch is not None:
                self._telemetry.on_batch(count)
            if sync:
              # a peer death can surface here as WorldBroken from the
              # escapable waits inside the cadence fetches / the pause
              # refresh (trainer._await_ready): take the same reform
              # path as a failed step — the validated steps were
              # flushed, and the one in flight fail-reports with it
              try:
                self._report_losses(losses)
                with phases.measure("report"):
                    # step i's records stay held: it has not validated
                    self._flush_unreported(
                        keep=self.trainer.steps_in_flight
                    )
                if batch is not None:
                    # step overlap: pull batch N+1 now — its H2D
                    # placement runs on the feeder thread while the
                    # cadence work below (checkpoint save, eval rounds,
                    # mirror refresh) runs here. Strictly AFTER the
                    # flush: every validated record is reported, and a
                    # round boundary this peek crosses settles the one
                    # step in flight itself (_batches), so it sees the
                    # same settled ledger the unpeeked loop's next
                    # _next_batch would — get_dataset never refuses
                    # over records this very iteration consumed.
                    with phases.measure("stage_next"):
                        self._peek_and_stage_next()
                with phases.measure("cadence"):
                    self._alarm_on_embedding_overflow()
                if (
                    aligned_sync
                    and consensus is not None
                    and consensus > world.epoch
                ):
                    # every member reads this SAME consensus value (of
                    # the step before) at this SAME step index, with
                    # this index's step dispatched — the whole world
                    # pauses in unison, no collective left hanging
                    logger.info(
                        "epoch bump %d -> %d; pausing at aligned sync",
                        world.epoch,
                        consensus,
                    )
                    if self.trainer.mirror_enabled():
                        # the pause is the one point where EVERY member
                        # (a draining victim included) sits at the same
                        # step: a refresh here makes the upcoming
                        # reform's replica-plane assembly LOSSLESS — the
                        # victim's shards ride the ppermute to its
                        # neighbor at the pause version, no disk needed
                        try:
                            self.trainer.refresh_mirror()
                        except Exception:
                            logger.warning(
                                "pause-point replica refresh failed; "
                                "reform falls back to the last refresh "
                                "or checkpoints",
                                exc_info=True,
                            )
                    return self._settle_and_leave("reform", losses=losses)
                with phases.measure("cadence"):
                    self._sync_cadence(world, aligned_sync)
              except Exception as cadence_err:
                # the reform path is only for WORLD failures — a peer
                # loss surfacing as WorldBroken (escaped wedge) or as a
                # raw collective/runtime error from the cadence
                # refresh/fetches. Local errors (e.g. a checkpoint-save
                # disk failure) must propagate untouched: tearing down
                # a healthy world for them would break peers' in-flight
                # collectives for nothing.
                from jax.errors import JaxRuntimeError

                if not isinstance(
                    cadence_err, (WorldBroken, JaxRuntimeError)
                ):
                    raise
                logger.exception(
                    "world broke during the sync cadence; re-forming"
                )
                self._settle_and_leave("reform", validate=False)
                if not self._await_epoch_bump(world.epoch):
                    raise
                return "reform"
            if n_active == 0:
                # global quiescence: every rank observes it in the same
                # collective round with the same (final) version. Sharded
                # ranks land their shards NOW — the export task (one
                # rank, in _finalize) needs every OTHER rank's manifest,
                # and those ranks may legitimately still be here waiting
                # for the job (incl. that very export task) to finish.
                if self.trainer.is_sharded:
                    self._save_ckpt_if_newer()
                if self._drained:
                    return "done"
                time.sleep(0.2)

    def _sync_cadence(self, world, aligned_sync):
        """What rides a sync point besides the reports: the checkpoint
        cadence, in-plane evaluation, the replica-plane refresh."""
        if (
            self._ckpt is not None
            and (world.process_id == 0 or self.trainer.is_sharded)
            and self._ckpt.is_enabled()
            # sharded checkpoints are only restorable when EVERY
            # rank wrote the same version, so the cadence must
            # trigger at rank-aligned sync points alone
            and (aligned_sync or not self.trainer.is_sharded)
        ):
            # checkpoints land at sync points, so the cadence is
            # "at least checkpoint_steps versions since the last
            # save" rather than an exact modulo (which would
            # silently degrade to lcm(sync_every, steps)). Rank 0
            # alone suffices on the replicated plane (it holds
            # replica 0 of every leaf); with sharded parameters
            # EVERY rank writes — each owns distinct table rows,
            # and the per-process manifests only assemble into a
            # restorable checkpoint when all ranks contributed.
            # Versions agree across ranks (lockstep collective
            # steps), so all ranks pick the same cadence points.
            version = self.trainer.version
            if version - self._last_ckpt_version >= self._ckpt.steps:
                self._ckpt.save(self.trainer._ts, version)
                self._last_ckpt_version = version
        if (
            aligned_sync
            and self.trainer.is_sharded
            and self._job_type == JobType.TRAINING_WITH_EVALUATION
        ):
            # in-plane eval: a lockstep protocol (consensus
            # gather + collective forwards), so it must run at
            # the same aligned index on every rank — exactly
            # here, after the pause check agreed nobody is
            # re-forming this round
            self._collective_evaluate()
        if aligned_sync and self.trainer.mirror_enabled():
            # replica-plane cadence: same aligned-sync trigger
            # discipline as the checkpoint cadence (the refresh
            # is a collective — every rank must take it at the
            # same step, which the version-based predicate
            # guarantees)
            self.trainer.maybe_refresh_mirror(self.trainer.version)

    def _alarm_on_embedding_overflow(self):
        """Surface a2a capacity overflow (ids silently trained on zero
        rows) at sync points. The counter is a replicated scalar in the
        model state that comes to the host in each step's receipt, so
        the read costs nothing."""
        total = self.trainer.embedding_overflow_total()
        if total and total > self._overflow_alarmed:
            logger.warning(
                "embedding a2a capacity overflow: %d ids have read zero "
                "rows since job start (+%d since last sync) — increase "
                "HbmEmbedding capacity (or leave it None for the exact "
                "worst case)",
                total,
                total - self._overflow_alarmed,
            )
            self._overflow_alarmed = total

    # -- evaluation (local devices only, host-fetched params) ---------------

    def _run_eval_only(self):
        """Pure evaluation: drain the eval queue against saved params.

        No collective, no world membership, no training loop — the
        reference serves eval-only from the same worker loop
        (reference worker/worker.py:866-876); here the loop shrinks to
        the eval-task drain the interleaved path already uses. Params
        come from the newest complete sharded checkpoint (sharded zoos
        score through their host twin via _sharded_forward) or an
        exported model file."""
        drained_rounds = 0
        while True:
            executed = self._evaluate_only()
            task = self.get_task()  # non-eval queue: detects job end
            if task.shard_name:
                # unexpected non-eval work (mixed job?): report it back
                # untouched as failed so the master re-routes it
                self.report_task_result(
                    task.task_id,
                    err_msg="eval-only worker cannot run task type %s"
                    % task.type,
                )
            if not executed and not task.shard_name:
                drained_rounds += 1
                if drained_rounds >= 3:
                    break
                time.sleep(0.5)
            else:
                drained_rounds = 0
        # giving up: a drained eval queue is normal completion, but a
        # task that is STILL there means every attempt deferred (e.g. the
        # checkpoint dir is empty and no trainer will ever fill it) —
        # fail loudly instead of letting the master wait on requeues
        # forever
        from elasticdl_tpu.common.constants import TaskType

        leftover = self.get_task(TaskType.EVALUATION)
        if leftover.shard_name:
            self.report_task_result(
                leftover.task_id,
                err_msg="eval-only worker giving up: no scoreable params",
            )
            raise RuntimeError(
                "evaluation-only job cannot make progress: eval tasks "
                "keep deferring (is --checkpoint_dir empty / "
                "--checkpoint_filename_for_init unreadable, or does the "
                "checkpoint's parameter structure mismatch the model "
                "built from --model_params?)"
            )
        return []

    def _run_predict_only(self):
        """Pure prediction: stream prediction tasks through the dataset
        machinery, forward with saved params, hand outputs to the zoo's
        processor — the PS worker's _predict_only shape (reference
        worker.py:879-899), with record accounting via
        report_record_done so a failed batch fail-reports its task."""
        import jax

        if self._prediction_outputs_processor is None:
            # reference contract (worker.py:230-240): warn, don't fail —
            # outputs are simply not processed
            logger.warning(
                "prediction_outputs_processor is not defined in the "
                "model definition. Prediction outputs are not processed."
            )
        while True:
            dataset = self._task_data_service.get_dataset()
            if not dataset:
                break
            dataset = self._dataset_fn(
                dataset,
                Mode.PREDICTION,
                self._task_data_service.data_reader.metadata,
            )
            dataset = dataset.batch(self._minibatch_size).prefetch(1)
            for features in dataset:
                count = int(
                    np.asarray(
                        jax.tree_util.tree_leaves(features)[0]
                    ).shape[0]
                )
                err_msg = ""
                outputs = None
                # bounded retry before giving up (parity with the
                # eval-only drain's 3 rounds): a transiently missing or
                # torn checkpoint — e.g. a trainer still flushing async
                # writes into a shared dir — resolves in seconds and must
                # not fail the whole predict job. Only the FORWARD
                # retries; the user's outputs processor runs once (a
                # replay would duplicate records already written to its
                # sink)
                for attempt in range(3):
                    err_msg = ""
                    try:
                        outputs = self._serving_forward(features)
                        break
                    except RuntimeError as e:
                        # e.g. no restorable checkpoint yet: retry, then
                        # fail-report so the task requeues; the give-up
                        # below keeps a dead checkpoint source from
                        # spinning forever
                        logger.warning(
                            "prediction batch deferred (attempt %d): %s",
                            attempt + 1,
                            e,
                        )
                        err_msg = str(e)
                        if attempt < 2:
                            time.sleep(0.5)
                if (
                    not err_msg
                    and self._prediction_outputs_processor is not None
                ):
                    try:
                        self._prediction_outputs_processor.process(
                            outputs, self._worker_id
                        )
                    except RuntimeError as e:
                        # processor failures are terminal (no replay —
                        # it may have partially written its sink) but
                        # must still fail-report below so the master
                        # requeues immediately instead of waiting for
                        # worker-death detection
                        logger.warning(
                            "prediction outputs processor failed: %s", e
                        )
                        err_msg = str(e)
                self._task_data_service.report_record_done(
                    count, err_msg
                )
                if err_msg:
                    raise RuntimeError(
                        "prediction-only job cannot make progress: %s"
                        % err_msg
                    )
        return []

    def _serving_forward(self, features):
        """Forward for serving jobs: sharded zoos go through the host
        twin, everything else through the checkpoint-loaded params."""
        if self.trainer.is_sharded:
            return self._sharded_forward(features)
        return self._eval_only_forward(features)

    def _eval_only_forward(self, features):
        if self._eval_params is None:
            self._load_eval_only_params(features)
        if self._forward_fn is None:
            from elasticdl_tpu.training.step import make_forward_fn

            self._forward_fn = make_forward_fn(self._model)
        params, state = self._eval_params
        return self._forward_fn(params, state, features)

    def _load_eval_only_params(self, features):
        """Newest complete sharded checkpoint, else the exported model
        file (params only — exported models carry no mutable state, so
        stateful models evaluate with init-fresh state)."""
        if self._ckpt is not None:
            from elasticdl_tpu.common.sharded_checkpoint import (
                load_sharded_to_host,
            )

            for directory in self._ckpt_dirs_newest_first():
                try:
                    loaded_version, tree = load_sharded_to_host(directory)
                except Exception:
                    logger.debug(
                        "eval restore skipped torn checkpoint %s",
                        directory,
                        exc_info=True,
                    )
                    continue
                self._eval_params = (
                    tree["params"],
                    tree.get("state") or {},
                )
                self._eval_scored_version = loaded_version
                return
        if self._init_ckpt_file:
            import jax

            from elasticdl_tpu.common.model_utils import (
                load_from_checkpoint_file,
            )
            from elasticdl_tpu.common.tensor import named_arrays_to_pytree
            from elasticdl_tpu.nn.model_api import (
                init_variables,
                split_variables,
            )

            # accepts a .chkpt file or an export-artifact directory
            # (load_from_checkpoint_file resolves both)
            version, named = load_from_checkpoint_file(
                self._init_ckpt_file
            )
            one = jax.tree_util.tree_map(
                lambda x: np.asarray(x)[:1], features
            )
            template, state = split_variables(
                init_variables(self._model, jax.random.PRNGKey(0), one)
            )
            params = named_arrays_to_pytree(named, template)
            logger.info(
                "eval-only: scoring exported model v%d from %s",
                version,
                self._init_ckpt_file,
            )
            self._eval_params = (params, state)
            self._eval_scored_version = version
            return
        raise RuntimeError(
            "no restorable checkpoint in %r for evaluation"
            % (self._ckpt._base if self._ckpt is not None else "")
        )

    def _local_forward(self, features, pinned_version=None):
        if self.trainer.is_sharded:
            return self._sharded_forward(features)
        if self._job_type == JobType.EVALUATION_ONLY:
            return self._eval_only_forward(features)
        if self._forward_fn is None:
            from elasticdl_tpu.training.step import make_forward_fn

            self._forward_fn = make_forward_fn(self._model)
        if (
            pinned_version is None
            or self._eval_params_version != pinned_version
        ):
            # eval rounds pin the version a sync-point report carried,
            # and the run loop polls the eval queue at the NEXT iteration
            # (before any further step), so the common case snapshots at
            # exactly the pinned version — the cached snapshot then
            # serves every task of the round even after training moves
            # on (the reference's pinned-checkpoint semantics,
            # reference master/evaluation_service.py:186-203). A late
            # grab (re-form raced the round) scores current params and
            # reports the true version alongside.
            version = self.trainer.version
            if self._eval_params_version != version:
                host_ts = self.trainer.snapshot()
                if host_ts is None:
                    # never trained (peers drained the queue before this
                    # process got a task): no params to evaluate with
                    raise RuntimeError(
                        "no local train state for evaluation"
                    )
                self._eval_params = (host_ts.params, host_ts.state)
                self._eval_params_version = version
        self._eval_scored_version = self._eval_params_version
        params, state = self._eval_params
        return self._forward_fn(params, state, features)

    def _sharded_forward(self, features):
        """Eval forward for sharded-parameter jobs: the host-twin model
        over full tables assembled from the newest complete checkpoint.

        Evaluation therefore scores the checkpoint version (lagged by at
        most the cadence) — the same approximation the replicated plane
        makes in the other direction (it scores current params whatever
        version the eval task pinned)."""
        from elasticdl_tpu.common.sharded_checkpoint import (
            load_sharded_to_host,
        )

        candidates = self._ckpt_dirs_newest_first()
        if not candidates:
            raise RuntimeError(
                "no sharded checkpoint yet; eval params unavailable"
            )
        # re-assemble only when a checkpoint newer than the last ATTEMPT
        # appears — keyed on the attempt, not the loaded dir, so a torn
        # newest (killed peer) doesn't trigger a full-model disk reload
        # on every eval minibatch
        if candidates[0] != self._eval_params_version:
            self._eval_params_version = candidates[0]
            tree = None
            for directory in candidates:
                try:
                    loaded_version, tree = load_sharded_to_host(directory)
                    # reported alongside the pinned round version so the
                    # published summary shows the cadence lag honestly
                    self._eval_scored_version = loaded_version
                    break
                except Exception:
                    # newest may be mid-write by a peer; older complete
                    # versions are fine for a lagged eval
                    logger.debug(
                        "lagged-eval restore skipped %s",
                        directory,
                        exc_info=True,
                    )
                    continue
            if tree is not None:
                if self._forward_fn is None:
                    from elasticdl_tpu.training.step import (
                        make_forward_fn,
                    )

                    self._forward_fn = make_forward_fn(
                        self._host_model_factory()
                    )
                self._eval_params = (
                    tree["params"],
                    tree.get("state") or {},
                )
            elif self._eval_params is None:
                self._eval_params_version = None  # retry next call
                raise RuntimeError(
                    "no complete sharded checkpoint for evaluation"
                )
            # else: every candidate torn right now; score the previous
            # assembly rather than thrashing the disk
        params, state = self._eval_params
        return self._forward_fn(params, state, features)

    def _evaluate_only(self, final=False):
        """Drain pending eval tasks. ``final=True`` (the _finalize call,
        where no later training iteration will retry) waits out transient
        deferrals — e.g. a peer's final checkpoint still landing — so a
        requeued eval task is never abandoned with the job unfinished."""
        from elasticdl_tpu.common.constants import TaskType

        eval_only = self._job_type == JobType.EVALUATION_ONLY
        if not eval_only and not self.trainer.has_state:
            # no params to evaluate with (never trained): leave the eval
            # tasks for peers that have state — grabbing one here would
            # fail-requeue-regrab in a tight livelock. Eval-only workers
            # instead score saved checkpoints, so they always proceed.
            return False
        executed = False
        retries = 30 if final else 0
        while True:
            task = self.get_task(TaskType.EVALUATION)
            if not task.shard_name:
                break
            if not self._process_eval_task(task):
                # deferred (e.g. no sharded checkpoint yet): the task
                # requeued. Mid-training, stop regrabbing in a tight
                # loop — the next training iteration retries.
                if retries <= 0:
                    break
                retries -= 1
                time.sleep(1.0)
                continue
            executed = True
        return executed

    def _collective_evaluate(self, final=False):
        """In-plane lockstep eval for sharded-parameter jobs: every
        rank participates in every collective forward (the model's
        lookups/ring ARE collectives), ranks without eval work feed
        dummy rows until the all-gathered pending count reaches zero.

        Called at ALIGNED points only — the same step index on every
        rank (the aligned-sync block mid-training; the quiescence-
        aligned _finalize) — so the consensus gathers and forwards
        line up. Scores CURRENT parameters on the training plane
        itself: no checkpoint in the path, no host twin, and the
        sharded tables never materialize in one host's RAM (the
        reference's evaluate-on-the-training-plane semantics,
        reference worker/worker.py:659-693).

        ``final=True`` (the _finalize call, no later iteration will
        retry): waits out transient empty consensus rounds — a task
        fail-requeued by one rank can land back on the master just
        after every rank polled empty, and abandoning it would hang
        the job. The re-check loop is itself consensus-driven, so all
        ranks count the same empty rounds and exit together."""
        from elasticdl_tpu.common.constants import TaskType

        pending = None  # (task_id, model_version, batches, outs, labels)
        empty_rounds = 0
        while True:
            if pending is None:
                task = self.get_task(TaskType.EVALUATION)
                if task.shard_name:
                    pending = self._start_eval_task(task)
            have = pending is not None
            if self.trainer.eval_have_consensus(have) == 0:
                empty_rounds += 1
                if not final or empty_rounds >= 3:
                    break
                time.sleep(0.5)
                continue
            empty_rounds = 0
            feats, labels, count = (None, None, 0)
            if pending is not None:
                feats, labels, count = pending[2].pop(0)
            outputs = self.trainer.eval_step(
                feats, self._minibatch_size
            )
            if pending is None:
                continue  # dummy participation for a busy peer
            if not isinstance(outputs, dict):
                outputs = {MetricsDictKey.MODEL_OUTPUT: outputs}
            for k, v in outputs.items():
                pending[3].setdefault(k, []).append(
                    np.asarray(v)[:count]
                )
            pending[4].append(np.asarray(labels))
            if not pending[2]:
                self._eval_scored_version = self.trainer.version
                self._report_eval_outputs(
                    pending[0], pending[1], pending[3], pending[4]
                )
                pending = None

    def _report_eval_outputs(
        self, task_id, model_version, out_chunks, label_chunks
    ):
        """Publish one eval task's accumulated outputs and complete it;
        a reporting failure fail-reports the task for retry instead of
        propagating (shared by the local and in-plane eval paths)."""
        try:
            if out_chunks:
                self._stub.report_evaluation_metrics(
                    model_version,
                    {
                        k: np.concatenate(v)
                        for k, v in out_chunks.items()
                    },
                    np.concatenate(label_chunks),
                    scored_version=self._eval_scored_version,
                )
            self.report_task_result(task_id)
        except Exception as e:
            logger.warning(
                "eval task %d report failed: %s", task_id, e
            )
            try:
                self.report_task_result(task_id, err_msg=str(e))
            except Exception:
                # master unreachable: its death detection requeues
                logger.debug(
                    "fail-report for eval task %d also failed",
                    task_id,
                    exc_info=True,
                )

    def _start_eval_task(self, task):
        """Materialize one eval task's batches for the lockstep rounds.
        Returns [task_id, model_version, [(features, labels, count)],
        out_chunks, label_chunks] or None (task fail-reported)."""
        eval_info = self._task_data_service.get_validation_dataset(task)
        if not eval_info:
            return None
        dataset, model_version, task_id = eval_info
        dataset = self._dataset_fn(
            dataset,
            Mode.EVALUATION,
            self._task_data_service.data_reader.metadata,
        )
        dataset = dataset.batch(self._minibatch_size)
        import jax

        batches = []
        try:
            for features, labels in dataset:
                count = int(
                    np.asarray(
                        jax.tree_util.tree_leaves(features)[0]
                    ).shape[0]
                )
                batches.append((features, labels, count))
        except Exception as e:
            logger.warning("eval task %d unreadable: %s", task_id, e)
            self.report_task_result(task_id, err_msg=str(e))
            return None
        if not batches:
            self.report_task_result(task_id)
            return None
        return [task_id, model_version, batches, {}, []]

    def _process_eval_task(self, task):
        """Returns True when the task completed (success or reported
        failure another worker should retry); False when deferred — the
        caller stops regrabbing until the next training iteration."""
        eval_info = self._task_data_service.get_validation_dataset(task)
        if not eval_info:
            return False
        dataset, model_version, task_id = eval_info
        dataset = self._dataset_fn(
            dataset,
            Mode.EVALUATION,
            self._task_data_service.data_reader.metadata,
        )
        dataset = dataset.batch(self._minibatch_size)
        if (
            self._job_type != JobType.EVALUATION_ONLY
            and not self.trainer.has_state
        ):
            # fail the task so a worker that has trained state redoes it
            self.report_task_result(
                task_id, err_msg="no local train state for evaluation"
            )
            return False
        out_chunks, label_chunks = {}, []
        try:
            for features, labels in dataset:
                outputs = self._local_forward(
                    features, pinned_version=model_version
                )
                if not isinstance(outputs, dict):
                    outputs = {MetricsDictKey.MODEL_OUTPUT: outputs}
                for k, v in outputs.items():
                    out_chunks.setdefault(k, []).append(np.asarray(v))
                label_chunks.append(np.asarray(labels))
        except RuntimeError as e:
            # e.g. a sharded job's first eval task arriving before any
            # checkpoint exists — fail-report so the task requeues and a
            # later round (with a checkpoint) redoes it, instead of
            # crash-looping the worker
            logger.warning("eval task %d deferred: %s", task_id, e)
            self.report_task_result(task_id, err_msg=str(e))
            return False
        self._report_eval_outputs(
            task_id, model_version, out_chunks, label_chunks
        )
        return True

    # -- export -------------------------------------------------------------

    def _process_save_model_task_if_needed(self):
        (
            task,
            dataset,
        ) = self._task_data_service.get_save_model_task_and_dataset()
        if task is None:
            return
        saved_model_path = task.extended_config.get(
            SaveModelConfig.SAVED_MODEL_PATH, "/tmp/edl_saved_model"
        )
        if self.trainer.is_sharded:
            params, state, version = self._assemble_sharded_export()
            if params is None:
                self.report_task_result(
                    task.task_id,
                    err_msg="no complete sharded checkpoint to export",
                )
                return
            # serving plane traces the host twin (dense lookups, same
            # param structure the sharded checkpoint assembles to)
            model = (
                self._host_model_factory()
                if self._host_model_factory is not None
                else None
            )
        else:
            host_ts = self.trainer.snapshot()
            if host_ts is None:
                # never trained (no data ever assigned); let another
                # worker with state pick the task up
                self.report_task_result(
                    task.task_id, err_msg="no local train state to export"
                )
                return
            params = host_ts.params
            state = host_ts.state
            version = max(0, int(np.asarray(host_ts.version)))
            model = self._model
        saved_model_path = os.path.join(
            saved_model_path, str(int(time.time()))
        )
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
            params,
            version,
            metadata=self._export_meta,
            serving_fn=(
                make_serving_fn(model, state)
                if model is not None and example is not None
                else None
            ),
            example_features=example,
        )
        logger.info("Exported model to %s", saved_model_path)
        self.report_task_result(task_id=task.task_id, err_msg="")

    def _assemble_sharded_export(self):
        """Full host model from the newest complete sharded checkpoint.

        Every rank wrote a final checkpoint entering _finalize, but the
        export-task rank may get here before its peers' manifests land —
        retry on incomplete coverage before falling back to the previous
        complete version."""
        from elasticdl_tpu.common.sharded_checkpoint import (
            load_sharded_to_host,
        )

        directory = self._latest_ckpt_dir()
        if directory is None:
            return None, None, 0
        last_err = None
        for attempt in range(10):
            try:
                version, tree = load_sharded_to_host(directory)
                return tree["params"], tree.get("state") or {}, version
            except Exception as e:  # noqa: BLE001 - retried, then logged
                last_err = e
                time.sleep(1.0)
        logger.warning(
            "newest checkpoint %s never completed (%s); exporting the "
            "previous one",
            directory,
            last_err,
        )
        for older in self._ckpt.dirs_newest_first()[1:]:
            try:
                v, tree = load_sharded_to_host(older)
                return tree["params"], tree.get("state") or {}, v
            except Exception:
                logger.debug(
                    "restore skipped torn checkpoint %s",
                    older,
                    exc_info=True,
                )
                continue
        return None, None, 0

    def _save_ckpt_if_newer(self):
        """Checkpoint the current state if its version advanced past the
        last save (all three call sites: graceful epoch bump, global
        quiescence, finalize)."""
        if self._ckpt is None or not self._ckpt.is_enabled():
            return
        version = self.trainer.version
        if version > self._last_ckpt_version:
            self._ckpt.save(self.trainer._ts, version)
            self._last_ckpt_version = version

    def _drain_ckpt(self):
        """Land queued async checkpoint writes; surface IO errors as a
        warning (teardown must not mask the original failure)."""
        if self._ckpt is None:
            return
        try:
            self._ckpt.close()
        except Exception:
            logger.warning(
                "async checkpoint writes failed at teardown",
                exc_info=True,
            )

    def _finalize(self):
        if self._preempted:
            # drained under a preemption notice: land queued checkpoint
            # writes and get out — taking MORE work (final eval rounds,
            # the SAVE_MODEL task) on a dying node would strand it
            self._drain_ckpt()
            from elasticdl_tpu.parallel import distributed

            if distributed.current_spec() is not None:
                distributed.leave_world()
            return
        if self.trainer.is_sharded and self.trainer._ts is not None:
            # every rank lands a final checkpoint so the export task (one
            # rank) and any resume see the finished state, not the last
            # cadence point
            self._save_ckpt_if_newer()
        self._drain_ckpt()
        if self._job_type == JobType.TRAINING_WITH_EVALUATION:
            try:
                if (
                    self.trainer.is_sharded
                    and self.trainer._ts is not None
                ):
                    # the world is still formed (ranks leave below) and
                    # every rank enters _finalize from the SAME
                    # quiescence round, so the lockstep eval stays
                    # aligned; it also drains the queue collectively —
                    # each round every idle rank re-polls for tasks
                    self._collective_evaluate(final=True)
                else:
                    self._evaluate_only(final=True)
            except Exception:
                logger.warning("final eval round failed", exc_info=True)
        self._process_save_model_task_if_needed()
        from elasticdl_tpu.utils.profiling import maybe_stop_trace

        maybe_stop_trace()
        from elasticdl_tpu.parallel import distributed

        if distributed.current_spec() is not None:
            distributed.leave_world()
