"""Master orchestrator: wires dispatcher, model, services, RPC, instances.

Parity: reference master/master.py — builds the task dispatcher from the
data reader's shards (:38-65), infers the job type from the data args
(:227-256), instantiates checkpoint/evaluation/tensorboard services and
the MasterServicer (:68-147), starts the RPC server and the instance
manager (:149-176), and polls ``task_d.finished()`` every 30 s (:178-195).

TPU-native deltas: the servicer optimizer exists only for
ParameterServerStrategy with master-central storage; AllreduceStrategy jobs
keep parameters in worker HBM and the master is pure control plane.
"""

import threading
import time

from elasticdl_tpu.common.constants import (
    DistributionStrategy,
    JobType,
)
from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.common.model_utils import (
    get_model_spec,
    get_module_file_path,
    load_module,
)
from elasticdl_tpu.data.data_reader import create_data_reader
from elasticdl_tpu.master.checkpoint_service import CheckpointService
from elasticdl_tpu.master.evaluation_service import EvaluationService
from elasticdl_tpu.master.rpc_service import MasterRpcService
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.master.tensorboard_service import TensorboardService


def _make_task_dispatcher(
    training_data,
    validation_data,
    prediction_data,
    records_per_task,
    num_epochs,
    data_reader_params=None,
    journal=None,
    streaming=False,
):
    """Reference master.py:38-65."""

    def _shards(origin):
        if not origin:
            return {}
        reader = create_data_reader(
            data_origin=origin,
            records_per_task=records_per_task,
            **(data_reader_params or {}),
        )
        return reader.create_shards()

    prediction_f_records = _shards(prediction_data)
    return TaskDispatcher(
        _shards(training_data),
        _shards(validation_data),
        prediction_f_records,
        records_per_task,
        num_epochs,
        journal=journal,
        streaming=streaming,
    )


class Master:
    def __init__(self, args):
        self.logger = logger
        self.args = args
        self.job_type = Master._get_job_type(args)
        if (
            getattr(args, "distribution_strategy", "")
            == DistributionStrategy.ALLREDUCE
            and self.job_type
            in (JobType.EVALUATION_ONLY, JobType.PREDICTION_ONLY)
            and not (
                getattr(args, "checkpoint_dir", "")
                or getattr(args, "checkpoint_filename_for_init", "")
            )
        ):
            # serving jobs (no training) score a saved model; reject a
            # sourceless submit before pods crash-loop on it
            raise ValueError(
                "%s under AllreduceStrategy scores a saved model: pass "
                "--checkpoint_dir (sharded checkpoints) or "
                "--checkpoint_filename_for_init (exported model file)"
                % self.job_type
            )

        records_per_task = (
            args.minibatch_size * args.num_minibatches_per_task
        )
        from elasticdl_tpu.common.model_utils import (
            get_dict_from_params_str,
        )

        # master recovery plane (docs/master_recovery.md): the durable
        # dispatch journal + this boot's epoch id. The journal is NOT
        # replayed here — prepare() replays it behind a "restoring"
        # /healthz before the RPC plane serves, so no worker ever talks
        # to a half-restored ledger.
        from elasticdl_tpu.master.journal import (
            MasterJournal,
            mint_master_epoch,
        )

        journal_dir = getattr(args, "master_journal_dir", "") or ""
        self.journal = (
            MasterJournal(
                journal_dir,
                fsync_interval_s=(
                    float(getattr(args, "master_journal_fsync_ms", 50))
                    / 1000.0
                ),
                segment_records=int(
                    getattr(args, "master_journal_segment_records", 4096)
                ),
            )
            if journal_dir
            else None
        )
        self.master_epoch = mint_master_epoch(journal_dir or None)
        self._health = "restoring"
        self._stopped = False
        # crash flight recorder (docs/observability.md): postmortems
        # land next to the dispatch journal (durable across the
        # relaunch, like everything recovery depends on);
        # EDL_FLIGHT_RECORDER_DIR overrides for journal-less masters
        import os as _os

        from elasticdl_tpu.utils import profiling as _profiling

        fr_dir = _os.environ.get("EDL_FLIGHT_RECORDER_DIR") or (
            _os.path.join(journal_dir, "postmortem")
            if journal_dir
            else ""
        )
        self._owns_flight_recorder = bool(fr_dir)
        if fr_dir:
            _profiling.flight_recorder.arm(fr_dir)

        self.task_d = _make_task_dispatcher(
            getattr(args, "training_data", ""),
            getattr(args, "validation_data", ""),
            getattr(args, "prediction_data", ""),
            records_per_task,
            args.num_epochs,
            get_dict_from_params_str(
                getattr(args, "data_reader_params", "")
            ),
            journal=self.journal,
            # --streaming_tasks: the unbounded train half of the
            # train->export->serve loop (docs/serving.md)
            streaming=bool(getattr(args, "streaming_tasks", False)),
        )

        model_module = load_module(
            get_module_file_path(args.model_zoo, args.model_def)
        ).__dict__
        self.model_module = model_module
        if (
            getattr(args, "distribution_strategy", "")
            == DistributionStrategy.ALLREDUCE
            and self.job_type
            in (JobType.EVALUATION_ONLY, JobType.PREDICTION_ONLY)
            and "build_collective_model" in model_module
            and not getattr(args, "checkpoint_dir", "")
        ):
            # sharded-table zoos serve through the host twin, which
            # assembles params from sharded checkpoint DIRECTORIES only;
            # accepting an exported-file-only job here would defer every
            # task until the worker gives up
            raise ValueError(
                "%s for model %s (sharded parameters) needs "
                "--checkpoint_dir pointing at sharded elastic "
                "checkpoints; --checkpoint_filename_for_init alone "
                "cannot feed the host-twin forward"
                % (self.job_type, args.model_def)
            )
        self.optimizer = model_module[args.optimizer]()

        # services
        self.checkpoint_service = self._create_checkpoint_service(args)
        self.tb_service = self._create_tensorboard_service(args)
        self.evaluation_service = self._create_evaluation_service(args)
        if self.evaluation_service:
            self.task_d.set_evaluation_service(self.evaluation_service)

        # deferred SavedModel-equivalent export task
        if getattr(args, "output", "") and self._job_has_training():
            self.task_d.add_deferred_callback_create_save_model_task(
                args.output
            )

        strategy = getattr(
            args,
            "distribution_strategy",
            DistributionStrategy.PARAMETER_SERVER,
        )
        master_holds_model = (
            strategy == DistributionStrategy.PARAMETER_SERVER
            and getattr(args, "num_ps_pods", 0) <= 0
        ) or strategy == DistributionStrategy.LOCAL
        # job-wide telemetry plane (docs/observability.md): fleet
        # aggregation is always on (it is scrape/report cadence, not a
        # hot path); the HTTP endpoint and JSONL sink are opt-in flags
        from elasticdl_tpu.master.telemetry import JobTelemetry
        from elasticdl_tpu.utils import profiling

        self.telemetry = JobTelemetry(task_dispatcher=self.task_d)
        events_path = getattr(args, "telemetry_events_path", "")
        self._owns_event_sink = bool(events_path)
        if events_path:
            profiling.events.attach_file(events_path)
        self._telemetry_http = None
        self._telemetry_tb = None
        self.master_servicer = MasterServicer(
            args.grads_to_wait,
            args.minibatch_size,
            self.optimizer if master_holds_model else None,
            self.task_d,
            checkpoint_filename_for_init=getattr(
                args, "checkpoint_filename_for_init", ""
            )
            or None,
            checkpoint_service=self.checkpoint_service,
            evaluation_service=self.evaluation_service,
            lr_staleness_modulation=getattr(
                args, "lr_staleness_modulation", False
            ),
            use_async=getattr(args, "use_async", False),
            coordinates_only=(strategy == DistributionStrategy.ALLREDUCE),
            telemetry=self.telemetry,
            journal=self.journal,
        )
        # membership epochs for the elastic allreduce plane (the PS plane
        # needs no inter-worker world)
        self.membership = None
        if strategy == DistributionStrategy.ALLREDUCE:
            from elasticdl_tpu.master.membership_service import (
                MembershipService,
            )

            import os

            # pipelined models need worlds whose DEVICE count divides
            # the stage count: round every formed world down to the
            # stage multiple and keep the overflow as hot spares
            # (membership_service world_size_multiple). Derived from
            # the model_params the job relays to every worker, assuming
            # one device per worker process (the k8s pod shape). On
            # multi-device hosts a smaller multiple suffices
            # (stages/gcd(stages, local_devices)) — set
            # EDL_WORLD_SIZE_MULTIPLE explicitly there.
            from elasticdl_tpu.common.model_utils import (
                get_dict_from_params_str,
            )

            stages = 0
            tp = 0
            try:
                mp = (
                    get_dict_from_params_str(
                        getattr(args, "model_params", "") or ""
                    )
                    or {}
                )
                stages = int(mp.get("pipeline_stages", 0) or 0)
                # the pjit dense plane needs worlds whose device count
                # divides the model axis the same way pipelining needs
                # the stage multiple (mesh_axes raises on non-divisor
                # worlds, which would otherwise crash-loop formation)
                tp = int(mp.get("tensor_parallel", 0) or 0)
                # min_tensor_parallel is the floor the layout solver
                # respects when re-planning dp x tp at establish; the
                # world multiple must honour the same floor so the
                # solver's smallest admissible tp always divides the
                # formed world (docs/distributed.md, Layout re-solve)
                tp = max(
                    tp, int(mp.get("min_tensor_parallel", 0) or 0)
                )
            except (TypeError, ValueError):
                pass
            raw_workers = int(getattr(args, "num_workers", 0) or 0)
            # the stage/tp multiple models ONE DEVICE PER WORKER
            # PROCESS (the k8s pod shape); a single-process job
            # (num_workers <= 1, e.g. the local in-process mode) holds
            # every local device in one mesh, where mesh_axes validates
            # the fit at establish instead. stages and tp cannot
            # combine (the zoo hook rejects the pair), so max() picks
            # whichever is in play.
            need = max(stages, tp)
            multiple = need if need > 1 and raw_workers > 1 else 1
            env_multiple = os.environ.get("EDL_WORLD_SIZE_MULTIPLE")
            if env_multiple:
                multiple = max(1, int(env_multiple))
            num_workers = max(1, raw_workers)
            if multiple > num_workers:
                # every bump would round the world down to ZERO members
                # — a silent never-trains stall, not elasticity
                raise ValueError(
                    "num_workers=%d cannot hold a world-size multiple "
                    "of %d (pipeline_stages=%d / tensor_parallel=%d "
                    "would round every world down to 0 processes). "
                    "Raise num_workers, lower the parallelism degree, "
                    "or — on multi-device hosts where it divides each "
                    "worker's devices — set EDL_WORLD_SIZE_MULTIPLE "
                    "to the true process multiple."
                    % (num_workers, multiple, stages, tp)
                )
            self.membership = MembershipService(
                expected_workers=num_workers,
                base_port=getattr(args, "comm_base_port", 0),
                # cold worker start (jax import + reader priming) can
                # exceed the default grace on loaded CI hosts; a partial
                # first world costs a churny re-form right at job start
                form_grace_secs=float(
                    os.environ.get("EDL_FORM_GRACE_SECS", "30")
                ),
                world_size_multiple=multiple,
                journal=self.journal,
            )
        self._server = None
        self.instance_manager = self._create_instance_manager(args)
        self._stop_requested = threading.Event()

    @staticmethod
    def _get_job_type(args):
        """Reference master.py:227-256."""
        has_training = bool(getattr(args, "training_data", ""))
        has_validation = bool(getattr(args, "validation_data", ""))
        has_prediction = bool(getattr(args, "prediction_data", ""))
        has_eval_trigger = bool(
            getattr(args, "evaluation_steps", 0)
            or getattr(args, "evaluation_throttle_secs", 0)
        )
        if has_prediction and not has_training:
            return JobType.PREDICTION_ONLY
        if has_validation and not has_training:
            return JobType.EVALUATION_ONLY
        if has_training and (has_validation or has_eval_trigger):
            return JobType.TRAINING_WITH_EVALUATION
        return JobType.TRAINING_ONLY

    def _job_has_training(self):
        return self.job_type in (
            JobType.TRAINING_ONLY,
            JobType.TRAINING_WITH_EVALUATION,
        )

    def _create_checkpoint_service(self, args):
        include_eval = self.job_type == JobType.TRAINING_WITH_EVALUATION
        return CheckpointService(
            getattr(args, "checkpoint_dir", ""),
            getattr(args, "checkpoint_steps", 0),
            getattr(args, "keep_checkpoint_max", 0),
            include_eval,
        )

    def _create_tensorboard_service(self, args):
        logdir = getattr(args, "tensorboard_log_dir", "")
        if not logdir:
            return None
        service = TensorboardService(logdir)
        service.start()
        import os as _os

        if _os.getenv("KUBERNETES_SERVICE_HOST"):
            # expose TB via a LoadBalancer service (reference
            # k8s_tensorboard_client.py); best-effort
            try:
                from elasticdl_tpu.common.k8s_tensorboard_client import (
                    TensorBoardClient,
                )

                TensorBoardClient(
                    image_name=None,
                    namespace=args.namespace,
                    job_name=args.job_name,
                ).create_tensorboard_service()
            except Exception:
                logger.warning(
                    "failed to create TensorBoard k8s service",
                    exc_info=True,
                )
        return service

    def _create_evaluation_service(self, args):
        if self.job_type == JobType.TRAINING_ONLY:
            return None
        eval_only = self.job_type == JobType.EVALUATION_ONLY
        return EvaluationService(
            self.checkpoint_service,
            self.tb_service,
            self.task_d,
            getattr(args, "evaluation_start_delay_secs", 0),
            getattr(args, "evaluation_throttle_secs", 0),
            getattr(args, "evaluation_steps", 0),
            eval_only,
            self.model_module[args.eval_metrics_fn],
        )

    def _create_instance_manager(self, args):
        """k8s-backed instance manager for in-cluster masters.

        Parity: reference master.py:379-450 — the master builds worker/PS
        command lines by relaying its own parsed args. Local runs get a
        LocalInstanceManager wired by api.py instead (or none for the
        inline single-process mode).
        """
        import os as _os

        if not _os.getenv("KUBERNETES_SERVICE_HOST"):
            return None
        if getattr(args, "num_workers", 0) <= 0:
            return None
        from elasticdl_tpu.common.args import (
            build_arguments_from_parsed_result,
            parse_envs,
        )
        from elasticdl_tpu.master.k8s_instance_manager import InstanceManager

        relay = build_arguments_from_parsed_result(
            args, filter_args={"port", "num_workers", "num_ps_pods"}
        )
        port = args.port if args.port is not None else 50001
        worker_args = [
            "-m",
            "elasticdl_tpu.worker.main",
            "--master_addr",
            "%s:%d" % (_os.getenv("MY_POD_IP", "localhost"), port),
            "--job_type",
            self.job_type,
        ] + relay
        ps_args = [
            "-m",
            "elasticdl_tpu.ps.main",
        ] + relay
        return InstanceManager(
            self.task_d,
            membership=self.membership,
            num_workers=args.num_workers,
            num_standby=getattr(args, "num_standby_workers", 0),
            worker_command=["python"],
            worker_args=worker_args,
            worker_resource_request=args.worker_resource_request,
            worker_resource_limit=args.worker_resource_limit,
            worker_pod_priority=args.worker_pod_priority,
            num_ps=args.num_ps_pods,
            ps_command=["python"],
            ps_args=ps_args,
            ps_resource_request=args.ps_resource_request,
            ps_resource_limit=args.ps_resource_limit,
            ps_pod_priority=args.ps_pod_priority,
            volume=args.volume,
            image_pull_policy=args.image_pull_policy,
            restart_policy=args.restart_policy,
            envs=parse_envs(args.envs),
            image_name=getattr(args, "worker_image", "") or None,
            namespace=args.namespace,
            job_name=args.job_name,
            cluster_spec=args.cluster_spec,
        )

    # -- lifecycle ----------------------------------------------------------

    def _recover_from_journal(self):
        """Replay the dispatch journal and fast-forward the ledger —
        BEFORE the RPC plane serves a single call, while /healthz says
        "restoring" (docs/master_recovery.md)."""
        if self.journal is None:
            return
        state = self.journal.replay()
        self.task_d.apply_recovery(state)
        self.master_servicer.restore_version(state.version)
        if self.membership is not None and state.member_epoch > 0:
            self.membership.seed_epoch(state.member_epoch)
        # the boot is a compaction point: the journal reopens on a
        # fresh segment headed by the post-recovery state and starts
        # its batched-fsync writer thread
        self.journal.start()

    def _master_status(self):
        """The ``master_status`` probe body (rpc_service wires it)."""
        status = {
            "state": self._health,
            "finished": self.task_d.finished(),
            "task_queues": self.task_d.queue_depths(),
        }
        if self.journal is not None:
            status["journal"] = self.journal.counts()
        return status

    def prepare(self):
        # readiness first: a relaunch probe must see "restoring" (503)
        # while the journal replays, not route traffic into a
        # half-restored ledger — and the endpoint re-binds the fixed
        # port its killed predecessor held (TelemetryHTTPServer._bind)
        telemetry_port = getattr(self.args, "telemetry_port", None)
        if telemetry_port is not None and telemetry_port >= 0:
            from elasticdl_tpu.master.telemetry import (
                TelemetryHTTPServer,
            )

            self._telemetry_http = TelemetryHTTPServer(
                self.telemetry,
                port=telemetry_port,
                health_fn=lambda: self._health,
            )
            self.telemetry_port = self._telemetry_http.port
        self._recover_from_journal()
        if self.evaluation_service:
            self.evaluation_service.start()
        from elasticdl_tpu.rpc.core import serve
        from elasticdl_tpu.rpc.shm_transport import install_shm_endpoint

        port = self.args.port if self.args.port is not None else 50001
        self._rpc_service = MasterRpcService(
            self.master_servicer,
            membership=self.membership,
            wire_dtype=getattr(self.args, "wire_dtype", ""),
            master_epoch=self.master_epoch,
            status_fn=self._master_status,
        )
        methods = self._rpc_service.rpc_methods()
        # shared-memory reply path for co-located worker pods
        # (docs/wire.md): workers negotiate per channel via
        # transport_hello and route ONLY their get_model pulls through
        # slots (MasterClient); plain requests pass through the wrap
        # untouched, so cross-host fleets see the bytes path unchanged
        methods, self._shm_registry = install_shm_endpoint(methods)
        self._server = serve(methods, port)
        self.port = self._server._edl_port
        self._health = "serving"
        logger.info(
            "Master RPC server started on port %d (master_epoch %d)",
            self.port,
            self.master_epoch,
        )
        logdir = getattr(self.args, "tensorboard_log_dir", "")
        if logdir:
            from elasticdl_tpu.master.telemetry import (
                TelemetryTBExporter,
            )

            self._telemetry_tb = TelemetryTBExporter(
                logdir,
                step_fn=self.master_servicer.get_model_version,
            )
        if self.instance_manager:
            self.instance_manager.start_all_ps()
            self.instance_manager.start_workers()

    def run(self, poll_secs=30):
        """Poll until all tasks are done (reference master.py:178-195).

        Returns the job's exit code: 0 when the ledger drained (or a
        stop was requested), 1 when a local job's worker processes are
        all gone for good with tasks still outstanding — nobody would
        ever take them, so polling on would hang the job forever."""
        rc = 0
        exhausted = getattr(
            self.instance_manager, "workers_exhausted", None
        )
        try:
            while not self._stop_requested.is_set():
                if self.task_d.finished():
                    if self.task_d.invoke_deferred_callback():
                        continue  # a SAVE_MODEL task was just queued
                    self._linger_for_pollers()
                    break
                if exhausted is not None and exhausted():
                    logger.error(
                        "every worker process has exited and none will "
                        "be relaunched, with tasks outstanding (%s): "
                        "failing the job",
                        self.task_d.queue_depths(),
                    )
                    rc = 1
                    break
                self._stop_requested.wait(poll_secs)
        except KeyboardInterrupt:
            logger.warning("Master stopping")
        finally:
            self.stop()
        return rc

    def _linger_for_pollers(self):
        """Serve briefly past the last ack when REMOTE workers exist.

        An OS-process worker learns "no more tasks" only from a
        get_task reply; a master that stops the instant the ledger
        drains races the last poller into its failover retry loop —
        burning the whole outage budget against a master that exited
        SUCCESSFULLY, then dying nonzero on a finished job. In-process
        jobs (the worker holds the servicer directly — api.py local
        mode, tests) never set served_get_task and keep the instant
        exit (docs/master_recovery.md)."""
        import os as _os

        grace = float(_os.environ.get("EDL_MASTER_EXIT_GRACE_S", "3"))
        rpc_service = getattr(self, "_rpc_service", None)
        if (
            grace > 0
            and rpc_service is not None
            and rpc_service.served_get_task
        ):
            self._stop_requested.wait(grace)

    def request_stop(self):
        self._stop_requested.set()

    def stop(self):
        if self._stopped:
            # the SIGTERM drain path stops the master and then lets the
            # run loop's finally reach here again — idempotent by flag
            # (several closes below are not re-entrant on their own)
            return
        self._stopped = True
        if self.evaluation_service:
            self.evaluation_service.stop()
        if self.tb_service:
            self.tb_service.close()
        if self._telemetry_tb:
            self._telemetry_tb.close()
            self._telemetry_tb = None
        if self._telemetry_http:
            self._telemetry_http.close()
            self._telemetry_http = None
        if self.telemetry:
            self.telemetry.close()
        if self._owns_event_sink:
            # detach the JSONL sink this master attached in __init__ —
            # the EventLog is process-global, so a later in-process job
            # must not keep appending to this job's file
            from elasticdl_tpu.utils import profiling

            profiling.events.close_file()
            self._owns_event_sink = False
        if self._owns_flight_recorder:
            # same process-global hygiene as the event sink: a later
            # in-process job must not dump into this job's directory
            from elasticdl_tpu.utils import profiling

            profiling.flight_recorder.disarm()
            self._owns_flight_recorder = False
        if self.instance_manager:
            self.instance_manager.stop_relaunch_and_remove_all_pods()
        if self._server:
            self._server.stop(grace=None)
            self._server = None
        if getattr(self, "_shm_registry", None) is not None:
            # reclaim attached worker rings — SIGKILLed clients' shm
            # segments included (their atexit unlink never ran)
            self._shm_registry.close()
            self._shm_registry = None
        if self.journal is not None:
            # settle every queued lifecycle record (flush + fsync) so a
            # clean stop is always a consistent replay point
            self.journal.close()

    def install_drain_handler(self):
        """SIGTERM = graceful preemption: drain the dispatch journal
        (flush + fsync) and exit 75 — the budget-exempt code the
        instance manager relaunches, PS-plane parity
        (ps/parameter_server.install_drain_handler). Installed only by
        the process entry; embedded masters keep their host's
        handlers."""
        import signal
        import sys

        def _drain(signum, frame):
            logger.warning(
                "SIGTERM: draining the dispatch journal before exit"
            )
            try:
                if self.journal is not None:
                    self.journal.flush()
            except Exception as err:  # noqa: BLE001 — exit regardless
                logger.error("journal drain failed: %s", err)
            self.stop()
            sys.exit(75)

        signal.signal(signal.SIGTERM, _drain)


def main():
    import os as _os

    from elasticdl_tpu.common.args import parse_master_args
    from elasticdl_tpu.utils import profiling

    args = parse_master_args()
    # name this process in every span id / postmortem header (entry
    # points only: in-process masters keep the owning process's tag)
    profiling.spans.set_process("master")
    master = Master(args)
    master.prepare()
    master.install_drain_handler()
    return master.run(
        poll_secs=float(_os.environ.get("EDL_MASTER_POLL_SECS", "30"))
    )


if __name__ == "__main__":
    import sys

    sys.exit(main())
