"""Client API: the ``edl train|evaluate|predict|clean`` implementations.

Parity: reference elasticdl/api.py + client.py — each subcommand parses
its flag set, builds+pushes the job image and submits only the master pod
(which then creates PS/worker pods itself).

TPU-native addition: **local mode**. On a TPU VM there is no need for a
k8s hop — when no ``--docker_image_repository`` is given the job runs
right here: the master (dispatcher + services + RPC) starts in-process
and workers run as local processes under the elastic
LocalInstanceManager (num_workers>0) or inline in this process
(num_workers=0). Same code paths, same elasticity, zero cluster.
"""

import os
import sys

from elasticdl_tpu.common import args as args_module
from elasticdl_tpu.common.args import (
    build_arguments_from_parsed_result,
    parse_envs,
)
from elasticdl_tpu.common.log_utils import default_logger as logger


def train(argv):
    args = args_module.parse_master_args(argv)
    return _run_job(args, argv)


def _serving_job(argv, verb, data_flag):
    """Shared gate + launch for serving-only jobs (evaluate / predict).

    Both need their data flag plus a model source: a pinned checkpoint
    file, or — only on the allreduce plane, whose workers read the
    sharded elastic format — a --checkpoint_dir (the PS-mode master
    initializes solely from --checkpoint_filename_for_init and would
    otherwise score a randomly-initialized model without error). One
    definition of "valid model source" here; Master.__init__ re-checks
    it server-side."""
    if not _has_flag(argv, data_flag):
        print("edl %s requires %s" % (verb, data_flag), file=sys.stderr)
        return 2
    allreduce = _flag_value(argv, "--distribution_strategy") == (
        "AllreduceStrategy"
    )
    if not (
        _has_flag(argv, "--checkpoint_filename_for_init")
        or (allreduce and _has_flag(argv, "--checkpoint_dir"))
    ):
        print(
            "edl %s requires --checkpoint_filename_for_init "
            "(or, under AllreduceStrategy, --checkpoint_dir with "
            "sharded elastic checkpoints)" % verb,
            file=sys.stderr,
        )
        return 2
    argv = list(argv)
    if not _has_flag(argv, "--training_data"):
        argv += ["--training_data", ""]
    args = args_module.parse_master_args(argv)
    return _run_job(args, argv)


def evaluate(argv):
    """Evaluation-only job (reference args.py add_evaluate_params)."""
    return _serving_job(argv, "evaluate", "--validation_data")


def predict(argv):
    """Prediction-only job (reference args.py add_predict_params)."""
    return _serving_job(argv, "predict", "--prediction_data")


def clean(argv):
    import argparse

    parser = argparse.ArgumentParser(description="edl clean")
    args_module.add_clean_params(parser)
    args = parser.parse_args(argv)
    from elasticdl_tpu.image_builder import remove_images

    removed = remove_images(
        docker_image_repository=args.docker_image_repository,
        all_images=args.all,
        docker_base_url=args.docker_base_url,
    )
    logger.info("Removed images: %s", removed)
    return 0


def _has_flag(argv, flag):
    return any(a == flag or a.startswith(flag + "=") for a in argv)


def _flag_value(argv, flag):
    for i, a in enumerate(argv):
        if a == flag:
            return argv[i + 1] if i + 1 < len(argv) else None
        if a.startswith(flag + "="):
            return a.split("=", 1)[1]
    return None


# -- job execution ----------------------------------------------------------


def _run_job(args, argv):
    if getattr(args, "docker_image_repository", ""):
        return _submit_cluster_job(args, argv)
    return _run_local_job(args)


def _submit_cluster_job(args, argv):
    """Build image, submit the master pod (reference api.py:132-154)."""
    from elasticdl_tpu.common.k8s_client import Client
    from elasticdl_tpu.image_builder import build_and_push_docker_image

    image_name = build_and_push_docker_image(
        model_zoo=args.model_zoo,
        docker_image_repository=args.docker_image_repository,
        base_image=args.image_base,
        extra_pypi=args.extra_pypi_index,
        cluster_spec=args.cluster_spec,
        docker_base_url=args.docker_base_url,
        docker_tlscert=args.docker_tlscert,
        docker_tlskey=args.docker_tlskey,
    )
    # in-image paths replace the client-local ones (reference
    # api.py:157-165 _model_zoo_in_docker/_cluster_spec_def_in_docker)
    relay = build_arguments_from_parsed_result(
        args, filter_args={"model_zoo", "cluster_spec"}
    )
    relay += ["--model_zoo", "/model_zoo"]
    if args.cluster_spec:
        relay += [
            "--cluster_spec",
            "/cluster_spec/" + os.path.basename(args.cluster_spec),
        ]
    container_args = ["-m", "elasticdl_tpu.master.main"] + relay
    client = Client(
        image_name=image_name,
        namespace=args.namespace,
        job_name=args.job_name,
        cluster_spec=args.cluster_spec,
    )
    client.create_master(
        resource_requests=args.master_resource_request,
        resource_limits=args.master_resource_limit,
        args=container_args,
        pod_priority=args.master_pod_priority,
        image_pull_policy=args.image_pull_policy,
        restart_policy=args.restart_policy,
        volume=args.volume,
        envs=parse_envs(args.envs),
    )
    logger.info("Job %s submitted (master pod created).", args.job_name)
    return 0


def _run_local_job(args):
    """Run master + workers on this machine (TPU-VM mode)."""
    from elasticdl_tpu.master.master import Master

    if getattr(args, "port", None) is None:
        args.port = 0  # local mode: bind an ephemeral port
    if (
        getattr(args, "num_ps_pods", 0) > 0
        and args.distribution_strategy != "AllreduceStrategy"
    ):
        # local mode never launches PS processes: every worker talks to
        # the master, so the master must hold the optimizer. With the
        # (cluster-oriented) default num_ps_pods=1 left in place the
        # master would hold none and dense gradients would be rejected.
        # (Under AllreduceStrategy the flag means nothing: parameters
        # live with the workers and the master holds no model.)
        logger.info(
            "local mode ignores --num_ps_pods=%d (no local PS fleet); "
            "the master holds the model",
            args.num_ps_pods,
        )
        args.num_ps_pods = 0
    master = Master(args)
    master.prepare()

    if args.num_workers <= 0:
        # single-process: worker drives the in-process servicer directly
        from elasticdl_tpu.common.model_utils import (
            get_dict_from_params_str,
        )

        if args.distribution_strategy == "AllreduceStrategy":
            from elasticdl_tpu.common.constants import JobType

            if master.job_type in (
                JobType.EVALUATION_ONLY,
                JobType.PREDICTION_ONLY,
            ):
                # pure eval/predict: no collective plane — the elastic
                # worker's serving drain scores the saved checkpoint
                from elasticdl_tpu.worker.elastic_allreduce_worker import (
                    ElasticAllReduceWorker,
                )

                worker = ElasticAllReduceWorker(
                    worker_id=0,
                    job_type=master.job_type,
                    minibatch_size=args.minibatch_size,
                    model_zoo=args.model_zoo,
                    model_def=args.model_def,
                    model_params=args.model_params,
                    dataset_fn=args.dataset_fn,
                    loss=args.loss,
                    optimizer=args.optimizer,
                    eval_metrics_fn=args.eval_metrics_fn,
                    stub=master.master_servicer,
                    data_reader_params=get_dict_from_params_str(
                        args.data_reader_params
                    ),
                    checkpoint_dir=getattr(args, "checkpoint_dir", ""),
                    checkpoint_filename_for_init=getattr(
                        args, "checkpoint_filename_for_init", ""
                    ),
                    prediction_outputs_processor=getattr(
                        args,
                        "prediction_outputs_processor",
                        "PredictionOutputsProcessor",
                    ),
                )
                try:
                    worker.run()
                except Exception:
                    # the master would otherwise poll the requeued eval
                    # tasks forever; shut it down, then surface the
                    # worker's error as the job failure
                    master.request_stop()
                    master.run(poll_secs=0.2)
                    raise
                return master.run(poll_secs=0.2)
            from elasticdl_tpu.worker.allreduce_worker import (
                AllReduceWorker,
            )

            worker = AllReduceWorker(
                worker_id=0,
                job_type=master.job_type,
                minibatch_size=args.minibatch_size,
                model_zoo=args.model_zoo,
                model_def=args.model_def,
                model_params=args.model_params,
                dataset_fn=args.dataset_fn,
                loss=args.loss,
                optimizer=args.optimizer,
                eval_metrics_fn=args.eval_metrics_fn,
                stub=master.master_servicer,
                data_reader_params=get_dict_from_params_str(
                    args.data_reader_params
                ),
                accum_steps=getattr(args, "grad_accum_steps", 1),
                precision=getattr(args, "precision_policy", "") or None,
                remat=getattr(args, "remat", ""),
                checkpoint_dir=getattr(args, "checkpoint_dir", ""),
                checkpoint_steps=getattr(args, "checkpoint_steps", 0),
                keep_checkpoint_max=getattr(
                    args, "keep_checkpoint_max", 0
                ),
            )
            try:
                worker.run()
            except Exception:
                # same as the eval-only path above: stop the master
                # rather than leave it polling requeued tasks forever
                master.request_stop()
                master.run(poll_secs=0.2)
                raise
            return master.run(poll_secs=0.2)

        from elasticdl_tpu.worker.worker import Worker

        worker = Worker(
            worker_id=0,
            job_type=master.job_type,
            minibatch_size=args.minibatch_size,
            model_zoo=args.model_zoo,
            model_def=args.model_def,
            model_params=args.model_params,
            dataset_fn=args.dataset_fn,
            loss=args.loss,
            optimizer=args.optimizer,
            eval_metrics_fn=args.eval_metrics_fn,
            stub=master.master_servicer,
            get_model_steps=args.get_model_steps,
            data_reader_params=get_dict_from_params_str(
                args.data_reader_params
            ),
            precision=getattr(args, "precision_policy", "") or None,
            prediction_outputs_processor=getattr(
                args,
                "prediction_outputs_processor",
                "PredictionOutputsProcessor",
            ),
            telemetry_report_secs=getattr(
                args, "telemetry_report_secs", 5.0
            ),
        )
        from elasticdl_tpu.common.args import warn_accum_unsupported

        warn_accum_unsupported(args, "the in-process PS worker")
        worker.run()
        rc = master.run(poll_secs=0.2)
        return rc

    from elasticdl_tpu.master.local_instance_manager import (
        LocalInstanceManager,
    )

    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.getcwd() + os.pathsep + env.get("PYTHONPATH", "")
    )
    # local workers all share this host; the allreduce coordinator must
    # advertise an address the sibling processes can dial
    env.setdefault("EDL_COMM_HOST", "localhost")

    def worker_command(worker_id):
        return [
            sys.executable,
            "-m",
            "elasticdl_tpu.worker.main",
            "--worker_id",
            str(worker_id),
            "--job_type",
            master.job_type,
            "--master_addr",
            "localhost:%d" % master.port,
        ] + build_arguments_from_parsed_result(
            args,
            filter_args={
                "port",
                "num_workers",
                "training_data",
                "validation_data",
                "prediction_data",
                "job_name",
            },
        )

    manager = LocalInstanceManager(
        master.task_d,
        args.num_workers,
        worker_command,
        restart_policy=args.restart_policy,
        env=env,
        membership=master.membership,
        num_standby=getattr(args, "num_standby_workers", 0),
    )
    master.instance_manager = manager
    manager.start_workers()
    rc = master.run(poll_secs=1)
    # the job is over, but a worker may still be landing its last
    # checkpoint, and it holds the accelerator until it exits: return
    # only once the processes this command started are gone
    manager.wait_stopped(grace_secs=120)
    return rc


# -- CLI --------------------------------------------------------------------

_SUBCOMMANDS = {
    "train": train,
    "evaluate": evaluate,
    "predict": predict,
    "clean": clean,
}


def cli_main(argv):
    """Reference client.py:13-46."""
    if not argv or argv[0] in ("-h", "--help"):
        print(
            "usage: edl {train|evaluate|predict|clean} [flags]",
            file=sys.stderr,
        )
        return 0 if argv else 2
    cmd = argv[0]
    fn = _SUBCOMMANDS.get(cmd)
    if fn is None:
        print("unknown subcommand %r" % cmd, file=sys.stderr)
        return 2
    return fn(argv[1:])
