"""Worker process entry.

Parity: reference worker/main.py — builds the master channel (256 MB caps
live in rpc.core), optional PS channels from ``--ps_addrs``, then runs the
task loop to completion.
"""

import os
import sys

from elasticdl_tpu.common.args import (
    parse_worker_args,
    warn_accum_unsupported,
)
from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.master.rpc_service import MasterClient
from elasticdl_tpu.worker.worker import Worker


def main():
    args = parse_worker_args()
    if args.distribution_strategy == "AllreduceStrategy":
        # the elastic worker must not touch the JAX backend before its
        # jax.distributed world forms; it starts the env-selected trace
        # itself after the first establish
        return _run(args)
    from elasticdl_tpu.utils.profiling import maybe_profile

    with maybe_profile():
        return _run(args)


def _run(args):
    from elasticdl_tpu.utils import profiling

    # tracing identity: every span id / postmortem header from this
    # process names the worker; the flight recorder arms only from the
    # env (worker pods own no durable directory — the operator points
    # EDL_FLIGHT_RECORDER_DIR at one) (docs/observability.md)
    profiling.spans.set_process("worker-%d" % args.worker_id)
    profiling.maybe_arm_flight_recorder()
    wire_dtype = getattr(args, "wire_dtype", "")
    stub = (
        MasterClient(
            args.master_addr,
            wire_dtype=wire_dtype,
            # co-located master pods serve get_model replies through a
            # negotiated shm ring; cross-host (or any attach failure)
            # silently keeps the bytes path (docs/wire.md)
            shm=getattr(args, "master_shm", "auto"),
            # ride out a master SIGKILL/relaunch instead of dying with
            # it: UNAVAILABLE retries through the outage window and
            # acks dedup on the new incarnation's journal
            # (docs/master_recovery.md)
            failover_s=getattr(args, "master_failover_s", 120.0),
        )
        if args.master_addr
        else None
    )
    ps_client = None
    bound_ps = []
    if args.ps_addrs:
        from elasticdl_tpu.worker.ps_client import BoundPS, PSClient

        addrs = [a for a in args.ps_addrs.split(",") if a]
        window = getattr(args, "hot_row_staleness_window", 0)
        if window <= 0:
            # default staleness bound: the SSP window the worker already
            # trains under between model pulls
            window = getattr(args, "get_model_steps", 1)
        deadline_s = getattr(args, "rpc_deadline_s", 60.0)
        bound_ps = [
            BoundPS(
                a,
                deadline_s=deadline_s if deadline_s > 0 else None,
                retries=getattr(args, "rpc_retries", 2),
                # co-located pods negotiate the shared-memory payload
                # path at first call; cross-host (or any attach
                # failure) silently keeps the bytes path (docs/wire.md)
                shm=getattr(args, "ps_shm", "auto"),
                shm_slots=getattr(args, "ps_shm_slots", 4),
                shm_slot_mb=getattr(args, "ps_shm_slot_mb", 8),
            )
            for a in addrs
        ]
        ps_client = PSClient(
            bound_ps,
            wire_dtype=wire_dtype,
            hot_row_cache_rows=getattr(args, "hot_row_cache_rows", 0),
            staleness_window=window,
            fanout=getattr(args, "ps_fanout", True),
            push_inflight=getattr(args, "ps_push_inflight", 0),
        )
    from elasticdl_tpu.common.model_utils import get_dict_from_params_str

    if args.distribution_strategy == "AllreduceStrategy":
        # a worker process under a master always runs the elastic
        # multi-process plane (a world of one process is the degenerate
        # case); the single-process AllReduceWorker remains the in-process
        # form used by the local API mode
        from elasticdl_tpu.worker.elastic_allreduce_worker import (
            ElasticAllReduceWorker,
        )

        worker = ElasticAllReduceWorker(
            worker_id=args.worker_id,
            job_type=args.job_type,
            minibatch_size=args.minibatch_size,
            model_zoo=args.model_zoo,
            model_def=args.model_def,
            model_params=args.model_params,
            dataset_fn=args.dataset_fn,
            loss=args.loss,
            optimizer=args.optimizer,
            eval_metrics_fn=args.eval_metrics_fn,
            stub=stub,
            data_reader_params=get_dict_from_params_str(
                args.data_reader_params
            ),
            comm_host=args.comm_host or None,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_steps=args.checkpoint_steps,
            keep_checkpoint_max=args.keep_checkpoint_max,
            checkpoint_filename_for_init=args.checkpoint_filename_for_init,
            prediction_outputs_processor=args.prediction_outputs_processor,
            precision=args.precision_policy or None,
            accum_steps=args.grad_accum_steps,
            remat=args.remat,
            replica_refresh_steps=args.replica_refresh_steps,
            task_prefetch=getattr(args, "task_prefetch", 1),
            speculative_compile=getattr(
                args, "speculative_compile", False
            ),
            telemetry_report_secs=getattr(
                args, "telemetry_report_secs", 5.0
            ),
        )
        if getattr(args, "standby", False):
            # pre-warmed spare: the cold start (jax/flax import chain
            # plus worker construction — ~all of a relaunch's 45-50 s)
            # was just paid ABOVE; park until the master
            # promotes this process, then adopt the assigned id. No
            # device is touched while parked (that would pin the
            # backend and break the world formation after promotion).
            import time as _time

            from elasticdl_tpu.common.log_utils import (
                default_logger as logger,
            )

            token = args.worker_id
            logger.info("standby %d warmed; parking", token)
            failures = 0
            while True:
                try:
                    wid = stub.standby_poll(token)
                    failures = 0
                except Exception:
                    # a transient RPC blip (master busy mid-formation)
                    # must not kill the spare that just paid its cold
                    # start — but a master that stays unreachable for
                    # ~2 min is gone, and an orphaned standby must not
                    # spin (and log) forever
                    failures += 1
                    if failures >= 60:
                        logger.error(
                            "standby %d: master unreachable for %d "
                            "consecutive polls; exiting",
                            token,
                            failures,
                        )
                        return 1
                    logger.warning(
                        "standby poll failed (%d); retrying", failures
                    )
                    wid = None
                if wid is not None:
                    logger.info(
                        "standby %d promoted to worker %d", token, wid
                    )
                    worker._worker_id = int(wid)
                    break
                _time.sleep(0.5 if failures == 0 else 2.0)
        # graceful preemption: cloud preemptions / pod evictions send
        # SIGTERM with notice — drain at the next batch boundary
        # (checkpoint + clean world leave) instead of dying
        # mid-collective
        worker.enable_drain_on_sigterm()
        worker.run()
        if not worker._preempted:
            # announce the clean completion BEFORE exiting: membership
            # exempts this process's coming rc-0 exit from the
            # survivors' wedge-escape dead list only for announced
            # leaves (an unannounced exit 0 — user code calling
            # sys.exit(0) mid-step — must still read as a death there).
            # All device/collective work is done (global quiescence +
            # _finalize), so nobody can be wedged on this rank.
            # Best-effort: if the RPC misses, the watch dead-lists the
            # exit and teardown-window survivors recover via one
            # (spurious but safe) reform.
            try:
                if stub is not None:
                    stub.leave_comm_world(worker._worker_id)
            except Exception:
                logger.debug(
                    "leave announcement missed; the watch dead-lists "
                    "this exit and survivors reform",
                    exc_info=True,
                )
        if worker._preempted:
            # distinct exit code: the instance manager relaunches a
            # replacement (exit 0 would read as "job done for me").
            # Hard exit, skipping atexit teardown: the drained world is
            # being torn down by every member at once, and a
            # jax.distributed.shutdown whose coordinator (rank 0's
            # process) already left FATALs in C++ — turning a clean
            # drain into a crash exit. Checkpoint writes were drained
            # in _finalize; there is nothing left worth tearing down.
            import sys as _sys

            _sys.stderr.flush()
            _sys.stdout.flush()
            os._exit(ElasticAllReduceWorker.PREEMPTED_EXIT_CODE)
        return 0

    warn_accum_unsupported(args, "the parameter-server worker")
    worker = Worker(
        worker_id=args.worker_id,
        job_type=args.job_type,
        minibatch_size=args.minibatch_size,
        model_zoo=args.model_zoo,
        model_def=args.model_def,
        model_params=args.model_params,
        dataset_fn=args.dataset_fn,
        loss=args.loss,
        optimizer=args.optimizer,
        eval_metrics_fn=args.eval_metrics_fn,
        prediction_outputs_processor=args.prediction_outputs_processor,
        stub=stub,
        ps_client=ps_client,
        get_model_steps=args.get_model_steps,
        data_reader_params=get_dict_from_params_str(
            args.data_reader_params
        ),
        precision=args.precision_policy or None,
        task_prefetch=getattr(args, "task_prefetch", 1),
        task_ack_queue=getattr(args, "task_ack_queue", 8),
        loss_log_steps=getattr(args, "loss_log_steps", 20),
        telemetry_report_secs=getattr(
            args, "telemetry_report_secs", 5.0
        ),
        embedding_plane=getattr(args, "embedding_plane", "ps"),
        # streaming serving exports (docs/serving.md): relayed from
        # the master's flags like every other train param
        export_dir=getattr(args, "export_dir", "") or None,
        export_every_versions=getattr(
            args, "export_every_versions", 0
        ),
        export_keep=getattr(args, "export_keep", 4),
    )
    try:
        worker.run()
    finally:
        if ps_client is not None:
            # settles any still-pending async pushes and releases the
            # fan-out threads
            ps_client.close()
        for bound in bound_ps:
            # unlink negotiated shm rings + close the channels (the
            # atexit hook is only the crash floor)
            bound.close()
        if stub is not None:
            # same discipline for the master channel's negotiated ring
            stub.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
