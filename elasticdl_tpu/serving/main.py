"""Scorer process entry: one pod of the serving fleet.

Boot order (docs/serving.md): build the PS channels (finite deadline +
bounded idempotent retries + shm negotiation — the serving-plane retry
discipline), share ONE version-tagged hot-row cache between the request
path and the delta sync, start the export-directory watcher (the first
artifact flips /healthz ``loading`` -> ``serving``), then serve. A
scorer never blocks the boot on the trainer: it answers
``scorer_status``/``/healthz`` immediately and ``score`` errors cleanly
until the first export lands.

SIGTERM drains: health flips to ``draining``, the micro-batcher stops
admitting (new submits shed ``draining``) and answers everything
already queued — an in-flight batch finishes on the model version it
acquired — then the RPC plane stops taking requests, sync/watcher
threads join, channels close, exit 0 — scorers are stateless, so there
is nothing to snapshot.
"""

import signal
import sys
import threading

from elasticdl_tpu.common.log_utils import default_logger as logger


def build_scorer(args):
    """Construct the scorer stack from parsed args; returns
    (scorer, watcher, sync, bound_channels, batcher). ``batcher`` is
    None when ``--serve_max_batch <= 1`` (the pre-PR-18 inline path)."""
    from elasticdl_tpu.nn.comm_plane import HotRowCache
    from elasticdl_tpu.serving.batcher import MicroBatcher
    from elasticdl_tpu.serving.delta_sync import EmbeddingDeltaSync
    from elasticdl_tpu.serving.scorer import ModelDirectoryWatcher, Scorer
    from elasticdl_tpu.worker.ps_client import BoundPS, PSClient

    bound = []
    ps_client = None
    sync = None
    cache = None
    addrs = [a for a in (args.ps_addrs or "").split(",") if a]
    if addrs:
        cache = HotRowCache(
            args.hot_row_cache_rows,
            window=args.serving_staleness_versions,
        )
        bound = [
            BoundPS(
                addr,
                deadline_s=args.rpc_deadline_s or None,
                retries=args.rpc_retries,
                shm=args.ps_shm,
            )
            for addr in addrs
        ]
        ps_client = PSClient(bound, cache=cache)
    scorer = Scorer(
        ps_client=ps_client,
        staleness_versions=args.serving_staleness_versions,
        model_zoo=args.model_zoo or None,
    )
    watcher = ModelDirectoryWatcher(
        args.export_dir,
        scorer,
        interval_s=args.watch_interval_s,
        model_zoo=args.model_zoo or None,
    )
    if ps_client is not None:
        sync = EmbeddingDeltaSync(
            ps_client,
            cache,
            interval_s=args.serving_sync_interval_s,
        )
    batcher = None
    if args.serve_max_batch > 1:
        batcher = MicroBatcher(
            scorer,
            max_batch=args.serve_max_batch,
            timeout_ms=args.serve_batch_timeout_ms,
            p99_slo_ms=args.serve_p99_slo_ms,
            queue_rows=args.serve_queue_rows,
        )
        # hot swaps pre-trace every bucket shape, never a request
        scorer.set_warm_batch_sizes(batcher.buckets)
    return scorer, watcher, sync, bound, batcher


def main():
    from elasticdl_tpu.common.args import parse_scorer_args
    from elasticdl_tpu.serving.server import ScorerServer
    from elasticdl_tpu.utils import profiling

    args = parse_scorer_args()
    profiling.spans.set_process("scorer-%d" % args.scorer_id)
    profiling.maybe_arm_flight_recorder()

    scorer, watcher, sync, bound, batcher = build_scorer(args)
    server = ScorerServer(
        scorer,
        port=args.port,
        telemetry_port=args.scorer_telemetry_port,
        batcher=batcher,
    )
    watcher.start()
    if sync is not None:
        sync.start()

    stop = threading.Event()

    def _drain(signum, frame):
        if stop.is_set():
            return
        logger.warning("SIGTERM: draining the scorer")
        stop.set()

    signal.signal(signal.SIGTERM, _drain)
    try:
        while not stop.wait(1.0):
            pass
    except KeyboardInterrupt:
        logger.warning("scorer stopping")
    finally:
        server.stop()
        watcher.stop()
        if sync is not None:
            sync.stop()
        scorer.close()
        for channel in bound:
            channel.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
