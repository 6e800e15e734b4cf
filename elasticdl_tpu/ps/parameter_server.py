"""Parameter-server process entry.

Parity: reference ps/parameter_server.py + ps/main.py — loads the
optimizer from the model-zoo module, serves the Pserver RPCs on a 64-thread
gRPC server, then sleeps forever (the master relaunches dead PS pods with
the same id/service DNS so workers re-resolve transparently).

Durability (docs/ps_recovery.md): with ``--ps_snapshot_versions`` +
``--ps_snapshot_dir`` set, the shard restores the newest valid snapshot
BEFORE serving, mints a fresh ``shard_epoch`` (boot id) carried in every
reply and in ``transport_hello``, snapshots every N optimizer versions
off the apply path, and drains a final snapshot on SIGTERM before
exiting 75 (EX_TEMPFAIL — the instance manager's graceful-drain code,
which relaunches without consuming the crash budget).
"""

import threading
import time

from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.common.model_utils import (
    get_module_file_path,
    load_module,
)
from elasticdl_tpu.ps.parameters import Parameters
from elasticdl_tpu.ps.servicer import PserverServicer
from elasticdl_tpu.rpc.core import serve


class ParameterServer:
    def __init__(self, args):
        self._args = args
        self._server = None
        self._shm_registry = None
        self._telemetry_http = None
        self._draining = threading.Event()
        # /healthz state machine (master parity): "restoring" until the
        # RPC plane serves, then "serving", "draining" through SIGTERM
        self._health = "restoring"
        self._owns_flight_recorder = False
        module = load_module(
            get_module_file_path(args.model_zoo, args.model_def)
        ).__dict__
        self._optimizer = module[args.optimizer]()
        # --ps_device: device-resident store + jitted apply paths
        # (docs/ps_device.md); everything downstream — snapshots, the
        # delta log, the RPC protocol — is mode-agnostic
        self.ps_device = bool(getattr(args, "ps_device", False))
        # --ps_warm_rows + --ps_spill_dir: tiered store
        # (docs/tiered_store.md) — tables spill cold rows past the
        # per-table warm budget to disk segments under the spill dir
        warm_rows = int(getattr(args, "ps_warm_rows", 0) or 0)
        spill_dir = getattr(args, "ps_spill_dir", "") or ""
        tier_config = None
        if warm_rows > 0 and spill_dir:
            import os as _os

            tier_config = {
                "warm_rows": warm_rows,
                "spill_dir": _os.path.join(
                    spill_dir, "ps-%d" % args.ps_id
                ),
            }
        elif warm_rows > 0 or spill_dir:
            logger.warning(
                "tiered store needs BOTH --ps_warm_rows and "
                "--ps_spill_dir; running untiered"
            )
        self.parameters = Parameters(
            device=self.ps_device, tier_config=tier_config
        )

        # durability plane: build the per-shard snapshotter (a no-op
        # object when the cadence/dir flags are unset), mint this
        # boot's epoch, and restore the newest valid snapshot before
        # the servicer exists — a restored shard must never serve a
        # single RPC from its step-0 init
        import os

        from elasticdl_tpu.ps.snapshot import (
            ShardSnapshotter,
            mint_shard_epoch,
        )

        snap_dir = getattr(args, "ps_snapshot_dir", "") or ""
        snap_every = int(getattr(args, "ps_snapshot_versions", 0) or 0)
        shard_dir = (
            os.path.join(snap_dir, "ps-%d" % args.ps_id)
            if snap_dir
            else None
        )
        self.shard_epoch = mint_shard_epoch(shard_dir)
        self.snapshotter = ShardSnapshotter(
            shard_dir or "",
            ps_id=args.ps_id,
            every_versions=snap_every if shard_dir else 0,
            keep=int(getattr(args, "ps_snapshot_keep", 2) or 2),
        )
        self.snapshotter.set_shard_epoch(self.shard_epoch)
        # crash flight recorder (docs/observability.md): postmortem
        # dumps land next to the shard's snapshots (durable across the
        # relaunch); EDL_FLIGHT_RECORDER_DIR overrides for
        # snapshot-less shards
        from elasticdl_tpu.utils import profiling

        fr_dir = os.environ.get("EDL_FLIGHT_RECORDER_DIR") or (
            os.path.join(shard_dir, "postmortem") if shard_dir else ""
        )
        if fr_dir:
            profiling.flight_recorder.arm(fr_dir)
            self._owns_flight_recorder = True
        self.restored_version = self.snapshotter.restore_into(
            self.parameters
        )

        self.servicer = PserverServicer(
            self.parameters,
            args.grads_to_wait,
            self._optimizer,
            lr_staleness_modulation=bool(args.lr_staleness_modulation),
            use_async=args.use_async,
            wire_dtype=getattr(args, "wire_dtype", ""),
            snapshotter=self.snapshotter if shard_dir else None,
            shard_epoch=self.shard_epoch,
            restored_version=self.restored_version,
        )

    def prepare(self):
        methods = self.servicer.rpc_methods()
        delay_ms = getattr(self._args, "rpc_inject_delay_ms", 0.0) or 0.0
        if delay_ms > 0:
            # test fault injection (--rpc_inject_delay_ms):
            # emulate cross-pod RTT on a loopback fleet by sleeping in
            # every handler before serving it
            def delayed(fn, delay_s=delay_ms / 1e3):
                def handler(req):
                    time.sleep(delay_s)
                    return fn(req)

                return handler

            methods = {name: delayed(fn) for name, fn in methods.items()}
        # the shared-memory endpoint is always offered (docs/wire.md):
        # it only engages when a co-located client negotiates a ring
        # via transport_hello, and costs nothing otherwise. Installed
        # OUTSIDE the delay wrap so the injected RTT still prices the
        # control round trip, not the slot reads.
        from elasticdl_tpu.rpc.shm_transport import install_shm_endpoint

        # the hello reply carries this incarnation's boot id too, so a
        # reconnecting co-located client learns the epoch at negotiation
        # time, before its first data-plane round (docs/ps_recovery.md)
        # device shards opt into WRITABLE request views: a shm-slot
        # gradient then dlpack-imports straight to device with zero
        # copies (the apply fences on its outputs before the reply
        # recycles the slot — docs/ps_device.md)
        methods, self._shm_registry = install_shm_endpoint(
            methods,
            hello_extra={"shard_epoch": self.shard_epoch},
            writable_request_views=self.ps_device,
        )
        telemetry_port = getattr(self._args, "ps_telemetry_port", None)
        if telemetry_port is None:
            # legacy attr name (pre-rename namespaces built by tests)
            telemetry_port = getattr(self._args, "telemetry_port", -1)
        if telemetry_port is not None and telemetry_port >= 0:
            # the PR-6 /metrics plane, per PS pod — full parity with
            # the master's endpoint (docs/observability.md): this
            # process's registry (per-method service histograms under
            # role=ps, the snapshot-age gauge), /events with the
            # ?since cursor, /trace (the shard's span ring), and a
            # /healthz that answers "restoring" 503 until the RPC
            # plane serves
            from elasticdl_tpu.master.telemetry import (
                ProcessTelemetry,
                TelemetryHTTPServer,
            )

            self._telemetry_http = TelemetryHTTPServer(
                ProcessTelemetry(),
                port=telemetry_port,
                health_fn=lambda: self._health,
            )
            self.ps_telemetry_port = self._telemetry_http.port
        self._server = serve(methods, self._args.port)
        self._health = "serving"
        logger.info(
            "RPC server started on port %d (shard_epoch %d%s)",
            self._server._edl_port,
            self.shard_epoch,
            (
                ", restored snapshot v%d" % self.restored_version
                if self.restored_version is not None
                else ""
            ),
        )

    def install_drain_handler(self):
        """SIGTERM = graceful preemption: drain one final snapshot and
        exit 75 so the instance manager relaunches without spending the
        crash budget. Installed only by the process entry (``main``) —
        embedded/test ParameterServers keep their host's handlers."""
        import signal
        import sys

        def _drain(signum, frame):
            if self._draining.is_set():
                return  # a second SIGTERM while draining: already going
            self._draining.set()
            self._health = "draining"
            logger.warning(
                "SIGTERM: draining a final shard snapshot before exit"
            )
            try:
                self.servicer.drain_snapshot()
            except Exception as err:  # noqa: BLE001 — exit regardless
                logger.error("drain snapshot failed: %s", err)
            self.stop()
            sys.exit(75)

        signal.signal(signal.SIGTERM, _drain)

    def run(self):
        try:
            while True:
                time.sleep(60)
        except KeyboardInterrupt:
            logger.warning("Server stopping")
        finally:
            self.stop()

    def stop(self):
        if self._server:
            self._server.stop(grace=None)
            self._server = None
        if self._telemetry_http is not None:
            self._telemetry_http.close()
            self._telemetry_http = None
        if self._shm_registry is not None:
            # reclaims every attached ring, including segments whose
            # creator worker was SIGKILLed mid-call (its atexit unlink
            # never ran — this is the orphan-reclamation path)
            self._shm_registry.close()
            self._shm_registry = None
        if self.snapshotter is not None:
            # settle queued cadence writes so a clean stop never drops
            # an already-captured snapshot on the floor
            try:
                self.snapshotter.close()
            except Exception as err:  # noqa: BLE001 — teardown
                logger.warning("snapshotter close failed: %s", err)
            self.snapshotter = None
        if self.parameters is not None:
            # tiered tables run a background demoter thread each; a
            # stopped shard must not leave them spilling to a dir the
            # relaunch is about to re-attach
            self.parameters.close()
        if self._owns_flight_recorder:
            # the recorder is process-global; embedded/test instances
            # must not leave it pointed at a torn-down tmpdir
            from elasticdl_tpu.utils import profiling

            profiling.flight_recorder.disarm()
            self._owns_flight_recorder = False


def main():
    from elasticdl_tpu.common.args import parse_ps_args
    from elasticdl_tpu.utils import profiling

    args = parse_ps_args()
    # name this process in every span id / postmortem header (entry
    # points only: embedded test instances keep the pid default)
    profiling.spans.set_process("ps-%d" % args.ps_id)
    server = ParameterServer(args)
    server.prepare()
    server.install_drain_handler()
    server.run()


if __name__ == "__main__":
    main()
