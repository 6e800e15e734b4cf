"""Multi-process world lifecycle: the TPU-native membership substrate.

The reference's distributed fabric is k8s-Service-DNS discovery plus
gRPC channels that tolerate peers coming and going
(reference common/k8s_client.py:89-97, docs/designs/parameter_server.md:
106-107). The TPU equivalent (SURVEY.md §2.3) is a ``jax.distributed``
process world: a coordination service hosted by rank 0, every process
holding a slot in one global device mesh, and XLA collectives riding
ICI/DCN between them.

Elasticity requires *re-forming* that world when membership changes. XLA
worlds are static per initialization, so a membership epoch is:

    leave_world()  ->  ensure_world(new_spec)

which tears down the coordination client, drops every initialized backend
(their device objects are invalid in the new world), and re-initializes
with the new rank/size/coordinator. Device state must be pulled to host
before leaving and re-placed after (parallel/elastic.py does this for the
train state).

Worlds are described by :class:`WorldSpec`, handed out by the master's
MembershipService over the control-plane RPC — the master is the single
source of membership truth, exactly as it is for task dispatch.

CPU bring-up: set ``EDL_DIST_PLATFORM=cpu`` (tests, local multi-process
jobs) to run the same code path over gloo TCP collectives with
``EDL_LOCAL_DEVICES`` virtual devices per process.
"""

import os
from dataclasses import dataclass

from elasticdl_tpu.common.log_utils import default_logger as logger


@dataclass(frozen=True)
class WorldSpec:
    """One membership epoch's process world."""

    coordinator: str  # host:port of rank 0's coordination service
    num_processes: int
    process_id: int
    epoch: int

    def singleton(self):
        return self.num_processes <= 1


class WorldBroken(RuntimeError):
    """A collective or coordination failure that requires re-forming."""


# Must sit BELOW the master's confirm/fence window (MembershipService
# confirm_timeout_secs, default 15): a member stuck in a stale formation
# barrier has to fail fast (WorldBroken -> re-poll, self-recovery) before
# the fencer declares it wedged and kills the healthy process. Healthy
# formations complete in well under a second (members only enter the
# barrier after the two-phase confirm). Shared with the master's
# staleness valve, which must outlast one full initialize timeout.
DEFAULT_WORLD_INIT_TIMEOUT = 10


def world_init_timeout():
    return int(
        os.environ.get(
            "EDL_WORLD_INIT_TIMEOUT", str(DEFAULT_WORLD_INIT_TIMEOUT)
        )
    )


_active_spec = None

# Monotonic count of backend teardowns in this process. Any compiled
# executable (or cached jitted callable bound to concrete devices) minted
# before the latest bump holds dead device handles; the compile plane's
# ExecutableCache keys on this so stale entries are evicted, never reused.
_backend_epoch = 0


def current_spec():
    return _active_spec


def backend_epoch():
    return _backend_epoch


def _bump_backend_epoch():
    global _backend_epoch
    _backend_epoch += 1


def _configure_platform():
    """Apply the ``EDL_DIST_PLATFORM=cpu`` bring-up before the backend
    initializes. Anything else is JAX's own platform selection
    (``JAX_PLATFORMS``, or the accelerator it finds)."""
    import jax

    # a dead peer must surface as a catchable error in the survivors, not
    # a process-killing propagated fatal — survivors re-form instead
    jax.config.update("jax_enable_recoverability", True)
    if os.environ.get("EDL_DIST_PLATFORM") == "cpu":
        n = os.environ.get("EDL_LOCAL_DEVICES")
        if n:
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count=" + n
                ).strip()
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_cpu_collectives_implementation", "gloo")


def _clear_backends():
    from jax.extend.backend import clear_backends

    clear_backends()
    _bump_backend_epoch()


def ensure_world(spec, init_timeout=None):
    """Join (or re-join) the process world described by ``spec``.

    Blocks until all ``spec.num_processes`` members arrive at the
    coordinator (jax.distributed's startup barrier) or the timeout
    elapses, in which case :class:`WorldBroken` is raised and the caller
    should re-poll the master for a fresher epoch.
    """
    global _active_spec
    if _active_spec == spec:
        return
    if _active_spec is not None:
        leave_world()

    import jax

    _configure_platform()
    # persistent compile cache: re-formed worlds drop every backend, so
    # each world's first compile of an already-seen step otherwise pays
    # full XLA compile again; the disk cache is keyed on the HLO and
    # survives both re-forms and process relaunches
    # (docs/compile_plane.md)
    from elasticdl_tpu.parallel.compile_plane import (
        enable_persistent_cache,
    )

    enable_persistent_cache()
    if init_timeout is None:
        # short by design: members only enter the barrier after the
        # master's two-phase confirm (everyone alive and polling), so a
        # healthy formation completes in well under a second. A long
        # timeout only prolongs the stale-barrier case — a member that
        # took a ready spec just before the epoch bumped again — which
        # must fail fast (WorldBroken -> re-poll) *before* the master's
        # confirm window fences the silent process.
        init_timeout = world_init_timeout()
    logger.info(
        "joining world epoch=%d rank=%d/%d coordinator=%s",
        spec.epoch,
        spec.process_id,
        spec.num_processes,
        spec.coordinator,
    )
    # short-ish failure detection and shutdown barrier: a dead member
    # otherwise stalls every survivor's graceful leave for the default
    # 100 s heartbeat + 300 s shutdown windows
    heartbeat = int(os.environ.get("EDL_HEARTBEAT_TIMEOUT", "30"))
    shutdown_timeout = int(os.environ.get("EDL_SHUTDOWN_TIMEOUT", "30"))
    import time as _time

    t0 = _time.time()
    try:
        jax.distributed.initialize(
            spec.coordinator,
            num_processes=spec.num_processes,
            process_id=spec.process_id,
            initialization_timeout=init_timeout,
            heartbeat_timeout_seconds=heartbeat,
            shutdown_timeout_seconds=shutdown_timeout,
        )
        logger.info(
            "world epoch=%d formed in %.1fs",
            spec.epoch,
            _time.time() - t0,
        )
    except Exception as e:
        # failed mid-handshake (peer missing, stale epoch): leave cleanly
        # so the next attempt starts from scratch
        try:
            jax.distributed.shutdown()
        except Exception:
            logger.debug(
                "shutdown during failed world-form also failed "
                "(backends are cleared next anyway)",
                exc_info=True,
            )
        _clear_backends()
        raise WorldBroken(
            "could not form world epoch %d (%s)" % (spec.epoch, e)
        ) from e
    _active_spec = spec


def leave_world():
    """Leave the current world and invalidate all device handles."""
    global _active_spec
    import jax

    try:
        jax.distributed.shutdown()
    except Exception:
        logger.warning("jax.distributed.shutdown failed", exc_info=True)
    _clear_backends()
    _active_spec = None
