"""Shared worker-side report helpers."""

from elasticdl_tpu.common.constants import TaskExecCounterKey


def with_model_version(read_version, exec_counters):
    """Piggyback the trainer's model version (``read_version()``) onto
    task-report counters so the coordinating (ALLREDUCE) master — which
    applies no gradients — can drive version-based triggers like the
    evaluation cadence. Reading the version may force a device sync and
    can re-raise a poisoned async dispatch on failure paths, so it is
    best-effort."""
    try:
        version = read_version()
    except Exception:  # noqa: BLE001 - failure paths must still report
        version = -1
    if version >= 0:
        exec_counters = dict(exec_counters or {})
        exec_counters.setdefault(
            TaskExecCounterKey.MODEL_VERSION, version
        )
    return exec_counters
