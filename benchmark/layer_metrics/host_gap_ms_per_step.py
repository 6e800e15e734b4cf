"""What a step costs beyond the device's busy time: step_ms_p50 (the
worker's clock, untraced windows of this same run) - step_device_ms."""
import _common

LAYER = "worker loop"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    device = _common.step_device_ms(run)
    return None if device is None else _common.step_ms_p50(run) - device
