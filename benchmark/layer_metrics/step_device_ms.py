"""Device busy time per step in the traced slice."""
import _common

LAYER = "step"
UNIT = "ms"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"
read = _common.step_device_ms
