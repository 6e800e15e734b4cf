"""1 - (union of device-op intervals / slice), in percent."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
BETTER = "lower"
MOVES = "tokens_per_s_per_chip"


def read(run):
    trace = run["trace"]
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
